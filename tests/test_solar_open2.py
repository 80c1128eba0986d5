"""``--model solar_open2`` (gated NoPE grouped-query attention 1:3 with a
delta-rule scan whose write strength reaches 2, sigmoid router beside a
shared expert) at small widths on the CPU, from seeded weights, against the
plain reference (``benchmark/reference_solar_open2.py``). The decoders'
shared tests are ``tests/decoder_contract.py``'s, read through ``SPEC`` (each
layer kind's forward; loss, every leaf's gradient and three Adam steps,
float32 and bfloat16; the share test: 40 expert shares and 8 head shares add
up to the uncut reference's layer; pairs over a small buffer; what ``Config``
refuses; the scopes and notes of the compiled step; a fit from TFRecord
shards). This model's own are the router plan's general rule at P = 2 and
at P = 5, the cell's flags and the stack's shapes, here, and its blocks (the
scan at write strengths to 2, the causal block kernel) in
``tests/test_solar_open2_blocks.py``."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import reference_solar_open2 as ref  # noqa: E402
from benchmark.drivers import _program  # noqa: E402
from benchmark.drivers import _program_kimi_linear  # noqa: E402
from benchmark.drivers import _program_solar_open2 as seeding  # noqa: E402
from decoder_contract import (DecoderContract, HybridStack,  # noqa: E402
                              SmallBuffer, Spec, cut_columns, cut_rows,
                              kda_head_share, off_one)
from deepfm_tpu.models import (get_model, kimi_linear,  # noqa: E402
                               registered_models, solar_open2)

V, L, B = 60, 24, 2
SMALL = dict(model="solar_open2", feature_size=V, field_size=1,
             embedding_size=32, history_max_len=L, decoder_layers=4,
             kda_heads=2, kda_head_dim=8, kda_conv=4, attn_every=4,
             attn_q_heads=4, attn_kv_heads=2, attn_head_dim=8,
             moe_experts=8, moe_top_k=2, moe_expert_width=16,
             moe_experts_held=4, moe_first_expert=2,
             moe_pair_capacity=B * L * 2, moe_shared_width=16,
             rms_norm_eps=1e-5, batch_size=B, l2_reg=0.0,
             learning_rate=1e-3, steps_per_loop=1)
SIZES = dict(kda_head_dim=8, head_dim=8, eps=1e-5, top_k=2, route_scale=1.0,
             first_expert=2)
KINDS = {"gqa+moe": ("gqa", "moe"), "kda+moe": ("kda", "moe")}
#: The shortest stack with both kinds of layer, the full one leading: what
#: the tests of a whole trainer step compile (half of a period's time; the
#: forward pass and the layers' order are held to the reference at 4).
PAIR = dict(decoder_layers=2, attn_every=2)


def uncut(kind, heads=8, kv_heads=2, experts=40, d=32):
    """One layer's leaves for ``heads`` heads of both mixers (``kv_heads``
    key/value heads) and ``experts`` experts, the published ratios kept at
    small widths (head 8, group 4, top-4), gains off one."""
    cfg = SPEC.config(kda_heads=heads, attn_q_heads=heads,
                      attn_kv_heads=kv_heads, moe_experts=experts,
                      moe_experts_held=experts, moe_first_expert=0,
                      moe_top_k=4, embedding_size=d)
    lp = get_model(cfg)._init_layer(jax.random.PRNGKey(3), *kind)
    return off_one(jax.random.PRNGKey(4), lp)


def head_share(lp, mixer, r, heads=8, kv_heads=2):
    """Share ``r`` of 8: one of a mixer's 8 heads; the full layer's 8 query
    heads are on 2 key/value heads, so 4 shares read one key/value head."""
    if mixer == "kda":
        return kda_head_share(lp, r, 1)
    kv = r // (heads // kv_heads)       # the head's key/value head
    return {**lp,
            **{n: cut_columns(lp[n], r, 1, heads, 8)
               for n in ("gqa_wq", "gqa_w_gate")},
            **{n: cut_columns(lp[n], kv, 1, kv_heads, 8)
               for n in ("gqa_wk", "gqa_wv")},
            "gqa_wo": cut_rows(lp["gqa_wo"], r, 1, heads, 8)}


def notes(trainer):
    return {"kda_scan": "chunk64/sub16", "attn_scores": "xla",
            "head_grad": "forward 3 products/chunk, 0.00 GB kept",
            "mlp_kept": "0/%d" % len(trainer.model.kinds),
            "attn_kept": "0/%d" % sum(
                mixer == "gqa" for mixer, _ in trainer.model.kinds),
            "moe_rows": "xla", "moe_products": "xla",
            "moe_rows_moved": "{moe_pairs_held}/%d" % (
                2 * trainer.cfg.moe_pair_capacity)}


SPEC = Spec(
    ref=ref, small=SMALL, sizes=SIZES, stack=PAIR,
    scopes=frozenset({"embed", "attn", "attn_scores", "kda", "kda_scan",
                      "mlp", "moe", "head", "opt"}),
    notes=notes, kinds=KINDS,
    layer_counts={"moe_pairs_held": "moe", kimi_linear.DECAY_MIN: "kda",
                  kimi_linear.BETA_OVER_ONE: "kda"},
    layer_flags=dict(moe_top_k=4, moe_experts=40, moe_experts_held=40,
                     moe_first_expert=0, kda_heads=8, attn_q_heads=8,
                     attn_kv_heads=2, moe_pair_capacity=4 * B * L),
    layer_sizes={"top_k": 4, "first_expert": 0}, layer_leaves=uncut,
    # 8 head shares of a mixer (one of 8 heads each) and 40 expert shares
    # (one of 40 experts each, top-4)
    share_kinds=tuple(sorted(KINDS)), share_leaves=uncut,
    head_share=head_share, head_shares=8, expert_shares=40, share_experts=40,
    small_buffer=4,
    refusals=(
        ({"tasks": "ctr,cvr"}, "tasks"),
        ({"embedding_update": "sparse"}, "embedding_update=sparse"),
        ({"task_type": "infer"}, "infer/export"),
        ({"task_type": "export"}, "infer/export"),
        ({"servable_model_dir": "/tmp/x"}, "servable_model_dir"),
        ({"online_mode": True}, "online_mode"),
        ({"mesh_model": 2}, "mesh_model"),
        ({"batch_norm": True}, "batch_norm"),
        ({"history_max_len": 1}, "history_max_len >= 2"),
        ({"decoder_layers": 0}, "decoder_layers"),
        ({"attn_every": 0}, "attn_every"),
        ({"kda_heads": 0}, "kda_heads"),
        ({"attn_kv_heads": 3}, "multiple of attn_kv_heads"),
        ({"attn_q_heads": 0}, "attn_q_heads"),
        ({"moe_shared_width": 0}, "moe_shared_width"),
        ({"moe_top_k": 9}, "moe_top_k"),
        ({"moe_first_expert": 6}, "moe_experts_held"),
        ({"moe_pair_capacity": 0}, "moe_pair_capacity"),
        ({"mla_latent_dim": 16}, "belong to --model kimi_linear"),
        ({"dense_layers": 1}, "belong to --model kimi_linear"),
        ({"model": "sdar_moe"}, "belong to --model kimi_linear"),
    ))
config = SPEC.config


class TestSolarOpen2(DecoderContract, HybridStack, SmallBuffer):
    spec = SPEC

    def step_metrics_hold(self, metrics):
        assert int(metrics[kimi_linear.BETA_OVER_ONE]) > 0

    def test_logits_and_loss_match_the_reference(self, seeded):
        counts = self.logits_and_loss(seeded)
        assert int(counts["moe_pairs_held"]) > 0
        assert float(counts[kimi_linear.DECAY_MIN]) < 0.0
        # three KDA layers of 2 heads: some strengths pass 1, not all
        assert 0 < int(counts[kimi_linear.BETA_OVER_ONE]) < 3 * B * L * 2

    def test_fit_trains_from_tfrecord_shards(self, tmp_path):
        seen, _ = self.fit_from_shards(tmp_path)
        assert int(seen[-1][kimi_linear.BETA_OVER_ONE]) > 0
        assert float(seen[-1][kimi_linear.DECAY_MIN]) < 0.0


# ------------------------------------------------- the benchmark's seeding

def test_the_general_plan_at_a_half_is_the_kimi_plan():
    """P = 2, offset 0: the same (class, layer) pairs on the same experts as
    ``_program_kimi_linear.router_plan``, array for array."""
    from benchmark import harness
    cfg = _program.make_config(harness.load_json(
        "configs", "kimi-linear-48b-a3b.json")["flags"])
    assert seeding.plan_period(cfg) == 2
    want = _program_kimi_linear.router_plan(cfg)
    got = seeding.router_plan(cfg, offset=0)
    np.testing.assert_array_equal(got["rows"], want["rows"])
    np.testing.assert_array_equal(got["boost"], want["boost"])
    assert not np.array_equal(seeding.router_plan(cfg, offset=1)["boost"],
                              want["boost"])


def test_the_plan_refuses_a_share_that_is_no_whole_period():
    cfg = config(moe_experts=9, moe_experts_held=2, moe_first_expert=0)
    with pytest.raises(ValueError, match="has to divide"):
        seeding.router_plan(cfg)


def test_the_placed_router_holds_a_fifth_of_a_layers_positions():
    """At the cell's widths and traffic (8,192 tokens, Zipf 1.05 over 24,576
    rows), the seeded router by itself (a position's stream taken as its
    token's row of the table): P = 5, every class placed where (c + l + 1)
    mod 5 = 0, every layer's held pairs within the configuration file's band
    of T/5."""
    from benchmark import harness, traffic_sequences, weights

    conf = harness.load_json("configs", "solar-open2-250b.json")
    cfg = _program.make_config(conf["flags"])
    assert seeding.plan_period(cfg) == 5 and seeding.PLAN_OFFSET == 1
    plan = seeding.router_plan(cfg)
    rows, d = 24576, 4096
    heavy = list(traffic_sequences.tokens_of_ranks(np.arange(8), rows))
    assert list(plan["rows"]) == heavy
    placed = plan["boost"] > 0
    assert (placed.sum(-1) == cfg.moe_top_k).all()
    here = placed[:, :, :cfg.moe_experts_held].sum(-1)      # [layer, class]
    assert [list(np.nonzero(row)[0]) for row in here] == [
        [4], [3], [2, 7], [1, 6]]       # ranks 0 and 5: the next stage's
    assert (here <= 1).all()
    seed = 2 ** 31 + 5
    kw = {"feature_size": rows, "padded_vocab": rows,
          "embedding_scale": conf["assumed"]["embedding_scale"],
          "router_plan": plan}
    tokens = traffic_sequences.generate_tokens(
        1, 8192, rows, seed, {"zipf_exponent": 1.05}).reshape(-1)
    ids, counts = np.unique(tokens, return_counts=True)
    table_salt = weights.leaf_salt(seed, "tok_emb")
    table = weights.leaf_values(table_salt, (rows, d), rows=ids,
                                **{k: v for k, v in kw.items()
                                   if k != "router_plan"})
    xn = table / np.sqrt(np.mean(table * table, axis=1, keepdims=True))
    low, high = conf["assumed"]["router_placement_band"]
    # ISSUE 37's rule: seeded at most 1.4 x T/5, so that the drift the other
    # decoder cells measured (up to 1.43 times the seeded load) stays inside
    # the buffer's twice the mean (one run here drifted 1.71 times from a
    # layer seeded at 1.04: the configuration file's moe_pair_capacity)
    assert high <= 1.4 and 1.43 * high * len(tokens) / 5 \
        < cfg.moe_pair_capacity
    for layer in range(4):
        name = f"layers.{layer}.router"
        router = seeding.seeded_leaf(
            {name: weights.leaf_salt(seed, name), "tok_emb": table_salt},
            name, (d, 320), kw)
        top = np.argsort(-(xn @ router), axis=-1)[:, :8]
        held = ((top < 8).sum(-1) * counts).sum()
        assert low <= held / (len(tokens) / 5) <= high, (layer, held)
        for c, tok in enumerate(heavy):     # the classes go where placed
            assert set(top[list(ids).index(tok)]) == set(
                np.nonzero(placed[layer, c])[0])


# ---------------------------------------------------------------- the rest

def test_config_accepts_the_cells_flags():
    from benchmark import harness
    flags = harness.load_json("configs", "solar-open2-250b.json")["flags"]
    cfg = _program.make_config(flags)
    assert (cfg.model, cfg.attn_every, cfg.attn_q_heads, cfg.attn_kv_heads,
            cfg.kda_heads, cfg.moe_experts_held, cfg.moe_route_scale) == (
        "solar_open2", 4, 8, 1, 8, 8, 1.0)
    assert solar_open2.layer_kinds(cfg) == (
        ("gqa", "moe"), ("kda", "moe"), ("kda", "moe"), ("kda", "moe"))
    # a stack of full layers alone needs no KDA flags
    assert config(attn_every=1, kda_heads=0).attn_every == 1


def test_a_constructors_own_key_error_is_not_an_unknown_model(monkeypatch):
    from deepfm_tpu import models

    def broken(cfg):
        raise KeyError("mla_scores")
    monkeypatch.setitem(models._REGISTRY, "solar_open2", broken)
    with pytest.raises(KeyError, match="mla_scores"):
        get_model(config())


def test_the_model_is_a_stack_led_by_its_full_layer():
    assert "solar_open2" not in registered_models()    # the rankers' zoo
    model = get_model(config(decoder_layers=6))
    assert [m for m, _ in model.kinds] == ["gqa", "kda", "kda", "kda", "gqa",
                                           "kda"]
    assert {f for _, f in model.kinds} == {"moe"}
    assert model.embedding_param_names() == ("tok_emb",)
    assert model.uses_history and model.owns_loss
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert params["tok_emb"].shape == (model.padded_vocab, 32)
    assert params["head"].shape == (32, V)
    full, kda = params["layers"]["4"], params["layers"]["5"]
    assert {n: full[n].shape for n in full if n.startswith("gqa_")} == {
        "gqa_wq": (32, 32), "gqa_wk": (32, 16), "gqa_wv": (32, 16),
        "gqa_w_gate": (32, 32), "gqa_wo": (32, 32)}
    assert not [n for n in full if n.startswith(("kda_", "mla_", "mlp_"))]
    assert not [n for n in kda if n.startswith(("gqa_", "mla_", "mlp_"))]
    assert kda["kda_w_b"].shape == (32, 2)
    for lp in (full, kda):
        assert lp["router"].shape == (32, 8)
        assert lp["w_gate"].shape == (4, 32, 16)
        assert lp["shared_w_gate"].shape == (32, 16)
    assert set(state) == {*kimi_linear.COUNT_NAMES, kimi_linear.DECAY_MIN,
                          kimi_linear.BETA_OVER_ONE}
