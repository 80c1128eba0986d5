"""``--model kimi_linear`` (hybrid KDA / MLA mixture-of-experts decoder) at
small widths on the CPU, from seeded weights, against the plain reference
(``benchmark/reference_kimi_linear.py``). The decoders' shared tests are
``tests/decoder_contract.py``'s, read through ``SPEC`` (each layer kind's
forward; the shares; logits and loss of the five-layer stack; every leaf's
gradient and three Adam steps; pairs over a small buffer; what ``Config``
refuses; the scopes in the compiled step; a fit from TFRecord shards). This
model's own are the benchmark's placed router and the stack's shapes, here;
its blocks (the delta-rule scan against the recurrence, the convolution,
the router by hand) and the contract's tests of its expert layers' rows (a
small buffer, the row kernels) are ``tests/test_kimi_linear_blocks.py``'s."""

import os
import sys

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import reference_kimi_linear as ref  # noqa: E402
from benchmark.drivers import _program  # noqa: E402
from decoder_contract import (DecoderContract, HybridStack,  # noqa: E402
                              Spec, cut_columns, cut_rows, kda_head_share,
                              off_one)
from deepfm_tpu.models import (get_model, kimi_linear,  # noqa: E402
                               registered_models)

V, L, B = 60, 24, 2
SMALL = dict(model="kimi_linear", feature_size=V, field_size=1,
             embedding_size=32, history_max_len=L, decoder_layers=5,
             kda_heads=2, kda_head_dim=8, kda_conv=4, attn_every=4,
             attn_q_heads=2, attn_kv_heads=2, attn_head_dim=8,
             mla_latent_dim=16, mla_rope_dim=4, dense_layers=1,
             dense_mlp_width=64, moe_experts=8, moe_top_k=2,
             moe_expert_width=16, moe_experts_held=4, moe_first_expert=2,
             moe_pair_capacity=B * L * 2, moe_shared_width=16,
             moe_route_scale=2.446, rms_norm_eps=1e-5, batch_size=B,
             l2_reg=0.0, learning_rate=1e-3, steps_per_loop=1)
SIZES = dict(kda_head_dim=8, head_dim=8, rope_dim=4, eps=1e-5, top_k=2,
             route_scale=2.446, first_expert=2)
KINDS = {"kda+mlp": ("kda", "mlp"), "mla+moe": ("mla", "moe"),
         "kda+moe": ("kda", "moe")}


def uncut(kind, heads=8, experts=32, d=32):
    """One layer's leaves for ``heads`` heads of both mixers and ``experts``
    experts, every published ratio kept at small widths (head 8, latent 16,
    shared key 4, top-4), gains off one."""
    cfg = SPEC.config(kda_heads=heads, attn_q_heads=heads,
                      attn_kv_heads=heads, moe_experts=experts,
                      moe_experts_held=experts, moe_first_expert=0,
                      moe_top_k=4, embedding_size=d)
    lp = get_model(cfg)._init_layer(jax.random.PRNGKey(3), *kind)
    return off_one(jax.random.PRNGKey(4), lp)


def head_share(lp, mixer, r):
    """Share ``r`` of 4: two of a mixer's 8 heads."""
    if mixer == "kda":
        return kda_head_share(lp, r, 2)
    return {**lp, "mla_wq": cut_columns(lp["mla_wq"], 2 * r, 2, 8, 12),
            "mla_w_kvb": cut_columns(lp["mla_w_kvb"], 2 * r, 2, 8, 16),
            "mla_wo": cut_rows(lp["mla_wo"], 2 * r, 2, 8, 8)}


def notes(trainer):
    layers = sum(ffn == "moe" for _, ffn in trainer.model.kinds)
    # (off a TPU no layer keeps its SwiGLU's first products)
    return {"kda_scan": "chunk64/sub16", "mla_scores": "xla",
            "head_grad": "forward 3 products/chunk, 0.00 GB kept",
            "mlp_kept": "0/%d" % len(trainer.model.kinds),
            # (nor, anywhere, a latent-attention layer its XLA scores)
            "attn_kept": "0/%d" % sum(
                mixer == "mla" for mixer, _ in trainer.model.kinds),
            "moe_rows": "xla", "moe_products": "xla",
            "moe_rows_moved": "{moe_pairs_held}/%d" % (
                layers * trainer.cfg.moe_pair_capacity)}


SPEC = Spec(
    ref=ref, small=SMALL, sizes=SIZES,
    scopes=frozenset({"embed", "kda", "kda_scan", "attn", "mlp", "moe",
                      "head", "opt"}),
    notes=notes, kinds=KINDS,
    layer_counts={"moe_pairs_held": "moe", kimi_linear.DECAY_MIN: "kda"},
    layer_flags=dict(moe_top_k=4, moe_experts=32, moe_experts_held=32,
                     moe_first_expert=0, kda_heads=8, attn_q_heads=8,
                     attn_kv_heads=8, moe_pair_capacity=4 * B * L),
    layer_sizes={"top_k": 4, "first_expert": 0}, layer_leaves=uncut,
    # 8 heads of each mixer and 32 experts top-4 over 4 shares
    share_kinds=tuple(sorted(KINDS)), share_leaves=uncut,
    head_share=head_share, head_shares=4, expert_shares=4, share_experts=32,
    small_buffer=4,
    # this model's sigmoid router and shared expert, in one pass a layer
    # and in two
    row_kernels=dict(flags=dict(embedding_size=128, decoder_layers=2,
                                attn_every=2, moe_pair_capacity=64),
                     passes={"one-pass": 20480, "two-passes": 32}, moved=64),
    refusals=(
        ({"tasks": "ctr,cvr"}, "tasks"),
        ({"embedding_update": "sparse"}, "embedding_update=sparse"),
        ({"task_type": "infer"}, "infer/export"),
        ({"servable_model_dir": "/tmp/x"}, "servable_model_dir"),
        ({"batch_norm": True}, "batch_norm"),
        ({"history_max_len": 1}, "history_max_len >= 2"),
        ({"decoder_layers": 0}, "decoder_layers"),
        ({"attn_every": 0}, "attn_every"),
        ({"kda_heads": 0}, "kda_heads"),
        ({"attn_kv_heads": 1}, "attn_q_heads = attn_kv_heads"),
        ({"mla_latent_dim": 0}, "mla_latent_dim"),
        ({"dense_layers": 6}, "dense_layers"),
        ({"dense_mlp_width": 0}, "dense_mlp_width"),
        ({"moe_shared_width": 0}, "moe_shared_width"),
        ({"moe_top_k": 9}, "moe_top_k"),
        ({"moe_first_expert": 6}, "moe_experts_held"),
        ({"moe_pair_capacity": 0}, "moe_pair_capacity"),
        ({"model": "sdar_moe"}, "belong to --model kimi_linear"),
        ({"model": "deepfm", "history_max_len": 0}, "belong to --model"),
    ))
config = SPEC.config


class Kimi:
    spec = SPEC

    def step_metrics_hold(self, metrics):
        assert float(metrics[kimi_linear.DECAY_MIN]) < 0.0


class TestKimiLinear(Kimi, DecoderContract, HybridStack):

    def test_logits_and_loss_match_the_reference(self, seeded):
        counts = self.logits_and_loss(seeded)
        assert int(counts["moe_pairs_held"]) > 0
        assert float(counts[kimi_linear.DECAY_MIN]) < 0.0

    def test_fit_trains_from_tfrecord_shards(self, tmp_path):
        seen, _ = self.fit_from_shards(tmp_path)
        assert float(seen[-1][kimi_linear.DECAY_MIN]) < 0.0


# ------------------------------------------------- the benchmark's seeding

def test_the_placed_router_holds_half_a_layers_positions():
    """At the cell's widths and traffic (2 x 8,192 tokens, Zipf 1.05 over
    20,480 rows), the seeded router by itself (a position's stream taken as
    its token's row of the table): every expert layer's held pairs lie
    within the configuration file's band of T/2."""
    from benchmark import harness, traffic_sequences, weights
    from benchmark.drivers import _program_kimi_linear as seeding

    conf = harness.load_json("configs", "kimi-linear-48b-a3b.json")
    cfg = _program.make_config(conf["flags"])
    plan = seeding.router_plan(cfg)
    heavy = list(traffic_sequences.tokens_of_ranks(np.arange(8), 20480))
    assert list(plan["rows"]) == heavy
    placed = plan["boost"] > 0
    assert not placed[0].any()                          # the dense layer
    assert (placed[1:].sum(-1) == cfg.moe_top_k).all()
    here = placed[1:, :, :cfg.moe_experts_held].sum(-1)
    assert (here.sum(0) == 2).all()         # a class: one in two layers
    assert (here.sum(1) == 4).all()         # a layer: every second class
    assert (placed[1:, :, :cfg.moe_experts_held].sum(1) <= 1).all()
    seed = 2 ** 31 + 5
    kw = {"feature_size": 20480, "padded_vocab": 20480,
          "embedding_scale": conf["assumed"]["embedding_scale"],
          "router_plan": plan}
    tokens = traffic_sequences.generate_tokens(
        2, 8192, 20480, seed, {"zipf_exponent": 1.05}).reshape(-1)
    ids, counts = np.unique(tokens, return_counts=True)
    table_salt = weights.leaf_salt(seed, "tok_emb")
    rows = weights.leaf_values(table_salt, (20480, 2304), rows=ids,
                               **{k: v for k, v in kw.items()
                                  if k != "router_plan"})
    xn = rows / np.sqrt(np.mean(rows * rows, axis=1, keepdims=True))
    low, high = conf["assumed"]["router_placement_band"]
    for layer in range(1, 5):
        name = f"layers.{layer}.router"
        router = seeding.seeded_leaf(
            {name: weights.leaf_salt(seed, name), "tok_emb": table_salt},
            name, (2304, 256), kw)
        top = np.argsort(-(xn @ router), axis=-1)[:, :8]
        held = ((top < 16).sum(-1) * counts).sum()
        assert low <= held / (len(tokens) / 2) <= high, (layer, held)
        for c, tok in enumerate(heavy):     # the classes go where placed
            assert set(top[list(ids).index(tok)]) == set(
                np.nonzero(placed[layer, c])[0])


# ---------------------------------------------------------------- the rest

def test_the_model_is_a_stack_of_three_layer_shapes():
    assert "kimi_linear" not in registered_models()    # the rankers' zoo
    model = get_model(config())
    assert model.kinds == (("kda", "mlp"), ("kda", "moe"), ("kda", "moe"),
                           ("mla", "moe"), ("kda", "moe"))
    assert model.embedding_param_names() == ("tok_emb",)
    assert model.uses_history and model.owns_loss
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert params["tok_emb"].shape == (model.padded_vocab, 32)
    assert params["head"].shape == (32, V)
    assert params["layers"]["0"]["mlp_w_gate"].shape == (32, 64)
    assert "router" not in params["layers"]["0"]
    assert params["layers"]["3"]["mla_w_kva"].shape == (32, 16 + 4)
    assert params["layers"]["4"]["w_gate"].shape == (4, 32, 16)
    assert params["layers"]["4"]["shared_w_gate"].shape == (32, 16)
    assert set(state) == {*kimi_linear.COUNT_NAMES, kimi_linear.DECAY_MIN}
    # the decays' rate and step size start in their published ranges
    lp = model.init(jax.random.PRNGKey(1))[0]["layers"]["1"]
    rate = np.exp(lp["kda_a_log"])
    step = np.log1p(np.exp(lp["kda_dt_bias"]))
    assert (rate >= 1).all() and (rate <= 16).all()
    assert (step > 0.99e-3).all() and (step < 0.101).all()
