"""The benchmark's pieces for ``glm-4.7-flash.train-sequences-8k-ep8``: the
configuration file against the catalog row's numbers, its parameter count
against the model's own leaves, the roofline counts by hand, the reader on
hand-made contexts, the check's own number (``mtp_share_gap``) on hand-made
moments, the seeded state, a tiny-size CPU rehearsal of the cell through the
harness's test-only seam (traced), its control (one precision lower), the
six faults of ISSUE 48 and an unchanged state, each of which has to be
judged not correct, and the parent's program refusing the cell at once.
Nothing here measures a speed."""

import contextlib
import io
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

from benchmark import control, harness, roofline_glm4_moe_lite  # noqa: E402
from benchmark.drivers import (_program_glm4_moe_lite,  # noqa: E402
                               train_glm4_moe_lite)
from benchmark.readers import roofline_glm4_moe_lite as reader  # noqa: E402
from benchmark.readers import roofline_moe  # noqa: E402

CELL = "glm-4.7-flash.train-sequences-8k-ep8"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CONFIG = harness.load_json("configs", "glm-4.7-flash.json")
FLAGS = CONFIG["flags"]

#: The catalog row ``GLM-4.7-Flash``'s ``config`` (model-configs guide).
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}

# The cell cut to a size a CPU rehearses in seconds (the dense layer, one
# expert layer and the module), in float32 (its control is then bfloat16);
# the limits are this size's own. The window is 1 s (a tiny step is
# milliseconds).
TINY = {
    "config": {"vocabulary_rows": 100},
    "flags": {"feature_size": 100, "embedding_size": 32,
              "history_max_len": 32, "decoder_layers": 2,
              "dense_mlp_width": 48, "attn_q_heads": 2, "attn_kv_heads": 2,
              "mla_q_rank": 12, "mla_latent_dim": 16, "mla_nope_dim": 6,
              "mla_rope_dim": 4, "mla_value_dim": 8, "moe_expert_width": 16,
              "moe_shared_width": 16, "moe_pair_capacity": 2 * 32 * 4,
              "learning_rate": 1e-3, "compute_dtype": "float32"},
    "traffic": {"shards": 2, "sequences_per_shard": 64,
                "sequence_length": 32,
                "limits": {"xent_gap": 1e-4, "mtp_xent_gap": 1e-4,
                           "first_moment_gap": 2e-3,
                           "first_moment_gap_unrouted": 2e-3,
                           "param_change_gap": 0.1,
                           "mtp_share_gap": 0.05}},
}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NEW = {"train_mtp_device_ms", "train_attn_scores_roofline.glm4_moe_lite",
       "train_step_roofline.glm4_moe_lite"}
CHECKS = ["xent_gap", "mtp_xent_gap", "first_moment_gap",
          "first_moment_gap_unrouted", "param_change_gap", "mtp_share_gap",
          "untouched_rows_moved", "bias_moved", "pairs_over_buffer"]


def rehearse(seed=2 ** 31 + 7, trace=False, **flags):
    over = {**TINY, "flags": {**TINY["flags"], **flags}}
    return harness.run(CELL, seed, 1.0, trace, overrides=over,
                       require_chip=False)


def checks_of(printed):
    """{check: (value, said ok)} of a run's ``check`` lines."""
    return {ln.split()[1].rstrip(":"): (float(ln.split()[2]),
                                        ln.endswith(" ok"))
            for ln in printed.splitlines()
            if ln.startswith("check ") and " (limit " in ln}


# ----------------------------------------------------------- configuration

def test_configuration_runs_every_published_width():
    entry = next(c for c in BENCH["configs"] if c["name"] == "glm-4.7-flash")
    assert BENCH["configs"][-1] is entry       # appended, nothing moved
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts_held", "attention_heads_held",
        "vocabulary_rows"]
    assert entry["source"].startswith(CONFIG["source"]) and CONFIG[
        "source"] == ("https://huggingface.co/zai-org/GLM-4.7-Flash/blob/"
                      "main/config.json")
    assert "model_type glm4_moe_lite" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/glm-4.7-flash.json"
    for key, value in CATALOG.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    published = {"num_hidden_layers": 47, "num_experts_held": 64,
                 "attention_heads_held": 20, "vocabulary_rows": 154880}
    held = {"num_hidden_layers": 5, "num_experts_held": 8,
            "attention_heads_held": 5, "vocabulary_rows": 38720}
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == published[key], key
        assert CONFIG[key] == held[key], key
    # the guide's floors: the dense layer and four after it, 8 routed
    # experts a layer, at least an eighth of the vocabulary
    assert held["num_hidden_layers"] - CATALOG["first_k_dense_replace"] >= 4
    assert held["num_experts_held"] >= 8
    assert held["vocabulary_rows"] * 4 == published["vocabulary_rows"]
    assert held["attention_heads_held"] * 4 == CATALOG["num_attention_heads"]
    # every width as published
    assert (FLAGS["embedding_size"], FLAGS["dense_mlp_width"],
            FLAGS["moe_expert_width"], FLAGS["moe_shared_width"]) == (
        CATALOG["hidden_size"], CATALOG["intermediate_size"],
        CATALOG["moe_intermediate_size"], CATALOG["moe_intermediate_size"])
    assert (FLAGS["mla_q_rank"], FLAGS["mla_latent_dim"],
            FLAGS["mla_nope_dim"], FLAGS["mla_rope_dim"],
            FLAGS["mla_value_dim"]) == (
        CATALOG["q_lora_rank"], CATALOG["kv_lora_rank"],
        CATALOG["qk_nope_head_dim"], CATALOG["qk_rope_head_dim"],
        CATALOG["v_head_dim"])
    assert (FLAGS["moe_experts"], FLAGS["moe_top_k"],
            FLAGS["moe_route_scale"], FLAGS["rope_theta"],
            FLAGS["rms_norm_eps"]) == (
        CATALOG["n_routed_experts"], CATALOG["num_experts_per_tok"],
        CATALOG["routed_scaling_factor"], CATALOG["rope_theta"],
        CATALOG["rms_norm_eps"])
    assert (FLAGS["decoder_layers"], FLAGS["dense_layers"],
            FLAGS["mtp_depth"]) == (5, CATALOG["first_k_dense_replace"],
                                    CATALOG["num_nextn_predict_layers"])
    assert (FLAGS["attn_q_heads"], FLAGS["attn_kv_heads"],
            FLAGS["moe_experts_held"], FLAGS["moe_first_expert"]) == (
                5, 5, 8, 0)
    assert FLAGS["feature_size"] == CONFIG["vocabulary_rows"] == 38720
    assert "attn_head_dim" not in FLAGS
    # twice the mean held pairs T * 4 * 8 / 64 at T = 16,384
    assert FLAGS["moe_pair_capacity"] == 2 * (2 * 8192 * 4 * 8 // 64)
    assert FLAGS["mtp_loss_weight"] == CONFIG["assumed"][
        "mtp_loss_weight"] == 0.1
    for said in ("mtp_module", "rotary_pairing", "softmax_scale",
                 "selection_bias", "renormalisation_eps", "balance_loss",
                 "optimizer", "precision", "packing", "weights",
                 "router_placement", "moe_pair_capacity", "from_memory"):
        assert said in CONFIG["assumed"], said
    for said in ("8 chips share each expert layer", "5 of 20",
                 "38,720 of 154,880", "What the cut distorts",
                 "Why not other cuts", "17.2 GB", "half"):
        assert said in CONFIG["deployment"], said


def test_parameter_count_is_the_models_own_leaves():
    import jax

    from benchmark.drivers import _program
    from deepfm_tpu.models import get_model

    model = get_model(_program.make_config(FLAGS))
    shapes, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    table_rows = shapes["tok_emb"].shape[0]
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # (the program's table may hold padding rows past the vocabulary's)
    assert total - (table_rows - 38720) * 2048 == CONFIG["parameters"] \
        == roofline_glm4_moe_lite.param_count(FLAGS)["all"] == 700292608
    # 4 ways throughout (16 experts a block) would not fit
    assert roofline_glm4_moe_lite.param_count(
        {**FLAGS, "moe_experts_held": 16})["all"] == 1077779968


def test_the_cell_its_traffic_and_its_who_are_the_issues():
    cell = harness.load_cell(CELL)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert BENCH["workloads"][-1] is entry
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "glm-4.7-flash", "train-sequences-8k-ep8", 1)
    assert len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["kind"], t["shards"], t["sequences_per_shard"],
            t["sequence_length"], t["sequences_per_step"], t["max_epochs"],
            t["zipf_exponent"]) == ("train-sequences", 16, 128, 8192, 2,
                                    1000, 1.05)
    assert "multi-token-prediction" in t["who"] and "5 of 20 heads" in t["who"]
    assert set(t["limits"]) == set(t["limits_why"]) == set(CHECKS)
    assert cell.driver == "train_glm4_moe_lite"
    assert set(cell.end_to_end) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    assert {"train_attn_device_ms", "train_attn_scores_device_ms",
            "train_moe_device_ms", "train_mlp_device_ms",
            # the expert blocks' share: every expert cell's since PR 50
            "train_moe_roofline",
            "train_head_device_ms", "moe_expert_load_max_over_mean",
            "moe_pairs_over_buffer", "device_idle_share.train",
            "peak_hbm_gb.train", "train_step_device_ms",
            "train_embed_device_ms", "train_update_device_ms",
            "train_dense_device_ms",      # none of its scopes here: reads 0
            "train_unscoped_device_ms", "compiles_in_window.train",
            "dispatch_interval_ms_p50", "input_ns_per_record",
            "stage_transfer_ms", "input_wait_ms_max", "input_busy_share",
            } | NEW <= set(cell.per_layer)  # what a later PR adds is welcome
    # entries are found by name, never by position: a later PR appends
    mine = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NEW}
    assert set(mine) == NEW
    assert all(m["workloads"] == [CELL] and m["moves"]
               == "train_examples_per_s_per_chip" for m in mine.values())
    assert all(m["unit"] == "%" for n, m in mine.items() if "roofline" in n)
    assert mine["train_mtp_device_ms"]["layer"] == \
        "multi-token-prediction module"
    # where the cell was appended it is the list's last
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL, m["name"]


@pytest.mark.parametrize("name", sorted(
    m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]))
def test_each_listed_metric_resolves(name):
    spec = harness.load_json("metrics", f"{name}.json")
    assert os.path.exists(os.path.join(
        harness.BENCH_DIR, "readers", spec["reader"] + ".py")), name


def test_the_scopes_metric_reads_the_modules_scopes():
    assert harness.load_json("metrics", "train_mtp_device_ms.json")[
        "args"]["scopes"] == ["mtp", "mtp_head"]
    from deepfm_tpu.utils import profiling
    assert {"mtp", "mtp_head"} <= set(profiling.STEP_SCOPES)
    # the innermost scope wins: the module's block's ops are a block's
    assert profiling.innermost_scope(
        "jit(step)/transpose(jvp(mtp_head))/dot_general") == "mtp_head"
    assert profiling.innermost_scope(
        "jit(step)/mtp_head/head/dot_general") == "head"


# ------------------------------------------------------------- the counts

def test_roofline_counts_by_hand():
    peaks = harness.peaks_for("TPU v5 lite")
    b, t, d, f, v, h = 2, 8192, 2048, 1536, 38720, 5
    pairs = 5 * 8192.0
    flops = roofline_glm4_moe_lite.forward_flops(FLAGS, pairs)
    mla = (d * 768 + 768 * h * 256 + d * 576 + 512 * h * 448 + h * 256 * d)
    assert flops["mla_projections"] == 2.0 * (b * t * 5 + b * (t - 1)) * mla
    # allowed pairs x (256 + 256) x 5 heads, five layers and the module
    assert flops["attn_scores"] == 2.0 * b * h * 512 * (
        5 * (t * (t + 1) // 2) + (t - 1) * t // 2)
    sparse = b * t * 4 + b * (t - 1)
    assert flops["router"] == 2.0 * sparse * d * 64
    assert flops["shared"] == 2.0 * sparse * 3 * d * f
    assert flops["experts"] == 2.0 * pairs * 3 * d * f
    assert flops["dense_mlp"] == 2.0 * b * t * 3 * d * 10240
    assert flops["mtp_w_eh"] == 2.0 * b * (t - 1) * 2 * d * d
    assert flops["head"] == 2.0 * b * (t - 1) * d * v
    assert flops["mtp_head"] == 2.0 * b * (t - 2) * d * v
    step = roofline_glm4_moe_lite.train_step_least_seconds(FLAGS, pairs,
                                                           peaks)
    assert step["bound"] == "flops"
    assert step["flops"] == 3.0 * sum(flops.values())
    assert step["bytes"] == 40.0 * 700292608
    assert 0.19 < step["seconds"] < 0.22
    assert roofline_moe.least_seconds(FLAGS, pairs, peaks) \
        == 3.0 * flops["experts"] / peaks["bf16_flops_per_s"]
    scores = roofline_glm4_moe_lite.attn_scores_least_seconds(FLAGS, peaks)
    assert scores["flops"] == 3.0 * flops["attn_scores"]
    assert scores["bytes"] == 2.0 * 2 * h * 2 * 512 * b * t * 6
    assert scores["bound"] == "flops"
    # a model without the module counts none of it
    bare = roofline_glm4_moe_lite.forward_flops(
        {**FLAGS, "mtp_depth": 0}, pairs)
    assert bare["mtp_head"] == bare["mtp_w_eh"] == 0.0


def test_roofline_reader_shares_and_nothing_to_read(monkeypatch):
    def ctx(trace=True, **counters):
        return types.SimpleNamespace(
            cell=types.SimpleNamespace(name=CELL, config={"flags": FLAGS}),
            devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
            counters={"steps_in_window": 10,
                      "moe_pairs_held_per_step": 40960.0, **counters},
            trace={"devices": 1, "busy_s": 5.0} if trace else None,
            window=(0, 1))
    peaks = harness.peaks_for("TPU v5 lite")
    least = roofline_glm4_moe_lite.train_step_least_seconds(
        FLAGS, 40960.0, peaks)["seconds"]
    assert reader.read(ctx(), "step") == pytest.approx(100 * least / 0.5)
    assert reader.read(ctx(trace=False), "step") is None
    assert reader.read(ctx(moe_pairs_held_per_step=0), "step") is None
    monkeypatch.setattr(reader.scope_device_ms, "read",
                        lambda c, scopes: {("attn_scores",): 200.0}[
                            tuple(scopes)])
    scores = roofline_glm4_moe_lite.attn_scores_least_seconds(FLAGS, peaks)
    assert reader.read(ctx(), "attn_scores") == pytest.approx(
        100 * scores["seconds"] / 0.2)
    # the expert blocks' share asks for scope `moe` and nothing of an op's
    # name: the pairs' products, forward and backward, over that time
    monkeypatch.setattr(roofline_moe.scope_device_ms, "read",
                        lambda c, scopes: {("moe",): 20.0}[tuple(scopes)])
    assert roofline_moe.read(ctx()) == pytest.approx(
        100 * roofline_moe.least_seconds(FLAGS, 40960.0, peaks) / 0.02)
    # a program from before the scopes (the parent), a map without `moe`:
    # nothing to read, and nothing raised
    monkeypatch.setattr(reader.scope_device_ms, "read",
                        lambda c, scopes: None)
    assert reader.read(ctx(), "attn_scores") is None
    assert roofline_moe.read(ctx()) is None
    monkeypatch.setattr(roofline_moe.scope_device_ms, "read",
                        lambda c, scopes: 0.0)
    assert roofline_moe.read(ctx()) is None
    with pytest.raises(ValueError):
        reader.read(ctx(), "moe_matmul")


# ------------------------------------------------------ the check's number

def test_mtp_share_gap_reads_one_where_a_part_was_dropped():
    """The program's first moment with the module's part in it reads 0, with
    the head's part or the last layer's dropped 1, whatever rounding noise
    lies on the rest; the routed leaves have no say."""
    rng = np.random.default_rng(0)
    names = ["head", "layers.4.mla_wo", "layers.4.norm1", "layers.4.w_down",
             "layers.4.router"]
    want = {n: rng.normal(size=500) for n in names}
    part = {n: 0.1 * rng.normal(size=500) for n in names}
    noise = {n: 0.01 * rng.normal(size=500) * 0.1 for n in names}
    got = {n: want[n] + noise[n] for n in names}
    gap = train_glm4_moe_lite.mtp_share_gap
    assert gap(got, want, part) < 0.15
    no_head = {**got, "head": got["head"] - 0.1 * part["head"]}
    assert gap(no_head, want, part) == pytest.approx(1.0, abs=0.15)
    no_layer = {n: got[n] - (0.1 * part[n] if n != "head" else 0.0)
                for n in names}
    assert gap(no_layer, want, part) == pytest.approx(1.0, abs=0.15)
    routed_only = {**got, "layers.4.w_down": got["layers.4.w_down"]
                   - 0.1 * part["layers.4.w_down"]}
    assert gap(routed_only, want, part) == gap(got, want, part)


def test_the_clock_keeps_the_modules_loss_of_the_first_steps():
    clock = train_glm4_moe_lite.LossesClock.__new__(
        train_glm4_moe_lite.LossesClock)
    clock.mtp_xents, clock.seen = [], 0
    calls = []
    base = train_glm4_moe_lite.StepClock.__call__
    try:
        train_glm4_moe_lite.StepClock.__call__ = lambda self, s, m: (
            calls.append(m), setattr(self, "seen", self.seen + 1))
        for i in range(5):
            clock(None, {"xent": 1.0, "mtp_xent": 10.0 + i})
    finally:
        train_glm4_moe_lite.StepClock.__call__ = base
    assert clock.mtp_xents == [10.0, 11.0, 12.0] and len(calls) == 5


# -------------------------------------------------------- the seeded state

def test_the_seeded_leaves_follow_their_rules():
    import jax.numpy as jnp

    from benchmark import weights
    cfg = types.SimpleNamespace(
        decoder_layers=2, dense_layers=1, mtp_depth=1, moe_top_k=4,
        moe_experts=64, moe_experts_held=8, moe_first_expert=0,
        feature_size=100)
    trainer = types.SimpleNamespace(
        cfg=cfg, model=types.SimpleNamespace(padded_vocab=128))
    kw = _program_glm4_moe_lite.weight_kwargs(
        {"assumed": {"embedding_scale": 3.0}}, trainer)
    assert kw["mtp_layer"] == 2
    assert kw["router_plan"]["boost"].shape[0] == 3   # the module's too
    assert _program_glm4_moe_lite.bias_shape(cfg) == (2, 64)
    names = {"layers.0.mla_q_norm": (12,), "mtp.enorm": (32,),
             "mtp.hnorm": (32,), "mtp.final_norm": (32,),
             "final_norm": (32,), "mtp.w_eh": (64, 32),
             "mtp.block.router": (32, 64), "layers.1.router": (32, 64),
             "mtp.block.mla_w_qa": (32, 12), "tok_emb": (128, 32)}
    salts = {n: weights.leaf_salt(7, n) for n in names}
    for xp in (np, jnp):
        got = {n: np.asarray(_program_glm4_moe_lite.seeded_leaf(
            salts, n, shape, kw, xp=xp)) for n, shape in names.items()}
        for gain in ("layers.0.mla_q_norm", "mtp.enorm", "mtp.hnorm",
                     "mtp.final_norm", "final_norm"):
            assert np.all(np.abs(got[gain] - 1.0) <= 0.1 + 1e-6), gain
            assert np.std(got[gain]) > 0.01, gain
        assert np.abs(got["mtp.w_eh"]).max() <= np.sqrt(6 / 96)
        assert np.abs(got["tok_emb"][:100]).max() > 2.5
        assert not got["tok_emb"][100:].any()
        # a module's router is its own draw with the plan's boost on it
        plain = np.asarray(weights.leaf_values(
            salts["mtp.block.router"], (32, 64), feature_size=100,
            padded_vocab=128, embedding_scale=3.0))
        assert np.abs(got["mtp.block.router"] - plain).max() > 0.01
        assert not np.array_equal(got["mtp.block.router"],
                                  got["layers.1.router"])
        # ... along what a heavy *next* token gives the module's stream,
        # RMSNorm(Emb(c); enorm) W_eh[:d], by sqrt(2) of a layer's boost
        plan = kw["router_plan"]
        row = got["tok_emb"][plan["rows"][1]]
        along = (row / np.sqrt(np.mean(row * row)) * got["mtp.enorm"]) \
            @ got["mtp.w_eh"][:32]
        rise = along / np.linalg.norm(along) @ (got["mtp.block.router"]
                                                - plain)
        np.testing.assert_allclose(
            rise, np.sqrt(2.0) * plan["boost"][2][1], atol=0.3)  # (d = 32:
        # eight classes' directions overlap by 0.2 or so)
        assert plan["boost"][2][1].max() > 0
    bias = _program_glm4_moe_lite.seeded_bias(
        weights.leaf_salt(7, _program_glm4_moe_lite.SELECT_BIAS), (5, 64))
    assert bias.shape == (5, 64) and 0.01 < np.abs(bias).max() <= 0.02


# ------------------------------------------------------------- rehearsals

@pytest.fixture(scope="module")
def traced():
    """(the line, what was printed) of the one sound rehearsal, traced."""
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        out = rehearse(trace=True)
    return out, printed.getvalue()


def test_rehearsal_prints_the_contracts_keys(traced):
    line, _ = traced
    assert set(line) == LINE_KEYS | {"breakdown"} and line["correct"] is True
    assert line["metrics"] and all(
        set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s", "window_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_rehearsal_prints_what_a_cpu_can_read(traced):
    """A traced run's line carries each per-layer metric the cell lists
    that has something to read on a CPU (no device plane in its trace: the
    device-trace metrics, the four new ones among them, are left out, not
    failed); the counts' line says the bias's picks; the check names its
    nine numbers, both losses and the module's share among them."""
    out, printed = traced
    cell = harness.load_cell(CELL)
    assert set(out["metrics"]) <= set(cell.per_layer)
    assert {"compiles_in_window.train", "dispatch_interval_ms_p50",
            "input_ns_per_record", "moe_expert_load_max_over_mean",
            "moe_pairs_over_buffer"} <= set(out["metrics"])
    assert not NEW & set(out["metrics"])
    counts = [ln for ln in printed.splitlines()
              if ln.startswith("counts (")][-1]
    assert float(counts.split("moe_bias_moved_picks ")[1].split(";")[0]) > 0
    checks = checks_of(printed)
    assert list(checks) == CHECKS and all(ok for _, ok in checks.values())
    said = [ln for ln in printed.splitlines() if "reference step 1" in ln][0]
    assert "the module's" in said
    leaves = [ln.split()[1].rstrip(":") for ln in printed.splitlines()
              if ln.startswith("leaf ")]
    assert {"mtp.w_eh", "mtp.block.router", "mtp.final_norm", "head",
            "layers.1.mla_w_qb"} <= set(leaves)


def test_one_precision_lower_is_judged_not_correct():
    out = control.run(CELL, 5, 1.0, overrides=TINY, require_chip=False)
    assert out["correct"] is False


#: fault -> a check that has to refuse it (others may too)
REFUSED_BY = {
    "shared-key-unrotated": "first_moment_gap_unrouted",
    "query-latent-norm-skipped": "first_moment_gap_unrouted",
    "embedding-of-this-token": "first_moment_gap_unrouted",
    "module-cotangent-into-h-dropped": "mtp_share_gap",
    "second-head-gradient-dropped": "mtp_share_gap",
    "lambda-zero": "mtp_share_gap",
}


@pytest.mark.parametrize("fault", sorted(REFUSED_BY))
def test_each_of_the_six_faults_is_caught(monkeypatch, capsys, fault):
    """ISSUE 48's faults at the rehearsal's size (``tests/test_glm4_moe_lite``
    has the patches): the shared key left unrotated, the query latent's norm
    skipped, ``Emb(t_i)`` in place of ``Emb(t_{i+1})``, the module's
    cotangent into ``h^last`` dropped, the head's gradient from the second
    pass dropped, lambda taken as 0."""
    import test_glm4_moe_lite

    test_glm4_moe_lite.FAULTS[fault](monkeypatch)
    assert rehearse()["correct"] is False
    value, ok = checks_of(capsys.readouterr().out)[REFUSED_BY[fault]]
    assert not ok
    if REFUSED_BY[fault] == "mtp_share_gap":    # the part is all gone
        assert value == pytest.approx(1.0, abs=0.05)


def test_a_step_that_leaves_the_parameters_unchanged_is_caught(
        monkeypatch, capsys):
    """A state left as it was reads a ``param_change_gap`` of 1, over the
    limit (the cell's 0.6 as the rehearsal's 0.1)."""
    import deepfm_tpu.train.loop as loop

    monkeypatch.setattr(loop.optax, "apply_updates",
                        lambda params, updates: params)
    line = rehearse()
    assert line["correct"] is False
    # an untraced line: the contract's keys and the two end-to-end metrics
    assert set(line) == LINE_KEYS and set(line["metrics"]) == {
        "train_examples_per_s_per_chip", "setup_s"}
    value, ok = checks_of(capsys.readouterr().out)["param_change_gap"]
    assert not ok and value == pytest.approx(1.0, abs=1e-3)


def test_the_parent_program_fails_the_cell_at_once(monkeypatch):
    """A program that does not know the model (the parent of PR 48) is
    refused where the configuration is built, before a shard is written or
    a device touched."""
    from benchmark.drivers import _program

    def unknown(flags):
        raise ValueError("unknown model: 'glm4_moe_lite'")
    monkeypatch.setattr(_program, "make_config", unknown)
    started = []
    monkeypatch.setattr(train_glm4_moe_lite.traffic_sequences, "ShardWriter",
                        lambda *a, **k: started.append(a))
    with pytest.raises(ValueError, match="unknown model"):
        harness.run(CELL, 1, 1.0, False, overrides=TINY, require_chip=False)
    assert not started
