"""The benchmark's pieces for ``kimi-linear-48b-a3b.train-sequences-8k``:
the configuration file against the catalog row's numbers, its parameter
count against the model's own leaves, the roofline counts by hand, the
readers on hand-made contexts, a tiny-size CPU rehearsal of the cell through
the harness's test-only seam (untraced and traced), its control (one
precision lower), three broken programs that have to be judged not correct,
and the parent's program refusing the cell at once. Nothing here measures a
speed."""

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import control, harness, roofline_kimi_linear  # noqa: E402
from benchmark.drivers import train_kimi_linear  # noqa: E402
from benchmark.readers import roofline_kimi_linear as reader  # noqa: E402

CELL = "kimi-linear-48b-a3b.train-sequences-8k"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CONFIG = harness.load_json("configs", "kimi-linear-48b-a3b.json")
FLAGS = CONFIG["flags"]

#: The catalog row ``Kimi-Linear-48B-A3B-Instruct``'s ``config``
#: (model-configs guide).
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}

# The cell cut to a size a CPU rehearses in seconds (three layers, one of each
# kind), in float32 (its control is then bfloat16); the limits are this
# size's own. The window is 3 s: under the suite's six workers a step of this
# size takes up to a quarter of a second, and a window closes on 10.
TINY = {
    "config": {"vocabulary_rows": 100},
    "flags": {"feature_size": 100, "embedding_size": 32,
              "history_max_len": 32, "decoder_layers": 3, "attn_every": 3,
              "kda_heads": 2, "kda_head_dim": 8,
              "attn_q_heads": 2, "attn_kv_heads": 2, "attn_head_dim": 8,
              "mla_latent_dim": 16, "mla_rope_dim": 4, "dense_mlp_width": 64,
              "moe_experts": 8, "moe_top_k": 2, "moe_expert_width": 16,
              "moe_shared_width": 16, "moe_experts_held": 2,
              "moe_pair_capacity": 128, "learning_rate": 1e-3,
              "compute_dtype": "float32"},
    "traffic": {"shards": 2, "sequences_per_shard": 64,
                "sequence_length": 32,
                "limits": {"xent_gap": 1e-4, "first_moment_gap": 2e-3,
                           "param_change_gap": 0.1,
                           "untouched_rows_moved": 0,
                           "pairs_over_buffer": 0}},
}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(seed=2 ** 31 + 7, trace=False, **flags):
    over = {**TINY, "flags": {**TINY["flags"], **flags}}
    return harness.run(CELL, seed, 3.0, trace, overrides=over,
                       require_chip=False)


# ----------------------------------------------------------- configuration

def test_configuration_runs_every_published_width():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "kimi-linear-48b-a3b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts_held", "kda_heads_held",
        "attention_heads_held", "vocabulary_rows"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    for key, value in CATALOG.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    published = {"num_hidden_layers": 27, "num_experts_held": 256,
                 "kda_heads_held": 32, "attention_heads_held": 32,
                 "vocabulary_rows": 163840}
    held = {"num_hidden_layers": 5, "num_experts_held": 16,
            "kda_heads_held": 2, "attention_heads_held": 2,
            "vocabulary_rows": 20480}
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == published[key], key
        assert CONFIG[key] == held[key], key
    # a sixteenth of a layer's heads and experts, an eighth of the
    # vocabulary: the guide's floors kept
    assert all(held[k] * 16 == published[k] for k in (
        "num_experts_held", "kda_heads_held", "attention_heads_held"))
    assert held["vocabulary_rows"] * 8 == published["vocabulary_rows"]
    assert held["num_hidden_layers"] - CATALOG["first_k_dense_replace"] >= 4
    assert held["num_experts_held"] >= 8
    # the flags the program is built with say the same, every width whole
    lin = CATALOG["linear_attn_config"]
    assert (FLAGS["embedding_size"], FLAGS["kda_head_dim"], FLAGS["kda_conv"],
            FLAGS["mla_latent_dim"], FLAGS["attn_head_dim"],
            FLAGS["mla_rope_dim"], FLAGS["moe_expert_width"],
            FLAGS["moe_shared_width"], FLAGS["dense_mlp_width"],
            FLAGS["moe_experts"], FLAGS["moe_top_k"],
            FLAGS["moe_route_scale"], FLAGS["rms_norm_eps"]) == (
        CATALOG["hidden_size"], lin["head_dim"],
        lin["short_conv_kernel_size"], CATALOG["kv_lora_rank"],
        CATALOG["qk_nope_head_dim"], CATALOG["qk_rope_head_dim"],
        CATALOG["moe_intermediate_size"],
        CATALOG["moe_intermediate_size"] * CATALOG["num_shared_experts"],
        CATALOG["intermediate_size"], CATALOG["num_experts"],
        CATALOG["num_experts_per_token"], CATALOG["routed_scaling_factor"],
        CATALOG["rms_norm_eps"]) == (
        2304, 128, 4, 512, 128, 64, 1024, 1024, 9216, 256, 8, 2.446, 1e-5)
    assert CATALOG["v_head_dim"] == FLAGS["attn_head_dim"]
    assert (FLAGS["decoder_layers"], FLAGS["dense_layers"],
            FLAGS["moe_experts_held"], FLAGS["kda_heads"],
            FLAGS["attn_q_heads"], FLAGS["attn_kv_heads"],
            FLAGS["feature_size"]) == (5, 1, 16, 2, 2, 2, 20480)
    # the layers the cut runs are the published pattern's first five
    kinds = roofline_kimi_linear.layer_kinds(FLAGS)
    assert [i + 1 for i, (m, _) in enumerate(kinds) if m == "mla"] == [
        n for n in lin["full_attn_layers"] if n <= 5]
    assert [i + 1 for i, (m, _) in enumerate(kinds) if m == "kda"] == [
        n for n in lin["kda_layers"] if n <= 5]
    assert [f for _, f in kinds] == ["mlp"] + ["moe"] * 4
    # twice the mean held pairs of a layer: T * top_k * held / experts
    assert FLAGS["moe_pair_capacity"] == 2 * (2 * 8192 * 8 * 16 // 256)
    assert (FLAGS["optimizer"], FLAGS["learning_rate"], FLAGS["l2_reg"],
            FLAGS["compute_dtype"]) == ("Adam", 1e-05, 0.0, "bfloat16")
    for key in ("kda_details", "decay_init", "selection_bias",
                "balance_loss", "packing", "weights", "precision",
                "router_placement", "from_memory"):
        assert CONFIG["assumed"][key]
    assert "16 chips share each layer" in CONFIG["deployment"]


def test_parameter_count_is_the_models_own_leaves():
    import jax

    from benchmark.drivers import _program
    from deepfm_tpu.models import get_model

    model = get_model(_program.make_config(FLAGS))
    shapes, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layers = [sum(int(np.prod(x.shape)) for x in jax.tree.leaves(lp))
              for _, lp in sorted(shapes["layers"].items())]
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    got = roofline_kimi_linear.param_count(FLAGS)
    assert layers == [got["kda"] + got["mlp"], got["kda"] + got["moe"],
                      got["kda"] + got["moe"], got["mla"] + got["moe"],
                      got["kda"] + got["moe"]]
    assert total == got["all"] == CONFIG["parameters"] == 656_909_064
    assert f"{total:,}" in CONFIG["deployment"]
    # by hand: a KDA mixer of 2 heads, the MLA mixer, an expert layer
    kda = (3 * 2304 * 256 + 3 * 4 * 256 + 2 * (2304 * 128 + 128 * 256)
           + 256 + 2 + 2304 * 2 + 128 + 256 * 2304 + 2 * 2304)
    mla = (2304 * 2 * 192 + 2304 * 576 + 512 + 512 * 2 * 256
           + 2 * 128 * 2304 + 2 * 2304)
    moe = 2304 * 256 + 17 * 3 * 2304 * 1024
    assert (got["kda"], got["mla"], got["moe"], got["mlp"]) == (
        kda, mla, moe, 3 * 2304 * 9216)
    # float32 weight and gradient, Adam's two moments: 16 bytes a parameter
    assert round(16 * total / 1e9, 2) == 10.51


def test_the_cell_its_traffic_and_its_who_are_the_issues():
    cell = harness.load_cell(CELL)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "kimi-linear-48b-a3b", "train-sequences-8k", 1)
    assert len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["kind"], t["shards"], t["sequences_per_shard"],
            t["sequence_length"], t["sequences_per_step"], t["max_epochs"],
            t["zipf_exponent"]) == ("train-sequences", 16, 128, 8192, 2,
                                    1000, 1.05)
    assert "hybrid linear-attention MoE checkpoint" in t["who"]
    assert set(t["limits"]) == set(t["limits_why"]) == {
        "xent_gap", "first_moment_gap", "param_change_gap",
        "untouched_rows_moved", "pairs_over_buffer"}
    assert cell.driver == "train_kimi_linear"
    assert set(cell.end_to_end) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    assert {"train_kda_device_ms", "train_mlp_device_ms",
            "train_kda_scan_roofline", "train_step_roofline.kimi_linear",
            "train_attn_device_ms", "train_moe_device_ms",
            "train_moe_roofline",         # every expert cell's since PR 50
            "train_head_device_ms", "moe_expert_load_max_over_mean",
            "moe_pairs_over_buffer", "device_idle_share.train",
            "peak_hbm_gb.train", "train_step_device_ms",
            "train_embed_device_ms", "train_update_device_ms",
            "train_dense_device_ms",      # none of its scopes here: reads 0
            "train_unscoped_device_ms", "compiles_in_window.train",
            "dispatch_interval_ms_p50", "input_ns_per_record",
            "stage_transfer_ms", "input_wait_ms_max", "input_busy_share",
            } <= set(cell.per_layer)        # what a later PR adds is welcome
    # not on `host_gc_ms_max` (no collection in a window of 25 dispatches)
    # nor on SDAR's step share (its count reads `diffusion_block`, a flag
    # this model has not)
    for name in ("host_gc_ms_max", "train_step_roofline.sdar_moe"):
        assert CELL not in next(m for m in BENCH["per_layer"]
                                if m["name"] == name)["workloads"]
    for name in cell.per_layer:
        spec = harness.load_json("metrics", f"{name}.json")
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "readers", spec["reader"] + ".py")), name
    # the four the cell came with, found by name (a later PR appends its
    # own, and later cells share the two scope metrics): its own rooflines
    # are its alone, and it was the first on the shared ones' lists
    new = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in (
        "train_kda_device_ms", "train_mlp_device_ms",
        "train_kda_scan_roofline", "train_step_roofline.kimi_linear")}
    assert len(new) == 4
    assert all(m["workloads"][0] == CELL for m in new.values())
    assert all(new[name]["workloads"] == [CELL] for name in (
        "train_kda_scan_roofline", "train_step_roofline.kimi_linear"))


def test_roofline_counts_by_hand():
    flags = {**FLAGS, "history_max_len": 8, "batch_size": 1,
             "decoder_layers": 5, "feature_size": 10, "embedding_size": 4,
             "kda_heads": 2, "kda_head_dim": 3, "attn_q_heads": 2,
             "attn_kv_heads": 2, "attn_head_dim": 2, "mla_rope_dim": 1,
             "mla_latent_dim": 5, "dense_mlp_width": 6, "moe_experts": 8,
             "moe_expert_width": 3, "moe_shared_width": 7}
    got = roofline_kimi_linear.forward_flops(flags, pairs=5)
    assert got == {
        # 4 KDA layers: q, k, v (3 * 4 * 6), two bottlenecks (4*3 + 3*6
        # each), beta (4 * 2), wo (6 * 4)
        "kda_projections": 2.0 * 8 * 4 * (72 + 2 * 30 + 8 + 24),
        # 1 MLA layer: wq 4 * 2 * 3, w_kva 4 * 6, w_kvb 5 * 2 * 4, wo 4 * 4
        "mla_projections": 2.0 * 8 * (24 + 24 + 40 + 16),
        # 36 causal pairs, 2 heads, scores 3 wide and values 2 wide
        "mla_attention": 2.0 * 2 * 36 * 5,
        "router": 2.0 * 8 * 4 * 4 * 8,
        "experts": 2.0 * 5 * 3 * 4 * 3,
        "shared": 2.0 * 8 * 4 * 3 * 4 * 7,
        "dense_mlp": 2.0 * 8 * 3 * 4 * 6,
        "head": 2.0 * 7 * 4 * 10}
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e9}
    least = roofline_kimi_linear.train_step_least_seconds(flags, 5, peaks)
    assert least["flops"] == 3 * sum(got.values())
    assert least["bound"] == "flops"
    assert least["seconds"] == least["flops"] / 1e3
    scan = roofline_kimi_linear.kda_scan_least_seconds(flags, peaks)
    # 8 positions, 2 heads, 4 layers; 7 * 3 * 3 FLOPs forward a token-head;
    # q, k, g 3 each, v and o 3 each, beta: 16 float32, both ways
    assert scan["flops"] == 3.0 * 63 * 64
    assert scan["bytes"] == 2.0 * 4 * 16 * 64
    # the cell's own, by ISSUE 33's arithmetic: 337 MFLOP a token forward
    # (dense MLP 127, head 94, shared experts 57, the mixers' projections
    # 30, routed pairs 28)
    cell = roofline_kimi_linear.forward_flops(FLAGS, pairs=4 * 8192)
    per = 16384 * 1e6
    assert round(cell["dense_mlp"] / per) == 127
    assert round(cell["head"] / per) == 94
    assert round(cell["shared"] / per) == 57
    assert round((cell["kda_projections"] + cell["mla_projections"])
                 / per) == 30
    assert round(cell["experts"] / per) == 28
    v5e = harness.peaks_for("TPU v5 lite")
    step = roofline_kimi_linear.train_step_least_seconds(FLAGS, 4 * 8192,
                                                         v5e)
    assert step["bound"] == "flops" and 0.08 < step["seconds"] < 0.09
    scan = roofline_kimi_linear.kda_scan_least_seconds(FLAGS, v5e)
    assert scan["bound"] == "bytes" and 0.7e-3 < scan["seconds"] < 0.9e-3


def test_roofline_reader_shares_and_nothing_to_read(monkeypatch):
    def ctx(trace=True, **counters):
        return types.SimpleNamespace(
            cell=types.SimpleNamespace(name=CELL, config={"flags": FLAGS}),
            devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
            counters={"steps_in_window": 10, **counters},
            trace={"devices": 1, "busy_s": 5.0} if trace else None,
            window=(0, 1))
    pairs = 4 * 8192.0
    peaks = harness.peaks_for("TPU v5 lite")
    least = roofline_kimi_linear.train_step_least_seconds(
        FLAGS, pairs, peaks)["seconds"]
    assert reader.read(ctx(moe_pairs_held_per_step=pairs), "step") \
        == pytest.approx(100 * least / 0.5)
    assert reader.read(ctx(), "step") is None
    assert reader.read(ctx(trace=False, moe_pairs_held_per_step=pairs),
                       "step") is None
    monkeypatch.setattr(reader.scope_device_ms, "read",
                        lambda c, scopes: {"kda_scan": 200.0}[scopes[0]])
    scan = roofline_kimi_linear.kda_scan_least_seconds(FLAGS, peaks)
    assert reader.read(ctx(moe_pairs_held_per_step=pairs), "kda_scan") \
        == pytest.approx(100 * scan["seconds"] / 0.2)
    monkeypatch.setattr(reader.scope_device_ms, "read",
                        lambda c, scopes: None)
    assert reader.read(ctx(moe_pairs_held_per_step=pairs),
                       "kda_scan") is None
    with pytest.raises(ValueError):
        reader.read(ctx(moe_pairs_held_per_step=pairs), "mfu")


# ------------------------------------------------------------- rehearsals

@pytest.fixture(scope="module")
def line():
    return rehearse()


def test_rehearsal_prints_the_contracts_keys(line):
    assert set(line) == LINE_KEYS and line["correct"] is True
    assert set(line["metrics"]) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_rehearsal_prints_every_listed_metric():
    """A traced run's line carries each per-layer metric the cell lists
    that has something to read on a CPU (no device plane in its trace: the
    device-trace metrics are left out, not failed)."""
    out = rehearse(trace=True)
    assert out["correct"] is True
    cell = harness.load_cell(CELL)
    assert set(out["metrics"]) <= set(cell.per_layer)
    assert {"moe_pairs_over_buffer", "moe_expert_load_max_over_mean",
            "compiles_in_window.train", "dispatch_interval_ms_p50",
            "input_ns_per_record"} <= set(out["metrics"])
    assert out["metrics"]["moe_pairs_over_buffer"]["value"] == 0


def test_one_precision_lower_is_judged_not_correct():
    out = control.run(CELL, 5, 3.0, overrides=TINY, require_chip=False)
    assert out["correct"] is False


def test_a_step_that_leaves_the_parameters_unchanged_is_caught(monkeypatch):
    import deepfm_tpu.train.loop as loop

    monkeypatch.setattr(loop.optax, "apply_updates",
                        lambda params, updates: params)
    assert rehearse()["correct"] is False


def test_a_recurrence_with_its_decay_left_out_is_caught(monkeypatch):
    from deepfm_tpu.models import kimi_linear

    scan = kimi_linear.kda_scan
    monkeypatch.setattr(
        kimi_linear, "kda_scan",
        lambda q, k, v, g, beta, **kw: scan(q, k, v, g * 0.0, beta, **kw))
    assert rehearse()["correct"] is False


def test_pairs_over_the_buffer_fail_the_run(capsys):
    assert rehearse(moe_pair_capacity=8)["correct"] is False
    out = capsys.readouterr().out
    over = [ln for ln in out.splitlines()
            if ln.startswith("check pairs_over_buffer")][-1]
    assert over.endswith("NOT OK")


def test_the_parent_program_fails_the_cell_at_once(monkeypatch):
    """A program that does not know the model (the parent of PR 33) is
    refused where the configuration is built, before a shard is written or
    a device touched."""
    from benchmark.drivers import _program

    def unknown(flags):
        raise TypeError("Config.__init__() got an unexpected keyword "
                        "argument 'attn_every'")
    monkeypatch.setattr(_program, "make_config", unknown)
    started = []
    monkeypatch.setattr(train_kimi_linear.traffic_sequences, "ShardWriter",
                        lambda *a, **k: started.append(a))
    with pytest.raises(TypeError):
        harness.run(CELL, 1, 1.0, False, overrides=TINY, require_chip=False)
    assert not started


def test_a_layers_decay_vectors_are_judged_as_one_leaf():
    """``kda_a_log`` has one element a held head: its sign-like first Adam
    steps make a relative norm of two elements a coin's throw, so the check
    pools it with its layer's ``kda_dt_bias``."""
    tree = {"layers.0.kda_a_log": np.array([1.0, 2.0]),
            "layers.0.kda_dt_bias": np.arange(4.0).reshape(2, 2),
            "layers.3.mla_wq": np.ones((2, 2)), "tok_emb": np.ones((3, 2))}
    got = train_kimi_linear.pooled(tree)
    assert set(got) == {"layers.0.kda_decay", "layers.3.mla_wq", "tok_emb"}
    np.testing.assert_array_equal(got["layers.0.kda_decay"],
                                  [1.0, 2.0, 0.0, 1.0, 2.0, 3.0])
    assert got["tok_emb"] is tree["tok_emb"] and len(tree) == 4
