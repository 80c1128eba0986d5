"""The benchmark's pieces for ``solar-open2-250b.train-sequences-8k-b1``: the
configuration file against the catalog row's numbers, its parameter count
against the model's own leaves, the roofline counts by hand, the readers on
hand-made contexts, a tiny-size CPU rehearsal of the cell through the
harness's test-only seam (untraced and traced), its control (one precision
lower), the three faults of ISSUE 37 step 2 and two more broken programs that
have to be judged not correct, and the parent's program refusing the cell at
once. Nothing here measures a speed."""

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

from benchmark import control, harness, roofline_solar_open2  # noqa: E402
from benchmark.drivers import train_solar_open2  # noqa: E402
from benchmark.readers import roofline_solar_open2 as reader  # noqa: E402

CELL = "solar-open2-250b.train-sequences-8k-b1"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CONFIG = harness.load_json("configs", "solar-open2-250b.json")
FLAGS = CONFIG["flags"]

#: The catalog row ``Solar-Open2-250B``'s ``config`` (model-configs guide).
CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}

# The cell cut to a size a CPU rehearses in seconds (one period: the full
# layer and three KDA layers), in float32 (its control is then bfloat16); the
# limits are this size's own. 16 experts, 2 a token, 2 held: the plan's
# period is 4. The window is 3 s, as the Kimi-Linear cell's rehearsal.
TINY = {
    "config": {"vocabulary_rows": 100},
    "flags": {"feature_size": 100, "embedding_size": 32,
              "history_max_len": 32, "decoder_layers": 4, "attn_every": 4,
              "kda_heads": 2, "kda_head_dim": 8,
              "attn_q_heads": 4, "attn_kv_heads": 1, "attn_head_dim": 8,
              "moe_experts": 16, "moe_top_k": 2, "moe_expert_width": 16,
              "moe_shared_width": 16, "moe_experts_held": 2,
              "moe_pair_capacity": 128, "learning_rate": 1e-3,
              "compute_dtype": "float32"},
    "traffic": {"shards": 2, "sequences_per_shard": 64,
                "sequence_length": 32,
                "limits": {"xent_gap": 1e-4, "first_moment_gap": 2e-3,
                           "first_moment_gap_unrouted": 2e-3,
                           "param_change_gap": 0.1,
                           "untouched_rows_moved": 0,
                           "pairs_over_buffer": 0}},
}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


#: What a run that has to come out not correct is cut to besides: the full
#: layer and one KDA layer (half the sound rehearsal's compilation, which is
#: most of a rehearsal's time here).
SHORT = {"decoder_layers": 2, "attn_every": 2}


def rehearse(seed=2 ** 31 + 7, trace=False, **flags):
    over = {**TINY, "flags": {**TINY["flags"], **flags}}
    return harness.run(CELL, seed, 3.0, trace, overrides=over,
                       require_chip=False)


# ----------------------------------------------------------- configuration

def test_configuration_runs_every_published_width():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "solar-open2-250b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts_held", "kda_heads_held",
        "attention_heads_held", "key_value_heads_held", "vocabulary_rows"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
        "config.json")
    assert entry["file"] == "benchmark/configs/solar-open2-250b.json"
    assert len(entry["why"]) <= 200
    for key, value in CATALOG.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    published = {"num_hidden_layers": 48, "num_experts_held": 320,
                 "kda_heads_held": 64, "attention_heads_held": 64,
                 "key_value_heads_held": 8, "vocabulary_rows": 196608}
    held = {"num_hidden_layers": 4, "num_experts_held": 8,
            "kda_heads_held": 8, "attention_heads_held": 8,
            "key_value_heads_held": 1, "vocabulary_rows": 24576}
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == published[key], key
        assert CONFIG[key] == held[key], key
    # a fortieth of a layer's experts, an eighth of its heads and of the
    # vocabulary: the guide's floors kept (a whole period, 8 experts, 1/8)
    assert held["num_experts_held"] * 40 == published["num_experts_held"]
    assert all(held[k] * 8 == published[k] for k in (
        "kda_heads_held", "attention_heads_held", "key_value_heads_held",
        "vocabulary_rows"))
    assert held["num_hidden_layers"] == CATALOG["gqa_interval"] + 1 >= 4
    assert held["num_experts_held"] >= 8
    # the flags the program is built with say the same, every width whole
    lin = CATALOG["linear_attn_config"]
    assert (FLAGS["embedding_size"], FLAGS["kda_head_dim"], FLAGS["kda_conv"],
            FLAGS["attn_head_dim"], FLAGS["moe_expert_width"],
            FLAGS["moe_shared_width"], FLAGS["moe_experts"],
            FLAGS["moe_top_k"], FLAGS["moe_route_scale"],
            FLAGS["rms_norm_eps"]) == (
        CATALOG["hidden_size"], lin["head_dim"],
        lin["short_conv_kernel_size"], CATALOG["head_dim"],
        CATALOG["moe_intermediate_size"],
        CATALOG["moe_intermediate_size"] * CATALOG["n_shared_experts"],
        CATALOG["n_routed_experts"], CATALOG["num_experts_per_tok"],
        CATALOG["routed_scaling_factor"], CATALOG["rms_norm_eps"]) == (
        4096, 128, 4, 128, 1280, 1280, 320, 8, 1, 1e-5)
    # the group of 8 query heads a key/value head is the published one
    assert FLAGS["attn_q_heads"] // FLAGS["attn_kv_heads"] == (
        CATALOG["num_attention_heads"] // CATALOG["num_key_value_heads"]) == 8
    assert (FLAGS["decoder_layers"], FLAGS["attn_every"],
            FLAGS["moe_experts_held"], FLAGS["kda_heads"],
            FLAGS["attn_q_heads"], FLAGS["attn_kv_heads"],
            FLAGS["feature_size"], FLAGS["batch_size"]) == (
        4, 4, 8, 8, 8, 1, 24576, 1)
    # the layers the cut runs are the published pattern's first period
    kinds = roofline_solar_open2.layer_kinds(FLAGS)
    assert [i for i, (m, _) in enumerate(kinds) if m == "gqa"] == [
        n for n in CATALOG["gqa_layers"] if n < 4] == [0]
    assert [f for _, f in kinds] == ["moe"] * 4     # no dense layer
    assert "dense_layers" not in FLAGS and "mla_latent_dim" not in FLAGS
    # twice the mean held pairs of a layer, T * top_k * held / experts (the
    # other decoder cells' rule), up to the next multiple of the grouped
    # products' tile: the rows the program holds for ISSUE 37's 3,280 too
    from deepfm_tpu.models import sdar_moe
    mean = 8192 * 8 * 8 / 320
    assert mean == 1638.4
    assert FLAGS["moe_pair_capacity"] == sdar_moe.pass_rows(3280)[1] \
        == -(-int(2 * mean + 0.5) // 256) * 256 == 3328
    assert (FLAGS["optimizer"], FLAGS["learning_rate"], FLAGS["l2_reg"],
            FLAGS["compute_dtype"]) == ("Adam", 1e-05, 0.0, "bfloat16")
    for key in ("router_score", "attention_details", "kda_details",
                "decay_init", "balance_loss", "packing", "weights",
                "precision", "router_placement", "router_placement_band",
                "moe_pair_capacity", "from_memory"):
        assert CONFIG["assumed"][key]
    assert "40 chips share each expert layer" in CONFIG["deployment"]
    assert "a fifth of the deployment's" in CONFIG["deployment"]
    for unused in ("intermediate_size", "rope_theta",
                   "partial_rotary_factor"):
        assert unused in CONFIG["published"]["note"]


def test_parameter_count_is_the_models_own_leaves():
    import jax

    from benchmark.drivers import _program
    from deepfm_tpu.models import get_model

    model = get_model(_program.make_config(FLAGS))
    shapes, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layers = [sum(int(np.prod(x.shape)) for x in jax.tree.leaves(lp))
              for _, lp in sorted(shapes["layers"].items())]
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    got = roofline_solar_open2.param_count(FLAGS)
    assert layers == [got["gqa"] + got["moe"]] + [got["kda"] + got["moe"]] * 3
    assert total == got["all"] == CONFIG["parameters"] == 840_871_320
    assert f"{total:,}" in CONFIG["deployment"]
    # by hand (ISSUE 37's table): the GQA mixer, a KDA mixer of 8 heads, an
    # expert layer, the ends
    gqa = 3 * 4096 * 1024 + 2 * 4096 * 128 + 2 * 4096
    kda = (3 * 4096 * 1024 + 3 * 4 * 1024 + 2 * (4096 * 128 + 128 * 1024)
           + 1024 + 8 + 4096 * 8 + 128 + 1024 * 4096 + 2 * 4096)
    moe = 4096 * 320 + 9 * 3 * 4096 * 1280
    assert (got["gqa"], got["kda"], got["moe"], got["ends"]) == (
        gqa, kda, moe, 2 * 24576 * 4096 + 4096) == (
        13_639_680, 18_142_344, 142_868_480, 201_330_688)
    # float32 weight and gradient, Adam's two moments: 16 bytes a parameter
    assert round(16 * total / 1e9, 2) == 13.45
    assert round(12 * total / 1e9, 2) == 10.09


def test_the_cell_its_traffic_and_its_who_are_the_issues():
    cell = harness.load_cell(CELL)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "solar-open2-250b", "train-sequences-8k-b1", 1)
    assert len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["kind"], t["shards"], t["sequences_per_shard"],
            t["sequence_length"], t["sequences_per_step"], t["max_epochs"],
            t["zipf_exponent"]) == ("train-sequences", 16, 128, 8192, 1,
                                    1000, 1.05)
    assert "250B hybrid linear-attention MoE checkpoint" in t["who"]
    assert set(t["limits"]) == set(t["limits_why"]) == {
        "xent_gap", "first_moment_gap", "first_moment_gap_unrouted",
        "param_change_gap", "untouched_rows_moved", "pairs_over_buffer"}
    # the unrouted leaves' limit is the tighter one, by a wide margin
    assert t["limits"]["first_moment_gap_unrouted"] * 5 < t["limits"][
        "first_moment_gap"]
    assert cell.driver == "train_solar_open2"
    assert set(cell.end_to_end) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    new = {"train_attn_scores_device_ms",
           "train_attn_scores_roofline.solar_open2",
           "train_kda_scan_roofline.solar_open2",
           "train_step_roofline.solar_open2"}
    assert {"train_kda_device_ms", "train_mlp_device_ms",
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
            } | new <= set(cell.per_layer)  # what a later PR adds is welcome
    # not on `host_gc_ms_max` (PERF.md section 7 row 20(c)) nor on the other
    # models' rooflines (entries are found by name, never by position: a
    # later PR appends its own)
    for m in BENCH["per_layer"]:
        if m["name"] in ("host_gc_ms_max", "train_kda_scan_roofline",
                         "train_step_roofline.kimi_linear"):
            assert CELL not in m["workloads"], m["name"]
    for name in cell.per_layer:
        spec = harness.load_json("metrics", f"{name}.json")
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "readers", spec["reader"] + ".py")), name
    mine = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in new}
    assert set(mine) == new
    assert all(CELL in m["workloads"] and m["moves"]
               == "train_examples_per_s_per_chip" for m in mine.values())
    assert all(m["unit"] == "%" for n, m in mine.items() if "roofline" in n)


def test_roofline_counts_by_hand():
    flags = {**FLAGS, "history_max_len": 8, "batch_size": 1,
             "decoder_layers": 4, "feature_size": 10, "embedding_size": 4,
             "kda_heads": 2, "kda_head_dim": 3, "attn_q_heads": 4,
             "attn_kv_heads": 2, "attn_head_dim": 2, "moe_experts": 8,
             "moe_expert_width": 3, "moe_shared_width": 7}
    got = roofline_solar_open2.forward_flops(flags, pairs=5)
    assert got == {
        # 1 full layer: wq 4 * 8, wk and wv 4 * 4 each, the gate 4 * 8, wo 8 * 4
        "gqa_projections": 2.0 * 8 * (32 + 2 * 16 + 32 + 32),
        # 36 causal pairs, 4 heads, scores and values 2 wide each
        "gqa_attention": 2.0 * 4 * 36 * 4,
        # 3 KDA layers: q, k, v (3 * 4 * 6), two bottlenecks (4*3 + 3*6
        # each), beta (4 * 2), wo (6 * 4)
        "kda_projections": 2.0 * 8 * 3 * (72 + 2 * 30 + 8 + 24),
        "router": 2.0 * 8 * 4 * 4 * 8,
        "experts": 2.0 * 5 * 3 * 4 * 3,
        "shared": 2.0 * 8 * 4 * 3 * 4 * 7,
        "head": 2.0 * 7 * 4 * 10}
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e9}
    least = roofline_solar_open2.train_step_least_seconds(flags, 5, peaks)
    assert least["flops"] == 3 * sum(got.values())
    assert least["bound"] == "flops"
    assert least["seconds"] == least["flops"] / 1e3
    scan = roofline_solar_open2.kda_scan_least_seconds(flags, peaks)
    # 8 positions, 2 heads, 3 layers; 7 * 3 * 3 FLOPs forward a token-head;
    # q, k, g 3 each, v and o 3 each, beta: 16 float32, both ways
    assert scan["flops"] == 3.0 * 63 * 48
    assert scan["bytes"] == 2.0 * 4 * 16 * 48
    scores = roofline_solar_open2.attn_scores_least_seconds(flags, peaks)
    # forward and twice that backward; q and o 4 heads, k and v 2, of 2
    # elements of 2 bytes over 8 positions, both ways
    assert scores["flops"] == 3.0 * got["gqa_attention"]
    assert scores["bytes"] == 2.0 * 2 * (2 * 4 + 2 * 2) * 2 * 8
    # the cell's own: 513 MFLOP a token forward (head 201, the shared
    # experts 126, the KDA mixers' projections 109, the full layer's 27 and
    # its causal scores 17, the routed pairs 23 at 0.9 of a balanced load,
    # the routers 10)
    cell = roofline_solar_open2.forward_flops(FLAGS, pairs=0.9 * 4 * 1638.4)
    per = 8192 * 1e6
    assert [round(cell[k] / per) for k in (
        "head", "shared", "kda_projections", "gqa_projections",
        "gqa_attention", "experts", "router")] == [201, 126, 109, 27, 17, 23,
                                                   10]
    v5e = harness.peaks_for("TPU v5 lite")
    step = roofline_solar_open2.train_step_least_seconds(
        FLAGS, 0.9 * 4 * 1638.4, v5e)
    assert step["bound"] == "flops" and 0.06 < step["seconds"] < 0.07
    assert step["bytes"] == 40 * 840_871_320
    scan = roofline_solar_open2.kda_scan_least_seconds(FLAGS, v5e)
    assert scan["bound"] == "bytes" and 1.1e-3 < scan["seconds"] < 1.4e-3
    scores = roofline_solar_open2.attn_scores_least_seconds(FLAGS, v5e)
    assert scores["bound"] == "flops" and 2.0e-3 < scores["seconds"] < 2.2e-3
    # the same count as roofline_kimi_linear's scan: 3 KDA layers of 8 heads
    # over 8,192 positions are 1.5 times its 4 layers of 2 heads over 16,384
    from benchmark import roofline_kimi_linear
    kimi = roofline_kimi_linear.kda_scan_least_seconds(
        harness.load_json("configs", "kimi-linear-48b-a3b.json")["flags"],
        v5e)
    assert scan["seconds"] == pytest.approx(1.5 * kimi["seconds"])


def test_roofline_reader_shares_and_nothing_to_read(monkeypatch):
    def ctx(trace=True, **counters):
        return types.SimpleNamespace(
            cell=types.SimpleNamespace(name=CELL, config={"flags": FLAGS}),
            devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
            counters={"steps_in_window": 10, **counters},
            trace={"devices": 1, "busy_s": 5.0} if trace else None,
            window=(0, 1))
    pairs = 0.9 * 4 * 1638.4
    peaks = harness.peaks_for("TPU v5 lite")
    least = roofline_solar_open2.train_step_least_seconds(
        FLAGS, pairs, peaks)["seconds"]
    assert reader.read(ctx(moe_pairs_held_per_step=pairs), "step") \
        == pytest.approx(100 * least / 0.5)
    assert reader.read(ctx(), "step") is None
    assert reader.read(ctx(trace=False, moe_pairs_held_per_step=pairs),
                       "step") is None
    monkeypatch.setattr(
        reader.scope_device_ms, "read",
        lambda c, scopes: {"kda_scan": 200.0, "attn_scores": 10.0}[scopes[0]])
    scan = roofline_solar_open2.kda_scan_least_seconds(FLAGS, peaks)
    assert reader.read(ctx(moe_pairs_held_per_step=pairs), "kda_scan") \
        == pytest.approx(100 * scan["seconds"] / 0.2)
    scores = roofline_solar_open2.attn_scores_least_seconds(FLAGS, peaks)
    assert reader.read(ctx(moe_pairs_held_per_step=pairs), "attn_scores") \
        == pytest.approx(100 * scores["seconds"] / 0.01)
    # a program from before the scope: nothing to read, and nothing raised
    monkeypatch.setattr(reader.scope_device_ms, "read",
                        lambda c, scopes: None)
    for share in ("kda_scan", "attn_scores"):
        assert reader.read(ctx(moe_pairs_held_per_step=pairs), share) is None
    with pytest.raises(ValueError):
        reader.read(ctx(moe_pairs_held_per_step=pairs), "mfu")


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
    # (a traced line holds the per-layer metrics in place of the end-to-end
    # ones, and adds its breakdown and the device's busy time; the untraced
    # line's keys are held by test_pairs_over_the_buffer_fail_the_run)
    assert set(line) == LINE_KEYS | {"breakdown"} and line["correct"] is True
    assert line["metrics"] and all(
        set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s", "window_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_rehearsal_prints_every_listed_metric(traced):
    """A traced run's line carries each per-layer metric the cell lists
    that has something to read on a CPU (no device plane in its trace: the
    device-trace metrics are left out, not failed); the counts' line says
    the negative-eigenvalue regime was exercised."""
    out, printed = traced
    cell = harness.load_cell(CELL)
    assert set(out["metrics"]) <= set(cell.per_layer)
    assert {"moe_pairs_over_buffer", "moe_expert_load_max_over_mean",
            "compiles_in_window.train", "dispatch_interval_ms_p50",
            "input_ns_per_record"} <= set(out["metrics"])
    assert out["metrics"]["moe_pairs_over_buffer"]["value"] == 0
    counts = [ln for ln in printed.splitlines()
              if ln.startswith("counts (")][-1]
    over_one = float(counts.split("kda_beta_over_one ")[1].split(";")[0])
    assert 0 < over_one < 3 * 32 * 2        # 3 KDA layers, 32 positions, 2 heads
    assert "kda_chunk_log_decay_min -" in counts


def test_one_precision_lower_is_judged_not_correct():
    over = {**TINY, "flags": {**TINY["flags"], **SHORT}}
    out = control.run(CELL, 5, 3.0, overrides=over, require_chip=False)
    assert out["correct"] is False


def _beta_without_its_factor(monkeypatch):
    from deepfm_tpu.models import solar_open2
    monkeypatch.setattr(solar_open2, "BETA_SCALE", 1.0)


def _gate_left_out(monkeypatch):
    from deepfm_tpu.models import solar_open2
    mixer = solar_open2.gqa_mixer
    # sigmoid(0) = 1/2 on every channel, times 2: no gate
    monkeypatch.setattr(
        solar_open2, "gqa_mixer", lambda lp, x, **kw: 2.0 * mixer(
            {**lp, "gqa_w_gate": 0.0 * lp["gqa_w_gate"]}, x, **kw))


def _a_key_one_block_ahead_readable(monkeypatch, block=8):
    from deepfm_tpu.models import sdar_moe, solar_open2
    monkeypatch.setattr(solar_open2, "causal", sdar_moe.ScoreMask(
        ("a block ahead", block), lambda q, k: k <= q + block))


FAULTS = {"beta-without-its-2": _beta_without_its_factor,
          "gate-left-out": _gate_left_out,
          "mask-not-causal": _a_key_one_block_ahead_readable}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_of_the_three_faults_is_caught(monkeypatch, fault):
    """ISSUE 37 step 2 at the rehearsal's size: the write strength without
    its factor 2, the full layer without its gate, a full layer that reads a
    block of keys ahead."""
    FAULTS[fault](monkeypatch)
    assert rehearse(**SHORT)["correct"] is False


def test_a_step_that_leaves_the_parameters_unchanged_is_caught(monkeypatch):
    import deepfm_tpu.train.loop as loop

    monkeypatch.setattr(loop.optax, "apply_updates",
                        lambda params, updates: params)
    assert rehearse(**SHORT)["correct"] is False


def test_pairs_over_the_buffer_fail_the_run(capsys):
    line = rehearse(moe_pair_capacity=8, **SHORT)
    assert line["correct"] is False
    # an untraced line: the contract's keys and the two end-to-end metrics
    assert set(line) == LINE_KEYS and set(line["metrics"]) == {
        "train_examples_per_s_per_chip", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    out = capsys.readouterr().out
    over = [ln for ln in out.splitlines()
            if ln.startswith("check pairs_over_buffer")][-1]
    assert over.endswith("NOT OK")


def test_the_parent_program_fails_the_cell_at_once(monkeypatch):
    """A program that does not know the model (the parent of PR 37) is
    refused where the configuration is built, before a shard is written or
    a device touched."""
    from benchmark.drivers import _program

    def unknown(flags):
        raise ValueError("unknown model: 'solar_open2'")
    monkeypatch.setattr(_program, "make_config", unknown)
    started = []
    monkeypatch.setattr(train_solar_open2.traffic_sequences, "ShardWriter",
                        lambda *a, **k: started.append(a))
    with pytest.raises(ValueError, match="unknown model"):
        harness.run(CELL, 1, 1.0, False, overrides=TINY, require_chip=False)
    assert not started


def test_the_step_counts_keep_both_kda_counts():
    counts = train_solar_open2.StepCounts()
    counts(None, {"loss": 1.0, "moe_pairs_held": 3, "kda_beta_over_one": 5,
                  "kda_chunk_log_decay_min": -2.0})
    assert counts.dispatches == [{"moe_pairs_held": 3, "kda_beta_over_one": 5,
                                  "kda_chunk_log_decay_min": -2.0}]
    got = counts.read(0, 1)
    assert got[train_solar_open2.BETA_OVER_ONE][0] == 5.0
    assert got[train_solar_open2.DECAY_MIN][0] == -2.0
