"""The benchmark's pieces for
``phi-4-mini-flash-reasoning.train-traces-8k-b1``: the configuration file
against the catalog row's numbers, its parameter count against the model's
own leaves, the roofline counts by hand, the reader on hand-made contexts, a
tiny-size CPU rehearsal of the cell through the harness's test-only seam
(traced), its control (one precision lower), the five faults of ISSUE 44 and
an unchanged state, each of which has to be judged not correct, the seeded
state, and the parent's program refusing the cell at once. Nothing here
measures a speed."""

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

from benchmark import control, harness, roofline_phi4_flash  # noqa: E402
from benchmark.drivers import (_program_phi4_flash,  # noqa: E402
                               train_phi4_flash)
from benchmark.readers import roofline_phi4_flash as reader  # noqa: E402

CELL = "phi-4-mini-flash-reasoning.train-traces-8k-b1"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CONFIG = harness.load_json("configs", "phi-4-mini-flash-reasoning.json")
FLAGS = CONFIG["flags"]

#: The catalog row ``Phi-4-mini-flash-reasoning``'s ``config``
#: (model-configs guide).
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}

# The cell cut to a size a CPU rehearses in seconds (the cut's own six
# kinds, published indices 14-19), in float32 (its control is then
# bfloat16); the limits are this size's own. The scan is XLA's form at its
# own chunks of 32: 32 positions are one chunk (the model's tests run
# several, and the kernels through the interpreter). The window is 1 s (a
# tiny step is milliseconds).
TINY = {
    "config": {"vocabulary_rows": 100},
    "flags": {"feature_size": 100, "embedding_size": 32,
              "history_max_len": 32, "attn_window": 6, "mamba_state": 4,
              "mamba_dt_rank": 2, "dense_mlp_width": 48, "attn_q_heads": 8,
              "attn_kv_heads": 4, "attn_head_dim": 8, "learning_rate": 1e-3,
              "compute_dtype": "float32"},
    "traffic": {"shards": 2, "sequences_per_shard": 64,
                "sequence_length": 32,
                "limits": {"xent_gap": 1e-4, "first_moment_gap": 2e-3,
                           "param_change_gap": 0.1}},
}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NEW = {"train_mamba_device_ms", "train_gmu_device_ms",
       "train_mamba_scan_roofline.phi4_flash",
       "train_attn_scores_roofline.phi4_flash",
       "train_matmul_roofline.phi4_flash", "train_step_roofline.phi4_flash"}


def rehearse(seed=2 ** 31 + 7, trace=False, **flags):
    over = {**TINY, "flags": {**TINY["flags"], **flags}}
    return harness.run(CELL, seed, 1.0, trace, overrides=over,
                       require_chip=False)


# ----------------------------------------------------------- configuration

def test_configuration_runs_every_published_width():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "phi-4-mini-flash-reasoning")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "vocabulary_rows"]
    assert entry["source"].startswith(CONFIG["source"]) and CONFIG[
        "source"] == ("https://huggingface.co/microsoft/"
                      "Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert "phi4flash" in entry["source"] and "2507.06607" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/phi-4-mini-flash-reasoning.json"
    for key, value in CATALOG.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    published = {"num_hidden_layers": 32, "vocabulary_rows": 200064}
    held = {"num_hidden_layers": 6, "vocabulary_rows": 25008}
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == published[key], key
        assert CONFIG[key] == held[key], key
    # an eighth of the vocabulary, the guide's floor
    assert held["vocabulary_rows"] * 8 == published["vocabulary_rows"]
    # published layers 14-19: one of each kind, by the reference's rule
    from benchmark import reference_phi4_flash as ref
    assert FLAGS["layer_types"].split(",") == [
        ref.kind(l) for l in range(14, 20)] == [
            "mamba", "window_attention", "mamba", "full_attention", "gmu",
            "cross_attention"]
    assert (FLAGS["decoder_layers"], FLAGS["first_layer"]) == (6, 14)
    # every width as published
    assert (FLAGS["embedding_size"], FLAGS["dense_mlp_width"],
            FLAGS["attn_window"], FLAGS["rms_norm_eps"]) == (
        CATALOG["hidden_size"], CATALOG["intermediate_size"],
        CATALOG["sliding_window"], CATALOG["layer_norm_eps"])
    assert (FLAGS["attn_q_heads"], FLAGS["attn_kv_heads"],
            FLAGS["attn_head_dim"]) == (40, 20, 2560 // 40)
    assert (FLAGS["mamba_state"], FLAGS["mamba_conv"], FLAGS["mamba_expand"],
            FLAGS["mamba_dt_rank"]) == (16, 4, 2, 2560 // 16)
    assert FLAGS["feature_size"] == CONFIG["vocabulary_rows"] == 25008
    assert not any(k.startswith("moe_") for k in FLAGS)
    for said in ("from_memory", "scan_sizes", "attention", "memory",
                 "initial_values", "dropout", "packing", "optimizer",
                 "precision", "weights"):
        assert said in CONFIG["assumed"], said
    assert "published layers 14-19" in CONFIG["deployment"]
    assert "What the cut distorts" in CONFIG["deployment"]
    assert "eight layers are 14.6 GB" in CONFIG["deployment"]


def test_parameter_count_is_the_models_own_leaves():
    import jax

    from benchmark.drivers import _program
    from deepfm_tpu.models import get_model

    model = get_model(_program.make_config(FLAGS))
    shapes, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    layers = [count(shapes["layers"][str(i)]) for i in range(6)]
    by_leaf = CONFIG["parameters_by_leaf"]
    assert layers == [by_leaf["mamba_layer"], by_leaf["attention_layer"],
                      by_leaf["mamba_layer"], by_leaf["attention_layer"],
                      by_leaf["memory_unit_layer"], by_leaf["cross_layer"]]
    ends = by_leaf["token_table"] + by_leaf["final_norm"]
    assert by_leaf["token_table"] == 25008 * 2560
    # (the program's table may hold padding rows past the vocabulary's)
    table_rows = shapes["tok_emb"].shape[0]
    assert count(shapes) - (table_rows - 25008) * 2560 \
        == sum(layers) + ends == CONFIG["parameters"] == 697094272
    assert roofline_phi4_flash.param_count(FLAGS)["all"] == 697094272
    # 16 bytes a parameter: 11.15 GB, 8.37 of it resident between steps
    assert round(16 * CONFIG["parameters"] / 1e9, 2) == 11.15
    assert round(12 * CONFIG["parameters"] / 1e9, 2) == 8.37


def test_the_cell_its_traffic_and_its_who_are_the_issues():
    cell = harness.load_cell(CELL)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "phi-4-mini-flash-reasoning", "train-traces-8k-b1", 1)
    assert len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["kind"], t["shards"], t["sequences_per_shard"],
            t["sequence_length"], t["sequences_per_step"], t["max_epochs"],
            t["zipf_exponent"]) == ("train-sequences", 16, 128, 8192, 1,
                                    1000, 1.05)
    assert "chain-of-thought traces" in t["who"]
    # no experts: nothing routed is judged apart, no pair is counted; the
    # tied table leaves `untouched_rows_moved` nothing to say
    assert set(t["limits"]) == set(t["limits_why"]) == {
        "xent_gap", "first_moment_gap", "param_change_gap"}
    assert cell.driver == "train_phi4_flash"
    assert set(cell.end_to_end) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    assert {"train_attn_device_ms", "train_attn_scores_device_ms",
            "train_head_device_ms", "train_mlp_device_ms",
            "device_idle_share.train", "peak_hbm_gb.train",
            "train_step_device_ms", "train_embed_device_ms",
            "train_update_device_ms",
            "train_dense_device_ms",      # none of its scopes here: reads 0
            "train_unscoped_device_ms", "compiles_in_window.train",
            "dispatch_interval_ms_p50", "input_ns_per_record",
            "stage_transfer_ms", "input_wait_ms_max", "input_busy_share",
            } | NEW <= set(cell.per_layer)  # what a later PR adds is welcome
    # entries are found by name, never by position: a later PR appends its
    # own
    for m in BENCH["per_layer"]:
        if m["name"] in ("train_cross_device_ms",   # DLRM's cross network
                         "train_moe_device_ms", "moe_pairs_over_buffer",
                         "train_moe_roofline",      # it has no experts
                         "train_kda_device_ms", "train_conv_device_ms",
                         "train_kda_scan_roofline"):
            assert CELL not in m["workloads"], m["name"]
    mine = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NEW}
    assert set(mine) == NEW
    assert all(m["workloads"] == [CELL] and m["moves"]
               == "train_examples_per_s_per_chip" for m in mine.values())
    assert all(m["unit"] == "%" for n, m in mine.items() if "roofline" in n)
    assert mine["train_mamba_device_ms"]["layer"] == "selective-scan mixer"
    assert mine["train_gmu_device_ms"]["layer"] == "gated memory unit"


@pytest.mark.parametrize("name", sorted(
    m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]))
def test_each_listed_metric_resolves(name):
    spec = harness.load_json("metrics", f"{name}.json")
    assert os.path.exists(os.path.join(
        harness.BENCH_DIR, "readers", spec["reader"] + ".py")), name


def test_the_scopes_metrics_read_the_models_scopes():
    assert harness.load_json("metrics", "train_mamba_device_ms.json")[
        "args"]["scopes"] == ["mamba", "mamba_scan"]
    assert harness.load_json("metrics", "train_gmu_device_ms.json")[
        "args"]["scopes"] == ["gmu"]
    from deepfm_tpu.utils import profiling
    assert {"mamba", "mamba_scan", "gmu"} <= set(profiling.STEP_SCOPES)


# ------------------------------------------------------------- the counts

def test_roofline_counts_by_hand():
    peaks = harness.peaks_for("TPU v5 lite")
    t, d, c, f, v = 8192, 2560, 5120, 10240, 25008
    flops = roofline_phi4_flash.forward_flops(FLAGS)
    assert flops["mlp"] == 2.0 * t * 6 * 3 * d * f
    assert flops["mamba_projections"] == 2.0 * t * 2 * (
        d * 2 * c + c * 192 + 160 * c + c * d)
    assert flops["gmu"] == 2.0 * t * 2 * d * c
    # q and o in three layers, k and v in the two that have their own
    assert flops["attn_projections"] == 2.0 * t * (3 * 2 * d * d
                                                   + 2 * 2 * d * 1280)
    window = 512 * 513 // 2 + (t - 512) * 512
    causal = t * (t + 1) // 2
    # 40 maps (two a query pair), 64 lanes of scores and 128 of values
    assert flops["attn_scores"] == 2.0 * 40 * (64 + 128) * (window
                                                            + 2 * causal)
    assert flops["head"] == 2.0 * (t - 1) * d * v
    step = roofline_phi4_flash.train_step_least_seconds(FLAGS, peaks)
    assert step["bound"] == "flops"
    assert step["flops"] == 3.0 * sum(flops.values())
    assert step["bytes"] == 40.0 * 697094272
    assert 0.18 < step["seconds"] < 0.20
    assert roofline_phi4_flash.matmul_flops(FLAGS) == 3.0 * (
        sum(flops.values()) - flops["attn_scores"])
    scan = roofline_phi4_flash.mamba_scan_least_seconds(FLAGS, peaks)
    assert scan["flops"] == 3.0 * 7 * t * c * 16 * 2
    assert scan["bytes"] == 2.0 * 4 * t * (3 * c + 32) * 2
    assert scan["bound"] == "bytes"
    scores = roofline_phi4_flash.attn_scores_least_seconds(FLAGS, peaks)
    assert scores["flops"] == 3.0 * flops["attn_scores"]
    assert scores["bytes"] == 2.0 * 2 * t * 3 * (40 * 192 + 20 * 192)
    assert scores["bound"] == "flops"


def test_roofline_reader_shares_and_nothing_to_read(monkeypatch):
    def ctx(trace=True, **counters):
        return types.SimpleNamespace(
            cell=types.SimpleNamespace(name=CELL, config={"flags": FLAGS}),
            devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
            counters={"steps_in_window": 10, **counters},
            trace={"devices": 1, "busy_s": 5.0} if trace else None,
            window=(0, 1))
    peaks = harness.peaks_for("TPU v5 lite")
    least = roofline_phi4_flash.train_step_least_seconds(
        FLAGS, peaks)["seconds"]
    assert reader.read(ctx(), "step") == pytest.approx(100 * least / 0.5)
    assert reader.read(ctx(trace=False), "step") is None
    asked = []

    def scoped(c, scopes):
        asked.append(tuple(scopes))
        return {"mamba_scan": 80.0, "attn_scores": 20.0,
                "mamba": 300.0}[scopes[0]]
    monkeypatch.setattr(reader.scope_device_ms, "read", scoped)
    scan = roofline_phi4_flash.mamba_scan_least_seconds(FLAGS, peaks)
    assert reader.read(ctx(), "mamba_scan") == pytest.approx(
        100 * scan["seconds"] / 0.08)
    scores = roofline_phi4_flash.attn_scores_least_seconds(FLAGS, peaks)
    assert reader.read(ctx(), "attn_scores") == pytest.approx(
        100 * scores["seconds"] / 0.02)
    assert reader.read(ctx(), "matmul") == pytest.approx(
        100 * roofline_phi4_flash.matmul_flops(FLAGS)
        / peaks["bf16_flops_per_s"] / 0.3)
    assert asked == [("mamba_scan",), ("attn_scores",),
                     ("mamba", "gmu", "attn", "mlp", "head")]
    # a program from before the scopes (the parent): nothing to read, and
    # nothing raised
    monkeypatch.setattr(reader.scope_device_ms, "read",
                        lambda c, scopes: None)
    for share in ("mamba_scan", "attn_scores", "matmul"):
        assert reader.read(ctx(), share) is None
    with pytest.raises(ValueError):
        reader.read(ctx(), "mfu")


# -------------------------------------------------------- the seeded state

def test_the_seeded_leaves_follow_their_rules():
    import jax.numpy as jnp

    from benchmark import weights
    kw = {"feature_size": 100, "padded_vocab": 128, "embedding_scale": 3.0}
    names = {"layers.0.mamba_a_log": (64, 4), "layers.0.mamba_dt_bias": (64,),
             "layers.0.mamba_d": (64,), "layers.1.sub_norm": (16,),
             "layers.1.lambda_q1": (8,), "layers.1.bk": (32,),
             "layers.0.norm1_b": (32,), "final_norm": (32,),
             "final_norm_b": (32,), "tok_emb": (128, 32),
             "layers.0.mamba_w_in": (32, 128)}
    salts = {n: weights.leaf_salt(7, n) for n in names}
    for xp in (np, jnp):
        got = {n: np.asarray(_program_phi4_flash.seeded_leaf(
            salts, n, shape, kw, xp=xp)) for n, shape in names.items()}
        np.testing.assert_allclose(got["layers.0.mamba_a_log"],
                                   np.log([[1, 2, 3, 4]] * 64), rtol=1e-6)
        step = np.log1p(np.exp(got["layers.0.mamba_dt_bias"]))
        assert 1e-3 * 0.999 < step.min() and step.max() < 1e-1 * 1.001
        for gain in ("layers.0.mamba_d", "layers.1.sub_norm"):
            assert np.all(np.abs(got[gain] - 1.0) <= 0.1)
            assert np.std(got[gain]) > 0.01
        for vector in ("layers.1.lambda_q1", "layers.1.bk",
                       "layers.0.norm1_b"):
            assert np.all(np.abs(got[vector]) <= 0.1)
            assert np.any(got[vector] != 0)
        scale = _program_phi4_flash.FINAL_GAIN
        assert scale == CONFIG["assumed"]["final_norm_gain"] == 2.0 ** -9
        assert np.all(np.abs(got["final_norm"] / scale - 1.0) <= 0.1 + 1e-6)
        assert np.all(np.abs(got["final_norm_b"]) <= 0.1 * scale)
        assert np.abs(got["tok_emb"][:100]).max() > 2.5
        assert not got["tok_emb"][100:].any()
        assert np.abs(got["layers.0.mamba_w_in"]).max() <= np.sqrt(6 / 160)
    # the device's and the host's agree bit for bit where no transcendental
    # function is taken
    assert np.array_equal(
        np.asarray(_program_phi4_flash.seeded_leaf(
            salts, "layers.0.mamba_w_in", (32, 128), kw, xp=jnp)),
        _program_phi4_flash.seeded_leaf(salts, "layers.0.mamba_w_in",
                                        (32, 128), kw))


def test_the_keys_bias_is_not_judged():
    tree = {"layers.1.bk": 1, "layers.1.bq": 2, "layers.3.bk": 3,
            "tok_emb": 4}
    assert train_phi4_flash.judged(tree) == {"layers.1.bq": 2, "tok_emb": 4}


def test_the_step_counts_keep_the_scans_count():
    counts = train_phi4_flash.StepCounts()
    for low in (-3.0, -5.0):
        counts(None, {"loss": 1.0, "xent": 1.0,
                      "mamba_chunk_log_decay_min": low})
    assert counts.dispatches[0] == {"mamba_chunk_log_decay_min": -3.0}
    assert counts.read(0, 2)[train_phi4_flash.DECAY_MIN].tolist() == [
        -3.0, -5.0]


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
    device-trace metrics, the six new ones among them, are left out, not
    failed); the counts' line says the scans' log-decay; the check names its
    three numbers and leaves the keys' biases out."""
    out, printed = traced
    cell = harness.load_cell(CELL)
    assert set(out["metrics"]) <= set(cell.per_layer)
    assert {"compiles_in_window.train", "dispatch_interval_ms_p50",
            "input_ns_per_record"} <= set(out["metrics"])
    assert not NEW & set(out["metrics"])
    counts = [ln for ln in printed.splitlines()
              if ln.startswith("counts (")][-1]
    assert float(counts.split("mamba_chunk_log_decay_min ")[1]) < 0.0
    checks = [ln.split()[1].rstrip(":") for ln in printed.splitlines()
              if ln.startswith("check ") and " (limit " in ln]
    assert checks == ["xent_gap", "first_moment_gap", "param_change_gap"]
    leaves = [ln.split()[1].rstrip(":") for ln in printed.splitlines()
              if ln.startswith("leaf ")]
    assert "layers.1.bq" in leaves and "layers.2.mamba_a_log" in leaves
    assert not [n for n in leaves if n.endswith(".bk")]


def test_one_precision_lower_is_judged_not_correct():
    out = control.run(CELL, 5, 1.0, overrides=TINY, require_chip=False)
    assert out["correct"] is False


def _faults():
    import test_phi4_flash
    return test_phi4_flash.FAULTS


@pytest.mark.parametrize("fault", [
    "window-as-causal", "lambda-from-held-index",
    "shared-kv-cotangent-dropped", "memory-cotangent-dropped",
    "state-not-carried"])
def test_each_of_the_five_faults_is_caught(monkeypatch, fault):
    """ISSUE 44's faults at the rehearsal's size (``tests/test_phi4_flash``
    has the patches): the windowed layer under the causal mask, lambda_init
    from the held index, the shared keys' and values' cotangent dropped, the
    memory's cotangent dropped, the scan's state not carried across a chunk
    (the rehearsal's scan then runs chunks of 4, which the fault cuts)."""
    from deepfm_tpu.models import phi4_flash
    if fault == "state-not-carried":
        monkeypatch.setattr(phi4_flash, "MAMBA_CHUNK", 4)
        monkeypatch.setattr(phi4_flash, "MAMBA_SEGMENT", 8)
    _faults()[fault](monkeypatch)
    assert rehearse()["correct"] is False


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
    gap = [ln for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("check param_change_gap")][-1]
    assert gap.endswith("NOT OK") and float(gap.split()[2]) == \
        pytest.approx(1.0, abs=1e-3)


def test_the_parent_program_fails_the_cell_at_once(monkeypatch):
    """A program that does not know the model (the parent of PR 44) is
    refused where the configuration is built, before a shard is written or
    a device touched."""
    from benchmark.drivers import _program

    def unknown(flags):
        raise ValueError("unknown model: 'phi4_flash'")
    monkeypatch.setattr(_program, "make_config", unknown)
    started = []
    monkeypatch.setattr(train_phi4_flash.traffic_sequences, "ShardWriter",
                        lambda *a, **k: started.append(a))
    with pytest.raises(ValueError, match="unknown model"):
        harness.run(CELL, 1, 1.0, False, overrides=TINY, require_chip=False)
    assert not started
