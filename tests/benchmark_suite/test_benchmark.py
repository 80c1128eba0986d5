"""The benchmark's own tests: its contract file, its yardstick (generator,
shard bytes, reference, roofline count, trace reduction), a tiny-size CPU
rehearsal of each driver through the harness's test-only seam, the control
(one precision lower) and two broken timed paths that have to be judged not
correct. Nothing here measures a speed.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import (control, harness, reference, roofline, traffic,  # noqa: E402
                       weights, xplane)

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# Cells whose files are kept and tested but that are not in BENCHMARK.json:
# benchmark/candidates/ holds the entries that admitting one adds. They are
# merged here and handed to the harness through its test-only seam.
for _f in sorted(os.listdir(os.path.join(ROOT, "benchmark", "candidates"))):
    _c = json.load(open(os.path.join(ROOT, "benchmark", "candidates", _f)))
    _have = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    BENCH["workloads"] += _c["workloads"]
    for _key in ("end_to_end", "per_layer"):
        for _m in _c[_key]:
            if _m["name"] not in _have:
                BENCH[_key].append(_m)
            elif "workloads" in _m:
                next(m for m in BENCH[_key] if m["name"] == _m["name"]
                     ).setdefault("workloads", []).extend(_m["workloads"])
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# A cell cut to a size a CPU rehearses in seconds. Limits are this size's
# own: a batch of 64 is far noisier than one of 4096.
ROWS = [50, 7, 400, 90, 11, 3, 120, 30, 3, 200, 60, 300, 40, 5, 70, 250, 4,
        33, 21, 2, 280, 6, 5, 150, 9, 100]
TINY = {
    "benchmark": BENCH,
    "config": {"numeric_fields": 13, "categorical_rows": ROWS},
    "flags": {"feature_size": 13 + sum(ROWS), "batch_size": 64,
              "embedding_size": 8, "deep_layers": "16,8"},
    "traffic": {"shards": 2, "examples_per_shard": 2048,
                "offered_rows_per_s": 4000.0,
                "limits": {"xent_gap": 1e-3, "first_moment_gap": 0.03,
                           "param_change_gap": 0.06, "logit_gap": 0.008}},
}
TRAIN, SERVE = "deepfm-criteo.train-files", "deepfm-criteo.serve-steady"
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(workload, trace=False, seed=2 ** 31 + 7):
    return harness.run(workload, seed, 1.0, trace, overrides=TINY,
                       require_chip=False)


# ---------------------------------------------------------------- contract

def test_names_units_and_lengths_are_within_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    named = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
             + BENCH["per_layer"])
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "source", "layer"):
            text = entry.get(key, "x")
            if entry in BENCH["configs"] or key != "source":
                assert 1 <= len(text) <= 200 and "\n" not in text
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for group in (BENCH["configs"], BENCH["workloads"],
                  BENCH["end_to_end"] + BENCH["per_layer"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    assert 1 <= BENCH["run_seconds"] <= 51
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_every_named_piece_resolves_to_a_file():
    bench_dir = os.path.join(ROOT, "benchmark")
    for cfg in BENCH["configs"]:
        body = json.load(open(os.path.join(ROOT, cfg["file"])))
        assert cfg["file"].startswith("benchmark/")
        assert set(cfg["reduced"]) <= set(body) and len(body["source"]) <= 200
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"], {"benchmark": BENCH})
        assert os.path.exists(os.path.join(
            bench_dir, "drivers", cell.driver + ".py"))
        assert cell.end_to_end and cell.per_layer
        assert cell.chips == w["chips"]
        layout = traffic.FieldLayout.from_config(cell.config)
        assert layout.feature_size == cell.config["flags"]["feature_size"] \
            == cell.config["vocabulary_rows"]
    for m in BENCH["per_layer"]:
        spec = json.load(open(os.path.join(
            bench_dir, "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(
            bench_dir, "readers", spec["reader"] + ".py"))


def test_per_layer_metrics_move_a_metric_their_cells_report():
    cells = {w["name"] for w in BENCH["workloads"]}
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in reports and m["moves"] != "setup_s"
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
    for cell in cells:
        assert sum(cell in r for n, r in reports.items() if n != "setup_s")


@pytest.mark.parametrize("stripped", [False, True])
def test_no_result_without_a_chip_or_without_the_program(tmp_path, stripped):
    """On the CPU, and in a directory that holds only the benchmark, the
    command exits non-zero and prints no result line."""
    cwd = ROOT
    if stripped:
        cwd = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), cwd)
        shutil.copytree(os.path.join(ROOT, "benchmark"),
                        os.path.join(cwd, "benchmark"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", TRAIN, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, timeout=120,
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


# ------------------------------------------------------------- rehearsals

@pytest.fixture(scope="module")
def train_line():
    return rehearse(TRAIN)


@pytest.fixture(scope="module")
def serve_line():
    return rehearse(SERVE)


def test_train_rehearsal_prints_the_contracts_keys(train_line):
    assert set(train_line) == LINE_KEYS and train_line["correct"] is True
    assert set(train_line["metrics"]) == {"train_examples_per_s_per_chip",
                                          "setup_s"}
    assert all(set(v) == {"value", "unit"}
               for v in train_line["metrics"].values())
    assert set(train_line["device"]) == {"platform", "kind", "count",
                                         "memory_peak_bytes"}
    assert train_line["attempted"] > 0 and train_line["failed"] == 0


def test_the_training_rate_is_all_the_work_over_all_the_window():
    from benchmark.drivers import train

    even = [0.5 * i for i in range(25)]
    rate, median_s = train.window_rate(even, 4096 * 8)
    assert rate == pytest.approx(65536.0) and median_s == pytest.approx(0.5)
    stalled = even[:12] + [t + 3.0 for t in even[12:]]    # one 3 s stall
    rate, median_s = train.window_rate(stalled, 4096 * 8)
    assert rate == pytest.approx(65536.0 * 12 / 15)
    assert median_s == pytest.approx(0.5)     # the median does not see it


def test_serve_rehearsal_prints_the_contracts_keys(serve_line):
    assert set(serve_line) == LINE_KEYS and serve_line["correct"] is True
    assert set(serve_line["metrics"]) == {"serve_p50_ms",
                                          "serve_rows_per_s", "setup_s"}
    assert serve_line["metrics"]["serve_rows_per_s"]["value"] > 0
    assert serve_line["attempted"] > 0 and serve_line["failed"] == 0


def test_traced_rehearsal_reports_per_layer_metrics_and_busy_time():
    line = rehearse(SERVE, trace=True)
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert {"serve_flush_ms_p50", "serve_queue_ms_p50", "serve_tail_ms_p95",
            "serve_flush_rows_mean", "loadgen_late_ms_p99",
            "compiles_in_window.serve"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window.serve"]["value"] == 0


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
def test_one_precision_lower_is_judged_not_correct(workload):
    line = control.run(workload, 5, 1.0, overrides=TINY, require_chip=False)
    assert line["correct"] is False


def test_a_step_that_leaves_the_parameters_unchanged_is_caught(monkeypatch):
    import deepfm_tpu.train.loop as loop

    monkeypatch.setattr(loop.optax, "apply_updates",
                        lambda params, updates: params)
    assert rehearse(TRAIN)["correct"] is False


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    from deepfm_tpu.utils import export as export_lib

    real = export_lib.padded_predict

    def altered(fn, ids, vals, buckets):
        out = np.array(real(fn, ids, vals, buckets))
        out[-1] = min(0.999, out[-1] * 1.2 + 0.01)
        return out
    monkeypatch.setattr(export_lib, "padded_predict", altered)
    assert rehearse(SERVE)["correct"] is False


# -------------------------------------------------------------- yardstick

def test_generator_is_seeded_ranged_and_skewed():
    cell = harness.load_cell(TRAIN, TINY)
    layout = traffic.FieldLayout.from_config(cell.config)
    a = traffic.generate_rows(layout, 4000, 2 ** 31 + 5, cell.traffic)
    b = traffic.generate_rows(layout, 4000, 2 ** 31 + 5, cell.traffic)
    c = traffic.generate_rows(layout, 4000, 2 ** 31 + 6, cell.traffic)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["feat_ids"], c["feat_ids"])
    lo = layout.offsets
    assert np.all(a["feat_ids"] >= lo) and np.all(
        a["feat_ids"] < lo + layout.rows)
    assert np.all(a["feat_vals"][:, 13:] == 1.0)
    assert np.all(a["feat_vals"][:, :13] > 0)
    assert 0.05 < a["label"].mean() < 0.6
    # field 15 (400 rows): the most frequent row far above the uniform share
    counts = np.bincount(a["feat_ids"][:, 15] - lo[15], minlength=400)
    assert counts.max() > 20 * 4000 / 400


def test_real_layout_has_the_issues_vocabulary():
    cell = harness.load_cell(TRAIN)
    layout = traffic.FieldLayout.from_config(cell.config)
    assert (layout.field_size, layout.feature_size) == (39, 16881309)
    assert 13 + sum(cell.config["source_categorical_rows"]) == 33762590


def test_shards_are_byte_for_byte_the_repos_own_format(tmp_path):
    from deepfm_tpu.data import example_codec, tfrecord

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 16881309, (300, 39)).astype(np.int32)
    ids[0], ids[1] = 0, 16881308
    ids[2, :20] = 127
    vals = rng.random((300, 39)).astype(np.float32)
    label = (rng.random(300) < 0.3).astype(np.float32)
    mine = traffic.write_shard(str(tmp_path / "a"), label, ids, vals)
    theirs = str(tmp_path / "b")
    with tfrecord.TFRecordWriter(theirs) as w:
        for i in range(300):
            w.write(example_codec.encode_ctr_example(
                float(label[i]), ids[i], vals[i]))
    assert open(mine, "rb").read() == open(theirs, "rb").read()


def test_every_seed_offers_the_same_serving_work():
    p = harness.load_cell(SERVE, {"benchmark": BENCH}).traffic
    (due1, rows1), (due2, rows2) = (
        traffic.arrival_schedule(20.0, 720.0, {**p, "arrival_seed": s})
        for s in (1, 2 ** 31 + 9))
    assert sorted(rows1) == sorted(rows2) and not np.array_equal(rows1, rows2)
    g1, g2 = np.sort(np.diff(due1)), np.sort(np.diff(due2))   # all gaps but
    assert len(g1) == len(g2)                                  # each one's first
    assert abs(np.median(g1) - np.median(g2)) < 1e-4 and \
        abs(g1.sum() - g2.sum()) < 0.2
    assert 0 <= due1.min() and due1.max() < 20.0
    assert rows1.min() >= 1 and rows1.max() == 256
    assert np.median(rows1) == 8 and 12 < rows1.mean() < 20
    assert abs(rows1.sum() / 20.0 - 720.0) < 0.03 * 720.0


def test_roofline_count_for_a_hand_checked_shape():
    flags = {"batch_size": 8, "field_size": 3, "embedding_size": 2,
             "deep_layers": "4"}
    c = roofline.train_step_counts(flags, chips=2)        # local batch 4
    tower = 6 * 4 + 4 * 1
    assert c["flops"] == 3 * (2 * 4 * tower + 6 * 4 * 3 * 2)
    assert c["bytes"] == (4 * 3 * 3 * 4 * 6            # rows, m, v, r+w
                          + (tower + 4 + 1 + 1) * 4 * 6  # dense, with fm_b
                          + 4 * (3 * 8 + 4))
    t = roofline.train_step_least_seconds(
        flags, 2, {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e9})
    assert t["bound"] == "compute" and t["seconds"] == c["flops"] / 1e3
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_reference_matches_a_hand_computed_two_field_example():
    # F=2, K=2, no hidden layer: logit = b + w.x + <v1 x1, v2 x2> + out.
    params = {"fm_b": np.array([0.1], np.float32),
              "fm_w": np.array([0.5, -0.25, 2.0], np.float32),
              "fm_v": np.array([[1.0, 2.0], [3.0, -1.0], [9.0, 9.0]],
                               np.float32),
              "tower.out.w": np.array([[0.1], [0.2], [0.3], [0.4]],
                                      np.float32),
              "tower.out.b": np.array([-0.05], np.float32)}
    ids = np.array([[0, 1]], np.int32)
    vals = np.array([[2.0, 0.5]], np.float32)
    e1, e2 = np.array([2.0, 4.0]), np.array([1.5, -0.5])
    want = (0.1 + 0.5 * 2.0 - 0.25 * 0.5 + float(e1 @ e2)
            + float(np.concatenate([e1, e2]) @ [0.1, 0.2, 0.3, 0.4]) - 0.05)
    got = reference.logits({k: np.asarray(v) for k, v in params.items()},
                           ids, vals, n_layers=0)
    assert abs(float(got[0]) - want) < 1e-6
    z = np.array([want])
    assert abs(float(reference.log_loss(z, np.array([1.0])))
               - np.log1p(np.exp(-want))) < 1e-6


def test_reference_first_adam_step_and_l2_pull_on_untouched_rows():
    params = {"fm_b": np.zeros(1, np.float32),
              "fm_w": np.array([0.5, -0.25, 2.0], np.float32),
              "fm_v": np.ones((3, 2), np.float32),
              "tower.out.w": np.full((4, 1), 0.1, np.float32),
              "tower.out.b": np.zeros(1, np.float32)}
    f = reference.Follower(params, np.array([10, 20, 30]), n_layers=0,
                           keep=[], l2_reg=1e-4, learning_rate=0.01)
    xent = f.step(np.array([[10, 20]]), np.ones((1, 2), np.float32),
                  np.array([1.0]), None)
    assert xent > 0 and f.count == 1
    # Adam's first step moves every element by lr against its gradient's
    # sign; row 30 is untouched, so only the L2 pull (sign of w) moves it.
    assert np.allclose(np.asarray(f.params["fm_w"])[2], 2.0 - 0.01, atol=1e-5)
    assert np.allclose(np.asarray(f.m["fm_w"])[2], 0.1 * 1e-4 * 2.0)
    assert np.asarray(f.params["fm_w"])[0] > 0.5     # label 1: pushed up
    with pytest.raises(ValueError):
        f.local_ids(np.array([[10, 25]]))


def test_worst_leaf_gap_uses_the_median_leaf_as_its_floor():
    want = {"a": np.full(4, 1.0), "b": np.full(4, 1e-9), "c": np.full(4, 2.0)}
    got = {"a": np.full(4, 1.1), "b": np.full(4, 3e-9), "c": np.full(4, 2.0)}
    gap, leaf = reference.worst_leaf_gap(got, want)
    assert leaf == "a" and abs(gap - 0.1) < 1e-9      # b is all but zero
    got["c"] = np.zeros(4)
    assert reference.worst_leaf_gap(got, want) == (1.0, "c")


def test_seeded_weights_agree_between_numpy_and_jax_and_by_rows():
    import jax
    import jax.numpy as jnp

    kw = dict(feature_size=1000, padded_vocab=1024, embedding_scale=0.1)
    salt = weights.leaf_salt(2 ** 31 + 11, "fm_v")        # above int32
    host = weights.leaf_values(salt, (1024, 8), **kw)
    dev = jax.jit(lambda s: weights.leaf_values(s, (1024, 8), xp=jnp, **kw))(
        np.uint32(salt))
    assert np.array_equal(host, np.asarray(dev))
    rows = np.array([3, 999, 1000, 1023])
    assert np.array_equal(weights.leaf_values(salt, (1024, 8), rows=rows,
                                              **kw), host[rows])
    assert np.all(host[1000:] == 0) and abs(host[:1000].std() - 0.1 / 3 ** .5) \
        < 0.002 and np.abs(host).max() <= 0.1
    dense = weights.leaf_values(weights.leaf_salt(1, "w"), (64, 32), **kw)
    assert np.abs(dense).max() <= (6 / 96) ** 0.5
    assert not np.array_equal(
        host, weights.leaf_values(weights.leaf_salt(2, "fm_v"), (1024, 8),
                                  **kw))


def test_interval_helpers_and_gap_names():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    spans = [{"name": "stage.wait", "ts": 3.0, "dur": 0.5},       # us
             {"name": "train.dispatch", "ts": 3.2, "dur": 1.8},
             {"name": "other", "ts": 0.0, "dur": 100.0}]
    assert xplane.name_gap((3000.0, 5000.0), spans) == "train.dispatch"
    assert xplane.name_gap((8000.0, 9000.0), spans) == "host.other"
    own = xplane.self_times([(0, 100, "%while.1 = x"), (10, 40, "a"),
                             (50, 90, "b"), (60, 70, "c")])
    assert own == {"%while.1 = x": 30, "a": 30, "b": 30, "c": 10}
    assert xplane.op_label(
        "%fusion.267 = f32[16881344,32]{0,1:T(8,128)} fusion(f32[] %p)") \
        == "fusion.267_f32_16881344_32"
    assert xplane.is_collective(
        "%psum_invariant.73 = f32[16881344,32]{0,1:T(8,128)} all-reduce("
        "f32[16881344,32]{0,1:T(8,128)} %fusion.1), channel_id=3")
    assert xplane.is_collective(
        "%ar = (f32[8], u32[]) all-reduce-start(f32[8] %x)")
    assert not xplane.is_collective(
        "%all-reduce-like.1 = f32[8]{0} fusion(f32[8] %x), kind=kLoop")


def test_recorded_trace_reduces_to_known_numbers():
    """1.55 s (three dispatches) of deepfm-criteo.train-files on a v5e, cut
    from a traced run of PR 24 to its device ops line, with the program's
    spans of the same stretch."""
    data = os.path.join(ROOT, "benchmark", "testdata")
    w = json.load(open(os.path.join(data, "train_window.json")))
    r = xplane.reduce(os.path.join(data, "train_window.xplane.pb"),
                      window_ns=tuple(w["window_ns"]), spans=w["spans"])
    assert r["devices"] == 1 and r["collective_s"] == 0.0
    assert abs(r["window_s"] - 1.550000128) < 1e-9
    assert abs(r["busy_s"] - 1.54255872) < 1e-6
    top, seconds = r["device_ops"][0]
    assert top == "multiply_add_fusion.38_f32_16881344_32"
    assert abs(seconds - 0.560868608) < 1e-6
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 5
    # the ops' own times fill the busy time (no op is counted inside its
    # `while`), and the gaps are what is left of the window
    assert sum(s for _, s in r["device_ops"]) <= r["busy_s"]
    assert sum(s for _, s in r["device_ops"]) > 0.95 * r["busy_s"]
    assert r["idle_gaps"][0] == ["stage.transfer", 0.004093184]
    assert r["idle_gaps"][1][0] == "stage.wait"
    assert abs(sum(s for _, s in r["idle_gaps"])
               - (r["window_s"] - r["busy_s"])) < 1e-4
