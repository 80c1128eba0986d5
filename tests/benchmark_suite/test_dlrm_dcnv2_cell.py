"""The benchmark's pieces for ``dlrm-dcnv2-criteo1tb.train-files``: the
configuration file against the published shape, the roofline count against a
hand-checked one, the readers on hand-made contexts, a tiny-size CPU
rehearsal of the cell through the harness's test-only seam, its control (one
precision lower) and two broken programs that have to be judged not correct.
Nothing here measures a speed. (``test_benchmark.py`` binds the new entries
of ``BENCHMARK.json`` to their files and to the contract.)"""

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import (control, harness, reference_dlrm_dcnv2,  # noqa: E402
                       roofline_dlrm_dcnv2)
from benchmark.readers import roofline_dlrm_dcnv2 as reader  # noqa: E402

CELL = "dlrm-dcnv2-criteo1tb.train-files"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

# The cell cut to a size a CPU rehearses in seconds: widths over 16 to 64.
# Limits are this size's own: a batch of 64 under Adagrad's first steps
# (every weight moves by about lr whatever its gradient's size) is far
# noisier than one of 8192.
ROWS = [50, 7, 400, 90, 11, 3, 120, 30, 3, 200, 60, 300, 40, 5, 70, 250, 4,
        33, 21, 2, 280, 6, 5, 150, 9, 100]
TINY = {
    "config": {"numeric_fields": 13, "categorical_rows": ROWS},
    "flags": {"feature_size": 13 + sum(ROWS), "batch_size": 64,
              "embedding_size": 8, "bottom_layers": "16,8", "cross_rank": 4,
              "deep_layers": "16,16,8", "dropout": "1,1,1"},
    "traffic": {"shards": 2, "examples_per_shard": 2048,
                "limits": {"xent_gap": 4e-3, "accumulator_gap": 0.4,
                           "param_change_gap": 0.2,
                           "untouched_rows_moved": 0}},
}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(seed=2 ** 31 + 7):
    return harness.run(CELL, seed, 1.0, False, overrides=TINY,
                       require_chip=False)


# ----------------------------------------------------------- configuration

def test_configuration_runs_every_published_width():
    cell = harness.load_cell(CELL)
    cfg, flags = cell.config, cell.config["flags"]
    pub = cfg["published"]
    assert flags["model"] == "dlrm_dcnv2" and cell.chips == 1
    assert flags["embedding_size"] == pub["embedding_dim"] == 128
    assert [int(x) for x in flags["bottom_layers"].split(",")] \
        == pub["dense_arch_layer_sizes"] == [512, 256, 128]
    assert (flags["cross_layers"], flags["cross_rank"]) \
        == (pub["dcn_num_layers"], pub["dcn_low_rank_dim"]) == (3, 512)
    assert [int(x) for x in flags["deep_layers"].split(",")] + [1] \
        == pub["over_arch_layer_sizes"] == [1024, 1024, 512, 256, 1]
    assert (flags["optimizer"], flags["learning_rate"]) \
        == (pub["optimizer"], pub["learning_rate"]) == ("Adagrad", 0.004)
    assert flags["l2_reg"] == 0 and flags["compute_dtype"] == "bfloat16"
    assert set(float(x) for x in flags["dropout"].split(",")) == {1.0}
    assert flags["batch_size"] * 8 == pub["global_batch_size"]
    # the one cut: 1/32 of every table's rows, rounded up
    src = cfg["source_categorical_rows"]
    assert len(src) == 26 and sum(src) == cfg["source_vocabulary_rows"] \
        == 204184588 and max(src) == 40000000
    assert cfg["categorical_rows"] == [-(-n // 32) for n in src]
    assert cfg["vocabulary_rows"] == flags["feature_size"] == 6380794
    assert cfg["reduced"] == ["vocabulary_rows", "multi_hot"]
    assert {"embedding_scale", "batch_per_chip", "adagrad_initial_accumulator",
            "adagrad_eps", "from_memory"} <= set(cfg["assumed"])
    assert cell.driver == "train_dlrm_dcnv2"
    assert set(cell.traffic["limits"]) == {
        "xent_gap", "accumulator_gap", "param_change_gap",
        "untouched_rows_moved"}
    assert cell.traffic["limits"]["untouched_rows_moved"] == 0


def test_the_cell_reports_what_the_issue_lists():
    cell = harness.load_cell(CELL)
    assert set(cell.end_to_end) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    assert {"train_cross_device_ms", "train_bottom_device_ms",
            "train_matmul_roofline", "train_step_roofline.dlrm_dcnv2",
            "train_embed_device_ms", "train_update_device_ms",
            "train_dense_device_ms", "train_unscoped_device_ms",
            "train_step_device_ms", "device_idle_share.train",
            "peak_hbm_gb.train"} <= set(cell.per_layer)
    assert not {"train_step_roofline", "collective_share"} \
        & set(cell.per_layer)
    # the cell's own four, found by name: a later PR appends its entries
    mine = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in (
        "train_cross_device_ms", "train_bottom_device_ms",
        "train_matmul_roofline", "train_step_roofline.dlrm_dcnv2")}
    assert len(mine) == 4
    assert all(m["workloads"] == [CELL] for m in mine.values())
    # the scope metrics partition the step's named scopes
    scopes = []
    for name in cell.per_layer:
        spec = harness.load_json("metrics", name + ".json")
        if spec["reader"] == "scope_device_ms":
            scopes += spec["args"]["scopes"]
    assert sorted(scopes) == sorted(
        ["embed", "opt", "l2", "fm", "tower", "loss", "cross", "bottom"])


# ---------------------------------------------------------------- roofline

HAND = {"batch_size": 8, "field_size": 5, "numeric_fields": 2,
        "embedding_size": 2, "bottom_layers": "3,2", "cross_layers": 2,
        "cross_rank": 1, "deep_layers": "4"}


def test_roofline_count_for_a_hand_checked_shape():
    # local batch 4; D = (1 + 3) * 2 = 8
    p = roofline_dlrm_dcnv2.layer_products(HAND)
    assert p == {"bottom": [2 * 3, 3 * 2], "cross": [8, 8, 8, 8],
                 "top": [8 * 4, 4 * 1]}
    every = 12 + 32 + 36
    # three products a layer (forward, weight gradient, input gradient); the
    # first bottom layer's input is data: no input gradient
    flops = 2 * 4 * (3 * every - 6)
    assert roofline_dlrm_dcnv2.matmul_flops(HAND, chips=2) == flops
    c = roofline_dlrm_dcnv2.train_step_counts(HAND, chips=2)
    dense = every + (3 + 2) + 2 * 8 + (4 + 1)
    assert c["flops"] == flops
    assert c["bytes"] == (4 * 3 * 2 * 4 * 4          # rows, accumulator, r+w
                          + dense * 4 * 4
                          + 4 * (5 * 8 + 4))
    t = roofline_dlrm_dcnv2.train_step_least_seconds(
        HAND, 2, {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e9})
    assert t["bound"] == "compute" and t["seconds"] == flops / 1e3


def test_roofline_count_of_the_cell_is_the_issues():
    flags = harness.load_cell(CELL).config["flags"]
    p = roofline_dlrm_dcnv2.layer_products(flags)
    assert (sum(p["cross"]), sum(p["top"]), sum(p["bottom"])) \
        == (10616832, 5243136, 170496)          # 16.0 M multiply-adds
    peaks = harness.peaks_for("TPU v5 lite")    # read, not copied
    t = roofline_dlrm_dcnv2.train_step_least_seconds(flags, 1, peaks)
    assert t["bound"] == "compute" and 0.0039 < t["seconds"] < 0.0041
    assert t["bytes"] / peaks["hbm_bytes_per_s"] < 0.001    # 0.7 GB of rows


def ctx_for(trace=True, steps=10, busy_s=0.5):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name="x", config={"flags": HAND}),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")] * 2,
        counters={"steps_in_window": steps},
        trace={"devices": 2, "busy_s": busy_s} if trace else None)


def test_roofline_reader_shares_and_nothing_to_read(monkeypatch):
    peaks = harness.peaks_for("TPU v5 lite")
    flops = roofline_dlrm_dcnv2.matmul_flops(HAND, 2)
    least = flops / peaks["bf16_flops_per_s"]
    step = roofline_dlrm_dcnv2.train_step_least_seconds(HAND, 2, peaks)
    assert step["bound"] == "memory"        # at this toy size
    assert reader.read(ctx_for(), "step") == pytest.approx(
        100 * step["seconds"] / 0.05)
    asked = []
    monkeypatch.setattr(reader.scope_device_ms, "read",
                        lambda ctx, scopes: asked.append(scopes) or 2.0)
    assert reader.read(ctx_for(), "matmul") == pytest.approx(
        100 * least / 2e-3)
    assert asked == [["bottom", "cross", "tower"]]
    # nothing to read: no trace; a program whose step names no such scope
    assert reader.read(ctx_for(trace=False), "step") is None
    assert reader.read(ctx_for(trace=False), "matmul") is None
    for nothing in (None, 0.0):
        monkeypatch.setattr(reader.scope_device_ms, "read",
                            lambda ctx, scopes: nothing)
        assert reader.read(ctx_for(), "matmul") is None
    with pytest.raises(ValueError):
        reader.read(ctx_for(), "other")


# --------------------------------------------------------------- the check

def test_gaps_count_untouched_rows_that_moved():
    params0 = {"fm_v": np.ones((4, 2), np.float32),
               "w": np.ones(3, np.float32)}
    f = reference_dlrm_dcnv2.Follower(
        params0, np.arange(4), n_bottom=0, n_cross=0, n_top=0,
        learning_rate=0.1, adagrad_init=1e-8, adagrad_eps=1e-7)
    init = np.float32(1e-8)
    s = {k: np.full_like(v, init) for k, v in params0.items()}
    touched = np.array([True, False, True, False])
    same = reference_dlrm_dcnv2.dispatch_gaps(
        params0, s, 0.5, f, 0.5, params0, {"fm_v"}, 4, touched)
    assert same["untouched_rows_moved"] == 0 and same["xent_gap"] == 0
    assert same["accumulator_gap"] == same["param_change_gap"] == 0
    moved = {**params0, "fm_v": params0["fm_v"].copy()}
    moved["fm_v"][3, 1] += 1e-6              # an untouched row, one element
    moved["fm_v"][0, 0] += 1.0               # a touched row: not counted
    grown = {**s, "fm_v": s["fm_v"].copy()}
    grown["fm_v"][1] += 1e-12
    got = reference_dlrm_dcnv2.dispatch_gaps(
        moved, grown, 0.5, f, 0.25, params0, {"fm_v"}, 4, touched)
    assert got["untouched_rows_moved"] == 1 + 2 and got["xent_gap"] == 0.25


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


def test_one_precision_lower_is_judged_not_correct():
    out = control.run(CELL, 5, 1.0, overrides=TINY, require_chip=False)
    assert out["correct"] is False


def test_a_step_that_leaves_the_parameters_unchanged_is_caught(monkeypatch):
    import deepfm_tpu.train.loop as loop

    monkeypatch.setattr(loop.optax, "apply_updates",
                        lambda params, updates: params)
    assert rehearse()["correct"] is False


def test_a_decay_that_moves_untouched_rows_is_caught(monkeypatch, capsys):
    """The reference has no L2 term, so the sampled untouched rows must not
    move at all: a program that decays every row is judged not correct by
    that count even where the norms hardly notice."""
    import deepfm_tpu.train.loop as loop

    real = loop.optax.apply_updates
    monkeypatch.setattr(
        loop.optax, "apply_updates", lambda params, updates: real(
            params, {**updates, "fm_v": updates["fm_v"]
                     - 1e-6 * params["fm_v"]}))
    assert rehearse()["correct"] is False
    out = capsys.readouterr().out
    assert "check untouched_rows_moved" in out
    moved = [ln for ln in out.splitlines()
             if ln.startswith("check untouched_rows_moved")][-1]
    assert moved.endswith("NOT OK") and not moved.startswith(
        "check untouched_rows_moved: 0 ")


def test_the_parent_program_fails_the_cell_at_once(monkeypatch):
    """A program that does not know the model (the parent of PR 27) is
    refused where the configuration is built, before a shard is written or
    a device touched."""
    from benchmark.drivers import _program, train_dlrm_dcnv2

    def unknown(flags):
        raise TypeError("Config.__init__() got an unexpected keyword "
                        "argument 'numeric_fields'")
    monkeypatch.setattr(_program, "make_config", unknown)
    started = []
    monkeypatch.setattr(train_dlrm_dcnv2.traffic, "ShardWriter",
                        lambda *a, **k: started.append(a))
    with pytest.raises(TypeError):
        harness.run(CELL, 1, 1.0, False, overrides=TINY, require_chip=False)
    assert not started
