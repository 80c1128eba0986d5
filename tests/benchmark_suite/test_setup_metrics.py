"""The six candidate per-layer metrics of set-up
(``benchmark/candidates/setup_metrics.json``): their entries against the
contract's form and their files, the reader on hand-made records, and a
tiny-size CPU rehearsal of one DeepFM cell and one decoder cell through the
harness's overrides seam, as ``scripts/setup_phases.py`` runs them on the
chip. Nothing here measures a speed."""

import json
import os
import re
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import setup_phases  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.readers import startup_phase_s as reader  # noqa: E402

SIX = ("setup_import_s", "setup_build_s", "setup_trace_lower_s",
       "setup_backend_compile_s", "setup_warmup_s", "setup_uncovered_s")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(ROOT, "benchmark", "candidates",
                       "setup_metrics.json")) as _f:
    CANDIDATES = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]

S = 10 ** 9
ORIGIN = 1_000 * S


def _ctx(open_s):
    return types.SimpleNamespace(window=(ORIGIN + int(open_s * S), 0))


#: A record as the harness leaves it: the driver's thread (2) imports
#: ``train.tasks`` while the main thread (1) starts JAX; no ``setup.state``
#: (the benchmark seeds its own) and no ``setup.backend`` (the harness asked
#: for the devices).
RECORD = [
    ("setup.import", ORIGIN + 1 * S, ORIGIN + 4 * S, 1, {"module": "jax"}),
    ("setup.import", ORIGIN + 2 * S, ORIGIN + 9 * S, 2,
     {"module": "deepfm_tpu.train.loop"}),
    ("setup.import", ORIGIN + 9 * S, ORIGIN + 15 * S, 2,
     {"module": "deepfm_tpu.train.tasks"}),
    ("setup.import", ORIGIN + 10 * S, ORIGIN + 14 * S, 2,
     {"module": "orbax.checkpoint"}),
    ("setup.trainer", ORIGIN + 16 * S, ORIGIN + 17 * S, 1, {}),
    ("compile.trace", ORIGIN + 17 * S + S // 2, ORIGIN + 18 * S, 1,
     {"fun_name": "make"}),
    ("compile.backend", ORIGIN + 18 * S, ORIGIN + 19 * S, 1,
     {"fun_name": "jit(make)", "cache": "hit"}),
    ("setup.pipeline", ORIGIN + 20 * S, ORIGIN + 20 * S + S // 2, 1, {}),
    ("setup.first_batch", ORIGIN + 21 * S, ORIGIN + 22 * S, 1, {}),
    ("compile.trace", ORIGIN + 22 * S, ORIGIN + 24 * S, 1,
     {"fun_name": "multi"}),
    ("compile.lower", ORIGIN + 24 * S, ORIGIN + 25 * S, 1,
     {"fun_name": "multi"}),
    ("compile.cache_fetch", ORIGIN + 25 * S, ORIGIN + 26 * S, 1,
     {"cache": "hit"}),
    ("compile.backend", ORIGIN + 25 * S, ORIGIN + 27 * S, 1,
     {"fun_name": "jit(multi)", "cache": "hit"}),
    ("setup.first_dispatch", ORIGIN + 22 * S, ORIGIN + 28 * S, 1,
     {"steps": 8}),
]


def _read(name, record=RECORD, open_s=31.0):
    spec = harness.load_json("metrics", f"{name}.json")
    assert spec["reader"] == "startup_phase_s"
    return reader.phase_seconds(record, ORIGIN, _ctx(open_s).window[0],
                                **spec["args"])


# ---------------------------------------------------------------- the entries

def test_the_candidates_are_six_entries_ready_to_paste():
    entries = CANDIDATES["per_layer_moving_setup_s"]
    assert tuple(e["name"] for e in entries) == SIX
    have = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    layers = set()
    for e in entries:
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert name.match(e["name"]) and e["name"] not in have
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "s", "lower", "program_span", "setup_s")
        assert e["workloads"] == CELLS            # every cell reports setup_s
        assert 0 < len(e["layer"]) <= 200 and "\n" not in e["layer"]
        layers.add(e["layer"])
        spec = harness.load_json("metrics", f"{e['name']}.json")
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "readers", spec["reader"] + ".py"))
    assert len(layers) == 6
    # the other keys are what test_benchmark.py merges: nothing, so that the
    # accepted contract and its tests stand as they are
    assert CANDIDATES["workloads"] == CANDIDATES["end_to_end"] \
        == CANDIDATES["per_layer"] == []
    bench = setup_phases.contract_with_candidates()
    assert bench["per_layer"][:len(BENCH["per_layer"])] == BENCH["per_layer"]
    assert bench["per_layer"][len(BENCH["per_layer"]):] == entries
    assert {k: v for k, v in bench.items() if k != "per_layer"} \
        == {k: v for k, v in BENCH.items() if k != "per_layer"}
    for cell in CELLS:
        loaded = harness.load_cell(cell, {"benchmark": bench})
        assert set(SIX) <= set(loaded.per_layer)


# ----------------------------------------------------------------- the reader

def test_reader_takes_unions_across_threads_and_zero_for_a_missing_phase():
    assert _read("setup_import_s") == 14.0       # 1..15 over two threads
    # trainer 1 + pipeline 0.5 + first batch 1; no setup.state here
    assert _read("setup_build_s") == 2.5
    assert _read("setup_trace_lower_s") == 3.5
    assert _read("setup_backend_compile_s") == 3.0      # the fetch inside
    assert _read("setup_warmup_s") == 3.0
    # 0..1, 15..16, 17..17.5, 19..20, 20.5..21
    assert _read("setup_uncovered_s") == 4.0
    only_state = reader.phase_seconds(RECORD, ORIGIN, ORIGIN + 31 * S,
                                      "union", ["setup.state"])
    assert only_state == 0.0 and only_state is not None
    with pytest.raises(ValueError):
        reader.phase_seconds(RECORD, ORIGIN, ORIGIN + 31 * S, "sum")


@pytest.mark.parametrize("open_s", [28.0, 31.0, 75.25])
def test_uncovered_plus_every_span_plus_warmup_is_window_open_minus_start(
        open_s):
    every = sorted({p[0] for p in RECORD})
    union_all = reader.phase_seconds(RECORD, ORIGIN, ORIGIN + int(open_s * S),
                                     "union", every)
    total = (_read("setup_uncovered_s", open_s=open_s) + union_all
             + _read("setup_warmup_s", open_s=open_s))
    assert total == pytest.approx(open_s)
    # the categories overlap (imports beside the backend's start): the six
    # need not sum to it
    assert sum(_read(n, open_s=open_s) for n in SIX) != pytest.approx(open_s)


def test_reader_finds_nothing_without_a_first_dispatch_or_without_the_record(
        monkeypatch):
    assert reader.phase_seconds(RECORD[:-1], ORIGIN, ORIGIN + 31 * S,
                                "warmup") is None
    assert reader.phase_seconds([], ORIGIN, ORIGIN + 31 * S, "union",
                                ["setup.import"]) is None
    # a program from before the record (the parent commit): left out, no
    # raise, whatever this process's own record holds by now
    monkeypatch.setitem(sys.modules, "deepfm_tpu.obs.startup", None)
    assert reader.read(_ctx(31.0), "uncovered") is None


# ------------------------------------------------------------- the rehearsals

ROWS = [50, 7, 400, 90, 11, 3, 120, 30, 3, 200, 60, 300, 40, 5, 70, 250, 4,
        33, 21, 2, 280, 6, 5, 150, 9, 100]
TINY = {
    "deepfm-criteo.train-files": {
        "config": {"numeric_fields": 13, "categorical_rows": ROWS},
        "flags": {"feature_size": 13 + sum(ROWS), "batch_size": 64,
                  "embedding_size": 8, "deep_layers": "16,8"},
        "traffic": {"shards": 2, "examples_per_shard": 2048,
                    "limits": {"xent_gap": 1e-3, "first_moment_gap": 0.03,
                               "param_change_gap": 0.06}}},
    "sdar-30b-a3b.train-sequences": {
        "config": {"vocabulary_rows": 100},
        "flags": {"feature_size": 100, "embedding_size": 32,
                  "history_max_len": 16, "decoder_layers": 2,
                  "attn_q_heads": 2, "attn_kv_heads": 1, "attn_head_dim": 8,
                  "moe_experts": 8, "moe_top_k": 2, "moe_expert_width": 16,
                  "moe_experts_held": 4, "moe_pair_capacity": 128,
                  "learning_rate": 1e-3, "compute_dtype": "float32"},
        "traffic": {"shards": 2, "sequences_per_shard": 64,
                    "sequence_length": 16,
                    "limits": {"xent_gap": 1e-4, "first_moment_gap": 2e-3,
                               "param_change_gap": 0.1,
                               "untouched_rows_moved": 0, "noise_z": 5.0,
                               "masked_count_gap": 0,
                               "pairs_over_buffer": 0}}},
}


#: Its own: a cell's scratch directory is named for the cell and the seed, and
#: the other files' rehearsals run beside this one under xdist.
SEED = 2 ** 31 + 35


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_rehearsal_through_the_seam_reports_all_six(cell):
    from deepfm_tpu.obs import startup
    from deepfm_tpu.obs import trace as trace_lib

    trace_lib.reset()          # this process made its first dispatch long ago
    try:
        line = harness.run(
            cell, SEED, 1.0, True, require_chip=False,
            overrides={**TINY[cell],
                       "benchmark": setup_phases.contract_with_candidates()})
        record, origin = startup.phases(), startup.process_start_ns()
    finally:
        trace_lib.reset()
    assert line["correct"] is True
    got = {n: line["metrics"][n] for n in SIX}          # all six, every one
    assert all(m["unit"] == "s" and m["value"] >= 0.0 for m in got.values())
    names = {p[0] for p in record}
    assert {"setup.trainer", "setup.pipeline", "setup.first_batch",
            "setup.first_dispatch", "compile.trace", "compile.lower",
            "compile.backend"} <= names
    assert "setup.state" not in names     # the benchmark seeds its own state
    assert got["setup_build_s"]["value"] > 0
    assert got["setup_trace_lower_s"]["value"] > 0
    assert got["setup_backend_compile_s"]["value"] > 0
    assert got["setup_warmup_s"]["value"] > 0
    # the identity, on the run's own record: window open - process start
    end = [p[2] for p in record if p[0] == "setup.first_dispatch"][-1]
    open_ns = end + int(got["setup_warmup_s"]["value"] * 1e9)
    union_all = reader.phase_seconds(record, origin, open_ns, "union", names)
    assert got["setup_uncovered_s"]["value"] + union_all \
        + got["setup_warmup_s"]["value"] \
        == pytest.approx((open_ns - origin) / 1e9, abs=1e-6)
    # beside the accepted metrics a CPU's traced line has
    assert {"compiles_in_window.train", "dispatch_interval_ms_p50",
            "input_wait_ms_max"} <= set(line["metrics"])
