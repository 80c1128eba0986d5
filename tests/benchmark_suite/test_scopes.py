"""The per-phase device metrics and the input-thread metrics PR 25 added:
``readers/scope_device_ms.py`` on a trace recorded on the chip, and
``readers/span_busy_share.py`` on hand-made spans. No test here runs a cell:
``test_benchmark.py`` rehearses those, and two files that ran the same cell
at once would share its work directory."""

import importlib
import inspect
import json
import os
import shutil
import types

import pytest

from benchmark import harness, xplane
from benchmark.readers import scope_device_ms as sdm
from benchmark.readers import span_busy_share, span_percentile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
DATA = os.path.join(ROOT, "benchmark", "testdata")
SCOPED = os.path.join(DATA, "train_window_scoped.xplane.pb")
NEW_METRICS = {
    "train_embed_device_ms": ["embed"],
    "train_update_device_ms": ["opt", "l2"],
    "train_dense_device_ms": ["fm", "tower", "loss"],
    "train_unscoped_device_ms": [],
    "input_wait_ms_max": None, "input_busy_share": None,
    "host_gc_ms_max": None,
}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
#: A listed metric has to be in every traced line, and a reader with nothing
#: to read leaves its metric out. ``host_gc_ms_max`` is the longest collection
#: of the window: it lists the cells in which one falls in every 12 s (the
#: rankers': 18 to 122 dispatches of 8 steps, each with host batches of its
#: own). The decoders run 20 to 43 dispatches of a step or two: no
#: collection ran in any of their traced chip runs (PERF.md section 7
#: row 20(c); the driver refused PR 31 for listing the metric there).
WORKLOADS = {"host_gc_ms_max": ["deepfm-criteo.train-files",
                                "deepfm-criteo-host4.train-files",
                                "dlrm-dcnv2-criteo1tb.train-files"]}


@pytest.fixture(scope="module")
def recorded():
    """0.8 s (one dispatch of 8 steps) of deepfm-criteo-host4.train-files on
    four v5e chips, cut from a traced run of PR 25 to the ops lines of its
    first two devices, with the map the program gave in that run for the
    ops of the cut, keyed as the reader keys it (name and result type; the
    step compiled for a described v5e:2x2 gives the same 231 entries)."""
    with open(os.path.join(DATA, "train_window_scoped.json")) as f:
        return json.load(f)


def ctx_for(window, spans=(), steps=8, trace=True, cell="scopes-under-test"):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell, config={"flags": {}}),
        devices=[], counters={"steps_in_window": steps}, spans=list(spans),
        trace={"devices": 2, "busy_s": 1.0} if trace else None,
        window=tuple(window))


def test_scopes_and_collectives_fill_the_busy_time(recorded):
    window = tuple(recorded["window_ns"])
    steps = recorded["steps_in_window"]
    ops, collective = sdm.own_seconds(SCOPED, window)
    times = sdm.by_scope(ops, recorded["op_scopes"])
    got = {name: sdm.value_ms(times, scopes, steps)
           for name, scopes in NEW_METRICS.items() if scopes is not None}
    busy_ms = 1e3 * xplane.reduce(SCOPED, window_ns=window)["busy_s"] / steps
    total = sum(got.values()) + 1e3 * collective / steps
    assert abs(total - busy_ms) < 0.02 * busy_ms
    for name, want in recorded["expected_ms"].items():
        assert abs(got[name] - want) < 1e-6, name
    assert abs(1e3 * collective / steps
               - recorded["expected_collective_ms"]) < 1e-6
    assert got["train_unscoped_device_ms"] < 0.05 * busy_ms
    # the table-shaped scatter-add (19.5 ms a step) is the gather's
    # transpose, not the sweep; the mesh renumbers one chip's fusion.267
    assert recorded["op_scopes"]["fusion.280 f32[16881344,32]"] == "embed"
    assert recorded["op_scopes"][
        "multiply_add_fusion.30 f32[16881344,32]"] == "opt"


def test_a_map_with_the_names_and_other_results_is_of_another_program(
        recorded):
    """A second compilation that numbered its fusions another way keeps the
    set of names: what gives it away is an op's result. The table-shaped
    scatter-add under a batch-shaped fusion's number is not known, and it is
    a fifth of the step."""
    ops, _ = sdm.own_seconds(SCOPED, tuple(recorded["window_ns"]))
    renumbered = dict(recorded["op_scopes"])
    renumbered["fusion.280 f32[4096,39,32]"] = renumbered.pop(
        "fusion.280 f32[16881344,32]")
    assert {k.split()[0] for k in renumbered} == {k.split()[0] for k in ops}
    assert sdm.by_scope(ops, renumbered) is None


def test_the_map_is_keyed_by_name_and_result_as_the_trace_names_its_ops():
    text = """HloModule jit_multi, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0: f32[8,4]) -> f32[8,4] {
  %param_0 = f32[8,4]{1,0:T(8,128)} parameter(0)
  ROOT %add.3 = f32[8,4]{1,0:T(8,128)} add(%param_0, %param_0)
}

ENTRY %main.9 () -> f32[] {
  %fusion.267 = (f32[16881344,32]{0,1:T(8,128)}, f32[]{:T(128)}) fusion(%p)
  %slice-start.2 = ((f32[16]{0}), f32[4]{0:S(1)}, s32[]{:S(2)}) async-start(%q)
  %nothing.1 = () tuple()
}
"""
    scopes = {"param_0": "", "add.3": "opt", "fusion.267": "embed",
              "slice-start.2": "", "nothing.1": ""}
    assert sdm.keyed_scopes(text, scopes) == {
        "param_0 f32[8,4]": "", "add.3 f32[8,4]": "opt",
        "fusion.267 f32[16881344,32]": "embed", "slice-start.2 f32[16]": ""}
    # the trace's event has the operands' types too; the key is the same
    assert sdm.op_key("%fusion.267 = (f32[16881344,32]{0,1:T(8,128)}, f32[]"
                      "{:T(128)}) fusion(f32[4096,39]{1,0} %p), kind=kLoop"
                      ) == "fusion.267 f32[16881344,32]"
    assert sdm.op_key("not an instruction") == "not an instruction"


def test_a_trace_without_known_scopes_reads_none_not_zero():
    """PR 24's fixture with the map an executable cached before the scopes
    existed would give: every op known, none in a scope. The named metrics
    are left out and the unscoped one is the whole step. A map that does not
    know the ops at all (another program's) gives nothing."""
    with open(os.path.join(DATA, "train_window.json")) as f:
        window = tuple(json.load(f)["window_ns"])
    old = os.path.join(DATA, "train_window.xplane.pb")
    ops, collective = sdm.own_seconds(old, window)
    assert collective == 0.0 and "fusion.267 f32[16881344,32]" in ops
    times = sdm.by_scope(ops, dict.fromkeys(ops, sdm.UNSCOPED))
    for scopes in (["embed"], ["opt", "l2"], ["fm", "tower", "loss"]):
        assert sdm.value_ms(times, scopes, 24) is None
    whole = sdm.value_ms(times, [], 24)
    busy_ms = 1e3 * xplane.reduce(old, window_ns=window)["busy_s"] / 24
    assert 0.98 * busy_ms < whole <= busy_ms
    assert sdm.by_scope(ops, {}) is None
    assert sdm.by_scope(ops, {"fusion.1 f32[8]": "opt",
                              "copy.9 f32[8]": ""}) is None
    assert sdm.value_ms(None, [], 24) is None
    # a few short ops the map lacks do not condemn it
    known = {n: "opt" for n, t in ops.items() if t > 1e-4 * sum(ops.values())}
    assert len(known) < len(ops)
    assert sdm.by_scope(ops, known) is not None


def test_read_finds_the_trace_and_parses_it_once(recorded, tmp_path,
                                                  monkeypatch):
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(sdm, "_reduced", {})
    ctx = ctx_for(recorded["window_ns"],
                  steps=recorded["steps_in_window"])
    assert sdm.read(ctx, ["embed"]) is None           # no trace on disk
    for stamp in ("2026_01_01", "2026_01_02"):
        d = tmp_path / ".bench_work" / f"{ctx.cell.name}.7" / "trace" / \
            "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"not a trace")
    shutil.copy(SCOPED, d / "host.xplane.pb")
    os.utime(d / "host.xplane.pb", (2e9, 2e9))        # the newest
    calls = []
    monkeypatch.setattr(sdm, "program_op_scopes",
                        lambda c: calls.append(c) or recorded["op_scopes"])
    for name, scopes in NEW_METRICS.items():
        if scopes is not None:
            assert abs(sdm.read(ctx, scopes)
                       - recorded["expected_ms"][name]) < 1e-6
    assert len(calls) == 1
    # no device trace, or a program that cannot say: nothing to read
    assert sdm.read(ctx_for(recorded["window_ns"], trace=False), []) is None
    monkeypatch.setattr(sdm, "_reduced", {})
    monkeypatch.setattr(sdm, "program_op_scopes", lambda c: None)
    assert sdm.read(ctx, []) is None


def test_a_program_without_the_method_supplies_no_map(monkeypatch):
    from benchmark.drivers import _program

    monkeypatch.setattr(_program, "make_config", lambda flags: flags)
    monkeypatch.setattr(_program, "build_trainer",
                        lambda cfg, devices: object())
    assert sdm.program_op_scopes(ctx_for((0, 1))) is None
    text = ('  %a.1 = f32[4]{0} add(%x, %y), metadata={op_name='
            '"jit(multi)/while/body/opt/add"}\n  %b.2 = f32[4]{0} copy(%a.1)')
    with_method = types.SimpleNamespace(step_hlo_text=lambda: text)
    monkeypatch.setattr(_program, "build_trainer",
                        lambda cfg, devices: with_method)
    assert sdm.program_op_scopes(ctx_for((0, 1))) == {
        "a.1 f32[4]": "opt", "b.2 f32[4]": ""}


def span(name, start_ms, dur_ms):
    return {"name": name, "ph": "X", "ts": start_ms * 1e3, "dur": dur_ms * 1e3}


def test_busy_share_is_the_union_clipped_to_the_window():
    names = ["input.pool_fill", "input.pool_drain", "input.emit"]
    spans = [span("input.pool_fill", 10, 30),         # 10..40
             span("input.pool_drain", 30, 30),        # 30..60 overlaps
             span("input.emit", 35, 1),               # inside both
             span("input.read", 0, 100),              # not asked for
             span("input.pool_fill", 90, 50)]         # 90..140, clipped at 100
    ctx = ctx_for((0, 100e6), spans)
    assert abs(span_busy_share.read(ctx, names) - 60.0) < 1e-9
    assert span_busy_share.read(ctx_for((0, 100e6), spans[3:4]),
                                names) is None
    assert span_busy_share.read(ctx_for((5, 5), spans), names) is None


def test_max_of_a_span_is_percentile_100_and_none_without_one():
    spans = [span("host.gc", 1, 0.3), span("host.gc", 5, 41.5),
             span("stage.input_wait", 7, 2300.0)]
    ctx = ctx_for((0, 1e9), spans)
    assert span_percentile.read(ctx, "host.gc", 100) == 41.5
    assert span_percentile.read(ctx, "stage.input_wait", 100) == 2300.0
    assert span_percentile.read(ctx_for((0, 1e9), spans[2:]),
                                "host.gc", 100) is None


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_resolves(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["moves"] == "train_examples_per_s_per_chip"
    assert entry["better"] == "lower"
    assert entry["workloads"] == WORKLOADS.get(
        name, [w["name"] for w in BENCH["workloads"]])
    spec = harness.load_json("metrics", f"{name}.json")
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    inspect.signature(reader.read).bind(None, **spec["args"])
    if NEW_METRICS[name] is not None:
        assert spec["args"]["scopes"] == NEW_METRICS[name]
    cell = harness.load_cell(BENCH["workloads"][0]["name"])
    assert name in cell.per_layer
