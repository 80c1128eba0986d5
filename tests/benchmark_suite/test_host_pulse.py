"""The three metrics PR 51 added over the program's host pulse
(``host.pulse`` / ``host.stall`` of ``deepfm_tpu.obs.trace``):
``readers/host_pulse.py`` on hand-made spans, and
``readers/device_idle_under_host_stall_share.py`` on hand-made gaps and on
the trace recorded on the chip. No test here runs a cell."""

import importlib
import inspect
import json
import os
import shutil
import types

import pytest

from benchmark import harness, xplane
from benchmark.readers import device_idle_under_host_stall_share as under
from benchmark.readers import host_pulse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
DATA = os.path.join(ROOT, "benchmark", "testdata")
SCOPED = os.path.join(DATA, "train_window_scoped.xplane.pb")
METRICS = {"host_beat_late_ms_max": "ms", "host_beat_runq_ms": "ms",
           "device_idle_under_host_stall_share": "%"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def ctx_for(window, spans=(), trace=True, cell="pulse-under-test"):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell), devices=[], counters={},
        spans=list(spans),
        trace={"devices": 1, "busy_s": 1.0} if trace else None,
        window=tuple(window))


def span(name, start_ms, dur_ms, **args):
    return {"name": name, "ph": "X", "ts": start_ms * 1e3,
            "dur": dur_ms * 1e3, "args": args}


def pulse(start_ms, late_ms_max, runq_ms=None, dur_ms=1000):
    args = {"beats": 200, "late_ms_max": late_ms_max, "cpu_ms": 9.0}
    if runq_ms is not None:
        args["runq_ms"] = runq_ms
    return span("host.pulse", start_ms, dur_ms, **args)


QUIET = [pulse(100, 0.75, 1.5), pulse(1100, 2.25, 0.25),
         pulse(2100, 1.5, 0.5)]


def test_late_max_and_runq_sum_over_the_windows_whole_pulses():
    # the window closes at 3,000 ms: the third pulse runs past it, and what
    # it carries (the profiler's stop) is not the window's
    spans = QUIET + [pulse(2100, 900.0, 700.0), span("stage.wait", 5, 400)]
    ctx = ctx_for((0, 3000e6), spans)
    assert host_pulse.read(ctx, "late_ms_max") == 2.25
    assert host_pulse.read(ctx, "runq_ms") == 1.75
    # a window shorter than two pulses reads the one that starts in it
    short = ctx_for((0, 500e6), QUIET[:1])
    assert host_pulse.read(short, "late_ms_max") == 0.75
    assert host_pulse.read(short, "runq_ms") == 1.5


def test_a_stall_counts_where_it_fell_cut_at_the_close():
    stalled = [pulse(100, 1450.0, 1440.0, dur_ms=1460),
               pulse(1560, 1.0, 0.5),
               span("host.stall", 105, 1450, late_ms=1450.0, cause="host_cpu"),
               # in the window's last, unfinished second: in no whole pulse
               span("host.stall", 2900, 400, late_ms=400.0, cause="gil")]
    ctx = ctx_for((0, 3000e6), stalled)
    assert host_pulse.read(ctx, "late_ms_max") == 1450.0
    assert host_pulse.read(ctx, "runq_ms") == 1440.5
    assert host_pulse.stalls(ctx) == [(105e6, 1555e6), (2900e6, 3000e6)]
    late = ctx_for((0, 3000e6), stalled[1:2] + stalled[3:])
    assert host_pulse.read(late, "late_ms_max") == 100.0


def test_none_without_a_pulse_or_without_the_source():
    parent = ctx_for((0, 3000e6), [span("stage.wait", 5, 400),
                                   span("host.gc", 9, 2, generation=2)])
    assert host_pulse.read(parent, "late_ms_max") is None
    assert host_pulse.read(parent, "runq_ms") is None
    assert under.read(parent) is None
    # a host without /proc/thread-self/schedstat: pulses, and no runq_ms
    bare = ctx_for((0, 3000e6), [pulse(100, 0.75), pulse(1100, 2.25)])
    assert host_pulse.read(bare, "late_ms_max") == 2.25
    assert host_pulse.read(bare, "runq_ms") is None


def test_overlap_of_gaps_and_stalls():
    gaps = [(0.0, 10.0), (20.0, 30.0), (50.0, 90.0)]
    assert under.overlap(gaps, []) == 0.0
    assert under.overlap([], gaps) == 0.0
    assert under.overlap(gaps, [(5.0, 25.0)]) == 10.0
    assert under.overlap(gaps, [(10.0, 20.0), (30.0, 50.0)]) == 0.0
    assert under.overlap(gaps, [(-5.0, 100.0)]) == 60.0
    assert under.overlap(gaps, [(8.0, 9.0), (9.0, 22.0), (60.0, 61.0),
                                (85.0, 95.0)]) == 10.0
    assert under.overlap([(0.0, 100.0)], gaps) == 60.0


def test_idle_under_a_stall_is_zero_without_one_and_never_read_then(
        monkeypatch):
    def unread(path, window):
        raise AssertionError("the trace is read only under a stall")
    monkeypatch.setattr(under, "first_device_idle", unread)
    monkeypatch.setattr(under.scope_device_ms, "newest_trace", unread)
    assert under.read(ctx_for((0, 3000e6), QUIET)) == 0.0
    assert under.read(ctx_for((0, 3000e6), QUIET, trace=False)) is None
    assert under.read(ctx_for((7, 7), QUIET)) is None


def test_idle_under_a_stall_from_hand_made_gaps(monkeypatch):
    monkeypatch.setattr(under.scope_device_ms, "newest_trace",
                        lambda cell: "a-trace")
    # idle 100..300 and 2,000..2,400 ms of a 4 s window (15%)
    monkeypatch.setattr(under, "first_device_idle", lambda path, window: [
        (100e6, 300e6), (2000e6, 2400e6)])
    spans = QUIET + [
        span("host.stall", 150, 100, late_ms=100.0, cause="gil"),   # inside
        span("host.stall", 200, 30, late_ms=30.0, cause="gil"),     # twice
        span("host.stall", 1000, 500, late_ms=500.0, cause="io"),   # busy
        span("host.stall", 2300, 3000, late_ms=3e3, cause="frozen")]
    got = under.read(ctx_for((0, 4000e6), spans))
    assert abs(got - 100.0 * (100 + 100) / 4000) < 1e-9
    assert got <= 15.0
    monkeypatch.setattr(under.scope_device_ms, "newest_trace",
                        lambda cell: None)
    assert under.read(ctx_for((0, 4000e6), spans)) is None


def test_the_first_devices_gaps_are_those_the_reduction_names(tmp_path,
                                                               monkeypatch):
    """On the trace recorded on the chip (two device planes): the reader's
    own pass finds the first device's gaps, which add up to the window less
    that device's busy time, and a stall laid over the longest reads its
    length."""
    with open(os.path.join(DATA, "train_window_scoped.json")) as f:
        window = tuple(json.load(f)["window_ns"])
    idle = under.first_device_idle(SCOPED, window)
    assert idle == sorted(idle) and all(a < b for a, b in idle)
    assert all(window[0] <= a and b <= window[1] for a, b in idle)
    reduced = xplane.reduce(SCOPED, window_ns=window)
    longest = max(idle, key=lambda g: g[1] - g[0])
    assert abs((longest[1] - longest[0]) / 1e9
               - reduced["idle_gaps"][0][1]) < 1e-9
    idle_s = sum(b - a for a, b in idle) / 1e9
    assert 0 < idle_s < reduced["window_s"]

    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    d = tmp_path / ".bench_work" / "pulse-under-test.7" / "trace" / \
        "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    shutil.copy(SCOPED, d / "host.xplane.pb")
    stall = span("host.stall", longest[0] / 1e6 - 1.0,
                 (longest[1] - longest[0]) / 1e6 + 2.0, cause="frozen")
    first = dict(stall, ts=window[0] / 1e3)      # starts with the window
    ctx = ctx_for(window, [pulse(window[0] / 1e6, 5.0), stall])
    width = window[1] - window[0]
    assert abs(under.read(ctx) - 100.0 * (longest[1] - longest[0]) / width) \
        < 1e-3      # the spans' microseconds as floats: a few ns
    everything = dict(first, dur=width / 1e3)
    ctx = ctx_for(window, [everything])
    assert abs(under.read(ctx) - 100.0 * idle_s * 1e9 / width) < 1e-6


@pytest.mark.parametrize("name", sorted(METRICS))
def test_new_metric_resolves(name):
    """Two are listed in all nine cells; ``host_beat_runq_ms`` is a candidate
    (``benchmark/candidates/``): the chip's host has no
    ``/proc/thread-self/schedstat`` (PERF.md section 6, PR 51), and a listed
    metric that reads nothing gets a PR refused."""
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    if name == "host_beat_runq_ms":
        assert name not in listed
        with open(os.path.join(ROOT, "benchmark", "candidates",
                               f"{name}.json")) as f:
            (entry,) = json.load(f)["per_layer"]
    else:
        entry = listed[name]
        for w in BENCH["workloads"]:
            assert name in harness.load_cell(w["name"]).per_layer
    assert entry == {
        "name": name, "unit": METRICS[name], "better": "lower",
        "source": "program_span", "layer": "host",
        "moves": "train_examples_per_s_per_chip",
        "workloads": [w["name"] for w in BENCH["workloads"]]}
    spec = harness.load_json("metrics", f"{name}.json")
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    inspect.signature(reader.read).bind(None, **spec["args"])
