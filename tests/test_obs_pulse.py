"""The host pulse of ``obs.trace``: one thread while tracing is on and none
while it is off, ``host.stall`` for a process that was stopped and for an
interpreter that was held, ``host.pulse`` once a second, the cause as a pure
function of the deltas, and ``trace_report --stalls`` naming it. Every wait
here has a time limit of its own."""

import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

import pytest

from deepfm_tpu.obs import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))
import trace_report  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace_lib.reset()
    yield
    trace_lib.reset()


def pulse_threads():
    return [t for t in threading.enumerate()
            if t.name == trace_lib.PULSE_THREAD]


def spans(name):
    return [e for e in trace_lib._tracer.events() if e["name"] == name]


def wait_for(found, seconds):
    """Poll ``found()`` until it returns something true or time runs out."""
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        got = found()
        if got:
            return got
        time.sleep(0.02)
    return found()


def test_the_pulse_lives_only_while_tracing_is_on(monkeypatch):
    assert pulse_threads() == []
    trace_lib.configure("off", export_env=False)
    assert pulse_threads() == []
    trace_lib.configure("full", export_env=False)
    trace_lib.configure("ring", capacity=64, export_env=False)
    (one,) = pulse_threads()
    assert one.daemon
    monkeypatch.setenv(trace_lib.ENV_MODE, "full")
    trace_lib.configure_from_env()
    (other,) = pulse_threads()
    assert other is not one and not one.is_alive()
    trace_lib.reset()
    assert pulse_threads() == [] and not other.is_alive()


CAUSES = {
    "host_cpu": dict(late_ms=1450.0, runq_ms=1440.0, cpu_ms=3.0,
                     steal_ms=1400.0),
    "host_cpu_though_others_ran": dict(late_ms=100.0, runq_ms=60.0,
                                       cpu_ms=90.0),
    "gil": dict(late_ms=300.0, runq_ms=2.0, cpu_ms=296.0, majflt=0,
                busiest_thread="pipeline-prefetch", busiest_cpu_ms=290.0),
    "memory_by_faults": dict(late_ms=80.0, runq_ms=1.0, cpu_ms=5.0,
                             majflt=12, psi_mem_ms=3.0),
    "memory_by_pressure": dict(late_ms=80.0, runq_ms=1.0, cpu_ms=5.0,
                               majflt=0, psi_mem_ms=70.0, psi_io_ms=75.0),
    "io": dict(late_ms=80.0, runq_ms=1.0, cpu_ms=5.0, majflt=0,
               psi_mem_ms=0.0, psi_io_ms=60.0),
    "frozen": dict(late_ms=300.0, runq_ms=0.4, cpu_ms=0.1, majflt=0,
                   nivcsw=0, steal_ms=0.0, psi_cpu_ms=0.0, psi_mem_ms=0.0,
                   psi_io_ms=0.0, throttled_ms=0.0),
    "frozen_with_every_optional_key_absent": dict(late_ms=25.0),
}


@pytest.mark.parametrize("case", sorted(CAUSES))
def test_cause_is_a_pure_function_of_the_deltas(case):
    want = case.split("_though_")[0].split("_by_")[0].split("_with_")[0]
    assert want in trace_lib.STALL_CAUSES
    assert trace_lib.stall_cause(CAUSES[case]) == want


def test_a_source_the_machine_has_not_leaves_its_key_out(monkeypatch):
    def missing(path, size=0):
        raise FileNotFoundError(path)
    monkeypatch.setattr(trace_lib, "_read", missing)
    counters = trace_lib._OsCounters()
    try:
        assert counters.dear() == ({}, {})
        if counters._schedstat is not None:
            assert counters.cheap()["runq_ms"] >= 0.0
            counters.close()
        assert set(counters.cheap()) == {"cpu_ms", "majflt", "nivcsw"}
    finally:
        counters.close()
    assert trace_lib._deltas({"cpu_ms": 5.0, "steal_ms": 30.0},
                             {"cpu_ms": 1.5}) == {"cpu_ms": 3.5}
    assert trace_lib._busiest({}, {}) == {}


CHILD = """
import sys, time
sys.path.insert(0, {root!r})
from deepfm_tpu.obs import trace
trace.configure("full", export_env=False)
print("ready", flush=True)
sys.stdin.readline()
trace.export({out!r})
"""


def test_a_stopped_process_exports_a_frozen_stall(tmp_path):
    out = str(tmp_path / "trace.json")
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD.format(root=ROOT, out=out)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        assert child.stdout.readline().strip() == "ready"
        time.sleep(0.2)
        child.send_signal(signal.SIGSTOP)
        time.sleep(0.3)
        child.send_signal(signal.SIGCONT)
        time.sleep(0.2)
        child.stdin.write("\n")
        child.stdin.flush()
        assert child.wait(timeout=30) == 0
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=10)
    with open(out) as f:
        stalls = [e for e in json.load(f)["traceEvents"]
                  if e["name"] == "host.stall"]
    long = [e for e in stalls if e["dur"] >= 0.25e6]
    assert len(long) == 1, stalls
    args = long[0]["args"]
    assert args["cause"] == "frozen", args
    assert abs(args["late_ms"] - long[0]["dur"] / 1e3) < 0.01
    for e in stalls:
        assert {"late_ms", "cpu_ms", "cause"} <= set(e["args"])
        assert e["ph"] == "X" and e["args"]["late_ms"] > trace_lib.STALL_MS
        if os.path.exists("/proc/thread-self/schedstat"):
            assert "runq_ms" in e["args"]
    if os.path.isdir("/proc/self/task"):
        assert args["busiest_cpu_ms"] <= 100.0, args   # nobody ran


def test_a_thread_that_holds_the_interpreter_is_a_stall_it_paid_for():
    """One C call that holds the interpreter for a few tenths of a second
    (how long depends on the machine, so the stall is held to the call's own
    length). The deltas are asserted, not the label: a loaded test machine
    may add run-queue wait."""
    numbers = [random.random() for _ in range(3_000_000)]
    trace_lib.configure("full", export_env=False)
    time.sleep(0.3)     # the pulse reads its baselines, then beats
    held_ms = []

    def hold():
        t0 = time.perf_counter()
        numbers.sort()
        held_ms.append(1e3 * (time.perf_counter() - t0))
    holder = threading.Thread(target=hold, name="test-sorter")
    holder.start()
    holder.join(timeout=60)
    assert not holder.is_alive() and held_ms[0] > 2 * trace_lib.STALL_MS
    stalls = wait_for(lambda: [e for e in spans("host.stall")
                               if e["args"]["late_ms"] >= 0.5 * held_ms[0]],
                      10)
    assert stalls, (held_ms, spans("host.stall"))
    args = max(stalls, key=lambda e: e["dur"])["args"]
    assert args["late_ms"] <= held_ms[0] + 100.0, (held_ms, args)
    assert args["cpu_ms"] >= 0.5 * args["late_ms"], args
    assert args["cause"] in trace_lib.STALL_CAUSES


def test_a_pulse_a_second_with_beats_near_two_hundred():
    trace_lib.configure("full", export_env=False)
    pulses = wait_for(lambda: len(spans("host.pulse")) >= 2
                      and spans("host.pulse"), 10)
    assert pulses and len(pulses) >= 2
    for a, b in zip(pulses, pulses[1:]):
        assert abs(a["ts"] + a["dur"] - b["ts"]) < 1.0     # end to start
    for e in pulses[:2]:
        assert 0.99e6 <= e["dur"] < 1.5e6
        args = e["args"]
        assert 120 <= args["beats"] <= 201, args    # 200 on a quiet host
        assert 0.0 <= args["late_ms_max"] and args["cpu_ms"] >= 0.0
        assert set(args) - {"beats", "late_ms_max"} <= set(
            trace_lib._PULSE_KEYS)
        if os.path.exists("/proc/thread-self/schedstat"):
            assert args["runq_ms"] >= 0.0


def test_every_thread_the_program_starts_has_a_name():
    """``busiest_thread`` names a suspect: never ``Thread-7``."""
    import ast
    import glob

    unnamed = []
    for path in glob.glob(os.path.join(ROOT, "deepfm_tpu", "**", "*.py"),
                          recursive=True) + glob.glob(
            os.path.join(ROOT, "benchmark", "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            called = getattr(node.func, "attr", getattr(node.func, "id", ""))
            want = {"Thread": "name",
                    "ThreadPoolExecutor": "thread_name_prefix"}.get(called)
            if want and want not in {k.arg for k in node.keywords}:
                unnamed.append(f"{os.path.relpath(path, ROOT)}:{node.lineno}")
    assert unnamed == []


def x(name, ts_ms, dur_ms, tid=1, **args):
    return {"name": name, "ph": "X", "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
            "pid": 1, "tid": tid, "args": args}


DISPATCHES = [x("stage.wait", 10, 490, seq=3),            # ends at 500
              x("stage.wait", 510, 480, seq=4),           # ends at 990
              x("stage.wait", 1000, 1450, seq=5),         # ends at 2450
              x("stage.wait", 2460, 480, seq=6)]          # ends at 2940
PULSE = [x("host.stall", 1005, 1440, tid=9, cause="host_cpu", late_ms=1440.0,
           runq_ms=1431.5, cpu_ms=2.25, steal_ms=1390.0, majflt=0,
           busiest_thread="pipeline-prefetch", busiest_cpu_ms=10.0),
         x("host.pulse", 0, 1000, tid=9, beats=200, late_ms_max=0.75,
           runq_ms=0.5, cpu_ms=40.0),
         x("host.pulse", 1000, 1450, tid=9, beats=2, late_ms_max=1440.0,
           runq_ms=1431.6, cpu_ms=3.0),
         x("host.pulse", 2450, 1000, tid=9, beats=200, late_ms_max=1.25,
           runq_ms=0.7, cpu_ms=41.0)]


def test_stalls_puts_the_host_stall_first_with_its_cause():
    assert trace_report.STALL_SPANS[0] == "host.stall"
    (st,) = trace_report.stalls(DISPATCHES + PULSE, 550)
    assert st["seq"] == 5 and st["interval_ms"] == 1460.0
    assert list(st["cover_ms"])[0] == "host.stall"
    assert st["cover_ms"]["host.stall"] == 1440.0
    assert st["cover_ms"]["stage.wait"] == 1450.0     # the victim
    (under,) = st["host_stalls"]
    assert under == {"cause": "host_cpu", "late_ms": 1440.0,
                     "runq_ms": 1431.5, "cpu_ms": 2.25, "steal_ms": 1390.0,
                     "busiest_thread": "pipeline-prefetch"}
    summary = trace_report.host_pulse(DISPATCHES + PULSE)
    assert summary == {"stalls": {"host_cpu": {"count": 1,
                                               "total_ms": 1440.0}},
                       "pulses": 3, "quiet_late_ms_max_median": 1.0}


@pytest.mark.parametrize("with_pulse", [True, False])
def test_the_report_prints_the_cause_and_reads_a_trace_without_the_pulse(
        tmp_path, capsys, with_pulse):
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(
        {"traceEvents": DISPATCHES + (PULSE if with_pulse else [])}))
    assert trace_report.main([str(path), "--stalls", "550"]) == 0
    out = capsys.readouterr().out
    assert "1 dispatch intervals over 550 ms" in out
    assert "stall before transfer seq=5" in out and "stage.wait 1450.0" in out
    assert ("host.stall: cause host_cpu, late_ms 1440.0, runq_ms 1431.5, "
            "cpu_ms 2.2, steal_ms 1390.0, busiest_thread pipeline-prefetch"
            in out) is with_pulse
    assert ("host pulse: 1 stalls (host_cpu 1, 1440.0 ms)" in out) \
        is with_pulse
    assert ("host pulse" in out) is with_pulse
    assert trace_report.main([str(path), "--stalls", "550", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert ("host_pulse" in doc) is with_pulse
    assert len(doc["stalls"][0]["host_stalls"]) == int(with_pulse)
