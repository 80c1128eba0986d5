"""The start-up record (``obs.startup``): phases kept whether tracing is on or
off, merged into whichever tracer exports; JAX's compile timings as
``compile.*`` spans; the launcher's start-up line; ``trace_report``'s
start-up section (TUNING §17)."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.obs import startup
from deepfm_tpu.obs import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import trace_report  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    """Every test starts and ends with tracing off and an empty, open
    record (``trace.reset`` resets both)."""
    trace_lib.reset()
    yield
    trace_lib.reset()


def _names(events):
    return [e["name"] for e in events if e.get("ph") == "X"]


def _fit_once(**over):
    from deepfm_tpu.train import Trainer

    cfg = Config(**{**dict(
        feature_size=200, field_size=4, embedding_size=4, deep_layers="8",
        dropout="1.0", batch_size=32, compute_dtype="float32",
        learning_rate=0.01, log_steps=0, seed=7, scale_lr_by_world=False,
        mesh_data=1, mesh_model=1, steps_per_loop=2), **over})
    rng = np.random.default_rng(3)
    batches = [{
        "label": rng.integers(0, 2, (32,)).astype(np.float32),
        "feat_ids": rng.integers(0, 200, (32, 4)).astype(np.int32),
        "feat_vals": rng.standard_normal((32, 4)).astype(np.float32),
    } for _ in range(4)]
    tr = Trainer(cfg)
    return tr.fit(tr.init_state(), batches)


# ---------------------------------------------------------------------------
# The record and the tracer
# ---------------------------------------------------------------------------

def test_a_phase_begun_before_configure_is_exported_on_the_wall_clock(
        tmp_path):
    before = time.time_ns()
    with startup.phase("setup.trainer", model="deepfm"):
        time.sleep(0.002)
    with startup.importing("no.such.module.loaded"):
        pass
    after = time.time_ns()
    trace_lib.configure("full", trace_dir=str(tmp_path), export_env=False)
    with trace_lib.span("train.dispatch", seq=1):
        pass
    with open(trace_lib.export()) as f:
        doc = json.load(f)
    evs = {e["name"]: e for e in doc["traceEvents"]}
    ph = evs["setup.trainer"]
    assert ph["ph"] == "X" and ph["args"] == {"model": "deepfm"}
    assert before <= ph["ts"] * 1e3 <= after and ph["dur"] >= 2000.0
    assert ph["pid"] == os.getpid() and ph["tid"] == threading.get_ident()
    assert evs["setup.import"]["args"] == {"module": "no.such.module.loaded"}
    # the record's origin: the process's start, before everything it holds
    origin = evs["setup.process_start"]
    assert origin["ph"] == "i"
    assert abs(origin["ts"] * 1e3 - startup.process_start_ns()) < 1e3
    assert startup.process_start_ns() < before
    # one timeline: the phase precedes the span recorded after configure()
    assert ph["ts"] + ph["dur"] <= evs["train.dispatch"]["ts"]


def test_process_start_is_the_kernels_and_precedes_this_modules_import():
    got = startup.process_start_ns()
    assert 0 < got < time.time_ns()
    # /proc is there on Linux: the origin is the process's own start, some
    # time before the interpreter could import anything
    with open("/proc/self/stat") as f:
        assert f.read()
    # never later than the first thing the process is known to have done
    assert startup._process_start_ns(got - 10 ** 9) == got - 10 ** 9


def test_two_configures_leave_one_event_for_a_phase():
    with startup.phase("setup.pipeline", files=3):
        pass
    trace_lib.configure("ring", export_env=False)
    trace_lib.configure("full", export_env=False)      # as harness.Spans does
    assert _names(trace_lib._tracer.events()).count("setup.pipeline") == 1
    assert _names(trace_lib._tracer.events()).count("setup.pipeline") == 1
    assert len(startup.phases()) == 1


def test_with_tracing_off_the_record_is_kept_and_nothing_is_exported(tmp_path):
    with startup.phase("setup.trainer"):
        pass
    assert not trace_lib.enabled()
    assert [p[0] for p in startup.phases()] == ["setup.trainer"]
    assert trace_lib._tracer.events() == []
    assert trace_lib.export(str(tmp_path / "t.json")) is None
    assert not os.listdir(tmp_path)


def test_the_record_is_bounded_and_counts_what_it_drops():
    for i in range(startup.CAPACITY + 7):
        with startup.phase("setup.pipeline", i=i):
            pass
    held = startup.phases()
    assert len(held) == startup.CAPACITY
    assert [p[4]["i"] for p in held] == list(range(startup.CAPACITY))
    assert startup.dropped() == 7 == trace_lib.dropped()
    assert "7 phases over the record's 256 dropped" in startup.log_line()


def test_after_the_first_dispatch_the_record_is_closed_to_setup_phases():
    boot = startup.first_fit()
    boot.batch_in_hand()
    line = boot.dispatched(steps=8)
    assert line.startswith("start-up ") and "first dispatch" in line
    assert startup.first_fit() is None
    held = startup.phases()
    assert [p[0] for p in held] == ["setup.first_batch",
                                    "setup.first_dispatch"]
    assert held[0][2] == held[1][1] and held[1][4] == {"steps": 8}
    with startup.phase("setup.trainer") as ph:     # a second trainer, later
        ph.add(model="x")
    with startup.importing("no.such.module.loaded"):
        pass
    assert startup.phases() == held


def test_an_import_that_is_loaded_already_is_not_stamped_again():
    assert "json" in sys.modules
    with startup.importing("json"):
        pass
    assert startup.phases() == []


def test_the_line_is_assembled_from_the_record():
    s = 10 ** 9
    o = 1_000 * s
    held = [
        ("setup.import", o + 1 * s, o + 4 * s, 1, {"module": "jax"}),
        # the driver's thread, beside the main thread's: a union over time
        ("setup.import", o + 2 * s, o + 15 * s, 2,
         {"module": "deepfm_tpu.train.tasks"}),
        ("setup.import", o + 3 * s, o + 14 * s, 2,
         {"module": "orbax.checkpoint"}),
        ("setup.import", o + 14 * s, o + 14 * s + s // 5, 2,
         {"module": "flax"}),                       # under half a second
        ("setup.backend", o + 4 * s, o + 7 * s, 1, {}),
        ("setup.trainer", o + 15 * s, o + 15 * s + s // 2, 1, {}),
        ("setup.pipeline", o + 16 * s, o + 16 * s + s // 4, 1, {}),
        ("setup.first_batch", o + 17 * s, o + 17 * s + s // 5, 1, {}),
        ("compile.trace", o + 18 * s, o + 19 * s, 1, {"fun_name": "multi"}),
        ("compile.lower", o + 19 * s, o + 21 * s, 1, {"fun_name": "multi"}),
        ("compile.cache_fetch", o + 21 * s, o + 22 * s, 1, {"cache": "hit"}),
        ("compile.backend", o + 21 * s, o + 27 * s, 1,
         {"fun_name": "jit(multi)", "cache": "hit"}),
        ("compile.backend", o + 27 * s, o + 27 * s + s // 2, 1,
         {"fun_name": "jit(f)", "cache": "miss"}),
        ("setup.first_dispatch", o + 17 * s + s // 5, o + 28 * s, 1,
         {"steps": 8}),
    ]
    got = startup.summary(held, o)
    assert got["total_s"] == 28.0
    assert got["phases"]["setup.import"] == 14.0         # 1..15, two threads
    assert got["imports"]["orbax.checkpoint"] == 11.0
    assert got["first_dispatch"] == {
        "compile.trace": 1.0, "compile.lower": 2.0, "compile.backend": 6.5,
        "cache_hits": 1, "cache_misses": 1}
    # under no span: 0..1, 15.5..16, 16.25..17
    assert got["uncovered_s"] == pytest.approx(1.0 + 0.5 + 0.75)
    assert startup.log_line(held, o) == (
        "start-up 28.0 s: import 14.0 (orbax.checkpoint 11.0, jax 3.0) · "
        "backend 3.0 · trainer 0.5 · pipeline 0.2 · first batch 0.2 · "
        "first dispatch 10.8 (trace 1.0, lower 2.0, backend 6.5, cache 1 hit "
        "/ 1 miss) · uncovered 2.2")
    # the benchmark's cut: only what ended by the window's opening counts,
    # and the warm-up is what lies between the first dispatch and it
    assert got["warmup_s"] is None                       # no window given
    cut = startup.summary(held, o, o + 31 * s)
    assert cut["warmup_s"] == 3.0
    assert {k: v for k, v in cut.items() if k != "warmup_s"} \
        == {k: v for k, v in got.items() if k != "warmup_s"}
    early = startup.summary(held, o, o + 20 * s)   # before the dispatch ended
    assert early["warmup_s"] is None
    assert "setup.first_dispatch" not in early["phases"]


# ---------------------------------------------------------------------------
# The trainer's phases, JAX's compile timings
# ---------------------------------------------------------------------------

def test_a_fit_with_tracing_off_records_the_phases_and_logs_the_line(caplog):
    import logging

    with caplog.at_level(logging.INFO, logger="deepfm_tpu"):
        _fit_once()
    names = [p[0] for p in startup.phases()]
    for want in ("setup.trainer", "setup.first_batch", "setup.first_dispatch",
                 "compile.trace", "compile.lower", "compile.backend"):
        assert want in names, want
    assert names[-1] == "setup.first_dispatch"
    assert startup.first_fit() is None
    by_name = {p[0]: p for p in startup.phases()}
    assert by_name["setup.first_dispatch"][4] == {"steps": 2}
    assert by_name["setup.trainer"][4] == {"model": "deepfm"}
    # the scanned step, by name, in all three stages, inside the dispatch
    d0, d1 = by_name["setup.first_dispatch"][1:3]
    for stage in ("compile.trace", "compile.lower", "compile.backend"):
        assert any(p[0] == stage and "multi" in p[4]["fun_name"]
                   and d0 <= p[1] and p[2] <= d1
                   for p in startup.phases()), stage
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("start-up ")]
    assert len(lines) == 1 and "first dispatch" in lines[0]
    assert trace_lib._tracer.events() == []
    # a second fit of the process: no second line, nothing more recorded
    n = len(startup.phases())
    with caplog.at_level(logging.INFO, logger="deepfm_tpu"):
        _fit_once()
    assert len(startup.phases()) == n
    assert len([r for r in caplog.records
                if r.getMessage().startswith("start-up ")]) == 1


def test_fit_does_not_hold_the_first_superbatch_past_its_dispatch(
        monkeypatch):
    """The loop iterates the staging iterator itself: whatever pulled the
    first superbatch ahead of it would pin its device arrays for the whole
    fit (about one superbatch of ``memory_peak_bytes`` on the chip)."""
    import gc
    import weakref

    from deepfm_tpu.train import Trainer

    alive_at_pull = []
    real = Trainer._stage

    def staged(self, batches, k, depth):
        refs = []
        for dev, m, n_ex in real(self, batches, k, 0):
            gc.collect()
            alive_at_pull.append([r() is not None for r in refs])
            refs.append(weakref.ref(dev["label"]))    # a device array
            yield dev, m, n_ex
            del dev

    monkeypatch.setattr(Trainer, "_stage", staged)
    _fit_once(steps_per_loop=1)
    # at each pull only the superbatch just dispatched may still be bound
    # (the loop's own variable), never the first one as well
    assert alive_at_pull[0] == [] and len(alive_at_pull) == 4
    assert [sum(a) for a in alive_at_pull[1:]] == [1, 1, 1]
    assert alive_at_pull[3][0] is False
    assert "setup.first_batch" in [p[0] for p in startup.phases()]


def test_the_backend_phase_is_stamped_once_a_process(monkeypatch):
    from deepfm_tpu.parallel import bootstrap

    monkeypatch.setattr(bootstrap, "_BACKEND_ASKED", False)
    bootstrap.start_backend()
    bootstrap.start_backend()          # ``tasks.run`` after ``launch.main``
    held = [p for p in startup.phases() if p[0] == "setup.backend"]
    assert len(held) == 1
    assert held[0][4]["devices"] >= 1 and held[0][4]["platform"] == "cpu"


def test_short_inner_traces_are_not_kept():
    startup.listen_to_jax()
    startup._on_time_span("/jax/core/compile/jaxpr_trace_duration",
                          100.0, 100.0 + 2e-5, fun_name="_uniform")
    startup._on_time_span("/jax/core/compile/jaxpr_trace_duration",
                          100.0, 100.5, fun_name="multi")
    startup._on_time_span("/jax/core/compile/some_other_duration",
                          100.0, 100.5, fun_name="multi")
    assert [(p[0], p[4]) for p in startup.phases()] == [
        ("compile.trace", {"fun_name": "multi"})]


def test_a_recompilation_after_start_up_is_a_span_with_tracing_on_only():
    import jax
    import jax.numpy as jnp

    startup.listen_to_jax()
    startup.close()

    def forced_again(x):
        return jnp.tanh(x) * 3.0 + x.sum()

    trace_lib.configure("full", export_env=False)
    jax.block_until_ready(jax.jit(forced_again)(jnp.ones((7, 3))))
    spans = [e for e in trace_lib._tracer.events() if e["ph"] == "X"]
    by = {e["name"]: e for e in spans
          if "forced_again" in e.get("args", {}).get("fun_name", "")}
    assert {"compile.lower", "compile.backend"} <= set(by)
    assert by["compile.lower"]["ts"] <= by["compile.backend"]["ts"]
    assert startup.phases() == []           # closed: nothing went in

    trace_lib.configure("off", export_env=False)
    jax.block_until_ready(jax.jit(forced_again)(jnp.ones((5, 2))))
    assert trace_lib._tracer.events() == [] and startup.phases() == []


_SECOND_PROCESS = """
import json, sys
from deepfm_tpu.utils import compile_cache
compile_cache.configure()
import jax, jax.numpy as jnp
from deepfm_tpu.obs import startup

def cached_fn(x):
    return (jnp.sin(x) @ x.T).sum() * 2.0

jax.block_until_ready(jax.jit(cached_fn)(jnp.ones((16, 16))))
print(json.dumps(startup.phases()))
"""


def test_a_second_process_fetches_inside_compile_backend(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}

    def run():
        out = subprocess.run([sys.executable, "-c", _SECOND_PROCESS],
                             env=env, capture_output=True, text=True,
                             timeout=300, cwd=str(tmp_path))
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    def of(phases, name):
        return [p for p in phases if p[0] == name
                and "cached_fn" in p[4].get("fun_name", "cached_fn")]

    cold, warm = run(), run()
    (cold_backend,) = [p for p in of(cold, "compile.backend")]
    assert cold_backend[4] == {"fun_name": "jit(cached_fn)", "cache": "miss"}
    assert not [p for p in cold if p[0] == "compile.cache_fetch"]
    (backend,) = of(warm, "compile.backend")
    assert backend[4] == {"fun_name": "jit(cached_fn)", "cache": "hit"}
    for stage in ("compile.trace", "compile.lower"):
        assert of(warm, stage), stage           # no cache saves these
    fetches = [p for p in warm if p[0] == "compile.cache_fetch"
               and backend[1] <= p[1] and p[2] <= backend[2] + 1_000_000
               and p[3] == backend[3]]
    assert len(fetches) == 1 and fetches[0][4] == {"cache": "hit"}
    # the imports the entry point paid, by name, before any of it
    assert any(p[0] == "setup.import" and p[4]["module"] == "jax"
               for p in warm)


# ---------------------------------------------------------------------------
# The launcher, end to end
# ---------------------------------------------------------------------------

def _launch(data, extra, tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = subprocess.run(
        [sys.executable, "-m", "deepfm_tpu.launch", "--task_type", "train",
         "--data_dir", data, "--val_data_dir", data, "--feature_size", "300",
         "--field_size", "5", "--embedding_size", "4", "--deep_layers", "8",
         "--dropout", "1.0", "--batch_size", "64", "--num_epochs", "1",
         "--steps_per_loop", "2", "--log_steps", "4"] + extra,
        env=env, capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-3000:]
    return out


def test_launcher_exports_start_up_with_trace_full_and_logs_it_with_off(
        tmp_path):
    from deepfm_tpu.data import libsvm

    data = str(tmp_path / "data")
    for prefix, seed in (("tr", 1), ("va", 2)):
        libsvm.generate_synthetic_ctr(data, num_files=1,
                                      examples_per_file=512, feature_size=300,
                                      field_size=5, prefix=prefix, seed=seed)
    run_dir = tmp_path / "full"
    out = _launch(data, ["--trace", "full", "--trace_dir", str(run_dir),
                         "--model_dir", str(tmp_path / "ckpt")], tmp_path)
    (path,) = [os.path.join(run_dir, f) for f in os.listdir(run_dir)
               if f.startswith("trace-")]
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    modules = {e["args"]["module"] for e in spans
               if e["name"] == "setup.import"}
    assert {"jax", "deepfm_tpu.train", "deepfm_tpu.train.loop",
            "deepfm_tpu.models", "deepfm_tpu.train.tasks",
            "orbax.checkpoint", "jax.experimental.pallas"} <= modules
    names = {e["name"] for e in spans}
    assert {"setup.backend", "setup.trainer", "setup.state",
            "setup.pipeline", "setup.first_batch", "setup.first_dispatch",
            "compile.trace", "compile.lower", "compile.backend",
            "train.dispatch"} <= names
    assert "setup.distributed" not in names            # dist_mode 0
    state = next(e for e in spans if e["name"] == "setup.state")
    assert state["args"]["source"] == "init"
    # on the tracer's clock, in order, those before configure() included
    first = {n: min(e["ts"] for e in spans if e["name"] == n) for n in names}
    origin = next(e["ts"] for e in events
                  if e["name"] == "setup.process_start")
    assert origin < first["setup.import"] < first["setup.backend"] \
        < first["setup.trainer"] < first["setup.state"] \
        < first["setup.first_dispatch"] <= first["train.dispatch"]
    assert abs(first["setup.first_dispatch"] / 1e6 - time.time()) < 3600
    line = [ln for ln in out.stderr.splitlines() if "start-up " in ln]
    assert line and "orbax.checkpoint" in line[0]
    # the report reads the section back from the file
    (boot,) = trace_report.start_up(events)
    assert boot["line"] in line[0]

    # a resumed run says where its state came from; --trace off writes no file
    off_dir = tmp_path / "off"
    out = _launch(data, ["--trace", "off", "--trace_dir", str(off_dir),
                         "--model_dir", str(tmp_path / "ckpt"),
                         "--num_epochs", "2"], tmp_path)
    line = [ln for ln in out.stderr.splitlines() if "start-up " in ln]
    assert line and "first dispatch" in line[0] and "state" in line[0]
    assert not os.path.exists(off_dir) or not [
        f for f in os.listdir(off_dir) if f.startswith("trace-")]


# ---------------------------------------------------------------------------
# trace_report
# ---------------------------------------------------------------------------

def _x(name, ts_ms, dur_ms, tid=1, **args):
    ev = {"name": name, "ph": "X", "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
          "pid": 9, "tid": tid}
    if args:
        ev["args"] = args
    return ev


def test_report_has_a_start_up_section_with_self_times_and_compiles(capsys,
                                                                    tmp_path):
    events = [
        {"name": "setup.process_start", "ph": "i", "s": "p", "ts": 0.0,
         "pid": 9, "tid": 1},
        _x("setup.import", 100, 5000, module="deepfm_tpu.train"),
        _x("setup.import", 200, 3000, module="orbax.checkpoint"),
        _x("setup.trainer", 5200, 300, model="deepfm"),
        _x("compile.trace", 5600, 100, fun_name="multi"),
        _x("compile.lower", 5700, 200, fun_name="multi"),
        _x("compile.backend", 5900, 1000, fun_name="jit(multi)", cache="hit"),
        _x("compile.cache_fetch", 5950, 900, cache="hit"),
        _x("setup.first_dispatch", 5500, 1500, steps=8),
        # after start-up: a recompilation, not part of the section
        _x("compile.backend", 9000, 400, fun_name="jit(step)", cache="miss"),
    ]
    (boot,) = trace_report.start_up(events)
    assert boot["pid"] == 9
    assert boot["line"].startswith(
        "start-up 7.0 s: import 5.0 (orbax.checkpoint 3.0) · trainer 0.3 · "
        "first dispatch 1.5 (trace 0.1, lower 0.2, backend 1.0, cache 1 hit "
        "/ 0 miss) · uncovered 0.2")
    rows = {(p["name"], p["module"]): p for p in boot["phases"]}
    outer = rows[("setup.import", "deepfm_tpu.train")]
    assert outer["inclusive_ms"] == 5000 and outer["self_ms"] == 2000
    assert rows[("setup.first_dispatch", None)]["self_ms"] == 200
    (multi,) = boot["compiles"]
    assert multi == {"fun_name": "multi", "count": 1, "trace_ms": 100.0,
                     "lower_ms": 200.0, "backend_ms": 1000.0,
                     "fetch_ms": 900.0, "hit": 1, "miss": 0}
    assert trace_report.start_up(events[1:]) == []       # an older trace
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert trace_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "start-up of pid 9: start-up 7.0 s" in out
    assert "setup.import orbax.checkpoint" in out and "1 hit" in out
    assert trace_report.main([str(path), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["start_up"][0]["pid"] == 9


def test_stalls_name_the_compilation_a_late_dispatch_lay_under():
    assert "compile.backend" in trace_report.STALL_SPANS
    events = [
        _x("stage.wait", 0, 10, tid=2, seq=3),
        _x("stage.wait", 100, 10, tid=2, seq=4),
        _x("compile.backend", 150, 700, fun_name="jit(step)", cache="miss"),
        _x("stage.wait", 200, 800, tid=2, seq=5),
    ]
    (stall,) = trace_report.stalls(events, 500)
    assert stall["seq"] == 5 and stall["interval_ms"] == 890
    assert stall["cover_ms"]["compile.backend"] == 700
    assert stall["compiled"] == ["jit(step)"]
    (quiet,) = trace_report.stalls(events[:2] + [
        _x("stage.wait", 200, 800, tid=2, seq=5)], 500)
    assert quiet["compiled"] == [] and not quiet["cover_ms"]["compile.backend"]
