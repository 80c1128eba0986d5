"""Profiling subsystem: trace context + throughput meter."""

import os
import time

import jax
import jax.numpy as jnp

from deepfm_tpu.utils import profiling


def test_maybe_trace_disabled_is_noop():
    with profiling.maybe_trace(""):
        pass
    with profiling.maybe_trace(None):
        pass


def test_maybe_trace_writes_xplane(tmp_path):
    out = str(tmp_path / "trace")
    with profiling.maybe_trace(out):
        x = jnp.ones((8, 8))
        jax.block_until_ready(x @ x)
    found = []
    for root, _, files in os.walk(out):
        found += [f for f in files if f.endswith(".xplane.pb")]
    assert found, f"no xplane trace written under {out}"


def test_throughput_meter_summary():
    m = profiling.ThroughputMeter(warmup_steps=1)
    for _ in range(5):
        time.sleep(0.002)
        m.update(100)
    s = m.summary()
    assert s["steps"] == 5.0
    assert s["examples_per_sec"] > 0
    assert s["step_ms_p50"] >= 1.0
    assert s["step_ms_p99"] >= s["step_ms_p50"]


def test_throughput_meter_warmup_only():
    m = profiling.ThroughputMeter(warmup_steps=5)
    m.update(10)
    assert m.summary() == {"steps": 1.0}


def test_step_window_tracer_bounded(tmp_path):
    out = str(tmp_path / "win")
    t = profiling.StepWindowTracer(out, start_step=1, num_steps=2)
    for _ in range(10):  # must stop after the window, not trace all 10
        jax.block_until_ready(jnp.ones((4, 4)) * 2)
        t.on_step()
    assert t._done and not t._active
    t.close()  # idempotent
    found = []
    for root, _, files in os.walk(out):
        found += [f for f in files if f.endswith(".xplane.pb")]
    assert found, f"no xplane trace written under {out}"


def test_step_window_tracer_close_mid_window(tmp_path):
    out = str(tmp_path / "mid")
    t = profiling.StepWindowTracer(out, start_step=1, num_steps=100)
    t.on_step()  # starts the trace; run ends before the window fills
    t.close()
    assert not t._active


def test_step_window_tracer_disabled():
    t = profiling.StepWindowTracer("")
    for _ in range(5):
        t.on_step()
    t.close()
    assert not t._active and not t._done
