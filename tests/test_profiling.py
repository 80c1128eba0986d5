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


_STEP_HLO = """\
HloModule jit_multi

%fused_computation.2 (param_0.1: f32[640,4], param_1.1: s32[96]) -> f32[640,4] {
  %param_0.1 = f32[640,4]{0,1:T(8,128)} parameter(0)
  %param_1.1 = s32[96]{0} parameter(1)
  ROOT %scatter.1 = f32[640,4]{0,1:T(8,128)} scatter(%param_0.1, %param_1.1), to_apply=%region_1.2
}

%region_0.5 (arg.1: (s32[], f32[640,4], f32[640,4], f32[640,4])) -> (s32[], f32[640,4], f32[640,4], f32[640,4]) {
  %arg.1 = (s32[], f32[640,4]{0,1:T(8,128)}, f32[640,4]{0,1:T(8,128)}, f32[640,4]{0,1:T(8,128)}) parameter(0)
  %get-tuple-element.1 = f32[640,4]{0,1:T(8,128)} get-tuple-element(%arg.1), index=1
  %get-tuple-element.2 = f32[640,4]{0,1:T(8,128)} get-tuple-element(%arg.1), index=2
  %constant.1 = f32[]{:T(128)} constant(0)
  %ids.1 = s32[96]{0} bitcast(%constant.1)
  %broadcast_in_dim.9 = f32[640,4]{0,1:T(8,128)} broadcast(%constant.1), dimensions={}, metadata={op_name="jit(multi)/while/body/transpose(jvp(embed))/broadcast_in_dim" stack_frame_id=3}
  %fusion.7 = f32[640,4]{0,1:T(8,128)} fusion(%broadcast_in_dim.9, %ids.1), kind=kCustom, calls=%fused_computation.2, metadata={op_name="jit(multi)/while/body/transpose(jvp(embed))/jit(_take)/scatter-add" stack_frame_id=4}, backend_config={"flag_configs":[],"aliasing_operands":{"lists":[{"indices":["0","2"]}]}}
  %multiply_add_fusion.3 = (f32[640,4]{0,1:T(8,128)}, f32[640,4]{0,1:T(8,128)}, f32[]{:T(128)}) fusion(%get-tuple-element.1, /*index=1*/%get-tuple-element.2, %fusion.7), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(multi)/while/body/opt/add"}, backend_config={"aliasing_operands":{"lists":[{"indices":["0","3"]},{"indices":["1","4"]}]}}
  ROOT %tuple.1 = (s32[], f32[640,4]{0,1:T(8,128)}) tuple(%constant.1, %fusion.7)
}

ENTRY %main.9 (Arg_0.1: f32[640,4]) -> f32[640,4] {
  %Arg_0.1 = f32[640,4]{0,1:T(8,128)} parameter(0)
  %while.1 = (s32[], f32[640,4]{0,1:T(8,128)}) while(%Arg_0.1), condition=%cond.1, body=%region_0.5
  ROOT %copy.1 = f32[640,4]{0,1:T(8,128)} copy(%Arg_0.1)
}
"""


def test_hlo_table_ops_lists_what_makes_a_table():
    ops = profiling.hlo_table_ops(_STEP_HLO, 640)
    assert [o["name"] for o in ops] == [
        "broadcast_in_dim.9", "fusion.7", "multiply_add_fusion.3", "copy.1"]
    fill, scatter, sweep, copy = ops
    # Plumbing and the fused computation's own instructions are left out;
    # what is left is one line per pass, with where it runs.
    assert [o["loop_body"] for o in ops] == [True, True, True, False]
    assert fill["opcode"] == "broadcast" and fill["operands"] == ["constant.1"]
    assert fill["tables"] == []
    assert scatter["operands"] == ["broadcast_in_dim.9", "ids.1"]
    assert scatter["tables"] == ["broadcast_in_dim.9"]
    assert scatter["in_place"] == [0] and scatter["scope"] == "embed"
    assert sweep["results"] == ["f32[640,4]", "f32[640,4]"]
    assert sweep["tables"] == sweep["operands"] == [
        "get-tuple-element.1", "get-tuple-element.2", "fusion.7"]
    assert sweep["in_place"] == [0, 1] and sweep["scope"] == "opt"
    assert copy["tables"] == ["Arg_0.1"] and copy["scope"] == ""
    # The primitive a line came from: a scatter in place costs its rows.
    assert [o["primitive"] for o in ops] == [
        "broadcast_in_dim", "scatter-add", "add", ""]
    assert profiling.hlo_table_ops(_STEP_HLO, 641) == []
