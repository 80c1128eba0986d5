"""What every run of every cell shares: finding the cell's files, refusing to
measure without the chip, the compile cache, the compile counter, the traced
window, and the one result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its files are found
by name: ``workloads/<cell>.json`` (driver and the cell's own traffic
numbers), ``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.json`` (which reader computes it, with what arguments),
``readers/<reader>.py`` and ``drivers/<driver>.py``. Adding a cell, a
configuration, a traffic mix or a per-layer metric is adding files and an
entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: jax.monitoring duration events that mean "a program was compiled or
#: fetched from the persistent cache": neither may happen inside a window.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")

#: Longest traced window, seconds: the trace is for shares and names, and a
#: device trace of a whole long window is only larger, not better.
MAX_TRACE_SECONDS = 12.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> Any:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def _merge(base: dict, over: Optional[dict]) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)) else v
    return out


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    driver: str
    config: dict                # configs/<config>.json
    traffic: dict               # traffic/<traffic>.json + the cell's own
    end_to_end: Dict[str, dict]   # BENCHMARK.json entries this cell reports
    per_layer: Dict[str, dict]


def load_cell(name: str, overrides: Optional[dict] = None) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` and its files describe it.
    ``overrides`` ({"flags": ..., "traffic": ..., "config": ...,
    "benchmark": ...}) is the test-only seam: it shrinks a cell to a size a
    CPU can rehearse, and can stand in for ``BENCHMARK.json`` itself."""
    overrides = overrides or {}
    bench = overrides.get("benchmark")
    if bench is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    wl = load_json("workloads", f"{name}.json")
    config = _merge(load_json("configs", f"{entry['config']}.json"),
                    overrides.get("config"))
    config["flags"] = _merge(config["flags"], overrides.get("flags"))
    traffic = _merge(_merge(load_json("traffic", f"{entry['traffic']}.json"),
                            wl.get("traffic")), overrides.get("traffic"))

    def mine(metrics: List[dict]) -> Dict[str, dict]:
        return {m["name"]: m for m in metrics
                if name in m.get("workloads", [name])}

    return Cell(name=name, chips=int(entry["chips"]), driver=wl["driver"],
                config=config, traffic=traffic,
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def work_dir(cell: str, seed: int) -> str:
    """A scratch directory inside the checkout, emptied first."""
    path = os.path.join(ROOT, ".bench_work", f"{cell}.{seed}")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def acquire_devices(chips: int, require_chip: bool = True) -> list:
    """Configure the compile cache, start JAX and return the ``chips``
    devices the cell runs on. No TPU, or too few: :class:`NoChip`."""
    from deepfm_tpu.utils import compile_cache

    if require_chip:
        # $JAX_COMPILATION_CACHE_DIR, else .jax_cache. The CPU rehearsal
        # shares its process with other tests and leaves JAX's settings alone.
        compile_cache.configure()
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"JAX reports platform {devices[0].platform!r}, not tpu")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def device_report(devices: list) -> dict:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       "in benchmark/peaks.json")
    return table[device_kind]


class CompileCounter:
    """Counts compilations (and persistent-cache fetches) between ``open``
    and ``close``, on whatever thread they happen."""

    def __init__(self):
        self._lock = threading.Lock()
        self._open = False
        self.count = 0
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event in COMPILE_EVENTS:
            with self._lock:
                if self._open:
                    self.count += 1

    def open(self) -> None:
        with self._lock:
            self._open = True

    def close(self) -> None:
        with self._lock:
            self._open = False


class Spans:
    """The program's own spans (``deepfm_tpu.obs.trace``), switched on for a
    traced run and read back as a list of complete events."""

    def __init__(self, enabled: bool):
        from deepfm_tpu.obs import trace as trace_lib

        self._lib = trace_lib
        self.enabled = enabled
        if enabled:
            trace_lib.configure("full", export_env=False)

    def events(self, path: str) -> List[dict]:
        if not self.enabled:
            return []
        with open(self._lib.export(path)) as f:
            return [e for e in json.load(f)["traceEvents"]
                    if e.get("ph") == "X"]


class DeviceTrace:
    """The profiler's trace of (part of) a window. ``start`` belongs to
    set-up (starting the profiler takes seconds); ``stop`` comes after the
    window. Host events are left out: with them every chunk of a host to
    device copy is an event, and the serving engine's 2.16 GB upload per
    flush ran 3.5 times slower under the tracer (my chip run, PR 24). The
    trace's own ``profile_start_time`` ties its clock to the wall clock."""

    def __init__(self, out_dir: str):
        self.dir = out_dir

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> str:
        import glob

        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return found[0]


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    cell: Cell
    devices: list
    counters: Dict[str, float]
    spans: List[dict]               # complete events inside the window
    trace: Optional[dict]           # xplane.reduce(...) of the traced window
    window: tuple                   # (open, close) wall-clock ns


def read_per_layer(ctx: Context) -> Dict[str, dict]:
    """Each of the cell's per-layer metrics through its reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for name, entry in ctx.cell.per_layer.items():
        spec = load_json("metrics", f"{name}.json")
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": entry["unit"]}
    return out


def spans_in(events: List[dict], t0_ns: int, t1_ns: int) -> List[dict]:
    """Events that start inside [t0, t1] (``ts``/``dur`` are microseconds)."""
    return [e for e in events if t0_ns <= e["ts"] * 1e3 <= t1_ns]


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        overrides: Optional[dict] = None, require_chip: bool = True,
        extra: Optional[dict] = None) -> dict:
    """One run of one cell; returns the result line as a dict. ``overrides``
    and ``require_chip=False`` are for the CPU rehearsal in the tests."""
    cell = load_cell(workload, overrides)
    driver = importlib.import_module(f"benchmark.drivers.{cell.driver}")
    work = work_dir(cell.name, seed)
    try:
        # The driver starts JAX itself, so that work that needs no device
        # (writing the seed's shards) can begin before and run beside it.
        return driver.run(cell, lambda: acquire_devices(cell.chips,
                                                        require_chip),
                          int(seed), float(seconds), bool(trace), work,
                          **(extra or {}))
    finally:
        shutil.rmtree(work, ignore_errors=True)


def result_line(cell: Cell, *, correct: bool, attempted: int, failed: int,
                end_to_end: Dict[str, float], ctx: Optional[Context],
                device: dict) -> dict:
    """The contract's last line: end-to-end metrics for an untraced run,
    per-layer metrics (with busy time and the breakdown) for a traced one."""
    if ctx is None:
        missing = set(cell.end_to_end) - set(end_to_end)
        if missing:
            raise RuntimeError(f"the driver did not measure {sorted(missing)}")
        metrics = {k: {"value": float(end_to_end[k]),
                       "unit": cell.end_to_end[k]["unit"]}
                   for k in cell.end_to_end}
        return {"correct": bool(correct), "attempted": int(attempted),
                "failed": int(failed), "metrics": metrics, "device": device}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": read_per_layer(ctx),
            "device": dict(device)}
    if ctx.trace is not None:
        line["device"]["busy_s"] = ctx.trace["busy_s"]
        line["device"]["window_s"] = ctx.trace["window_s"]
        line["breakdown"] = {"device_ops": ctx.trace["device_ops"][:10],
                             "idle_gaps": ctx.trace["idle_gaps"][:5]}
    return line


def say(t0: float, msg: str) -> None:
    print(f"bench [{time.time() - t0:7.2f}s] {msg}", flush=True)


def report_check(name: str, value: float, limit: float) -> bool:
    """Print one compared number beside its limit; True when within it."""
    ok = bool(value <= limit)
    print(f"check {name}: {value:.6g} (limit {limit:.6g}) "
          f"{'ok' if ok else 'NOT OK'}", flush=True)
    return ok
