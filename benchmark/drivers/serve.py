"""Serving driver: the engine as a deployment brings it up, under open-loop
load at a rate fixed in the cell.

Set-up exports a seeded ``TrainState`` into a publish directory and starts
``ServingEngine.serve_latest`` on it (buckets pre-warmed by the engine's own
watcher). Requests are scheduled before the window opens: Poisson arrivals
at the cell's ``offered_rows_per_s``, slates drawn from a clipped log-normal
law, the same arrival times and sizes for every seed (the seed draws the rows
asked for). One
generator thread submits each request when it is due; a request is timed
from when it was *due*, so a stalled generator or a full queue counts
against the server, and how late the generator ran is reported. A short
lead-in at the same rate comes first, so the window opens on a queue in its
steady state.

After the window a seeded sample of its requests (the longest among them) is
checked against the plain reference's forward pass, as logits.
"""

from __future__ import annotations

import os
import threading
import time
from typing import List, Optional

import numpy as np

from benchmark import harness, reference, traffic, weights, xplane
from benchmark.drivers import _program


class Load:
    """One span of scheduled requests, submitted by one thread."""

    def __init__(self, engine, layout, seed: int, seconds: float,
                 rows_per_s: float, params: dict, lead_in: float):
        due_w, rows_w = traffic.arrival_schedule(seconds, rows_per_s, params)
        due_l, rows_l = traffic.arrival_schedule(lead_in, rows_per_s, params,
                                                 stream=1)
        self.lead = len(due_l)
        self.due = np.concatenate([due_l, lead_in + due_w])
        self.rows = np.concatenate([rows_l, rows_w])
        self.open_at, self.close_at = lead_in, lead_in + seconds
        cols = traffic.generate_rows(layout, int(self.rows.sum()), seed,
                                     params)
        ends = np.cumsum(self.rows)
        self.ids = np.split(cols["feat_ids"], ends[:-1])
        self.vals = np.split(cols["feat_vals"], ends[:-1])
        n = len(self.due)
        self.engine = engine
        self.submitted = np.full(n, np.nan)     # offsets from t_base, seconds
        self.done = np.full(n, np.nan)
        self.refused = np.zeros(n, bool)
        self.futures: List[Optional[object]] = [None] * n
        self.answers: List[Optional[np.ndarray]] = [None] * n
        self.t_base = 0.0
        self.base_wall_ns = 0

    def run(self) -> None:
        """Submit every request at its due time (generator thread)."""
        from deepfm_tpu.serve.engine import ServerOverloaded

        self.t_base, self.base_wall_ns = time.perf_counter(), time.time_ns()
        for i, due in enumerate(self.due):
            while True:
                wait = self.t_base + due - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(wait)
            self.submitted[i] = time.perf_counter() - self.t_base
            try:
                fut = self.engine.submit(self.ids[i], self.vals[i])
            except ServerOverloaded:
                self.refused[i] = True
                continue
            fut.add_done_callback(
                lambda _f, i=i: self.done.__setitem__(
                    i, time.perf_counter() - self.t_base))
            self.futures[i] = fut

    def drain(self, timeout: float) -> None:
        """Wait (at most ``timeout`` s in all) for every admitted request
        and keep its answer; one that failed or timed out has none."""
        deadline = time.perf_counter() + timeout
        for i, fut in enumerate(self.futures):
            if fut is None:
                continue
            try:
                self.answers[i] = fut.result(
                    max(0.0, deadline - time.perf_counter()))
            except Exception:  # noqa: BLE001 — any failure is a failed request
                self.answers[i] = None

    # -- what the span measured -------------------------------------------
    def in_window(self) -> np.ndarray:
        return np.arange(len(self.due)) >= self.lead

    def ok(self) -> np.ndarray:
        return np.asarray([a is not None for a in self.answers]) \
            & np.isfinite(self.done)

    def latencies_ms(self) -> np.ndarray:
        """Completion minus due time of the window's requests; a request
        that failed, was refused or never finished is +inf."""
        lat = 1e3 * (self.done - self.due)
        lat[~self.ok()] = np.inf
        return lat[self.in_window()]

    def rows_completed_in_window(self) -> int:
        inside = self.ok() & (self.done >= self.open_at) \
            & (self.done <= self.close_at)
        return int(self.rows[inside].sum())

    def late_ms(self) -> np.ndarray:
        return 1e3 * (self.submitted - self.due)[self.in_window()]


def bring_up(cell: harness.Cell, devices: list, seed: int, work: str, t0):
    """Seeded state -> ``export_serving`` -> publish dir -> engine."""
    import jax

    from deepfm_tpu.serve.engine import ServingEngine
    from deepfm_tpu.utils import export as export_lib

    cfg = _program.make_config(cell.config["flags"])
    trainer = _program.build_trainer(cfg, devices)
    state, _ = _program.seeded_state(trainer, seed, cell.config)
    jax.block_until_ready(state.params)
    harness.say(t0, "seeded state on the device")
    publish = os.path.join(work, "publish")
    # The TensorFlow SavedModel sidecar serves no request of this engine
    # (TF-Serving reads it); the program's own seam leaves it out, for this
    # export only.
    before = os.environ.get("DEEPFM_TPU_SKIP_TF_EXPORT")
    os.environ["DEEPFM_TPU_SKIP_TF_EXPORT"] = "1"
    try:
        export_lib.export_serving(trainer.model, state, cfg,
                                  os.path.join(publish, "1"))
    finally:
        if before is None:
            del os.environ["DEEPFM_TPU_SKIP_TF_EXPORT"]
        else:
            os.environ["DEEPFM_TPU_SKIP_TF_EXPORT"] = before
    export_lib.write_latest(publish, "1")
    harness.say(t0, "artifact exported")
    peak = harness.device_report(devices)
    del state
    engine = ServingEngine.serve_latest(
        publish, max_batch=cfg.serve_max_batch,
        max_delay_ms=cfg.serve_max_delay_ms, queue_rows=cfg.serve_queue_rows,
        inflight=cfg.serve_inflight, small_rows=cfg.serve_small_rows)
    harness.say(t0, f"engine up, {engine.watcher.prewarmed_buckets} buckets "
                    "pre-warmed")
    return trainer, engine, peak


def run(cell: harness.Cell, acquire, seed: int, seconds: float,
        trace: bool, work: str, t_start: Optional[float] = None) -> dict:
    devices = acquire()
    t0 = t_start if t_start is not None else time.time()
    t_perf0 = time.perf_counter() - (time.time() - t0)
    if trace:
        seconds = min(seconds, harness.MAX_TRACE_SECONDS)
    layout = traffic.FieldLayout.from_config(cell.config)
    params = cell.traffic
    compiles = harness.CompileCounter()
    spans = harness.Spans(trace)
    trainer, engine, peak = bring_up(cell, devices, seed, work, t0)
    try:
        device_trace = None
        if trace:
            device_trace = harness.DeviceTrace(os.path.join(work, "trace"))
            device_trace.start()
        load = Load(engine, layout, seed, seconds,
                    float(params["offered_rows_per_s"]), params,
                    float(params["lead_in_seconds"]))
        gen = threading.Thread(target=load.run, name="bench-loadgen")
        gen.start()
        while load.t_base == 0.0:
            time.sleep(0.001)
        setup_s = load.t_base + load.open_at - t_perf0
        time.sleep(max(0.0, load.t_base + load.open_at - time.perf_counter()))
        compiles.open()
        time.sleep(max(0.0, load.t_base + load.close_at
                       - time.perf_counter()))
        compiles.close()
        gen.join()
        load.drain(float(params["drain_timeout_seconds"]))
        xplane_path = device_trace.stop() if trace else None
        stats = engine.stats.summary()
    finally:
        engine.close(timeout=60.0)
    device = harness.device_report(devices)
    device["memory_peak_bytes"] = max(device["memory_peak_bytes"],
                                      peak["memory_peak_bytes"])
    harness.say(t0, "window closed and drained")

    lat = load.latencies_ms()
    attempted, failed = len(lat), int(np.sum(~np.isfinite(lat)))
    rows_done = load.rows_completed_in_window()
    late = load.late_ms()
    print(f"window: {attempted} requests due in {seconds:.1f} s "
          f"({int(load.rows[load.in_window()].sum())} rows offered), "
          f"{failed} failed, {rows_done} rows completed inside; "
          f"engine totals: {stats['serving_flushes']} flushes, "
          f"{stats['serving_rows_per_flush']} rows/flush, "
          f"{stats['serving_overloads']} overloads; generator late p99 "
          f"{np.percentile(late, 99):.3f} ms; set-up {setup_s:.2f} s",
          flush=True)
    if not trace and attempted < 200:
        raise RuntimeError(f"only {attempted} requests in the window: a 95th "
                           "percentile needs 200")
    print(f"latency from due time: p50 {np.percentile(lat, 50):.1f} ms, "
          f"p95 {np.percentile(lat, 95):.1f} ms", flush=True)
    end_to_end = {
        "serve_p50_ms": float(np.percentile(lat, 50)),
        "serve_rows_per_s": rows_done / seconds,
        "setup_s": setup_s,
    }
    # Failed requests are reported as ``failed`` (and as +inf latencies), not
    # as wrong answers: ``correct`` is about what the engine answered.
    correct = check_answers(cell, trainer, layout, seed, load)

    ctx = None
    if trace:
        window_ns = (load.base_wall_ns + int(load.open_at * 1e9),
                     load.base_wall_ns + int(load.close_at * 1e9))
        events = harness.spans_in(
            spans.events(os.path.join(work, "spans.json")), *window_ns)
        reduced = xplane.reduce(xplane_path, window_ns=window_ns,
                                spans=events)
        flushes = [e for e in events if e["name"] == "serve.flush"]
        counters = {"memory_peak_bytes": device["memory_peak_bytes"],
                    "compiles_in_window": compiles.count,
                    "loadgen_late_ms_p99": float(np.percentile(late, 99)),
                    "tail_ms_p95": float(np.percentile(lat, 95)),
                    "flushes": len(flushes),
                    "flush_rows": sum(e["args"]["rows"] for e in flushes)}
        queue = queue_waits_ms(load, flushes)
        if len(queue):
            counters["queue_ms_p50"] = float(np.percentile(queue, 50))
        ctx = harness.Context(cell=cell, devices=devices, spans=events,
                              trace=reduced, window=window_ns,
                              counters=counters)
    if compiles.count:
        print(f"check compiles_in_window: {compiles.count} (limit 0) NOT OK",
              flush=True)
        correct = False
    return harness.result_line(cell, correct=correct, attempted=attempted,
                               failed=failed, end_to_end=end_to_end, ctx=ctx,
                               device=device)


def queue_waits_ms(load: Load, flushes: List[dict]) -> np.ndarray:
    """Submission to the start of the flush that answered it, for the
    window's requests: each is joined to the ``serve.flush`` span inside
    which it completed (spans carry no request id yet)."""
    if not flushes:
        return np.empty(0)
    starts = np.asarray([e["ts"] * 1e3 for e in flushes])
    ends = starts + np.asarray([e["dur"] * 1e3 for e in flushes])
    order = np.argsort(starts)
    starts, ends = starts[order], ends[order]
    sel = load.in_window() & load.ok()
    done = load.base_wall_ns + load.done[sel] * 1e9
    sub = load.base_wall_ns + load.submitted[sel] * 1e9
    k = np.searchsorted(starts, done) - 1
    hit = (k >= 0) & (done <= ends[np.maximum(k, 0)] + 5e6)
    return (starts[k[hit]] - sub[hit]) / 1e6


def check_answers(cell, trainer, layout, seed: int, load: Load) -> bool:
    """The engine's answers for a seeded sample of the window's requests,
    the one with the most rows among them, against the reference's forward
    pass from the same seeded weights: the widest gap between logits."""
    done = np.flatnonzero(load.in_window() & load.ok())
    if not len(done):
        print("check answers: no request finished NOT OK", flush=True)
        return False
    rng = np.random.default_rng([seed, 0x616E])
    n = min(int(cell.traffic["check_requests"]), len(done))
    pick = set(rng.choice(done, n, replace=False).tolist())
    pick.add(int(done[np.argmax(load.rows[done])]))
    pick = sorted(pick)
    ids = np.concatenate([load.ids[i] for i in pick])
    vals = np.concatenate([load.vals[i] for i in pick])
    got = np.concatenate([np.asarray(load.answers[i], np.float64).reshape(-1)
                          for i in pick])
    if got.shape != (len(ids),) or not np.all(np.isfinite(got)) \
            or got.min() <= 0.0 or got.max() >= 1.0:
        print("check answers: wrong shape or not probabilities NOT OK",
              flush=True)
        return False
    specs = _program.leaf_specs(trainer)
    wkw = _program.weight_kwargs(cell.config, trainer)
    rows = np.unique(ids)
    params0 = {name: weights.leaf_values(
        weights.leaf_salt(seed, name), shape,
        rows=rows if shape and shape[0] == wkw["padded_vocab"] else None,
        **wkw) for name, shape in specs.items()}
    want = reference.predict_logits(
        params0, rows, ids, vals,
        n_layers=len(trainer.cfg.deep_layer_sizes))
    gap = float(np.max(np.abs(np.log(got) - np.log1p(-got) - want)))
    print(f"check answers: {len(pick)} requests, {len(ids)} rows, logits "
          f"from {want.min():.3f} to {want.max():.3f}", flush=True)
    return harness.report_check("logit_gap", gap,
                                cell.traffic["limits"]["logit_gap"])

