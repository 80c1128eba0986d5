#!/usr/bin/env python
"""Benchmark harness: DeepFM training throughput on the reference config.

Measures steady-state examples/sec of the shipped training loop — K=8
optimizer steps per dispatch via ``Trainer.multi_step`` (one stacked
host->device transfer + one ``lax.scan`` program; forward + backward + Adam
update per step) — at the reference benchmark anchors (docs/PARITY.md):
feature_size=117581, field_size=39, embedding_size=32, deep_layers 128/64/32,
global batch 1024, Adam lr 5e-4 — on whatever accelerator JAX exposes (the
driver runs this on one real TPU chip). Host batches are pre-staged so the
number isolates transfer+device throughput; disk decode is benched separately
(host-pipeline-bound on a 1-core host).

Also runs an 8-way-DP wiring check on a virtual 8-device CPU mesh (the
collective layout is identical to real multi-chip; the aggregate ratio it
reports is time-slicing overhead, NOT hardware scaling — real multi-chip
hardware is not available this round). Disable with --no-scaling.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "examples/sec", "vs_baseline": N, ...}

vs_baseline: the reference publishes no numbers (docs/PARITY.md), so the
comparison anchor is a documented nominal estimate of the reference Horovod
recipe: ~250k examples/sec aggregate on the 4xV100 p3.8xlarge (TF1 DeepFM at
batch 1024/GPU is input/update-bound, not FLOP-bound). Per-accelerator
baseline = 62.5k examples/sec; vs_baseline = measured_per_chip / 62.5k.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

K_STEPS = 8          # steps per dispatch (cfg.steps_per_loop default)
N_DISPATCH = 12      # dispatches per trial -> 96 steps/trial
N_TRIALS = 5


def _make_groups(cfg, n_groups: int):
    rng = np.random.default_rng(0)
    groups = []
    for _ in range(n_groups):
        group = []
        for _ in range(K_STEPS):
            group.append({
                "feat_ids": rng.integers(
                    0, cfg.feature_size,
                    (cfg.batch_size, cfg.field_size)).astype(np.int32),
                "feat_vals": rng.normal(
                    size=(cfg.batch_size, cfg.field_size)).astype(np.float32),
                "label": (rng.random(
                    (cfg.batch_size, 1)) < 0.25).astype(np.float32),
            })
        groups.append(group)
    return groups


def measure(cfg) -> dict:
    """Best-of-N-trials throughput of put_superbatch + multi_step(K)."""
    import jax

    from deepfm_tpu.train import Trainer

    n_dev = len(jax.devices())
    trainer = Trainer(cfg)
    state = trainer.init_state()
    groups = _make_groups(cfg, 4)

    step = trainer.multi_step
    for g in groups[:2]:  # warmup/compile
        state, m = step(state, trainer.put_superbatch(g))
    jax.block_until_ready(m["loss"])

    # Several trials, best wins: host jitter dominates a single trial; the
    # fastest trial is the steady-state device+transfer throughput.
    dt = float("inf")
    for _ in range(N_TRIALS):
        t0 = time.perf_counter()
        for i in range(N_DISPATCH):
            state, m = step(state, trainer.put_superbatch(groups[i % 4]))
        jax.block_until_ready(m["loss"])
        dt = min(dt, time.perf_counter() - t0)

    # Device-only series: the same dispatch loop over PRE-STAGED device
    # superbatches — no bulk host->device data transfer inside the timed
    # window. Each dispatch still pays its launch latency.
    sb_dev = [trainer.put_superbatch(g) for g in groups]
    dt_dev = float("inf")
    for _ in range(N_TRIALS):
        t0 = time.perf_counter()
        for i in range(N_DISPATCH):
            state, m = step(state, sb_dev[i % 4])
        jax.block_until_ready(m["loss"])
        dt_dev = min(dt_dev, time.perf_counter() - t0)

    n_examples = N_DISPATCH * K_STEPS * cfg.batch_size
    total_eps = n_examples / dt
    return {
        "devices": n_dev,
        "total_eps": total_eps,
        "per_chip_eps": total_eps / max(n_dev, 1),
        "ms_per_step": 1000 * dt / (N_DISPATCH * K_STEPS),
        "device_only_ms_per_step": 1000 * dt_dev / (N_DISPATCH * K_STEPS),
        "loss": float(m["loss"]),
    }


def host_stage_series() -> dict:
    """Host-pipeline series: ns/record of the TFRecord frame stage, the
    full decode-to-arrays stage, and the complete staged pipeline (decode
    pool + shuffle + batch assembly) on synthetic Criteo-shaped data. Runs
    entirely on the host CPU and touches no device."""
    import glob as glob_mod
    import tempfile

    from deepfm_tpu.data import libsvm
    from deepfm_tpu.data.pipeline import CtrPipeline
    from deepfm_tpu.native import loader
    from deepfm_tpu.utils import profiling

    out = {}
    with tempfile.TemporaryDirectory() as d:
        libsvm.generate_synthetic_ctr(
            d, num_files=2, examples_per_file=20000,
            feature_size=117581, field_size=39, prefix="tr", seed=0)
        files = sorted(glob_mod.glob(os.path.join(d, "tr*.tfrecords")))
        bufs = [open(f, "rb").read() for f in files]
        n_records = 2 * 20000

        def best_of(fn, trials=3):
            best = float("inf")
            for _ in range(trials):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        if loader.available():
            dt = best_of(lambda: [loader.split_frames(b, verify_crc=False)
                                  for b in bufs])
            out["frame_ns_per_record"] = round(1e9 * dt / n_records, 1)
            dt = best_of(lambda: [loader.decode_file_bytes(
                b, 39, verify_crc=False) for b in bufs])
            out["decode_ns_per_record"] = round(1e9 * dt / n_records, 1)

        def make_pipe(**kw):
            return CtrPipeline(
                files, field_size=39, batch_size=1024, num_epochs=1,
                shuffle=True, shuffle_files=True, drop_remainder=True,
                seed=0, **kw)

        def staged_ns(trials=3, with_stages=False, **kw):
            """Best-of-N ns/record of the full staged pipeline. The
            pipeline is built OUTSIDE the timed region (construction is
            not staging cost) and the denominator is the record count the
            pipeline actually returned — drop_remainder eats the tail, so
            dividing by the on-disk count understated the per-record cost
            (advisor r5, both). With ``with_stages`` the BEST trial's
            per-stage breakdown rides along (read/frame/pool_drain/
            emit + unattributed 'other'), so a total-ns regression is
            attributable to a stage, not just asserted."""
            best, n = float("inf"), 0
            breakdown = None
            for _ in range(trials):
                pipe = make_pipe(**kw)  # single-use: fresh per trial
                stats = profiling.HostStageStats() if with_stages else None
                pipe.stage_stats = stats
                t0 = time.perf_counter()
                n = sum(n_ex for _, _, n_ex
                        in pipe.iter_superbatches(K_STEPS))
                dt = time.perf_counter() - t0
                if dt < best:
                    best = dt
                    if stats is not None:
                        per = stats.ns_per_record(n)
                        per["other"] = round(
                            1e9 * dt / max(n, 1) - sum(per.values()), 1)
                        breakdown = per
            return round(1e9 * best / max(n, 1), 1), n, breakdown

        out["staged_pipeline_ns_per_record"], n_staged, stage_bd = staged_ns(
            with_stages=True)
        out["staged_records_returned"] = n_staged
        if stage_bd is not None:
            out["host_stage_breakdown_ns_per_record"] = stage_bd
        if "decode_ns_per_record" in out:
            # What the pool/shuffle/assembly machinery costs on top of the
            # raw decode — the part a decoded-epoch cache cannot remove.
            out["pool_overhead_ns_per_record"] = round(
                out["staged_pipeline_ns_per_record"]
                - out["decode_ns_per_record"], 1)

        # Decoded-epoch cache, warm: every trial pipeline hits the RAM
        # registry (built once, outside the timed region), so this is the
        # cached-epoch cost — pool + batch slicing over memres columns,
        # zero frame/decode.
        from deepfm_tpu.data import cache as cache_lib
        cache_lib.clear_ram_cache()
        make_pipe(decoded_cache="ram").decoded_epoch_columns()
        out["cached_epoch_ns_per_record"], _, _ = staged_ns(
            decoded_cache="ram")
        out["cached_over_staged_ratio"] = round(
            out["cached_epoch_ns_per_record"]
            / max(out["staged_pipeline_ns_per_record"], 1e-9), 3)

        if loader.available():
            # Forced fused-assembly fallback (per-chunk scatter decode):
            # quantifies what the one-C-call-per-drain path buys, and keeps
            # an always-on measurement of the kill-switch path.
            out["staged_fallback_ns_per_record"], _, _ = staged_ns(
                native_assembly=False)
            # Prefetch-thread-free: on a 1-core bench host the prefetch
            # thread is pure GIL contention with this consumer (it exists
            # to overlap DEVICE work, absent here), so this series is the
            # pipeline's own cost without measurement-rig interference.
            out["staged_noprefetch_ns_per_record"], _, _ = staged_ns(
                prefetch_batches=0)
            # Worker path: decode in 2 processes feeding shared-memory
            # slabs. On a multi-core host this should beat the in-process
            # series; on a 1-core host it mostly measures IPC overhead —
            # report both and let the reader compare against nproc.
            out["staged_workers2_ns_per_record"], _, _ = staged_ns(
                input_workers=2)
            out["host_cores"] = os.cpu_count()

            def stream_hash(**kw):
                import hashlib
                h = hashlib.blake2b(digest_size=12)
                for rows, m, n_ex in make_pipe(**kw).iter_superbatches(
                        K_STEPS):
                    for key in ("label", "feat_ids", "feat_vals"):
                        h.update(rows[key].tobytes())
                return h.hexdigest()

            # Same-seed parity: the worker path must emit the bit-identical
            # batch stream (same records, same shuffle, same grouping).
            out["worker_parity_bit_identical"] = (
                stream_hash() == stream_hash(input_workers=2))
            # ...as must the fused-assembly kill switch (per-chunk scatter).
            out["assembly_parity_bit_identical"] = (
                stream_hash() == stream_hash(native_assembly=False))
            # ...and so must a cached epoch (whole-epoch pool: emission is
            # one full permutation, independent of chunk arrival shape).
            out["cache_parity_bit_identical"] = (
                stream_hash() == stream_hash(decoded_cache="ram"))
    return out


def _model_flops_per_example(cfg) -> float:
    """Analytic training FLOPs per example at the bench shape.

    Dense-math inventory of one example: the DNN tower matmuls (2*m*n
    FLOPs each) over [F*k, *deep_layers, 1] plus the FM second-order
    interaction (~5*F*k: square-of-sum, sum-of-squares, combine on [F, k]).
    Embedding gathers and the first-order term are lookups/adds of
    negligible FLOP count. Training ~= 3x forward (backward re-runs each
    matmul twice: grad-wrt-input and grad-wrt-weights)."""
    layers = [int(x) for x in str(cfg.deep_layers).split(",") if x]
    dims = [cfg.field_size * cfg.embedding_size] + layers + [1]
    dnn = sum(2 * m * n for m, n in zip(dims[:-1], dims[1:]))
    fm = 5 * cfg.field_size * cfg.embedding_size
    return 3.0 * (dnn + fm)


# The peak-FLOPS table lives in deepfm_tpu.utils.mfu, shared with
# bench_multiprocess.py: an MFU exists only against a published device peak.


def _bench_cfg(batch_size: int = 1024, mesh_data: int = 0,
               mesh_model: int = 1, use_pallas: bool = True, **extra):
    from deepfm_tpu.config import Config
    return Config(
        feature_size=117581, field_size=39, embedding_size=32,
        deep_layers="128,64,32", dropout="0.5,0.5,0.5",
        batch_size=batch_size, learning_rate=5e-4, optimizer="Adam",
        l2_reg=1e-4, compute_dtype="bfloat16", mesh_data=mesh_data,
        mesh_model=mesh_model, log_steps=0, seed=0, steps_per_loop=K_STEPS,
        use_pallas=use_pallas, **extra)


def device_resident_series() -> dict:
    """End-to-end epoch throughput: staged host pipeline vs --device_dataset
    over the SAME files, cache, and trainer config on one chip. The staged
    number pays decode-or-cache + pool + host->device transfer per epoch;
    the device-resident number pays a one-time column upload, then each
    dispatch ships ONE int32 cursor. Warmup epoch first (compiles + builds
    the cache + uploads), then best-of-2 measured epochs per mode."""
    import glob as glob_mod
    import tempfile

    from deepfm_tpu.data import cache as cache_lib
    from deepfm_tpu.data import libsvm
    from deepfm_tpu.train import Trainer
    from deepfm_tpu.train import tasks as tasks_lib

    with tempfile.TemporaryDirectory() as d:
        libsvm.generate_synthetic_ctr(
            d, num_files=2, examples_per_file=8192,
            feature_size=117581, field_size=39, prefix="tr", seed=0)
        files = sorted(glob_mod.glob(os.path.join(d, "tr*.tfrecords")))
        cfg = _bench_cfg(mesh_data=1, decoded_cache="ram",
                         shuffle_buffer=1 << 20, drop_remainder=True)

        def run(device: bool) -> float:
            cache_lib.clear_ram_cache()
            trainer = Trainer(cfg)
            state = trainer.init_state()
            best, n = float("inf"), 0
            for e in range(3):  # epoch 0 = warmup (compile/cache/upload)
                pipe = tasks_lib.make_pipeline(
                    cfg, files, epochs=1, shuffle=True, epoch_offset=e)
                t0 = time.perf_counter()
                if device:
                    state, m = trainer.fit_device_resident(state, pipe)
                else:
                    state, m = trainer.fit(state, pipe)
                dt = time.perf_counter() - t0
                n = int(m["steps"]) * cfg.batch_size
                if e:
                    best = min(best, dt)
            return n / best

        staged = run(False)
        # Preflight: if this config is ineligible the honest answer is an
        # explicit reason, not a silently-staged "device" number.
        trainer = Trainer(cfg)
        cache_lib.clear_ram_cache()
        probe = tasks_lib.make_pipeline(cfg, files, epochs=1, shuffle=True)
        reason = trainer.device_dataset_ineligible(probe)
        if reason is not None:
            return {"staged_ex_per_s": round(staged, 1),
                    "device_resident_ineligible": reason}
        device = run(True)
        return {
            "staged_ex_per_s": round(staged, 1),
            "device_resident_ex_per_s": round(device, 1),
            "device_over_staged_speedup": round(device / max(staged, 1e-9),
                                                3),
        }


def online_publish_series() -> dict:
    """Hot-publishing interference: ex/s of the same pre-staged dispatch
    loop with the Publisher hook active vs absent (the <5% acceptance bar
    from docs/TUNING.md §2.9), plus publish latency p50/p99 and worst-case
    artifact staleness. The hook's synchronous cost is the device->host
    params snapshot; the artifact write itself runs on the async executor,
    so on a real TPU it overlaps device compute (on a 1-core CPU host the
    background export steals the only core and the overhead reads high)."""
    import shutil
    import tempfile

    import jax

    from deepfm_tpu.train import Trainer
    from deepfm_tpu.train.publish import Publisher

    cfg = _bench_cfg()
    trainer = Trainer(cfg)
    state = trainer.init_state()
    sb = [trainer.put_superbatch(g) for g in _make_groups(cfg, 4)]
    step = trainer.multi_step
    state, m = step(state, sb[0])  # compile
    jax.block_until_ready(m["loss"])

    def run(publisher):
        nonlocal state
        dt = float("inf")
        steps = 0
        for _ in range(N_TRIALS):
            t0 = time.perf_counter()
            for i in range(N_DISPATCH):
                state, m = step(state, sb[i % 4])
                steps += K_STEPS
                if publisher is not None:
                    publisher.maybe_publish(state, steps)
            jax.block_until_ready(m["loss"])
            dt = min(dt, time.perf_counter() - t0)
        return N_DISPATCH * K_STEPS * cfg.batch_size / dt

    off_eps = run(None)
    tmp = tempfile.mkdtemp(prefix="bench_publish_")
    try:
        # ~3 cadence crossings per trial; in-flight skips (counted below)
        # are the expected steady state when the export outlasts the
        # interval, exactly as in production short-cadence configs.
        pub = Publisher(trainer.model, cfg, tmp,
                        every_steps=N_DISPATCH * K_STEPS // 3)
        on_eps = run(pub)
        pub.close()
        stats = pub.stats()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "publish_off_ex_per_s": round(off_eps, 1),
        "publish_on_ex_per_s": round(on_eps, 1),
        "online_publish_overhead_pct": round(
            100.0 * (1.0 - on_eps / max(off_eps, 1e-9)), 2),
        "publish_count": stats["publish_count"],
        "publish_skipped_inflight": stats["publish_skipped_inflight"],
        "publish_latency_p50_s": (
            round(stats["publish_latency_p50_s"], 3)
            if stats["publish_latency_p50_s"] is not None else None),
        "publish_latency_p99_s": (
            round(stats["publish_latency_p99_s"], 3)
            if stats["publish_latency_p99_s"] is not None else None),
        "publish_staleness_steps_max": stats["publish_staleness_steps_max"],
    }


def observability_series() -> dict:
    """Telemetry-plane overhead: ex/s of the same pre-staged dispatch loop
    with ``--trace off`` vs ``ring`` (acceptance: < 2% — cheap enough to
    leave on), the raw per-span cost in each mode, and the metrics
    SnapshotWriter's per-write cost. Honesty: on a 1-core CPU host span
    emission contends with compute for the only core, so the measured
    overhead is an upper bound — on a TPU host the host-side span emit
    overlaps the async-dispatched device step."""
    import tempfile

    import jax

    from deepfm_tpu.obs import metrics as obs_metrics
    from deepfm_tpu.obs import trace as trace_lib

    cfg = _bench_cfg()
    from deepfm_tpu.train import Trainer
    trainer = Trainer(cfg)
    state = trainer.init_state()
    sb = [trainer.put_superbatch(g) for g in _make_groups(cfg, 4)]
    step = trainer.multi_step
    state, m = step(state, sb[0])  # compile
    jax.block_until_ready(m["loss"])

    def run() -> float:
        # The loop as loop.fit instruments it: one train.dispatch span per
        # dispatch (the hot-path span density; the staging spans fire on
        # the transfer path, absent with pre-staged superbatches).
        nonlocal state
        dt = float("inf")
        for _ in range(N_TRIALS):
            t0 = time.perf_counter()
            for i in range(N_DISPATCH):
                with trace_lib.span("train.dispatch", steps=K_STEPS,
                                    examples=cfg.batch_size):
                    state, m = step(state, sb[i % 4])
            jax.block_until_ready(m["loss"])
            dt = min(dt, time.perf_counter() - t0)
        return N_DISPATCH * K_STEPS * cfg.batch_size / dt

    def span_cost_ns(n: int = 20000) -> float:
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with trace_lib.span("bench.probe", i=0):
                pass
        return (time.perf_counter_ns() - t0) / n

    trace_lib.reset()
    off_eps = run()
    off_ns = span_cost_ns()
    trace_lib.configure("ring", export_env=False)
    ring_eps = run()
    ring_ns = span_cost_ns()
    dropped = trace_lib.dropped()
    trace_lib.reset()

    # SnapshotWriter cost with the live registry (whatever stat objects
    # this process auto-registered so far).
    with tempfile.TemporaryDirectory() as d:
        w = obs_metrics.SnapshotWriter(os.path.join(d, "metrics.jsonl"),
                                       period_secs=0.02)
        time.sleep(0.3)
        w.close()
        writes, write_s = w.writes, w.write_s

    overhead_pct = 100.0 * (1.0 - ring_eps / max(off_eps, 1e-9))
    return {
        "trace_off_ex_per_s": round(off_eps, 1),
        "trace_ring_ex_per_s": round(ring_eps, 1),
        "trace_overhead_pct": round(overhead_pct, 2),
        "trace_overhead_lt_2pct": bool(overhead_pct < 2.0),
        "span_cost_off_ns": round(off_ns, 1),
        "span_cost_ring_ns": round(ring_ns, 1),
        "ring_dropped_spans": dropped,
        "snapshot_writes": writes,
        "snapshot_write_ms_mean": round(1000.0 * write_s / max(writes, 1),
                                        3),
        "overhead_basis": "1-core-CPU-host-upper-bound",
    }


def export_serving_artifacts(workdir: str) -> str:
    """Two complete bench-config artifacts + LATEST->1 under ``workdir``
    (the mid-run swap is then a pure pointer move + off-to-the-side load,
    as in production — the publisher never writes into a live artifact
    dir). Returns ``workdir``. Split out so a sweep exports ONCE and runs
    many engine configurations against the same artifacts."""
    from deepfm_tpu.train import Trainer
    from deepfm_tpu.utils import export as export_lib

    cfg = _bench_cfg()
    trainer = Trainer(cfg)
    state = trainer.init_state()
    orig_tf = export_lib._export_tf_savedmodel
    export_lib._export_tf_savedmodel = lambda *a, **k: None  # not served
    try:
        for version in ("1", "2"):
            export_lib.export_serving(
                trainer.model, state, cfg, os.path.join(workdir, version))
    finally:
        export_lib._export_tf_savedmodel = orig_tf
    export_lib.write_latest(workdir, "1")
    return workdir


def serving_series(replicas: int = 1, inflight: int = 2,
                   small_rows: int = 4, run_secs: float = 3.0,
                   n_clients: int = 4,
                   artifact_dir: "str | None" = None) -> dict:
    """Serving runtime under synthetic closed-loop load, with a hot swap
    mid-run: per-request latency p50/p99 (global and per priority lane),
    QPS, batch occupancy, and the measured swap blackout (swap instant ->
    first completed flush that EXECUTED the new model version).

    Parameterized for the scale-out sweep (``scripts/bench_serving.py``):
    ``replicas`` > 1 runs a ReplicatedEngine fleet (sticky client
    affinity, staggered swaps), ``inflight`` sets the pipelined batching
    depth, ``small_rows`` the priority-lane threshold. ``artifact_dir``
    reuses pre-exported artifacts (export once, sweep many).

    Honesty fields mirror the train series: ``device_kind`` names the chip
    that actually served; ``load_kind`` labels the traffic as a
    closed-loop synthetic driver (``n_clients`` in-process clients, batch
    1..32), NOT a production trace — occupancy/QPS are properties of that
    load; ``host_cpu_count`` is what a replica-scaling reading must be
    judged against (replicas time-slice the same cores on this box)."""
    import shutil
    import tempfile
    import threading

    import jax

    from deepfm_tpu.serve import ReplicatedEngine, ServingEngine
    from deepfm_tpu.utils import export as export_lib

    cfg = _bench_cfg()
    max_req = 32
    tmp = artifact_dir or export_serving_artifacts(
        tempfile.mkdtemp(prefix="bench_serving_"))
    export_lib.write_latest(tmp, "1")   # reset for sweep re-entry
    orig_tf = export_lib._export_tf_savedmodel
    export_lib._export_tf_savedmodel = lambda *a, **k: None  # not served
    try:
        engine_kw = dict(poll_secs=0.05, max_batch=256, max_delay_ms=2.0,
                         inflight=inflight, small_rows=small_rows)
        if replicas > 1:
            engine = ReplicatedEngine.serve_latest(
                tmp, replicas=replicas, **engine_kw)
            watchers = [e.watcher for e in engine.engines]
        else:
            engine = ServingEngine.serve_latest(tmp, **engine_kw)
            watchers = [engine.watcher]
        stop = threading.Event()
        failures = []

        def client(seed):
            rng = np.random.default_rng(seed)
            kw = ({"affinity": seed}
                  if getattr(engine, "supports_affinity", False) else {})
            while not stop.is_set():
                n = int(rng.integers(1, max_req + 1))
                ids = rng.integers(0, cfg.feature_size,
                                   (n, cfg.field_size)).astype(np.int32)
                vals = rng.normal(size=(n, cfg.field_size)).astype(np.float32)
                try:
                    engine.predict(ids, vals, timeout=30, **kw)
                except Exception as e:  # noqa: BLE001 — the honesty counter
                    failures.append(repr(e))
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(n_clients)]
        for t in threads:
            t.start()
        try:
            time.sleep(run_secs / 2)
            export_lib.write_latest(tmp, "2")   # the hot swap, under load
            time.sleep(run_secs / 2)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        if replicas > 1:
            summary = engine.summary()
            blackout_per_replica = summary["swap_blackout_ms_per_replica"]
        else:
            summary = engine.stats.summary()
            blackout_per_replica = [summary["swap_blackout_ms"]]
        swaps = min(w.swap_count for w in watchers)
        swap_failures = sum(w.swap_failures for w in watchers)
        engine.close()
    finally:
        export_lib._export_tf_savedmodel = orig_tf
        if artifact_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
    return {
        "replicas": replicas,
        "serve_inflight": inflight,
        "serve_small_rows": small_rows,
        "serving_p50_ms": summary["serving_p50_ms"],
        "serving_p99_ms": summary["serving_p99_ms"],
        "serving_small_p50_ms": summary["serving_small_p50_ms"],
        "serving_small_p99_ms": summary["serving_small_p99_ms"],
        "serving_large_p50_ms": summary["serving_large_p50_ms"],
        "serving_large_p99_ms": summary["serving_large_p99_ms"],
        "serving_qps": summary["serving_qps"],
        "batch_occupancy_pct": summary["batch_occupancy_pct"],
        "swap_blackout_ms": summary["swap_blackout_ms"],
        "swap_blackout_ms_per_replica": blackout_per_replica,
        "serving_requests": summary["serving_requests"],
        "serving_failed": summary["serving_failed"] + len(failures),
        "serving_overloads": summary["serving_overloads"],
        "hot_swaps": swaps,
        "swap_failures": swap_failures,
        "clients": n_clients,
        "load_kind": "synthetic-closed-loop",
        "device_kind": jax.devices()[0].device_kind,
        "host_cpu_count": os.cpu_count(),
    }


def experiment_series(n_requests: int = 150, max_req: int = 4,
                      permille: int = 100, qps: float = 50.0,
                      rounds: int = 5) -> dict:
    """Cost of the gated-deployment plane, in three numbers.

    1. **Shadow overhead** — primary-lane p99 for the SAME deterministic
       paced request stream served bare (one engine) vs. through an
       ``ExperimentRouter`` in shadow mode (``permille``/1000 of requests
       duplicated to a second engine on the side lane). The acceptance bar
       is < 10% p99 overhead on this host. Two design choices make the
       number mean something on a 1-core box: the load is a PACED open
       loop below saturation (production serving is not run at 100% CPU —
       a back-to-back closed loop would measure core time-slicing, not
       router overhead), and the challenger engine batches its shadow rows
       with a generous ``max_delay_ms`` — the shadow lane's response is
       never returned to anyone, so it is latency-insensitive by
       definition, and delaying its flush schedules challenger compute
       into the pacing gaps instead of on top of the primary's own
       service window. Baseline and shadow passes ALTERNATE for
       ``rounds`` rounds and the reported p99s are medians-of-rounds, so
       host drift (the dominant noise source here) hits both arms
       equally.
    2. **Promotion pointer-move latency** — wall time of the
       ``PromotionController.observe()`` call that PROMOTES (history
       append + atomic ``LATEST`` move), sampled over fresh controllers.
       This is the control-plane step a canary waits on after its last
       passing window.
    3. **Rollback detection windows** — health windows observed until each
       poison kind (NaN, absolute-latency, calibration, staleness) flips
       the decision to ``rollback``. Gate evaluation is a pure function of
       the window, so every breach kind must detect in exactly 1 window —
       this series is the regression trip-wire for that contract (a value
       > 1 means a guardrail went soft).

    Honesty fields: ``device_kind`` names the serving chip; ``load_kind``
    labels the stream (single paced client at ``qps``, not a production
    trace); ``host_cpu_count`` says how independent the two arms' compute
    really is on this box — both arms time-slice the same core(s), which
    INFLATES measured shadow overhead relative to a host with real spare
    capacity, so the < 10% bar is conservative here."""
    import shutil
    import tempfile

    import jax

    from deepfm_tpu.serve import ARM_CHALLENGER, ExperimentRouter, \
        ServingEngine
    from deepfm_tpu.train import promote as promote_lib
    from deepfm_tpu.utils import export as export_lib

    cfg = _bench_cfg()
    tmp = export_serving_artifacts(tempfile.mkdtemp(prefix="bench_exp_"))
    try:
        buckets = export_lib.serving_buckets(16)
        control = ServingEngine(
            export_lib.load_serving(os.path.join(tmp, "1"),
                                    buckets=tuple(buckets)),
            max_batch=16, max_delay_ms=0.5, buckets=buckets)
        challenger = ServingEngine(
            export_lib.load_serving(os.path.join(tmp, "2"),
                                    buckets=tuple(buckets)),
            max_batch=16, max_delay_ms=25.0, buckets=buckets)

        rng = np.random.default_rng(7)
        stream = []
        for rid in range(n_requests):
            n = int(rng.integers(1, max_req + 1))
            ids = rng.integers(0, cfg.feature_size,
                               (n, cfg.field_size)).astype(np.int32)
            vals = rng.normal(size=(n, cfg.field_size)).astype(np.float32)
            stream.append((rid, ids, vals))
        for eng in (control, challenger):    # compile every bucket up front
            for n in range(1, max_req + 1):
                eng.predict(np.zeros((n, cfg.field_size), np.int32),
                            np.zeros((n, cfg.field_size), np.float32),
                            timeout=60)

        def drive(submit):
            lat = []
            t0 = time.monotonic()
            for i, (rid, ids, vals) in enumerate(stream):
                wait = t0 + i / qps - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                s = time.monotonic()
                submit(rid, ids, vals)
                lat.append((time.monotonic() - s) * 1000.0)
            lat.sort()
            return (lat[len(lat) // 2],
                    lat[min(len(lat) - 1, int(0.99 * len(lat)))])

        router = ExperimentRouter(control, challenger, mode="shadow",
                                  seed=7, challenger_permille=permille,
                                  shadow_slo_ms=0.0)
        base_p50s, base_p99s, shadow_p50s, shadow_p99s = [], [], [], []
        for _ in range(rounds):
            b50, b99 = drive(lambda rid, ids, vals:
                             control.predict(ids, vals, timeout=30))
            s50, s99 = drive(lambda rid, ids, vals:
                             router.predict(ids, vals, rid, timeout=30))
            base_p50s.append(b50)
            base_p99s.append(b99)
            shadow_p50s.append(s50)
            shadow_p99s.append(s99)

        def med(xs):
            return round(sorted(xs)[len(xs) // 2], 3)
        base_p50, base_p99 = med(base_p50s), med(base_p99s)
        shadow_p50, shadow_p99 = med(shadow_p50s), med(shadow_p99s)
        shadowed = rounds * sum(1 for rid, _, _ in stream
                                if router.assign(rid) == ARM_CHALLENGER)
        deadline = time.monotonic() + 30.0    # drain the side lane before
        while time.monotonic() < deadline:    # reading its counters
            s = router.summary()
            if (s["shadow_completed"] + s["shadow_errors"]
                    >= s["shadow_submitted"]):
                break
            time.sleep(0.01)
        router_summary = router.summary()
        router.close()
        for eng in (control, challenger):
            eng.close()

        # --- promotion pointer-move latency (control plane, no serving) --
        gates = promote_lib.GateConfig(
            min_samples=1, min_auc_delta=-0.05, max_p99_ratio=10.0,
            max_p99_ms=1000.0, max_nonfinite=0, max_calibration_err=0.25,
            max_candidate_age_s=3600.0, windows_required=1)
        healthy = dict(arm=1, n=1000, auc=0.75, p99_latency_ms=5.0,
                       nonfinite=0, mean_pred=0.5, observed_ctr=0.5,
                       calibration_err=0.0)
        ctl_health = dict(healthy, arm=0)
        promote_ms = []
        for _ in range(5):
            export_lib.write_latest(tmp, "1")
            ctl = promote_lib.PromotionController(tmp, gates=gates)
            assert ctl.offer("2")
            t0 = time.monotonic()
            d = ctl.observe(healthy, ctl_health)
            promote_ms.append((time.monotonic() - t0) * 1000.0)
            assert d.action == "promote", d
        promote_ms.sort()

        # --- rollback detection windows per poison kind ------------------
        poisons = {
            "nan": (dict(healthy, nonfinite=7),
                    promote_lib.REASON_NONFINITE, None),
            "latency": (dict(healthy, p99_latency_ms=5000.0),
                        promote_lib.REASON_LATENCY, None),
            "calibration": (dict(healthy, mean_pred=0.9,
                                 calibration_err=0.4),
                            promote_lib.REASON_CALIBRATION, None),
            "stale": (healthy, promote_lib.REASON_STALE, 7200.0),
        }
        detection = {}
        for kind, (health, reason, age_s) in poisons.items():
            ctl = promote_lib.PromotionController(tmp, gates=gates)
            assert ctl.offer("1", now_s=0.0 if age_s is not None else None)
            windows = 0
            while True:
                windows += 1
                kw = {"now_s": age_s} if age_s is not None else {}
                d = ctl.observe(health, ctl_health, **kw)
                if d.action == "rollback":
                    break
                assert windows < 10, f"{kind} never detected"
            detection[kind] = {"windows": windows,
                               "reason_typed": reason in d.reasons}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "requests_per_round": n_requests,
        "rounds": rounds,
        "offered_qps": qps,
        "challenger_permille": permille,
        "shadow_duplicated": shadowed,
        "baseline_p50_ms": base_p50,
        "baseline_p99_ms": base_p99,
        "shadow_p50_ms": shadow_p50,
        "shadow_p99_ms": shadow_p99,
        "baseline_p99_ms_rounds": [round(x, 3) for x in base_p99s],
        "shadow_p99_ms_rounds": [round(x, 3) for x in shadow_p99s],
        "shadow_p99_overhead_pct": round(
            (shadow_p99 - base_p99) / base_p99 * 100.0, 2)
        if base_p99 > 0 else None,
        "shadow_errors": router_summary["shadow_errors"],
        "shadow_nonfinite": router_summary["shadow_nonfinite"],
        "promotion_pointer_move_p50_ms": round(
            promote_ms[len(promote_ms) // 2], 3),
        "promotion_pointer_move_max_ms": round(promote_ms[-1], 3),
        "rollback_detection": detection,
        "load_kind": "synthetic-open-loop-paced-median-of-rounds",
        "device_kind": jax.devices()[0].device_kind,
        "host_cpu_count": os.cpu_count(),
    }


#: Fleet shape shared by the saturation probe and every flood point — a
#: deliberately SMALL queue (512 rows -> 256-row shed watermark) so the
#: post-window drain stays short and the admission gate, not the queue
#: depth, is what absorbs the flood.
_FLOOD_ENGINE_KW = dict(poll_secs=5.0, max_batch=64, max_delay_ms=2.0,
                        inflight=2, small_rows=0, queue_rows=512)


def serving_saturation_qps(artifact_dir: str, *, replicas: int = 2,
                           probe_secs: float = 1.5,
                           n_clients: int = 32,
                           warmup_secs: float = 0.4) -> float:
    """Measured saturation throughput for the flood fleet shape: a short
    closed-loop probe (``n_clients`` threads, 1-row requests — the flood
    plan's request shape) against the SAME engine configuration the
    overload series floods, with no admission gate and no hedging, so the
    number is the fleet's raw service rate. ``n_clients`` is the in-flight
    depth — it must be large enough to fill the batcher's buckets, or the
    probe measures round-trip serialization instead of service rate — and
    ``warmup_secs`` keeps bucket JIT compiles out of the measured window.
    The flood sweep expresses its offered loads as multiples of this
    measurement — "4x saturation" means the same thing on a laptop and a
    TPU host."""
    import threading

    from deepfm_tpu.serve import ReplicatedEngine

    cfg = _bench_cfg()
    engine = ReplicatedEngine.serve_latest(
        artifact_dir, replicas=replicas, **_FLOOD_ENGINE_KW)
    stop = threading.Event()
    done = [0] * n_clients

    def client(k):
        rng = np.random.default_rng(k)
        while not stop.is_set():
            ids = rng.integers(0, cfg.feature_size,
                               (1, cfg.field_size)).astype(np.int32)
            vals = rng.normal(size=(1, cfg.field_size)).astype(np.float32)
            try:
                engine.predict(ids, vals, timeout=30, affinity=k)
                done[k] += 1
            except Exception:  # noqa: BLE001 — probe counts successes only
                pass

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(n_clients)]
    for t in threads:
        t.start()
    time.sleep(warmup_secs)
    base = sum(done)
    t0 = time.monotonic()
    time.sleep(probe_secs)
    count = sum(done) - base
    elapsed = max(time.monotonic() - t0, 1e-9)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    engine.close()
    return max(1.0, count / elapsed)


def serving_drain_qps(artifact_dir: str, *, replicas: int = 2,
                      rows: int = 6144, warmup_rows: int = 512,
                      queue_rows: int = 32_768,
                      submit_threads: int = 4) -> float:
    """Open-loop drain throughput for the flood fleet shape: pre-fill the
    queue with a burst of 1-row requests submitted flat-out and measure
    completions/second while the backlog drains. This is the capacity
    number an overload flood actually fights — past saturation the
    executor runs back-to-back FULL batches off a deep queue, a regime a
    closed-loop probe (bounded in-flight depth, per-request round trips)
    underestimates by 30-50%. The fast-path A/B keys its "Nx saturation"
    multipliers off THIS number so "2x" reliably means a growing backlog.

    ``warmup_rows`` are burned first (bucket JIT compiles out of the
    window); the measured burst then drains with the queue never empty,
    so rows/elapsed IS the service rate."""
    import threading

    from deepfm_tpu.serve import ReplicatedEngine

    cfg = _bench_cfg()
    kw = dict(_FLOOD_ENGINE_KW)
    kw["queue_rows"] = int(queue_rows)
    engine = ReplicatedEngine.serve_latest(
        artifact_dir, replicas=replicas, **kw)
    rng = np.random.default_rng(0)

    def burst(n, affinity_base):
        reqs = [(rng.integers(0, cfg.feature_size,
                              (1, cfg.field_size)).astype(np.int32),
                 rng.normal(size=(1, cfg.field_size)).astype(np.float32))
                for _ in range(n)]
        futs = [None] * n
        per = (n + submit_threads - 1) // submit_threads

        def feeder(k):
            lo = k * per
            for j, (ids, vals) in enumerate(reqs[lo:lo + per]):
                # Per-request affinity: hash-spreads rows over replicas.
                futs[lo + j] = engine.submit(
                    ids, vals, affinity=affinity_base + lo + j)

        threads = [threading.Thread(target=feeder, args=(k,))
                   for k in range(submit_threads)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for f in futs:
            f.result(timeout=60)
        return time.monotonic() - t0

    try:
        burst(warmup_rows, 0)
        elapsed = burst(rows, submit_threads)
    finally:
        engine.close()
    return max(1.0, rows / max(elapsed, 1e-9))


def overload_point(engine, plan, *, slo_ms: float,
                   resolve_timeout_s: float) -> dict:
    """Drive one ``FloodTrafficPlan`` open-loop against a live fleet and
    tally the full accounting: every offered request ends as exactly ONE
    of completed / shed / overload / timeout / failed — the
    zero-silent-drop identity the flood gate asserts (``accounting_ok``).

    Open-loop means the driver submits on the plan's clock regardless of
    completions — past saturation it does NOT self-throttle, which is the
    whole point; ``offered_qps_achieved`` records what the single-threaded
    submitter actually sustained so a fast plan on a slow host is labeled
    rather than silently rescaled. Goodput counts only in-SLO completions
    over the offered window.

    With the serving fast path armed the identity grows one bucket:
    ``coalesced`` counts successes that joined an in-flight leader instead
    of executing (completed + coalesced + sheds + overloads + timeouts +
    failed == offered); ``cache_hits`` counts successes answered from the
    result cache (a hit IS a completion — it consumed no device time, not
    no request)."""
    from deepfm_tpu.serve import (AdmissionShed, ServerOverloaded,
                                  ServeTimeout)

    futs = []
    sheds = overloads = 0
    t0 = time.monotonic()
    for r in plan.requests:
        wait = t0 + r.t_s - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        try:
            futs.append(engine.submit(r.ids, r.vals, affinity=r.user_id,
                                      value=r.value))
        except AdmissionShed:
            sheds += 1
        except ServerOverloaded:
            overloads += 1
    submit_elapsed = max(time.monotonic() - t0, 1e-9)
    completed = in_slo = timeouts = failed = 0
    coalesced = cache_hits = 0
    lat: list = []
    deadline = time.monotonic() + resolve_timeout_s
    for fut in futs:
        try:
            fut.result(timeout=max(0.05, deadline - time.monotonic()))
        except ServeTimeout:
            timeouts += 1
            fut.cancel()
            continue
        except Exception:  # noqa: BLE001 — typed into the identity
            failed += 1
            continue
        if getattr(fut, "coalesced", False):
            coalesced += 1
        else:
            completed += 1
        if getattr(fut, "cache_hit", False):
            cache_hits += 1
        ms = fut.latency_ms
        if ms is not None:
            lat.append(ms)
            if ms <= slo_ms:
                in_slo += 1
    offered = len(plan.requests)
    succeeded = completed + coalesced
    lat.sort()
    return {
        "offered_requests": offered,
        "offered_qps_target": round(plan.offered_qps, 1),
        "offered_qps_achieved": round(offered / submit_elapsed, 1),
        "completed": completed,
        "coalesced": coalesced,
        "cache_hits": cache_hits,
        "cache_hit_rate": (round(cache_hits / succeeded, 4)
                           if succeeded else None),
        "coalesce_rate": (round(coalesced / succeeded, 4)
                          if succeeded else None),
        "in_slo": in_slo,
        "goodput_qps": round(in_slo / plan.duration_s, 1),
        "sheds": sheds,
        "overloads": overloads,
        "timeouts": timeouts,
        "failed": failed,
        "accounting_ok": (completed + coalesced + sheds + overloads
                          + timeouts + failed) == offered,
        "p50_ms": round(lat[len(lat) // 2], 3) if lat else None,
        "p99_ms": (round(lat[min(len(lat) - 1, int(0.99 * len(lat)))], 3)
                   if lat else None),
    }


def overload_series(run_secs: float = 1.5,
                    mults=(1.0, 2.0, 4.0),
                    replicas: int = 2, slo_ms: float = 50.0,
                    hedge_ms: float = 25.0, shed_watermark: int = 256,
                    users: int = 1_000_000,
                    artifact_dir: "str | None" = None,
                    saturation_qps: "float | None" = None,
                    population=None, seed: int = 0,
                    cache_rows: int = 0, cache_ttl_s: float = 0.0,
                    coalesce: bool = False,
                    repeat_p: float = 0.0,
                    queue_rows: "int | None" = None) -> dict:
    """The overload plane under open-loop Zipf flood: goodput (in-SLO
    completions/s), p50/p99, and shed/overload/hedge counts at multiples
    of the MEASURED saturation QPS, with the zero-silent-drop accounting
    identity asserted per point. Each point gets a fresh fleet (admission
    gate + hedging armed) so its counters and queue state are clean; the
    user population is shared across points, so head users carry history
    continuity through the whole sweep.

    Honesty fields: ``load_kind`` labels the traffic as an open-loop
    synthetic Zipf flood (``users`` synthetic users, NOT a production
    trace); ``saturation_qps`` is measured on THIS host immediately before
    the sweep, so the multiples survive host-speed changes;
    ``host_cpu_count`` is what any scaling reading must be judged against
    (the driver, hedger, and both replicas time-slice the same cores).

    The serving fast path rides on four knobs: ``cache_rows``/
    ``cache_ttl_s``/``coalesce`` arm each replica's result cache and
    in-flight coalescing, and ``repeat_p`` makes the flood replay each
    returning user's previous request byte-identically with that
    probability — fresh randoms never repeat, so without it a flood
    cannot exercise the cache at all. All four default off, keeping
    existing sweeps bit-comparable."""
    import shutil
    import tempfile

    import jax

    from deepfm_tpu.loop.traffic import FloodTrafficPlan, ZipfUserPopulation
    from deepfm_tpu.serve import ReplicatedEngine
    from deepfm_tpu.utils import export as export_lib

    cfg = _bench_cfg()
    tmp = artifact_dir or export_serving_artifacts(
        tempfile.mkdtemp(prefix="bench_flood_"))
    orig_tf = export_lib._export_tf_savedmodel
    export_lib._export_tf_savedmodel = lambda *a, **k: None  # not served
    try:
        export_lib.write_latest(tmp, "1")
        if saturation_qps is None:
            saturation_qps = serving_saturation_qps(
                tmp, replicas=replicas, probe_secs=max(1.0, run_secs))
        pop = population if population is not None else ZipfUserPopulation(
            seed, users=users)
        fast_kw = dict(_FLOOD_ENGINE_KW)
        fast_kw.update(cache_rows=cache_rows, cache_ttl_s=cache_ttl_s,
                       coalesce=coalesce)
        if queue_rows is not None:
            fast_kw["queue_rows"] = int(queue_rows)
        points = []
        for i, mult in enumerate(mults):
            plan = FloodTrafficPlan(
                seed + 100 + i, offered_qps=mult * saturation_qps,
                duration_s=run_secs, population=pop,
                field_size=cfg.field_size, feature_size=cfg.feature_size,
                repeat_p=repeat_p)
            # shed_watermark <= 0 parks the admission gate entirely (the
            # fast-path A/B: shedding clamps p99 identically in both arms,
            # hiding the backlog the cache exists to absorb).
            adm_kw = ({"slo_ms": slo_ms, "shed_watermark": shed_watermark}
                      if shed_watermark > 0 else {})
            engine = ReplicatedEngine.serve_latest(
                tmp, replicas=replicas, hedge_ms=hedge_ms,
                hedge_poll_secs=0.02, admission_kw=adm_kw,
                **fast_kw)
            try:
                point = overload_point(
                    engine, plan, slo_ms=slo_ms,
                    resolve_timeout_s=max(10.0, 4.0 * run_secs))
                s = engine.summary()
            finally:
                engine.close()
            point.update({
                "offered_mult": mult,
                "repeat_requests": plan.repeat_requests,
                "hedges_fired": s["hedges_fired"],
                "hedges_won": s["hedges_won"],
                "hedges_cancelled": s["hedges_cancelled"],
                "sheds_by_class": s["serving_sheds_by_class"],
                "admission_transitions": s["admission_transitions"],
                "engine_cache_hits": s.get("serving_cache_hits", 0),
                "engine_cache_misses": s.get("serving_cache_misses", 0),
                "engine_coalesced": s.get("serving_coalesced", 0),
            })
            points.append(point)
    finally:
        export_lib._export_tf_savedmodel = orig_tf
        if artifact_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
    return {
        "saturation_qps": round(float(saturation_qps), 1),
        "replicas": replicas,
        "serve_slo_ms": slo_ms,
        "serve_hedge_ms": hedge_ms,
        "serve_shed_watermark": shed_watermark,
        "serve_cache_rows": cache_rows,
        "serve_cache_ttl_s": cache_ttl_s,
        "serve_coalesce": coalesce,
        "flood_repeat_p": repeat_p,
        "users": pop.users,
        "zipf_q": pop.zipf_q,
        "touched_users": pop.touched_users,
        "points": points,
        "load_kind": "synthetic-open-loop-zipf-flood",
        "device_kind": jax.devices()[0].device_kind,
        "host_cpu_count": os.cpu_count(),
    }


def serving_fastpath_series(run_secs: float = 1.5,
                            mults=(0.5, 1.0, 2.0, 4.0),
                            replicas: int = 2, slo_ms: float = 50.0,
                            hedge_ms: float = 25.0,
                            users: int = 1_000_000,
                            repeat_p: float = 0.5,
                            cache_rows: int = 4096,
                            cache_ttl_s: float = 0.0,
                            queue_rows: int = 16_384,
                            seed: int = 0) -> dict:
    """Fast-path A/B under the SAME flood: one artifact, one measured
    saturation, identical per-arm traffic (fresh same-seed populations →
    bit-identical plans), cache+coalescing OFF vs ON. The deltas are the
    headline: with ``repeat_p`` of returning-user requests replayed
    byte-identically, the ON arm answers repeats from the version-keyed
    cache (and coalesces concurrent twins) instead of spending device
    time, so p99 at and past saturation should drop while the accounting
    identity still closes at every point.

    Unlike ``overload_series``'s defaults, BOTH arms here run with the
    admission gate effectively parked (huge shed watermark) and a deep
    queue: shedding/queue-full refusals clamp p99 at the queue cap in
    both arms, which would hide exactly the backlog the fast path exists
    to absorb. The A/B therefore measures queueing honestly — the off arm
    pays the full backlog past saturation, the on arm's repeats skip it.

    Two structural defenses against shared-host noise (the probe and the
    flood share cores with whatever else the machine runs):

    * saturation is the BEST of three closed-loop probes — capacity is
      the highest sustained rate, and background contention only ever
      biases a probe downward, so max-of-N converges on the true number
      while mean-of-N would undershoot and quietly deflate every "Nx"
      offered load;
    * the arms are PAIRED per multiplier (off then on, back-to-back)
      instead of sweeping one full series after the other, so a drift in
      background load lands on at most one point of the comparison, not
      on an entire arm.

    Honesty fields: both arms inherit ``overload_series``'s labels
    (synthetic Zipf flood, host-measured saturation, shared cores);
    ``repeat_p`` is the workload assumption the speedup is conditional
    on — a flood with no repeats (repeat_p=0) gives the cache nothing."""
    import shutil
    import tempfile

    from deepfm_tpu.loop.traffic import ZipfUserPopulation

    tmp = export_serving_artifacts(tempfile.mkdtemp(prefix="bench_fast_"))
    try:
        # Drain-rate saturation, best of 3: the open-loop burst probe
        # measures the full-batch service rate an overloaded flood
        # actually drains at (a closed-loop probe underestimates it by
        # 30-50%, which would quietly deflate every "Nx" offered load
        # until "2x" no longer overloads); max-of-N because background
        # contention only ever biases a probe downward.
        base = max(serving_drain_qps(tmp, replicas=replicas,
                                     queue_rows=queue_rows)
                   for _ in range(3))
        common = dict(run_secs=run_secs, replicas=replicas,
                      slo_ms=slo_ms, hedge_ms=hedge_ms,
                      shed_watermark=0, artifact_dir=tmp,
                      saturation_qps=base, seed=seed, repeat_p=repeat_p,
                      queue_rows=queue_rows)
        off_pts, on_pts = [], []
        for m in mults:
            off_m = overload_series(
                mults=(m,),
                population=ZipfUserPopulation(seed, users=users), **common)
            on_m = overload_series(
                mults=(m,),
                population=ZipfUserPopulation(seed, users=users),
                cache_rows=cache_rows, cache_ttl_s=cache_ttl_s,
                coalesce=True, **common)
            off_pts.append(off_m["points"][0])
            on_pts.append(on_m["points"][0])
        off = dict(off_m, points=off_pts)
        on = dict(on_m, points=on_pts)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    comparison = []
    for p_off, p_on in zip(off["points"], on["points"]):
        p99_off, p99_on = p_off["p99_ms"], p_on["p99_ms"]
        comparison.append({
            "offered_mult": p_off["offered_mult"],
            "p50_ms_off": p_off["p50_ms"], "p50_ms_on": p_on["p50_ms"],
            "p99_ms_off": p99_off, "p99_ms_on": p99_on,
            "p99_improvement_pct": (
                round(100.0 * (p99_off - p99_on) / p99_off, 1)
                if p99_off and p99_on is not None else None),
            "goodput_qps_off": p_off["goodput_qps"],
            "goodput_qps_on": p_on["goodput_qps"],
            "cache_hit_rate_on": p_on["cache_hit_rate"],
            "coalesce_rate_on": p_on["coalesce_rate"],
            "accounting_ok": (p_off["accounting_ok"]
                              and p_on["accounting_ok"]),
        })
    return {
        "saturation_qps": round(float(base), 1),
        "repeat_p": repeat_p,
        "serve_cache_rows": cache_rows,
        "serve_cache_ttl_s": cache_ttl_s,
        "off": off,
        "on": on,
        "comparison": comparison,
    }


def multitask_series() -> dict:
    """Multi-task head comparison: per-task AUC + train ex/s for a
    single-task baseline vs shared_bottom vs MMoE over the SAME data,
    shared-bottom capacity, optimizer, and step budget.

    Honesty fields: the data is synthetic two-label CTR/CVR (click-gated
    conversions over hidden linear weights, ``libsvm.generate_synthetic_ctr
    num_labels=2``), so the AUC DELTAS between variants are the meaningful
    signal, not the absolute values; ex/s times the full ``Trainer.fit``
    loop over pre-decoded in-memory batches (no disk decode in the window,
    but host->device transfer included) — it is a relative head-cost
    series, not the headline throughput anchor."""
    import glob as glob_mod
    import tempfile

    import jax

    from deepfm_tpu.config import Config
    from deepfm_tpu.data import libsvm
    from deepfm_tpu.data.pipeline import CtrPipeline
    from deepfm_tpu.train import Trainer

    fs, fields, bs = 20000, 13, 512
    out = {
        "data_kind": "synthetic-two-label",
        "device_kind": jax.devices()[0].device_kind,
    }
    with tempfile.TemporaryDirectory() as d:
        libsvm.generate_synthetic_ctr(
            d, num_files=2, examples_per_file=8192, feature_size=fs,
            field_size=fields, prefix="tr", seed=0, num_labels=2)
        libsvm.generate_synthetic_ctr(
            d, num_files=1, examples_per_file=8192, feature_size=fs,
            field_size=fields, prefix="va", seed=1, num_labels=2)
        tr_files = sorted(glob_mod.glob(os.path.join(d, "tr*.tfrecords")))
        va_files = sorted(glob_mod.glob(os.path.join(d, "va*.tfrecords")))

        def batches(files, shuffle, epochs=1):
            return list(CtrPipeline(
                files, field_size=fields, batch_size=bs, num_epochs=epochs,
                shuffle=shuffle, shuffle_files=shuffle, seed=0,
                drop_remainder=True, prefetch_batches=0, num_labels=2))

        train_b = batches(tr_files, shuffle=True, epochs=2)
        val_b = batches(va_files, shuffle=False)

        def run(**kw):
            cfg = Config(
                feature_size=fs, field_size=fields, embedding_size=16,
                deep_layers="64,32", dropout="1.0,1.0", batch_size=bs,
                learning_rate=1e-3, optimizer="Adam", l2_reg=1e-5,
                compute_dtype="float32", log_steps=0, seed=0,
                scale_lr_by_world=False, **kw)
            trainer = Trainer(cfg)
            state = trainer.init_state()
            state, _ = trainer.fit(state, train_b[:2])  # compile warmup
            t0 = time.perf_counter()
            state, m = trainer.fit(state, train_b)
            dt = time.perf_counter() - t0
            ev = trainer.evaluate(state, val_b)
            entry = {
                "ex_per_s": round(int(m["steps"]) * bs / dt, 1),
                "auc_ctr": round(float(ev.get("auc_ctr", ev["auc"])), 4),
            }
            if "auc_cvr" in ev:
                entry["auc_cvr"] = round(float(ev["auc_cvr"]), 4)
            return entry

        out["single_task_baseline"] = run()
        out["shared_bottom"] = run(tasks="ctr,cvr",
                                   multitask="shared_bottom")
        out["mmoe"] = run(tasks="ctr,cvr", multitask="mmoe",
                          mmoe_experts=4)
        base = out["single_task_baseline"]["ex_per_s"]
        for key in ("shared_bottom", "mmoe"):
            out[key]["ex_per_s_vs_single_task"] = round(
                out[key]["ex_per_s"] / max(base, 1e-9), 3)
    return out


def production_day_series() -> dict:
    """Closed-loop production-day drill (``scripts/production_drill.py``
    smoke variant): the serve->log->join->train->publish loop in one
    process, with the seeded publish crash live. Reports the loop's
    operational envelope — end-to-end staleness percentiles, request loss
    across hot swaps, serving latency under diurnal load, and the
    windowed online-vs-frozen AUC — from ONE drill run.

    Honesty fields mirror the serving series: ``device_kind`` names the
    chip; ``load_kind`` labels the traffic as the seeded diurnal synthetic
    plan (not a production trace); ``baseline_kind`` labels the AUC
    comparator as the frozen bootstrap artifact, not a tuned champion.
    ``chaos_fingerprint`` pins the exact fault plan the numbers were
    measured under."""
    import sys as _sys

    import jax

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import shutil
    import tempfile

    import production_drill

    tmp = tempfile.mkdtemp(prefix="bench_production_")
    try:
        r = production_drill.run_smoke(tmp, verbose=False)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "device_kind": jax.devices()[0].device_kind,
        "load_kind": r["load_kind"],
        "baseline_kind": r["baseline_kind"],
        "chaos_fingerprint": r["chaos"]["fingerprint"],
        "requests": r["traffic"]["requests"],
        "rows": r["traffic"]["rows"],
        "hot_swaps": r["request_loss"]["hot_swaps"],
        "requests_failed": r["request_loss"]["failed"],
        "publish_crash_fired": r["chaos"]["publish_crash_fired"],
        "staleness_p50_s": r["staleness"]["staleness_p50_s"],
        "staleness_p95_s": r["staleness"]["staleness_p95_s"],
        "staleness_uncovered_rows": r["staleness"]["uncovered_rows"],
        "serving_p50_ms": (round(r["serving"]["serving_p50_ms"], 3)
                           if r["serving"]["serving_p50_ms"] is not None
                           else None),
        "serving_p99_ms": (round(r["serving"]["serving_p99_ms"], 3)
                           if r["serving"]["serving_p99_ms"] is not None
                           else None),
        "skew_mismatches": r["skew"]["mismatches"],
        "windowed_auc": r["windowed_auc"],
        "drill_elapsed_s": r["elapsed_s"],
    }


def cascade_series() -> dict:
    """Retrieval→ranking cascade: end-to-end ``recommend()`` latency (user
    tower -> candidate index -> packed ranking batch -> top-k) p50/p99 and
    QPS, the ANN index's measured recall@k against the brute-force oracle,
    and the train-throughput cost of sequence features — the SAME DIN graph
    fit over the same batches WITH the history columns vs with them
    stripped (the stripped run rides the empty-history fallback, so the
    delta prices target attention + history transfer, not a different
    model).

    Honesty fields mirror the serving series: ``device_kind`` names the
    chip; ``load_kind`` labels the latency loop as a SEQUENTIAL synthetic
    driver (one recommend() per call, one in-process caller) — p50/p99 are
    closed-loop single-stream numbers, not concurrent-traffic tails; and
    recall@k is measured on this run's synthetic corpus, never assumed
    (brute is measured too — it must read 1.0)."""
    import glob as glob_mod
    import shutil
    import tempfile

    import jax

    from deepfm_tpu.config import Config
    from deepfm_tpu.data import libsvm
    from deepfm_tpu.data.pipeline import CtrPipeline
    from deepfm_tpu.models.twin_tower import train_twin_tower
    from deepfm_tpu.rec.cascade import CascadeEngine, export_cascade
    from deepfm_tpu.rec.index import CandidateIndex
    from deepfm_tpu.train import Trainer
    from deepfm_tpu.utils import export as export_lib

    fs, fields, hist, bs = 5000, 5, 8, 256
    retrieve_k, rank_k, recall_k = 50, 10, 50
    cfg = Config(
        feature_size=fs, field_size=fields, embedding_size=8,
        deep_layers="32,16", dropout="1.0,1.0", batch_size=bs,
        learning_rate=1e-3, optimizer="Adam", l2_reg=1e-5,
        compute_dtype="float32", log_steps=0, seed=0,
        scale_lr_by_world=False, model="din", history_max_len=hist)
    out = {
        "device_kind": jax.devices()[0].device_kind,
        "load_kind": "synthetic-sequential",
        "corpus_items": fs,
        "retrieve_k": retrieve_k,
        "rank_k": rank_k,
    }
    tmp = tempfile.mkdtemp(prefix="bench_cascade_")
    orig_tf = export_lib._export_tf_savedmodel
    export_lib._export_tf_savedmodel = lambda *a, **k: None  # not served
    try:
        libsvm.generate_synthetic_ctr(
            tmp, num_files=2, examples_per_file=4096, feature_size=fs,
            field_size=fields, prefix="tr", seed=0, history=hist)
        files = sorted(glob_mod.glob(os.path.join(tmp, "tr*.tfrecords")))
        hist_b = list(CtrPipeline(
            files, field_size=fields, batch_size=bs, num_epochs=1,
            shuffle=True, shuffle_files=True, seed=0, drop_remainder=True,
            prefetch_batches=0, history=True, history_max_len=hist))
        plain_b = [{k: v for k, v in b.items()
                    if k not in ("hist_ids", "hist_mask")} for b in hist_b]

        # --- sequence-feature train cost: history columns on vs off -----
        def train_eps(batches):
            trainer = Trainer(cfg)
            state = trainer.init_state()
            state, _ = trainer.fit(state, batches[:2])  # compile warmup
            t0 = time.perf_counter()
            state, m = trainer.fit(state, batches)
            return state, trainer, int(m["steps"]) * bs / (
                time.perf_counter() - t0)

        _, _, off_eps = train_eps(plain_b)
        state, trainer, on_eps = train_eps(hist_b)
        out["train_ex_per_s_history_on"] = round(on_eps, 1)
        out["train_ex_per_s_history_off"] = round(off_eps, 1)
        out["history_on_over_off_ratio"] = round(
            on_eps / max(off_eps, 1e-9), 3)

        # --- retrieval stage: towers + index, recall measured ----------
        tower_model, tower_params, _ = train_twin_tower(cfg, hist_b)
        items = tower_model.all_item_embeddings(tower_params, fs)
        queries = np.asarray(tower_model.user_embed(
            tower_params, hist_b[0]["hist_ids"], hist_b[0]["hist_mask"]))
        brute = CandidateIndex(items, kind="brute")
        ann = CandidateIndex(items, kind="ann", seed=0)
        # A second measured operating point on the recall-vs-latency curve
        # (TUNING.md §2.14): same corpus, half the partitions probed.
        ann_wide = CandidateIndex(items, kind="ann", seed=0,
                                  num_partitions=32, nprobe=16)
        out["recall_at_k"] = recall_k
        out["brute_recall"] = round(brute.recall_at_k(queries, recall_k), 4)

        def ann_point(idx):
            r = idx.recall_at_k(queries, recall_k)
            t0 = time.perf_counter()
            idx.search(queries, recall_k)
            ms = 1000 * (time.perf_counter() - t0) / queries.shape[0]
            return {"num_partitions": idx.num_partitions,
                    "nprobe": idx.nprobe,
                    "recall": round(r, 4),
                    "search_ms_per_query": round(ms, 4)}

        out["ann_default"] = ann_point(ann)
        out["ann_wide_probe"] = ann_point(ann_wide)
        out["ann_recall"] = out["ann_default"]["recall"]

        # --- end-to-end recommend() latency over a live artifact -------
        publish_dir = os.path.join(tmp, "publish")
        export_cascade(
            trainer.model, state, cfg, os.path.join(publish_dir, "1"),
            tower_params=tower_params, index=ann,
            index_meta={"recall_at_50": out["ann_recall"]})
        export_lib.write_latest(publish_dir, "1")
        engine = CascadeEngine(
            publish_dir, retrieve_k=retrieve_k, max_batch=64,
            max_delay_ms=1.0, watcher_kw={"poll_secs": 3600, "start": False})
        try:
            # (the watcher's constructor already did the initial check_once)
            assert engine.watcher.swap_count >= 1, "cascade artifact not loaded"
            rng = np.random.default_rng(7)

            def one_request():
                ln = int(rng.integers(1, hist + 1))
                h_ids = np.zeros((hist,), np.int32)
                h_ids[:ln] = rng.integers(1, fs, ln)
                h_mask = (np.arange(hist) < ln).astype(np.float32)
                ids = rng.integers(0, fs, fields).astype(np.int32)
                vals = rng.normal(size=fields).astype(np.float32)
                return engine.recommend(h_ids, h_mask, ids, vals, k=rank_k)

            for _ in range(5):  # compile/warm both stages + buckets
                one_request()
            lat = []
            t_all = time.perf_counter()
            for _ in range(60):
                t0 = time.perf_counter()
                cand, probs = one_request()
                lat.append(1000 * (time.perf_counter() - t0))
                assert np.all(np.isfinite(probs)), probs
            wall = time.perf_counter() - t_all
            out["e2e_p50_ms"] = round(float(np.percentile(lat, 50)), 3)
            out["e2e_p99_ms"] = round(float(np.percentile(lat, 99)), 3)
            out["e2e_qps"] = round(len(lat) / wall, 1)
        finally:
            engine.close()
    finally:
        export_lib._export_tf_savedmodel = orig_tf
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def pallas_ab_device_ratio() -> dict:
    """Interleaved Pallas-vs-XLA A/B over the device-only staged multi-step
    (no transfer inside the timed window) — the regression canary for the
    fused FM kernel. The variants alternate trial-by-trial so host noise
    hits both equally; best-of-N each; the RATIO is the stable series
    (both numerators ride the same window)."""
    import jax

    from deepfm_tpu.train import Trainer

    setups = {}
    for pallas in (True, False):
        cfg = _bench_cfg(use_pallas=pallas)
        tr = Trainer(cfg)
        st = tr.init_state()
        sb = [tr.put_superbatch(g) for g in _make_groups(cfg, 2)]
        st, m = tr.multi_step(st, sb[0])  # compile
        jax.block_until_ready(m["loss"])
        setups[pallas] = [tr, st, sb]
    trials = []
    for _ in range(N_TRIALS):
        pair = {}
        for pallas in (True, False):
            tr, st, sb = setups[pallas]
            t0 = time.perf_counter()
            for i in range(N_DISPATCH):
                st, m = tr.multi_step(st, sb[i % 2])
            jax.block_until_ready(m["loss"])
            setups[pallas][1] = st
            pair[pallas] = time.perf_counter() - t0
        trials.append(pair)
    # The ratio is taken WITHIN one trial pair (the cleanest-window pair,
    # by combined time) — taking each variant's independent best could mix
    # measurements from different windows and report a ratio no single
    # window ever exhibited.
    pair = min(trials, key=lambda p: p[True] + p[False])
    denom = N_DISPATCH * K_STEPS
    leg_pallas_ms = 1000 * pair[True] / denom
    leg_xla_ms = 1000 * pair[False] / denom
    # Self-gating cleanliness: a ratio from a window in which either leg
    # ran far above the threshold records launch noise, not kernel speed.
    # clean=False means "discard this ratio", not "kernel regressed".
    clean_thresh = 0.02
    return {
        "pallas_ms_per_step": round(
            1000 * min(p[True] for p in trials) / denom, 4),
        "xla_ms_per_step": round(
            1000 * min(p[False] for p in trials) / denom, 4),
        "pallas_over_xla_ratio": round(pair[True] / pair[False], 3),
        "clean_pair_pallas_ms_per_step": round(leg_pallas_ms, 4),
        "clean_pair_xla_ms_per_step": round(leg_xla_ms, 4),
        "clean_threshold_ms_per_step": clean_thresh,
        "clean": bool(leg_pallas_ms <= clean_thresh
                      and leg_xla_ms <= clean_thresh),
    }


def embedding_kernels_series() -> dict:
    """Fused-embedding-plane regression canary: dense vs seed-sparse
    (``--embedding_kernels off``) vs fused-sparse (``auto``) ms/step at
    the EMBED bench shape, few steps (compile excluded). The claims under
    guard: the fused sparse step stays at or under dense
    (``sparse_beats_dense``, EMBED_r02 headline) and well under the seed
    formulation. Full per-kernel A/Bs + per-stage breakdown live in
    scripts/bench_embedding.py; this is the cheap canary that rides the
    main bench."""
    import jax

    from deepfm_tpu.config import Config
    from deepfm_tpu.train import Trainer

    v, b, f, nb = 100_000, 1024, 39, 16
    rng = np.random.default_rng(3)
    batches = [dict(
        feat_ids=rng.integers(0, v, size=(b, f)).astype(np.int32),
        feat_vals=rng.normal(size=(b, f)).astype(np.float32),
        label=rng.integers(0, 2, size=(b,)).astype(np.float32))
        for _ in range(nb + 2)]
    out = {"V": v, "B": b, "steps": nb}
    for label, kw in (
            ("dense", dict(embedding_update="dense")),
            ("sparse_seed", dict(embedding_update="sparse",
                                 embedding_kernels="off")),
            ("sparse_fused", dict(embedding_update="sparse",
                                  embedding_kernels="auto"))):
        cfg = Config(
            feature_size=v, field_size=f, embedding_size=8,
            deep_layers="32,16", dropout="1.0,1.0", batch_size=b,
            compute_dtype="float32", l2_reg=0.0, learning_rate=0.001,
            log_steps=0, seed=11, scale_lr_by_world=False, mesh_data=1,
            mesh_model=1, steps_per_loop=1, transfer_ahead=0, **kw)
        tr = Trainer(cfg)
        st = tr.init_state()
        st, _ = tr.fit(st, batches[:2])  # compile
        t0 = time.perf_counter()
        st, summary = tr.fit(st, batches[2:])
        jax.block_until_ready(st.params)
        out[f"{label}_ms_per_step"] = round(
            (time.perf_counter() - t0) * 1000.0 / max(summary["steps"], 1),
            3)
    out["fused_over_dense_ratio"] = round(
        out["sparse_fused_ms_per_step"] / out["dense_ms_per_step"], 3)
    out["fused_speedup_vs_seed"] = round(
        out["sparse_seed_ms_per_step"] / out["sparse_fused_ms_per_step"], 2)
    out["sparse_beats_dense"] = bool(
        out["sparse_fused_ms_per_step"] <= out["dense_ms_per_step"])
    return out


def scaling_probe() -> None:
    """--scaling mode (run in a subprocess): 1-dev vs 8-dev DP vs 4x2
    DP x row-shard on a virtual CPU mesh; prints one JSON line. The value
    is wiring-level (the collective programs compile and execute over the
    full mesh, including the masked-gather+psum embedding lookup on the
    'model' axis); the ratios measure host time-slicing, not hardware."""
    from __graft_entry__ import _provision_virtual_devices
    _provision_virtual_devices(8)

    # Wiring check, not a measurement: cut the trial budget so the three
    # virtual-mesh legs (1-dev, DP8, DP4xMP2) stay well under any harness
    # timeout on a 1-core host (best-of-5 x 12 here would triple the cost
    # for a number that only reflects time-slicing anyway).
    global N_TRIALS, N_DISPATCH
    N_TRIALS, N_DISPATCH = 2, 6

    r1 = measure(_bench_cfg(batch_size=1024, mesh_data=1))
    r8 = measure(_bench_cfg(batch_size=8 * 1024, mesh_data=8))
    out = {
        "one_dev_eps": round(r1["total_eps"], 1),
        "eight_dev_eps": round(r8["total_eps"], 1),
        "aggregate_ratio_8v1": round(
            r8["total_eps"] / (8 * r1["total_eps"]), 3),
    }
    # The 4x2 leg must not sink the (older) DP-only signal if it breaks.
    try:
        r42 = measure(_bench_cfg(batch_size=4 * 1024, mesh_data=4,
                                 mesh_model=2))
        out["dp4_mp2_eps"] = round(r42["total_eps"], 1)
        out["dp4_mp2_loss_finite"] = bool(np.isfinite(r42["loss"]))
    except Exception as e:
        out["dp4_mp2_error"] = str(e)[:300]
    print(json.dumps(out))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scaling", action="store_true",
                    help="internal: run the CPU-mesh scaling probe")
    ap.add_argument("--no-scaling", action="store_true",
                    help="skip the scaling-efficiency subprocess")
    args = ap.parse_args()

    if args.scaling:
        scaling_probe()
        return

    from deepfm_tpu.utils import compile_cache
    compile_cache.configure()

    import jax

    print(f"bench: devices={jax.devices()}", file=sys.stderr)
    cfg = _bench_cfg()
    r = measure(cfg)
    print(
        f"bench: {r['ms_per_step']:.3f} ms/step, total {r['total_eps']:,.0f} "
        f"ex/s on {r['devices']} device(s), loss={r['loss']:.4f}",
        file=sys.stderr)

    scaling = None
    if not args.no_scaling:
        # Subprocess: the scaling probe must own backend init (virtual CPU
        # mesh), which cannot coexist with this process's TPU backend.
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--scaling"],
                capture_output=True, text=True, timeout=1200, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)) or ".")
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith("{")]
            if line:
                scaling = json.loads(line[-1])
            else:
                print(f"bench: scaling probe failed:\n{out.stderr[-2000:]}",
                      file=sys.stderr)
        except (subprocess.TimeoutExpired, OSError) as e:
            print(f"bench: scaling probe error: {e}", file=sys.stderr)

    try:
        host_series = host_stage_series()
    except Exception as e:  # never let the canary sink the headline number
        print(f"bench: host series error: {e}", file=sys.stderr)
        host_series = {"error": str(e)}

    try:
        pallas_ab = pallas_ab_device_ratio()
    except Exception as e:
        print(f"bench: pallas A/B error: {e}", file=sys.stderr)
        pallas_ab = {"error": str(e)}

    try:
        embedding_kernels = embedding_kernels_series()
    except Exception as e:
        print(f"bench: embedding-kernels series error: {e}", file=sys.stderr)
        embedding_kernels = {"error": str(e)}

    try:
        device_resident = device_resident_series()
    except Exception as e:
        print(f"bench: device-resident series error: {e}", file=sys.stderr)
        device_resident = {"error": str(e)}

    try:
        online_publish = online_publish_series()
    except Exception as e:
        print(f"bench: online publish series error: {e}", file=sys.stderr)
        online_publish = {"error": str(e)}

    try:
        serving = serving_series()
    except Exception as e:
        print(f"bench: serving series error: {e}", file=sys.stderr)
        serving = {"error": str(e)}

    try:
        overload = overload_series()
    except Exception as e:
        print(f"bench: overload series error: {e}", file=sys.stderr)
        overload = {"error": str(e)}

    try:
        serving_fastpath = serving_fastpath_series()
    except Exception as e:
        print(f"bench: serving fast-path series error: {e}", file=sys.stderr)
        serving_fastpath = {"error": str(e)}

    try:
        experiment = experiment_series()
    except Exception as e:
        print(f"bench: experiment series error: {e}", file=sys.stderr)
        experiment = {"error": str(e)}

    try:
        multitask = multitask_series()
    except Exception as e:
        print(f"bench: multitask series error: {e}", file=sys.stderr)
        multitask = {"error": str(e)}

    try:
        cascade = cascade_series()
    except Exception as e:
        print(f"bench: cascade series error: {e}", file=sys.stderr)
        cascade = {"error": str(e)}

    try:
        production_day = production_day_series()
    except Exception as e:
        print(f"bench: production-day series error: {e}", file=sys.stderr)
        production_day = {"error": str(e)}

    try:
        observability = observability_series()
    except Exception as e:
        print(f"bench: observability series error: {e}", file=sys.stderr)
        observability = {"error": str(e)}

    nominal_per_accel_baseline = 250_000.0 / 4.0
    # MFU from the device-only series (no transfer in the window): model
    # FLOPs/example x device-only examples/sec/chip over the chip's
    # published bf16 peak — null on a device without one (the CPU backend
    # included). The tiny number it yields on a TPU is the honest headline:
    # DeepFM at batch 1024 is lookup/update-bound, so "fast" here means
    # low step LATENCY, and MFU quantifies distance from a FLOP wall.
    from deepfm_tpu.utils import mfu as mfu_lib
    flops_per_example = _model_flops_per_example(cfg)
    device_only_eps_per_chip = (
        cfg.batch_size / (r["device_only_ms_per_step"] / 1000.0)
        / max(r["devices"], 1))
    device_kind = jax.devices()[0].device_kind
    device_only_mfu_pct = mfu_lib.mfu_pct(
        flops_per_example, device_only_eps_per_chip, device_kind)
    result = {
        "metric": "deepfm_criteo_train_throughput_per_chip",
        "value": round(r["per_chip_eps"], 1),
        "unit": "examples/sec",
        "vs_baseline": round(r["per_chip_eps"] / nominal_per_accel_baseline, 3),
        # The anchor is a documented nominal ESTIMATE of the reference
        # 4xV100 recipe (no published number exists) — labeled in-band so
        # downstream readers can't mistake the ratio for a measured-vs-
        # measured comparison (VERDICT r5 #9).
        "baseline_kind": "nominal-estimate",
        "devices": r["devices"],
        "aggregate_eps": round(r["total_eps"], 1),
        "device_only_ms_per_step": round(r["device_only_ms_per_step"], 4),
        "device_kind": device_kind,
        "model_flops_per_example": flops_per_example,
        "device_only_mfu_pct": device_only_mfu_pct,
        "host_series": host_series,
        "pallas_ab_device": pallas_ab,
        "embedding_kernels": embedding_kernels,
        "device_resident": device_resident,
        "online_publish": online_publish,
        "serving": serving,
        "overload": overload,
        "serving_fastpath": serving_fastpath,
        "experiment": experiment,
        "multitask": multitask,
        "cascade": cascade,
        "production_day": production_day,
        "observability": observability,
    }
    if scaling is not None:
        # Deliberately NOT named "scaling efficiency": 8 VIRTUAL XLA devices
        # time-slice this host's core(s), so the aggregate ratio mostly
        # measures time-slicing (~1/8 on a 1-core host), not hardware
        # scaling. Its value here is wiring-level: the 8-way DP collective
        # program AND the 4x2 DP x row-shard program (masked-gather+psum
        # embedding lookup over 'model') compiled and executed. Real
        # scaling needs real chips.
        result["dp8_virtual_cpu_mesh_check"] = {
            "ok": True,
            "aggregate_ratio_8v1_timeslicing": scaling["aggregate_ratio_8v1"],
            "dp4_mp2_ok": bool(scaling.get("dp4_mp2_loss_finite", False)),
        }
    print(json.dumps(result))
    # Every series above is caught so that one failure cannot hide the
    # others' output — but a run in which any of them failed has failed.
    failed = sorted(name for name, val in result.items()
                    if isinstance(val, dict) and "error" in val)
    if not args.no_scaling and (scaling is None
                                or "dp4_mp2_error" in scaling):
        failed.append("scaling_probe")
    if failed:
        print(f"bench: FAILED series: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
