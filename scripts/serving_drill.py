#!/usr/bin/env python
"""Serving drill: hot-swapping serving engine against a LIVE publisher.

The executable acceptance check for the TPU-native serving runtime
(``serve/`` + the bucketed-predict seam in ``utils/export.py``):

  1. **Live publisher.** A real training loop (tiny config) runs in this
     process and publishes a servable artifact through the production
     ``Publisher`` every few steps — staging dir, atomic rename, ``LATEST``
     pointer — at least 3 versions.
  2. **Concurrent serving under load.** A replicated fleet (default 2
     pipelined engines with a small-request priority lane, sticky client
     affinity, staggered swaps — ``--replicas 1`` reproduces the single
     PR 7-style engine) over the publish dir serves closed-loop client
     threads the whole time. EVERY replica must hot-swap through >= 2
     version changes (beyond the initial load) with ZERO dropped or
     failed requests and zero failed swaps — and every returned prob
     finite and in [0, 1].
  3. **Near-zero blackout, PER REPLICA.** Each replica's watcher
     pre-warms every serving bucket off-thread before its one-assignment
     swap, and the coordinator staggers the fleet (one replica mid-swap
     at a time), so the measured swap-to-first-new-version-flush blackout
     must stay under ``MAX_BLACKOUT_MS`` on every replica (without the
     pre-warm the gap is the post-swap bucket compiles) and
     ``prewarmed_buckets`` must be > 0.
  4. **Bucket parity.** After the run, the final artifact is loaded twice
     — raw and bucket-padded — and the padded outputs must be BIT-EQUAL
     to the unpadded call row-for-row across non-bucket batch sizes.
  5. **Report.** p50/p99 latency, QPS, batch occupancy (> 0 required),
     and measured swap blackout go to ``SERVING_r0N.json`` at the repo
     root (next free N).

Run on CPU:  JAX_PLATFORMS=cpu python scripts/serving_drill.py
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deepfm_tpu.config import Config
from deepfm_tpu.serve import ReplicatedEngine, ServingEngine
from deepfm_tpu.train import Trainer
from deepfm_tpu.train.publish import Publisher
from deepfm_tpu.utils import export as export_lib

FEATURE_SIZE = 120
FIELD_SIZE = 5
TRAIN_STEPS = 16
PUBLISH_EVERY = 4        # versions at steps 4, 8, 12, 16
N_CLIENTS = 3
MAX_REQ_ROWS = 24
REPLICAS = 2             # the fleet under test (1 = the PR 7-style engine)
INFLIGHT = 2             # pipelined batching depth per replica
SMALL_ROWS = 4           # priority-lane threshold (exercised under swaps)
MIN_SWAPS = 3            # initial load + >= 2 hot swaps, PER replica
# Worst-case swap-to-next-flush gap with bucket pre-warm. Without it the
# gap is the post-swap bucket compiles on the serving path; with the
# watcher warming every bucket off-thread what remains is scheduling noise.
MAX_BLACKOUT_MS = 100.0

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def say(msg):
    print(f"[serving_drill] {msg}", flush=True)


def _tiny_cfg():
    return Config(
        feature_size=FEATURE_SIZE, field_size=FIELD_SIZE, embedding_size=4,
        deep_layers="8", dropout="1.0", batch_size=32,
        compute_dtype="float32", mesh_data=1, log_steps=0, seed=29,
        scale_lr_by_world=False,
        serve_max_batch=64, serve_max_delay_ms=3.0)


def _train_batch(cfg, rng):
    return {
        "label": (rng.random((cfg.batch_size, 1)) < 0.25).astype(np.float32),
        "feat_ids": rng.integers(0, cfg.feature_size,
                                 (cfg.batch_size, cfg.field_size)
                                 ).astype(np.int32),
        "feat_vals": rng.normal(size=(cfg.batch_size, cfg.field_size)
                                ).astype(np.float32),
    }


def _publish_while_training(cfg, publish_dir, swap_seen):
    """The live side: real train steps, real Publisher, >= 3 versions.
    Publishing is synchronous here so every version lands; between
    versions the loop waits until the serving side has swapped to the
    previous one — the drill must observe every hot swap, not only the
    last (a too-fast publisher would collapse them into one)."""
    trainer = Trainer(cfg)
    state = trainer.init_state()
    step_fn = trainer._make_train_step()
    rng = np.random.default_rng(5)
    pub = Publisher(trainer.model, cfg, publish_dir,
                    every_steps=PUBLISH_EVERY)
    versions = []
    try:
        for step in range(1, TRAIN_STEPS + 1):
            state, _ = step_fn(state, trainer.put_batch(_train_batch(cfg, rng)))
            if step % PUBLISH_EVERY == 0:
                pub.publish_now(state, step)
                versions.append(step)
                say(f"published version {step}")
                deadline = time.monotonic() + 60
                while (swap_seen() < len(versions)
                       and time.monotonic() < deadline):
                    time.sleep(0.02)
    finally:
        pub.close()
    return versions


def _client_loop(engine, seed, stop, counts, failures):
    rng = np.random.default_rng(seed)
    # A replicated fleet routes sticky by client id: each drill client
    # keeps its seed as the affinity key, so every replica sees sustained
    # traffic (the per-replica blackout gate needs post-swap flushes on
    # every replica).
    kw = ({"affinity": seed}
          if getattr(engine, "supports_affinity", False) else {})
    while not stop.is_set():
        n = int(rng.integers(1, MAX_REQ_ROWS + 1))
        ids = rng.integers(0, FEATURE_SIZE, (n, FIELD_SIZE)).astype(np.int32)
        vals = rng.normal(size=(n, FIELD_SIZE)).astype(np.float32)
        try:
            probs = engine.predict(ids, vals, timeout=60, **kw)
        except Exception as e:  # noqa: BLE001 — the drill's core assertion
            failures.append(repr(e))
            continue
        if (probs.shape != (n,) or not np.all(np.isfinite(probs))
                or not np.all((probs >= 0) & (probs <= 1))):
            failures.append(f"bad probs: shape={probs.shape}")
        counts[0] += 1


def _assert_bucket_parity(artifact_dir):
    """Padded-bucket outputs bit-equal to the unpadded call, row-for-row."""
    raw = export_lib.load_serving(artifact_dir)
    bucketed = export_lib.load_serving(artifact_dir, buckets=(4, 16, 64))
    rng = np.random.default_rng(11)
    for n in (1, 3, 5, 16, 23, 64):
        ids = rng.integers(0, FEATURE_SIZE, (n, FIELD_SIZE)).astype(np.int32)
        vals = rng.normal(size=(n, FIELD_SIZE)).astype(np.float32)
        np.testing.assert_array_equal(
            bucketed(ids, vals), np.asarray(raw(ids, vals)),
            err_msg=f"bucket parity broke at n={n}")
    say(f"bucket parity ok (calls_per_bucket={bucketed.calls_per_bucket})")


def _next_report_path():
    n = 1
    while os.path.exists(os.path.join(_REPO_ROOT, f"SERVING_r{n:02d}.json")):
        n += 1
    return os.path.join(_REPO_ROOT, f"SERVING_r{n:02d}.json")


def run_drill(workdir=None, report_path=None, verbose=True,
              replicas=REPLICAS, inflight=INFLIGHT, small_rows=SMALL_ROWS):
    """The whole drill; returns the report dict (also written to disk)."""
    global say
    if not verbose:
        say = lambda msg: None  # noqa: E731
    t_start = time.time()
    # The serving runtime consumes the StableHLO+params artifact; the TF
    # SavedModel sidecar (~10s/publish) only slows the swap cadence here.
    export_lib._export_tf_savedmodel = lambda *a, **k: None
    cfg = _tiny_cfg()
    workdir = workdir or tempfile.mkdtemp(prefix="serving_drill_")
    publish_dir = os.path.join(workdir, "publish")
    say(f"workdir {workdir} replicas={replicas} inflight={inflight} "
        f"small_rows={small_rows}")

    # Serving side first: it must come up BEFORE any artifact exists and
    # start serving the moment version 1 lands.
    engine_kw = dict(
        poll_secs=0.05, max_batch=cfg.serve_max_batch,
        max_delay_ms=cfg.serve_max_delay_ms, inflight=inflight,
        small_rows=small_rows)
    if replicas > 1:
        engine = ReplicatedEngine.serve_latest(
            publish_dir, replicas=replicas, **engine_kw)
        watchers = [e.watcher for e in engine.engines]
    else:
        engine = ServingEngine.serve_latest(publish_dir, **engine_kw)
        watchers = [engine.watcher]
    # The publisher's between-version wait counts the SLOWEST replica:
    # every replica must observe every version (the stagger means they
    # arrive one after another, never together).
    fleet_swaps = lambda: min(w.swap_count for w in watchers)  # noqa: E731
    stop = threading.Event()
    counts = [0]
    failures = []
    clients = [threading.Thread(target=_client_loop,
                                args=(engine, 100 + i, stop, counts, failures))
               for i in range(N_CLIENTS)]

    # The live side runs in the background; the publisher's between-version
    # wait (swap_seen) guarantees client traffic lands on EVERY version.
    versions = []
    pub_error = []

    def publisher_thread():
        try:
            versions.extend(_publish_while_training(
                cfg, publish_dir, swap_seen=fleet_swaps))
        except BaseException as e:  # noqa: BLE001 — re-raised in main
            pub_error.append(e)

    pub_t = threading.Thread(target=publisher_thread)
    pub_t.start()
    # Clients start once version 1 is visible on EVERY replica (before
    # that, predict fails by design: there is nothing to serve) and then
    # run across every subsequent hot swap — the part under test.
    deadline = time.monotonic() + 120
    while fleet_swaps() < 1 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert fleet_swaps() >= 1, "first artifact never appeared fleet-wide"
    say(f"first artifact live ({watchers[0].current_path}); "
        "starting clients")
    for c in clients:
        c.start()
    try:
        pub_t.join(timeout=300)
        assert not pub_t.is_alive(), "publisher wedged"
        if pub_error:
            raise pub_error[0]
        deadline = time.monotonic() + 60
        while counts[0] < 200 and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        stop.set()
        for c in clients:
            c.join(timeout=60)
    assert len(versions) >= MIN_SWAPS, versions

    if replicas > 1:
        summary = engine.summary()
        blackouts = summary["swap_blackout_ms_per_replica"]
    else:
        summary = engine.stats.summary()
        blackouts = [summary["swap_blackout_ms"]]
    swaps = fleet_swaps()
    swap_failures = sum(w.swap_failures for w in watchers)
    prewarmed = sum(w.prewarmed_buckets for w in watchers)
    final_artifact = watchers[0].current_path
    engine.close()

    say(f"requests={counts[0]} failures={len(failures)} swaps={swaps} "
        f"(failures={swap_failures}) summary={json.dumps(summary)}")

    # ---- acceptance ----
    assert not failures, failures[:5]
    assert summary["serving_failed"] == 0, summary
    assert summary["serving_overloads"] == 0, summary
    assert swaps >= MIN_SWAPS, \
        f"only {swaps} fleet-wide swaps (need >= {MIN_SWAPS} per replica)"
    assert swap_failures == 0, f"{swap_failures} failed swaps"
    assert counts[0] >= 200, f"only {counts[0]} requests completed"
    assert summary["batch_occupancy_pct"] is not None \
        and summary["batch_occupancy_pct"] > 0, summary
    assert summary["serving_p50_ms"] is not None \
        and summary["serving_p99_ms"] is not None, summary
    # Near-zero blackout ON EVERY REPLICA: each bucket was compiled
    # off-thread before each swap assignment (no post-swap request pays a
    # compile), and flushes are version-stamped so a pre-swap flush
    # completing post-swap (routine under pipelining) cannot close the
    # window early.
    assert prewarmed > 0, "no watcher ever pre-warmed a bucket"
    for i, b in enumerate(blackouts):
        assert b is not None and b < MAX_BLACKOUT_MS, \
            f"replica {i} swap blackout {b}ms >= {MAX_BLACKOUT_MS}ms " \
            f"(per-replica: {blackouts})"
    _assert_bucket_parity(final_artifact)

    report = {
        "drill": "serving",
        "ok": True,
        "replicas": replicas,
        "serve_inflight": inflight,
        "serve_small_rows": small_rows,
        "serving_p50_ms": summary["serving_p50_ms"],
        "serving_p99_ms": summary["serving_p99_ms"],
        "serving_small_p99_ms": summary["serving_small_p99_ms"],
        "serving_large_p99_ms": summary["serving_large_p99_ms"],
        "serving_qps": summary["serving_qps"],
        "batch_occupancy_pct": summary["batch_occupancy_pct"],
        "swap_blackout_ms": summary["swap_blackout_ms"],
        "swap_blackout_ms_per_replica": blackouts,
        "serving_requests": summary["serving_requests"],
        "serving_failed": summary["serving_failed"],
        "serving_overloads": summary["serving_overloads"],
        "hot_swaps": swaps,
        "swap_failures": swap_failures,
        "prewarmed_buckets": prewarmed,
        "versions_published": versions,
        "clients": N_CLIENTS,
        "load_kind": "synthetic-closed-loop",
        "elapsed_s": round(time.time() - t_start, 1),
    }
    path = report_path or _next_report_path()
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    say(f"PASS -> {path}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--report", default=None,
                    help="report path (default: SERVING_r0N.json, next free N)")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--replicas", type=int, default=REPLICAS,
                    help="fleet size (1 = the single PR 7-style engine)")
    ap.add_argument("--inflight", type=int, default=INFLIGHT,
                    help="pipelined batching depth per replica")
    ap.add_argument("--small_rows", type=int, default=SMALL_ROWS,
                    help="priority-lane row threshold (0 disables)")
    args = ap.parse_args()
    run_drill(args.workdir, args.report, replicas=args.replicas,
              inflight=args.inflight, small_rows=args.small_rows)


if __name__ == "__main__":
    main()
