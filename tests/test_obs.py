"""Unified telemetry plane tests (obs.trace + obs.metrics + correlation).

Covers the tracer's three event shapes (cross-thread async pairing, ring
wraparound with counted drops, Chrome-JSON schema of export/merge), the
trace_report aggregation (self-time from ts/dur containment, async
pairing, percentiles), the typed metrics registry with its weakref
collector adapters and JSONL SnapshotWriter, the correlation stamps
(impression records, ServeFuture/flush spans), the per-replica
watcher-error/prewarm surfacing, and the golden pin: a 5-step training
trajectory under ``--trace ring`` is bit-identical to ``--trace off``.
"""

import gc
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.obs import metrics as obs_metrics
from deepfm_tpu.obs import trace as trace_lib

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
import trace_report  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with tracing off and env vars clear."""
    trace_lib.reset()
    yield
    trace_lib.reset()


def _mine(events):
    """``events`` without what the tracer's own pulse thread may add on a
    loaded machine (a ``host.stall``; after a second, a ``host.pulse``)."""
    return [e for e in events
            if e["name"] not in ("host.stall", "host.pulse")]


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------

class TestTracerCore:
    def test_off_mode_is_free_and_null(self):
        assert not trace_lib.enabled()
        # span() hands out ONE shared singleton: no per-call allocation.
        s = trace_lib.span("a", k=1)
        assert s is trace_lib.span("b")
        with s as inner:
            inner.add(more=2)
        assert trace_lib.begin("x") is None
        trace_lib.end(None)          # None handle must be a no-op
        trace_lib.instant("x")
        assert trace_lib._tracer.events() == []

    def test_span_records_complete_event_with_args(self):
        trace_lib.configure("full", export_env=False)
        with trace_lib.span("unit.work", rows=3) as sp:
            sp.add(extra=7)          # attrs discovered mid-span attach too
        (ev,) = _mine(trace_lib._tracer.events())
        assert ev["ph"] == "X" and ev["name"] == "unit.work"
        assert ev["args"] == {"rows": 3, "extra": 7}
        assert ev["dur"] >= 0.0
        assert ev["pid"] == os.getpid()
        assert ev["tid"] == threading.get_ident()

    def test_span_closes_on_exception(self):
        trace_lib.configure("full", export_env=False)
        with pytest.raises(RuntimeError):
            with trace_lib.span("unit.boom"):
                raise RuntimeError("x")
        (ev,) = _mine(trace_lib._tracer.events())
        assert ev["name"] == "unit.boom" and ev["ph"] == "X"

    def test_cross_thread_async_pair(self):
        """begin() on one thread, end() on another: same id, same name,
        different tids — the shape the ring waits use."""
        trace_lib.configure("full", export_env=False)
        h = trace_lib.begin("ring.wait", worker=0)
        t = threading.Thread(target=trace_lib.end, args=(h,), kwargs={"got": 1})
        t.start()
        t.join(timeout=10)
        evs = trace_lib._tracer.events()
        b = next(e for e in evs if e["ph"] == "b")
        e = next(e for e in evs if e["ph"] == "e")
        assert b["name"] == e["name"] == "ring.wait"
        assert b["id"] == e["id"]
        assert b["cat"] == e["cat"] == "ring"
        assert b["tid"] != e["tid"]
        assert e["ts"] >= b["ts"]

    def test_ring_wraparound_drops_counted_oldest_first(self):
        trace_lib.configure("ring", capacity=8, export_env=False)
        for i in range(20):
            with trace_lib.span("s", i=i):
                pass
        assert trace_lib.dropped() == 12
        evs = trace_lib._tracer.events()
        assert len(evs) == 8
        # Snapshot unrolls the ring oldest-first: the surviving events are
        # exactly the newest 8, in emit order.
        assert [e["args"]["i"] for e in evs] == list(range(12, 20))

    def test_full_mode_never_drops(self):
        trace_lib.configure("full", capacity=4, export_env=False)
        for i in range(50):
            trace_lib.instant("i", n=i)
        assert trace_lib.dropped() == 0
        assert len(_mine(trace_lib._tracer.events())) == 50

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            trace_lib.Tracer("bogus")

    def test_trace_ids_unique_and_minted_when_off(self):
        assert not trace_lib.enabled()
        ids = [trace_lib.new_trace_id() for _ in range(100)]
        assert len(set(ids)) == 100
        assert all(i >> 20 == os.getpid() for i in ids)

    def test_env_inheritance_roundtrip(self, tmp_path):
        trace_lib.configure("ring", capacity=77, trace_dir=str(tmp_path))
        assert os.environ[trace_lib.ENV_MODE] == "ring"
        assert os.environ[trace_lib.ENV_BUFFER] == "77"
        assert os.environ[trace_lib.ENV_DIR] == str(tmp_path)
        # Simulate the child process: fresh tracer, adopt from env.
        trace_lib._tracer = trace_lib.Tracer()
        trace_lib.configure_from_env()
        assert trace_lib._tracer.mode == "ring"
        assert trace_lib._tracer.capacity == 77
        trace_lib.reset()
        assert trace_lib.ENV_MODE not in os.environ
        assert trace_lib.ENV_DIR not in os.environ


# ---------------------------------------------------------------------------
# Export / merge: Chrome trace_event JSON schema
# ---------------------------------------------------------------------------

class TestExportMerge:
    def test_export_off_returns_none(self):
        assert trace_lib.export() is None

    def test_chrome_schema(self, tmp_path):
        trace_lib.configure("full", trace_dir=str(tmp_path),
                            export_env=False)
        with trace_lib.span("a.work", k=1):
            pass
        trace_lib.instant("a.mark")
        trace_lib.end(trace_lib.begin("a.wait"))
        path = trace_lib.export()
        assert os.path.basename(path) == f"trace-{os.getpid()}.json"
        with open(path) as f:
            doc = json.load(f)
        evs = doc["traceEvents"][:1] + _mine(doc["traceEvents"][1:])
        # First event names the process (Perfetto track label).
        assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
        assert sorted(e["ph"] for e in evs[1:]) == ["X", "b", "e", "i"]
        for e in evs[1:]:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
            if e["ph"] == "X":
                assert "dur" in e
            if e["ph"] in ("b", "e"):
                assert "cat" in e and "id" in e
            if e["ph"] == "i":
                assert e["s"] == "t"
        other = doc["otherData"]
        assert other["pid"] == os.getpid()
        assert other["mode"] == "full"
        assert other["dropped_spans"] == 0

    def test_merge_sums_drops_and_records_pids(self, tmp_path):
        trace_lib.configure("full", trace_dir=str(tmp_path),
                            export_env=False)
        with trace_lib.span("a.work"):
            pass
        trace_lib.export()
        n_mine = len(json.load(open(
            tmp_path / f"trace-{os.getpid()}.json"))["traceEvents"])
        # Fake a second process's export with a wrapped ring.
        second = {"traceEvents": [{"name": "z", "ph": "i", "s": "t",
                                   "ts": 1.0, "pid": 999, "tid": 1}],
                  "otherData": {"pid": 999, "mode": "ring",
                                "dropped_spans": 3}}
        with open(tmp_path / "trace-999.json", "w") as f:
            json.dump(second, f)
        out = trace_lib.merge(str(tmp_path),
                              str(tmp_path / "merged_trace.json"))
        with open(out) as f:
            m = json.load(f)
        assert m["otherData"]["merged_from"] == 2
        assert sorted(m["otherData"]["pids"]) == sorted([os.getpid(), 999])
        assert m["otherData"]["dropped_spans"] == 3
        assert len(m["traceEvents"]) == n_mine + 1


# ---------------------------------------------------------------------------
# trace_report: self time, async pairing, percentiles
# ---------------------------------------------------------------------------

class TestTraceReport:
    def test_self_time_subtracts_nested_children(self):
        evs = [
            # parent [0, 100ms) contains child [10ms, 40ms) on one thread.
            {"name": "parent", "ph": "X", "ts": 0.0, "dur": 100_000.0,
             "pid": 1, "tid": 1},
            {"name": "child", "ph": "X", "ts": 10_000.0, "dur": 30_000.0,
             "pid": 1, "tid": 1},
            # Cross-thread async pair: 50ms wait.
            {"name": "w", "ph": "b", "id": 5, "cat": "w", "ts": 0.0,
             "pid": 1, "tid": 1},
            {"name": "w", "ph": "e", "id": 5, "cat": "w", "ts": 50_000.0,
             "pid": 1, "tid": 2},
            # Orphan end: partner lost to the ring.
            {"name": "orphan", "ph": "e", "id": 9, "cat": "o", "ts": 1.0,
             "pid": 1, "tid": 1},
        ]
        rows, instants, unmatched = trace_report.summarize(evs)
        by = {r["name"]: r for r in rows}
        assert by["parent"]["total_ms"] == pytest.approx(100.0)
        assert by["parent"]["self_ms"] == pytest.approx(70.0)
        assert by["child"]["self_ms"] == pytest.approx(30.0)
        assert by["w"]["kind"] == "async"
        assert by["w"]["total_ms"] == pytest.approx(50.0)
        assert unmatched == 1
        assert instants == {}

    def test_same_thread_sequential_spans_do_not_nest(self):
        evs = [
            {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0,
             "pid": 1, "tid": 1},
            {"name": "b", "ph": "X", "ts": 10.0, "dur": 10.0,
             "pid": 1, "tid": 1},
        ]
        rows, _, _ = trace_report.summarize(evs)
        by = {r["name"]: r for r in rows}
        assert by["a"]["self_ms"] == pytest.approx(by["a"]["total_ms"])
        assert by["b"]["self_ms"] == pytest.approx(by["b"]["total_ms"])

    def test_percentiles_nearest_rank(self):
        durs = sorted(float(v) for v in range(1, 101))
        assert trace_report._pct(durs, 50) == 50.0
        assert trace_report._pct(durs, 99) == 99.0
        assert trace_report._pct([], 50) is None

    def test_stalls_names_what_covers_a_long_dispatch_interval(self):
        def x(name, ts_ms, dur_ms, tid=1, **args):
            return {"name": name, "ph": "X", "ts": ts_ms * 1e3,
                    "dur": dur_ms * 1e3, "pid": 1, "tid": tid, "args": args}
        events = [
            x("stage.wait", 10, 490, seq=3),              # ends at 500
            x("stage.wait", 510, 480, seq=4),             # ends at 990
            x("stage.input_wait", 1000, 2000, seq=5),     # the input is late
            x("input.pool_drain", 1100, 1500, tid=3),
            x("host.gc", 1200, 900, tid=3),
            x("stage.wait", 3000, 1, seq=5),              # ends at 3001
            x("input.pool_drain", 3050, 100, tid=3),      # after the stall
            x("stage.wait", 3010, 481, seq=6),            # ends at 3491
            # the fit thread enqueues in bunches: not the clock
            x("train.dispatch", 0, 1, seq=1),
            x("train.dispatch", 2000, 1, seq=2),
        ]
        (st,) = trace_report.stalls(events, 550)
        assert st["seq"] == 5 and st["interval_ms"] == 2011.0
        assert st["at_ms"] == 490.0
        cover = st["cover_ms"]
        assert cover["stage.input_wait"] == 2000.0
        assert cover["input.pool_drain"] == 1500.0
        assert cover["host.gc"] == 900.0
        assert cover["stage.wait"] == 1.0
        assert cover["train.log_sync"] == 0.0
        assert trace_report.stalls(events, 3000) == []

    def test_cli_json_roundtrip(self, tmp_path, capsys):
        trace_lib.configure("full", trace_dir=str(tmp_path),
                            export_env=False)
        with trace_lib.span("cli.span"):
            pass
        path = trace_lib.export()
        assert trace_report.main([path, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert [r["name"] for r in doc["spans"]] == ["cli.span"]
        assert doc["dropped_spans"] == 0
        # Table mode on the same file also runs clean.
        assert trace_report.main([path, "--stalls", "550"]) == 0
        assert "0 dispatch intervals over 550 ms" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_typed_metrics_and_snapshot(self):
        reg = obs_metrics.Registry()
        c = reg.counter("reqs")
        c.inc()
        c.inc(2)
        reg.gauge("lag").set(1.5)
        h = reg.histogram("lat_ms")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["reqs"] == 3
        assert snap["lag"] == 1.5
        assert snap["lat_ms.count"] == 4
        assert snap["lat_ms.sum"] == 10.0
        assert snap["lat_ms.p50"] == 2.0
        assert snap["lat_ms.p99"] == 4.0
        # Same name -> same instance; same name, other kind -> TypeError.
        assert reg.counter("reqs") is c
        with pytest.raises(TypeError):
            reg.gauge("reqs")

    def test_histogram_reservoir_keeps_exact_count_sum(self):
        h = obs_metrics.Histogram("h", cap=8)
        for v in range(100):
            h.observe(float(v))
        assert h.count == 100
        assert h.sum == float(sum(range(100)))
        assert len(h._vals) == 8  # bounded memory

    def test_collector_weakref_prunes_dead_objects(self):
        reg = obs_metrics.Registry()

        class Stat:
            def snap(self):
                return {"x": 1}

        s = Stat()
        reg.register_collector("thing", Stat.snap, obj=s)
        assert reg.snapshot()["thing.x"] == 1
        del s
        gc.collect()
        assert "thing.x" not in reg.snapshot()

    def test_collector_name_collisions_suffix(self):
        reg = obs_metrics.Registry()
        n1 = reg.register_collector("k", lambda: {"v": 1})
        n2 = reg.register_collector("k", lambda: {"v": 2})
        assert (n1, n2) == ("k", "k#2")
        snap = reg.snapshot()
        assert snap["k.v"] == 1 and snap["k#2.v"] == 2

    def test_broken_collector_isolated(self):
        reg = obs_metrics.Registry()
        reg.register_collector("bad", lambda: 1 / 0)
        reg.counter("ok").inc()
        snap = reg.snapshot()
        assert snap["ok"] == 1
        assert "bad.error" in snap

    def test_existing_stat_classes_auto_register(self):
        """The stat surfaces self-register at construction and surface
        their EXISTING keys namespaced — no key renames."""
        from deepfm_tpu.data.health import DataHealth
        from deepfm_tpu.loop.health import LoopHealth
        from deepfm_tpu.serve.stats import ServingStats
        from deepfm_tpu.train.guard import TrainHealth

        obs_metrics.REGISTRY.reset()
        try:
            dh, lh = DataHealth(), LoopHealth()
            th, ss = TrainHealth(), ServingStats()
            dh.record_retry("f")
            lh.record("labels_joined", 2)
            snap = obs_metrics.REGISTRY.snapshot()
            assert snap["data_health.read_retries"] == 1
            assert snap["loop_health.labels_joined"] == 2
            assert snap["train_health.nonfinite_skips"] == 0
            assert snap["serving.serving_requests"] == 0
            del dh, lh, th, ss
        finally:
            gc.collect()
            obs_metrics.REGISTRY.reset()

    def test_snapshot_writer_jsonl(self, tmp_path):
        reg = obs_metrics.Registry()
        reg.counter("n").inc(5)
        p = tmp_path / "metrics.jsonl"
        w = obs_metrics.SnapshotWriter(str(p), period_secs=0.02,
                                       registry=reg)
        time.sleep(0.15)
        w.close()
        w.close()  # idempotent
        lines = [json.loads(l) for l in p.read_text().splitlines()]
        assert len(lines) >= 2  # periodic lines + the final close() flush
        assert all(l["metrics"]["n"] == 5 for l in lines)
        assert all(l["t"] > 0 for l in lines)
        assert w.writes == len(lines)
        assert w.write_s >= 0.0

    def test_snapshot_writer_rejects_nonpositive_period(self, tmp_path):
        with pytest.raises(ValueError):
            obs_metrics.SnapshotWriter(str(tmp_path / "m.jsonl"),
                                       period_secs=0)


# ---------------------------------------------------------------------------
# Correlation: impressions, futures, flush spans
# ---------------------------------------------------------------------------

class TestCorrelation:
    def test_impression_stamping_roundtrip(self):
        from deepfm_tpu.loop import impressions as imp
        ids = np.arange(3, dtype=np.int64)
        vals = np.ones(3, np.float32)
        buf = imp.encode_impression(7, 1.5, ids, vals,
                                    trace_id=12345, model_version=8)
        # The legacy decode is unaffected by the extra features.
        iid, at, dids, dvals = imp.decode_impression(buf)
        assert iid == 7 and at == pytest.approx(1.5)
        np.testing.assert_array_equal(dids, ids)
        assert imp.read_correlation(buf) == (12345, 8)
        # Unstamped records read back as (None, None), not an error.
        plain = imp.encode_impression(7, 1.5, ids, vals)
        assert imp.read_correlation(plain) == (None, None)

    def test_engine_stamps_future_and_flush_span(self):
        from deepfm_tpu.serve.engine import ServingEngine
        trace_lib.configure("full", export_env=False)

        def fn(ids, vals):
            return np.zeros(ids.shape[0], np.float32)

        eng = ServingEngine(fn, max_batch=8, max_delay_ms=1.0)
        try:
            tid = trace_lib.new_trace_id()
            fut = eng.submit(np.zeros((2, 4), np.int32),
                             np.zeros((2, 4), np.float32), trace_id=tid)
            fut.result(timeout=10)
        finally:
            eng.close(timeout=10)
        assert fut.trace_id == tid
        flushes = [e for e in trace_lib._tracer.events()
                   if e.get("name") == "serve.flush" and e["ph"] == "X"]
        assert flushes
        assert tid in flushes[0]["args"]["trace_ids"]

    def test_frontend_carries_trace_id_over_the_rings(self):
        """The shm wire tuple grows a 5th element only when a trace id is
        present; the server re-stamps it into engine.submit."""
        from deepfm_tpu.data.shm_ring import THREAD_CTX
        from deepfm_tpu.serve import FrontendServer, ServingClient

        seen = []

        class _F:
            def __init__(self, n):
                self._n = n

            def done(self):
                return True

            def result(self, timeout=None):
                return np.zeros(self._n, np.float32)

        class _Eng:
            max_batch = 8

            def submit(self, ids, vals, trace_id=None):
                seen.append(trace_id)
                return _F(ids.shape[0])

        srv = FrontendServer(_Eng(), 1, field_size=4, ctx=THREAD_CTX)
        t = threading.Thread(target=srv.serve, daemon=True)
        t.start()
        try:
            with ServingClient(srv.handle(0)) as c:
                ids = np.zeros((2, 4), np.int32)
                vals = np.ones((2, 4), np.float32)
                tid = trace_lib.new_trace_id()
                c.predict(ids, vals, timeout=10, trace_id=tid)
                c.predict(ids, vals, timeout=10)  # legacy 4-tuple path
            t.join(timeout=10)
            assert not t.is_alive()
        finally:
            srv.stop()
            srv.close()
        assert seen == [tid, None]


# ---------------------------------------------------------------------------
# Replica fleet summary: per-replica fault visibility
# ---------------------------------------------------------------------------

class TestReplicaSummary:
    def test_per_replica_watcher_errors_and_prewarm(self):
        from deepfm_tpu.serve.engine import ServingEngine
        from deepfm_tpu.serve.replicas import ReplicatedEngine

        def fn(ids, vals):
            return np.zeros(ids.shape[0], np.float32)

        rep = ReplicatedEngine(
            [ServingEngine(fn, max_batch=8, max_delay_ms=1.0)
             for _ in range(2)], start=False)
        try:
            rep.predict(np.zeros((1, 4), np.int32),
                        np.zeros((1, 4), np.float32),
                        timeout=10, affinity=0)
            rep._engines[1].stats.record_watcher_error()
            s = rep.summary()
            # One replica's alive-but-failing watcher is invisible in the
            # fleet total unless surfaced per replica.
            assert s["serving_watcher_errors"] == 1
            assert s["serving_watcher_errors_per_replica"] == [0, 1]
            # Plain-fn replicas have no watcher: explicit None, not 0.
            assert s["prewarmed_buckets_per_replica"] == [None, None]
        finally:
            rep.close(timeout=10)


# ---------------------------------------------------------------------------
# Config gates
# ---------------------------------------------------------------------------

class TestConfigGates:
    def test_trace_mode_validated(self):
        with pytest.raises(ValueError):
            Config(trace="bogus")

    def test_defaults_off(self):
        cfg = Config()
        assert cfg.trace == "off"
        assert cfg.metrics_snapshot_secs == 0.0


# ---------------------------------------------------------------------------
# Golden pin: tracing must not move the trajectory
# ---------------------------------------------------------------------------

class TestBitIdentityPin:
    def _run(self):
        from deepfm_tpu.train import Trainer
        cfg = Config(
            feature_size=200, field_size=4, embedding_size=4,
            deep_layers="8", dropout="1.0", batch_size=32,
            compute_dtype="float32", l2_reg=1e-4, learning_rate=0.01,
            log_steps=0, seed=7, scale_lr_by_world=False,
            mesh_data=1, mesh_model=1, steps_per_loop=1)
        rng = np.random.default_rng(3)
        batches = [{
            "label": rng.integers(0, 2, (32,)).astype(np.float32),
            "feat_ids": rng.integers(0, 200, (32, 4)).astype(np.int32),
            "feat_vals": rng.standard_normal((32, 4)).astype(np.float32),
        } for _ in range(5)]
        tr = Trainer(cfg)
        state, _ = tr.fit(tr.init_state(), batches)
        return state

    def test_trace_ring_trajectory_bit_identical_to_off(self):
        trace_lib.reset()
        base = self._run()
        trace_lib.configure("ring", export_env=False)
        traced = self._run()
        spans = trace_lib._tracer.events()
        assert any(e["name"] == "train.dispatch" for e in spans
                   if e["ph"] == "X")
        trace_lib.reset()
        import jax
        base_leaves, base_tree = jax.tree_util.tree_flatten(base.params)
        traced_leaves, traced_tree = jax.tree_util.tree_flatten(traced.params)
        assert base_tree == traced_tree
        assert base_leaves  # a vacuous pin would hide a broken harness
        for i, (a, b) in enumerate(zip(base_leaves, traced_leaves)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), (
                f"param leaf {i} drifted")
