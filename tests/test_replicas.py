"""Replica scale-out tests: sticky/spill routing, dead-replica re-route,
fleet-wide drain-on-close, staggered swap coordination, aggregate stats,
frontend client-affinity passthrough, and the tier-1 serving smoke (lane
p99 <= global p99 under a bypass-favoring load)."""

import threading
import time

import numpy as np
import pytest

from deepfm_tpu.data.shm_ring import THREAD_CTX
from deepfm_tpu.serve import (AdmissionShed, FrontendServer, ReplicatedEngine,
                              ServerOverloaded, ServingClient, ServingEngine,
                              ServingStats, aggregate_summary)
from deepfm_tpu.serve.replicas import HedgedFuture

pytestmark = pytest.mark.serving

FIELD_SIZE = 3


def _rows(n, base=0):
    ids = np.full((n, FIELD_SIZE), base, np.int32)
    vals = np.ones((n, FIELD_SIZE), np.float32)
    return ids, vals


def base_predict(feat_ids, feat_vals):
    return feat_ids[:, 0].astype(np.float32) + 0.5 * feat_vals[:, 0]


def _fleet(n=2, start=True, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_delay_ms", 1)
    return ReplicatedEngine(
        [ServingEngine(base_predict, start=start, **kw) for _ in range(n)])


# ---------------------------------------------------------------------------
# Routing: sticky affinity, least-loaded spill, typed refusal
# ---------------------------------------------------------------------------

class TestRouting:
    def test_sticky_affinity_holds_across_reconnect(self):
        """The same affinity key lands on the same replica every time —
        including after a gap with other clients' traffic in between (a
        client that reconnects with its id keeps its replica)."""
        fleet = _fleet(3)
        try:
            for _ in range(4):
                fleet.predict(*_rows(2, base=1), timeout=10, affinity=7)
            before = list(fleet.routed)
            home = before.index(max(before))
            assert before[home] == 4 and sum(before) == 4
            # "Reconnect": other clients hammer (key 1 shares key 7's home
            # replica, 1 ≡ 7 mod 3), then key 7 returns — same replica.
            for other in (0, 1, 2, 5):
                fleet.predict(*_rows(1), timeout=10, affinity=other)
            fleet.predict(*_rows(2, base=1), timeout=10, affinity=7)
            assert fleet.routed[home] == before[home] + 2
        finally:
            fleet.close(timeout=10)

    def test_no_affinity_routes_least_loaded(self):
        fleet = _fleet(2, start=False)
        try:
            # Load replica 0 directly; the router must prefer replica 1.
            fleet.engines[0].submit(*_rows(6))
            fut = fleet.submit(*_rows(2))
            assert fleet.routed == [0, 1]
            assert fleet.engines[1].pending_rows == 2
            assert not fut.done()
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=10)

    def test_overloaded_sticky_replica_spills(self):
        fleet = _fleet(2, start=False, max_batch=4, queue_rows=4)
        try:
            # Fill affinity-0's home replica to its queue bound.
            fleet.submit(*_rows(4), affinity=0)
            fut = fleet.submit(*_rows(2), affinity=0)    # spills to 1
            assert fleet.routed == [1, 1]
            assert fleet.spills == 1
            assert not fut.done()
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=10)

    def test_all_replicas_refusing_is_typed(self):
        fleet = _fleet(2, start=False, max_batch=4, queue_rows=4)
        try:
            fleet.submit(*_rows(4), affinity=0)
            fleet.submit(*_rows(4), affinity=1)
            with pytest.raises(ServerOverloaded, match="all 2 replicas"):
                fleet.submit(*_rows(1))
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=10)

    def test_dead_replica_reroutes_never_hangs(self):
        """A closed (dead) replica is just a refusing replica: requests
        with affinity for it re-route to a live one; when the whole fleet
        is dead the caller gets the typed error, not a hang."""
        fleet = _fleet(2)
        fleet.engines[0].close(timeout=10)     # replica 0 dies
        probs = fleet.predict(*_rows(2, base=4), timeout=10, affinity=0)
        np.testing.assert_array_equal(probs, np.full(2, 4.5, np.float32))
        assert fleet.routed == [0, 1]
        fleet.close(timeout=10)                # whole fleet dead
        with pytest.raises(ServerOverloaded):
            fleet.submit(*_rows(1))

    def test_malformed_request_fails_fast_without_reroute(self):
        fleet = _fleet(2)
        try:
            with pytest.raises(ValueError, match="one \\[n, F\\] shape"):
                fleet.submit(np.zeros((2, 3), np.int32),
                             np.zeros((3, 3), np.float32))
            assert fleet.routed == [0, 0]
        finally:
            fleet.close(timeout=10)


# ---------------------------------------------------------------------------
# Fleet lifecycle: drain-on-close, staggered swaps
# ---------------------------------------------------------------------------

class TestFleetLifecycle:
    def test_close_drains_every_replica(self):
        """Drain-on-close resolves EVERY admitted future across all
        replicas, including formed-but-unflushed pipeline batches."""
        fleet = _fleet(3, start=False, max_batch=2, max_delay_ms=0)
        futs = [fleet.submit(*_rows(2, base=i), affinity=i)
                for i in range(9)]
        assert all(r > 0 for r in fleet.routed)
        for e in fleet.engines:
            e.start()
        fleet.close(timeout=30)
        for f in futs:
            assert f.done()
            assert f.result(timeout=0).shape == (2,)

    def test_staggered_swap_one_replica_at_a_time(self):
        """The coordinator walks the fleet SEQUENTIALLY: each replica's
        swap (load + prewarm + assignment) completes before the next
        replica's begins, so at most one replica is ever mid-swap."""
        active = []
        overlap = []
        order = []

        class FakeWatcher:
            def __init__(self, name):
                self.name = name

            def check_once(self):
                if active:
                    overlap.append((active[0], self.name))
                active.append(self.name)
                time.sleep(0.01)          # a "slow" load+prewarm
                order.append(self.name)
                active.pop()
                return True

            def close(self):
                pass

        fleet = _fleet(3)
        try:
            for i, eng in enumerate(fleet.engines):
                eng._watcher = FakeWatcher(f"r{i}")
            assert fleet.check_swaps_once() == 3
            assert order == ["r0", "r1", "r2"]
            assert not overlap
        finally:
            for eng in fleet.engines:
                eng._watcher = None
            fleet.close(timeout=10)

    def test_swap_fault_counts_and_does_not_stop_the_walk(self):
        class BoomWatcher:
            def check_once(self):
                raise RuntimeError("poll boom")

            def close(self):
                pass

        fleet = _fleet(2)
        try:
            fleet.engines[0]._watcher = BoomWatcher()
            assert fleet.check_swaps_once() == 0
            assert fleet.engines[0].stats.watcher_errors == 1
            assert fleet.engines[1].stats.watcher_errors == 0
        finally:
            for eng in fleet.engines:
                eng._watcher = None
            fleet.close(timeout=10)


# ---------------------------------------------------------------------------
# Per-attempt routing re-snapshot (regression) + request hedging
# ---------------------------------------------------------------------------

class TestRoutingResnapshot:
    def test_spill_burst_spreads_by_live_pending_rows(self):
        """``_next_attempt`` re-reads pending rows at EVERY attempt: a
        burst of spills off a full home replica spreads across the fleet
        instead of piling onto whichever replica was least loaded when the
        first spill was computed."""
        fleet = _fleet(3, start=False, max_batch=8, queue_rows=8)
        try:
            fleet.engines[0].submit(*_rows(8))         # home full
            for _ in range(3):
                fleet.submit(*_rows(4), affinity=0)
            # 1st spill -> r1 (tie, lowest idx), 2nd -> r2 (r1 now has 4),
            # 3rd -> r1 (tie again at 4 rows each).
            assert fleet.routed == [0, 2, 1]
            assert fleet.spills == 3
            assert [e.pending_rows for e in fleet.engines] == [8, 8, 4]
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=30)

    def test_blocked_spill_target_reroutes_past_it(self):
        """The least-loaded spill target refusing (a dead replica shows 0
        pending, so it LOOKS least loaded) must not end the attempt walk:
        the next attempt re-snapshots and lands on a live replica."""
        fleet = _fleet(3, start=False, max_batch=4, queue_rows=4)
        try:
            fleet.engines[0].submit(*_rows(4))         # home full
            fleet.engines[2].submit(*_rows(1))
            fleet.engines[1].close(timeout=10)         # blocked: pending 0
            fut = fleet.submit(*_rows(1), affinity=0)
            assert fleet.routed == [0, 0, 1]
            assert fleet.spills == 1
            assert not fut.done()
        finally:
            for e in (fleet.engines[0], fleet.engines[2]):
                e.start()
            fleet.close(timeout=30)


class TestHedging:
    def _hedged_fleet(self, n=2, hedge_ms=5.0, **kw):
        kw.setdefault("max_batch", 8)
        kw.setdefault("max_delay_ms", 1)
        # start=False: no hedger thread — tests drive hedge_pass() by hand.
        return ReplicatedEngine(
            [ServingEngine(base_predict, start=False, **kw)
             for _ in range(n)],
            hedge_ms=hedge_ms, start=False)

    def test_hedge_fires_to_other_replica_and_wins(self):
        """Primary parked on a blocked replica: after the hedge delay the
        monitor re-submits to the least-loaded OTHER replica, the hedge
        resolves first, the caller gets its result, and the loser is
        cancelled — all counted (fired/won/cancelled)."""
        fleet = self._hedged_fleet()
        try:
            hf = fleet.submit(*_rows(1, base=3), affinity=0)
            assert isinstance(hf, HedgedFuture) and not hf.hedged
            # Not yet past the delay: nothing fires.
            assert fleet.hedge_pass(now=hf.t_enqueue) == 0
            assert fleet.hedge_pass(now=hf.t_enqueue + 1.0) == 1
            assert hf.hedged
            assert fleet.engines[1].pending_rows == 1
            # Second pass never double-hedges the same wrapper.
            assert fleet.hedge_pass(now=hf.t_enqueue + 2.0) == 0
            fleet.engines[1].start()
            np.testing.assert_array_equal(
                hf.result(timeout=10), np.full(1, 3.5, np.float32))
            assert hf._primary.cancelled()
            s = fleet.summary()
            assert s["hedges_fired"] == 1
            assert s["hedges_won"] == 1
            assert s["hedges_cancelled"] == 1
            # The resolved wrapper prunes into the p99 window.
            fleet.hedge_pass(now=hf.t_enqueue + 3.0)
            assert len(fleet._recent_latencies) == 1
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=30)

    def test_late_loser_never_double_resolves(self):
        """A cancelled loser that was already mid-flush resolving late is
        harmless: the wrapper's result and latency stamp are immutable
        after the winner."""
        fleet = self._hedged_fleet()
        try:
            hf = fleet.submit(*_rows(1, base=3), affinity=0)
            fleet.hedge_pass(now=hf.t_enqueue + 1.0)
            fleet.engines[1].start()
            want = hf.result(timeout=10)
            stamp = hf.latency_ms
            # The loser resolves anyway (as if mid-flush at cancel time).
            hf._primary.set_result(np.full(1, -99.0, np.float32), 0.0)
            np.testing.assert_array_equal(hf.result(timeout=0), want)
            assert hf.latency_ms == stamp
            assert fleet.summary()["hedges_won"] == 1
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=30)

    def test_attach_after_race_over_is_refused_and_cancelled(self):
        fleet = self._hedged_fleet()
        try:
            hf = fleet.submit(*_rows(1, base=2), affinity=0)
            hf._primary.set_result(np.full(1, 2.5, np.float32), 1.0)
            late = fleet.engines[1].submit(*_rows(1, base=2))
            assert hf.attach_hedge(late) is False
            assert late.cancelled()
            assert fleet.summary()["hedges_fired"] == 0
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=30)

    def test_errored_primary_holds_wrapper_for_healthy_hedge(self):
        """A failed primary with a hedge in flight does NOT resolve the
        wrapper: the caller only sees an error when no leg can succeed."""
        fleet = self._hedged_fleet()
        try:
            hf = fleet.submit(*_rows(1, base=4), affinity=0)
            fleet.hedge_pass(now=hf.t_enqueue + 1.0)
            hf._primary.set_error(RuntimeError("primary boom"))
            assert not hf.done()
            fleet.engines[1].start()
            np.testing.assert_array_equal(
                hf.result(timeout=10), np.full(1, 4.5, np.float32))
            assert fleet.summary()["hedges_won"] == 1
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=30)

    def test_both_legs_failing_surfaces_the_error(self):
        fleet = self._hedged_fleet()
        try:
            hf = fleet.submit(*_rows(1, base=4), affinity=0)
            fleet.hedge_pass(now=hf.t_enqueue + 1.0)
            hf._primary.set_error(RuntimeError("primary boom"))
            hf._hedge.set_error(RuntimeError("hedge boom"))
            assert hf.done()
            with pytest.raises(RuntimeError, match="boom"):
                hf.result(timeout=0)
            assert fleet.summary()["hedges_won"] == 0
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=30)

    def test_hot_fleet_skips_hedge_and_retries_next_pass(self):
        """When every other replica refuses the hedge submission (full
        queue), the pass skips it — the wrapper stays eligible and hedges
        on a later pass once capacity returns."""
        fleet = self._hedged_fleet(max_batch=4, queue_rows=4)
        try:
            hf = fleet.submit(*_rows(1), affinity=0)
            fleet.engines[1].submit(*_rows(3))   # only 1 row of room left
            fleet.engines[1].submit(*_rows(1))   # ...now zero
            assert fleet.hedge_pass(now=hf.t_enqueue + 1.0) == 0
            assert not hf.hedged
            fleet.engines[1].start()
            fleet.engines[1].close(timeout=10)   # drains; capacity back...
            # ...but a closed replica refuses: still no hedge, no crash.
            assert fleet.hedge_pass(now=hf.t_enqueue + 2.0) == 0
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=30)

    def test_cancelled_hedge_loser_never_reaches_the_device(self):
        """Regression: a hedge loser cancelled while its flush sits in the
        batcher->executor handoff must be dropped by the flush prologue,
        not computed and discarded. The primary replica is plugged by an
        in-flight batch (inflight=1 holds the loser's formed flush), the
        hedge wins on the healthy replica, and only then does the plug
        release — if the loser still reached the device, its feature id
        would show up in the plugged replica's seen-set."""
        seen = []
        entered = threading.Event()
        gate = threading.Event()

        def plugged_predict(feat_ids, feat_vals):
            seen.extend(np.asarray(feat_ids)[:, 0].tolist())
            if int(feat_ids[0, 0]) == 999:
                entered.set()
                assert gate.wait(timeout=30)
            return base_predict(feat_ids, feat_vals)

        eng0 = ServingEngine(plugged_predict, max_batch=8, max_delay_ms=1,
                             inflight=1)
        eng1 = ServingEngine(base_predict, max_batch=8, max_delay_ms=1)
        fleet = ReplicatedEngine([eng0, eng1], hedge_ms=5.0, start=False)
        try:
            plug = eng0.submit(*_rows(1, base=999))
            assert entered.wait(timeout=10)
            hf = fleet.submit(*_rows(1, base=777), affinity=0)
            # Wait for the batcher to form the loser's flush (it parks in
            # the handoff behind the plugged inflight slot).
            deadline = time.monotonic() + 10
            while eng0.pending_rows and time.monotonic() < deadline:
                time.sleep(0.005)
            assert eng0.pending_rows == 0
            assert fleet.hedge_pass(now=hf.t_enqueue + 10.0) == 1
            np.testing.assert_array_equal(
                hf.result(timeout=10), np.full(1, 777.5, np.float32))
            assert hf._primary.cancelled()
            gate.set()
            np.testing.assert_array_equal(
                plug.result(timeout=10), np.full(1, 999.5, np.float32))
            fleet.close(timeout=30)
            assert 777 not in seen
            s = fleet.summary()
            assert s["hedges_won"] == 1
            assert s["hedges_cancelled"] == 1
        finally:
            gate.set()
            fleet.close(timeout=30)

    def test_hedge_delay_tracks_fleet_p99_above_floor(self):
        fleet = self._hedged_fleet(hedge_ms=5.0)
        try:
            assert fleet.hedge_delay_s() == pytest.approx(0.005)
            # Under 20 samples the floor still rules.
            fleet._recent_latencies.extend([100.0] * 19)
            assert fleet.hedge_delay_s() == pytest.approx(0.005)
            fleet._recent_latencies.append(100.0)
            assert fleet.hedge_delay_s() == pytest.approx(0.1)
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=30)

    def test_all_sheds_raise_typed_admission_shed(self):
        """When EVERY replica's refusal was an admission shed, the fleet
        raises AdmissionShed (the fleet CHOSE to refuse the class), not
        ServerOverloaded."""
        fleet = ReplicatedEngine(
            [ServingEngine(base_predict, start=False, max_batch=8,
                           max_delay_ms=1, queue_rows=8,
                           admission_kw={"shed_watermark": 2})
             for _ in range(2)], start=False)
        try:
            for e in fleet.engines:
                e.submit(*_rows(2), value="critical")
            with pytest.raises(AdmissionShed, match="all 2 replicas"):
                fleet.submit(*_rows(1), value="bulk")
        finally:
            for e in fleet.engines:
                e.start()
            fleet.close(timeout=30)


# ---------------------------------------------------------------------------
# Aggregate stats
# ---------------------------------------------------------------------------

class TestAggregateStats:
    def test_fleet_percentiles_and_totals(self):
        clock = [0.0]
        a, b = (ServingStats(clock=lambda: clock[0]) for _ in range(2))
        for ms in (1.0, 2.0, 3.0):
            a.record_request_done(ms)
        for ms in (10.0, 20.0):
            b.record_request_done(ms, lane="small")
        a.record_flush(4, 8)
        clock[0] = 2.0
        b.record_flush(2, 4)
        b.record_overload()
        agg = aggregate_summary([a, b])
        assert agg["replicas"] == 2
        assert agg["serving_requests"] == 5
        assert agg["serving_overloads"] == 1
        # True fleet percentile over the CONCATENATED reservoir — the
        # median of {1,2,3,10,20}, not an average of per-replica medians.
        assert agg["serving_p50_ms"] == 3.0
        assert agg["serving_small_requests"] == 2
        # Union completion window: 5 requests over (2.0 - 0.0) seconds.
        assert agg["serving_qps"] == 2.5
        assert agg["batch_occupancy_pct"] == pytest.approx(50.0)

    def test_empty_window_is_none_and_zero_qps(self):
        """A replica that has served NOTHING yet (startup, or a canary arm
        drained by the kill-switch before its first completion) must
        summarize as 0 QPS with None percentiles — never raise, never
        fabricate a number (regression: the percentile helper used to
        index into an empty reservoir)."""
        s = ServingStats()
        one = s.summary()
        assert one["serving_requests"] == 0
        assert one["serving_qps"] == 0.0
        assert one["serving_p50_ms"] is None
        assert one["serving_p99_ms"] is None
        agg = aggregate_summary([ServingStats(), ServingStats()])
        assert agg["replicas"] == 2
        assert agg["serving_requests"] == 0
        assert agg["serving_qps"] == 0.0
        assert agg["serving_p50_ms"] is None
        assert agg["serving_p99_ms"] is None
        assert agg["batch_occupancy_pct"] is None

    def test_empty_fleet_aggregate(self):
        agg = aggregate_summary([])
        assert agg["replicas"] == 0
        assert agg["serving_requests"] == 0
        assert agg["serving_qps"] == 0.0
        assert agg["serving_p99_ms"] is None

    def test_worst_replica_blackout_and_per_replica_list(self):
        clock = [0.0]
        a, b = (ServingStats(clock=lambda: clock[0]) for _ in range(2))
        a.record_swap(version=2)
        clock[0] = 0.02
        a.record_flush(1, 1, version=2)
        b.record_swap(version=2)
        clock[0] = 0.07
        b.record_flush(1, 1, version=2)
        agg = aggregate_summary([a, b])
        assert agg["swap_blackout_ms"] == 50.0
        assert agg["swap_blackout_ms_per_replica"] == [20.0, 50.0]


# ---------------------------------------------------------------------------
# Frontend passthrough: client id IS the affinity key
# ---------------------------------------------------------------------------

class TestFrontendAffinity:
    def test_client_id_is_sticky_key(self):
        fleet = _fleet(2)
        srv = FrontendServer(fleet, 2, field_size=FIELD_SIZE, ctx=THREAD_CTX)
        t = threading.Thread(target=srv.serve, daemon=True)
        t.start()
        try:
            with ServingClient(srv.handle(0)) as c0, \
                    ServingClient(srv.handle(1)) as c1:
                for base in (1, 2, 3):
                    np.testing.assert_array_equal(
                        c0.predict(*_rows(2, base=base), timeout=10),
                        np.full(2, base + 0.5, np.float32))
                    c1.predict(*_rows(1, base=base), timeout=10)
            t.join(timeout=10)
            assert not t.is_alive()
            # cid 0 -> replica 0, cid 1 -> replica 1, no spills.
            assert fleet.routed == [3, 3]
            assert fleet.spills == 0
        finally:
            srv.stop()
            srv.close()
            fleet.close(timeout=10)

    def test_dead_replica_behind_frontend_stays_live(self):
        """A replica dying under a running frontend degrades to re-routing,
        not client-visible failures or hangs."""
        fleet = _fleet(2)
        srv = FrontendServer(fleet, 2, field_size=FIELD_SIZE, ctx=THREAD_CTX)
        t = threading.Thread(target=srv.serve, daemon=True)
        t.start()
        try:
            fleet.engines[1].close(timeout=10)   # cid 1's home replica dies
            with ServingClient(srv.handle(0)) as c0, \
                    ServingClient(srv.handle(1)) as c1:
                np.testing.assert_array_equal(
                    c1.predict(*_rows(2, base=9), timeout=10),
                    np.full(2, 9.5, np.float32))
                assert c0.predict(*_rows(1), timeout=10).shape == (1,)
            t.join(timeout=10)
            assert not t.is_alive()
            assert srv.errors_sent == 0
            assert fleet.routed[0] == 2      # both clients served by r0
        finally:
            srv.stop()
            srv.close()
            fleet.close(timeout=10)


# ---------------------------------------------------------------------------
# Tier-1 serving smoke (lane p99)
# ---------------------------------------------------------------------------

class TestServingSmoke:
    def test_lane_p99_at_most_global_p99_under_bypass_load(self):
        """The priority lane's whole job: under a backlog of max-batch
        large fills, head-of-line bypass keeps small-request p99 at or
        under the global p99 (dominated by the queued larges)."""
        def slow_predict(ids, vals):
            time.sleep(0.004)
            return base_predict(ids, vals)

        eng = ServingEngine(slow_predict, max_batch=8, max_delay_ms=1,
                            inflight=2, small_rows=1, queue_rows=512)
        try:
            futs = [eng.submit(*_rows(8, base=i)) for i in range(20)]
            smalls = []
            for i in range(10):
                smalls.append(eng.submit(*_rows(1, base=50 + i)))
                time.sleep(0.005)
            for f in futs + smalls:
                f.result(timeout=30)
            s = eng.stats.summary()
            assert s["serving_small_requests"] == 10
            assert s["serving_small_p99_ms"] <= s["serving_p99_ms"], s
        finally:
            eng.close()
