"""Serving fast-path tests: request fingerprints, the version-keyed LRU
result cache (copy semantics, TTL, eviction), cache/hot-swap interaction
through the engine (hit before swap, stale-version miss after, TTL expiry,
LRU under concurrent submit, shadow bypass never warms), in-flight
coalescing (join/fan-out, leader cancel refusal, error propagation) and the
repeat-flood knob. The accounting identity with the fast path armed is
``test_overload.py::TestServingAccountingIdentity``."""

import os
import sys
import threading
import time

import numpy as np
import pytest

from deepfm_tpu.loop.traffic import FloodTrafficPlan, ZipfUserPopulation
from deepfm_tpu.serve import (ReplicatedEngine, ResultCache, ServingEngine,
                              request_fingerprint)

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scripts"))
import production_drill  # noqa: E402

pytestmark = pytest.mark.cache

FIELD_SIZE = 5


def _rows(n, base=0):
    ids = (base + np.arange(n * FIELD_SIZE, dtype=np.int32)
           ).reshape(n, FIELD_SIZE) % 120
    vals = np.ones((n, FIELD_SIZE), np.float32)
    return ids, vals


def first_col_predict(feat_ids, feat_vals):
    """Row-local fake model, same idiom as test_serving."""
    return feat_ids[:, 0].astype(np.float32) * 0.001 + feat_vals[:, 0] * 0.1


# ---------------------------------------------------------------------------
# Request fingerprints
# ---------------------------------------------------------------------------

class TestFingerprint:
    def test_identical_bytes_identical_fingerprint(self):
        a, b = _rows(3), _rows(3)
        assert request_fingerprint(*a) == request_fingerprint(*b)
        # Copies (fresh allocations) fingerprint the same — content, not id.
        assert request_fingerprint(a[0].copy(), a[1].copy()) == \
            request_fingerprint(*a)

    def test_value_change_changes_fingerprint(self):
        ids, vals = _rows(3)
        bumped = vals.copy()
        bumped[1, 2] += 1e-6
        assert request_fingerprint(ids, bumped) != \
            request_fingerprint(ids, vals)

    def test_dtype_matters(self):
        ids, vals = _rows(2)
        assert request_fingerprint(ids.astype(np.int64), vals) != \
            request_fingerprint(ids, vals)

    def test_shape_matters_for_same_bytes(self):
        ids, vals = _rows(2)   # [2, 5]
        re_ids = ids.reshape(1, 10)
        re_vals = vals.reshape(1, 10)
        assert request_fingerprint(re_ids, re_vals) != \
            request_fingerprint(ids, vals)


# ---------------------------------------------------------------------------
# ResultCache unit behavior
# ---------------------------------------------------------------------------

class TestResultCache:
    def test_validation(self):
        with pytest.raises(ValueError, match="rows"):
            ResultCache(0)
        with pytest.raises(ValueError, match="ttl"):
            ResultCache(4, ttl_s=-1.0)

    def test_roundtrip_bit_identical_and_version_keyed(self):
        cache = ResultCache(16)
        fp = request_fingerprint(*_rows(2))
        probs = np.asarray([0.25, 0.75], np.float32)
        cache.put(7, fp, probs, rows=2)
        np.testing.assert_array_equal(cache.get(7, fp), probs)
        assert cache.get(8, fp) is None          # other version: miss
        assert cache.get(7, b"other") is None    # other request: miss

    def test_get_returns_copy(self):
        cache = ResultCache(16)
        cache.put(1, b"fp", np.asarray([0.5], np.float32), rows=1)
        out = cache.get(1, b"fp")
        out[0] = 99.0
        assert cache.get(1, b"fp")[0] == np.float32(0.5)

    def test_put_stores_copy(self):
        cache = ResultCache(16)
        probs = np.asarray([0.5], np.float32)
        cache.put(1, b"fp", probs, rows=1)
        probs[0] = 99.0
        assert cache.get(1, b"fp")[0] == np.float32(0.5)

    def test_multitask_dict_values_copied(self):
        cache = ResultCache(16)
        cache.put(1, b"fp", {"ctr": np.asarray([0.5], np.float32)}, rows=1)
        out = cache.get(1, b"fp")
        out["ctr"][0] = 99.0
        assert cache.get(1, b"fp")["ctr"][0] == np.float32(0.5)

    def test_lru_eviction_in_row_units(self):
        cache = ResultCache(4)
        for i in range(3):
            cache.put(1, bytes([i]), np.zeros(2, np.float32), rows=2)
        # 3 x 2 rows over a 4-row budget: entry 0 (LRU tail) evicted.
        assert cache.get(1, bytes([0])) is None
        assert cache.get(1, bytes([1])) is not None
        assert cache.get(1, bytes([2])) is not None
        assert cache.evictions == 1
        assert cache.rows == 4

    def test_get_refreshes_recency(self):
        cache = ResultCache(4)
        cache.put(1, b"a", np.zeros(2, np.float32), rows=2)
        cache.put(1, b"b", np.zeros(2, np.float32), rows=2)
        cache.get(1, b"a")                       # refresh a -> b is LRU
        cache.put(1, b"c", np.zeros(2, np.float32), rows=2)
        assert cache.get(1, b"a") is not None
        assert cache.get(1, b"b") is None

    def test_over_budget_entry_not_cached(self):
        cache = ResultCache(4)
        cache.put(1, b"a", np.zeros(2, np.float32), rows=2)
        cache.put(1, b"big", np.zeros(8, np.float32), rows=8)
        assert cache.get(1, b"big") is None
        assert cache.get(1, b"a") is not None    # and nothing was evicted

    def test_ttl_expires_lazily_with_injected_clock(self):
        clk = [0.0]
        cache = ResultCache(16, ttl_s=5.0, clock=lambda: clk[0])
        cache.put(1, b"fp", np.zeros(1, np.float32), rows=1)
        clk[0] = 4.9
        assert cache.get(1, b"fp") is not None
        clk[0] = 5.1
        assert cache.get(1, b"fp") is None
        assert cache.expirations == 1
        assert len(cache) == 0 and cache.rows == 0

    def test_summary_schema(self):
        cache = ResultCache(8, ttl_s=2.0)
        cache.put(1, b"fp", np.zeros(3, np.float32), rows=3)
        s = cache.summary()
        assert s == {"cache_entries": 1, "cache_rows_used": 3,
                     "cache_capacity_rows": 8, "cache_ttl_s": 2.0,
                     "cache_evictions": 0, "cache_expirations": 0}


# ---------------------------------------------------------------------------
# Engine-level cache x hot-swap interaction
# ---------------------------------------------------------------------------

class VersionedFn:
    """Minimal LatestWatcher stand-in: ``current()`` -> (fn, version)."""

    def __init__(self, fn=first_col_predict):
        self.version = 1
        self.fn = fn

    def current(self):
        v = self.version
        return (lambda ids, vals: self.fn(ids, vals)), v


class TestEngineCache:
    def test_hit_is_bit_identical_and_skips_device(self):
        calls = []

        def spy(ids, vals):
            calls.append(ids.shape[0])
            return first_col_predict(ids, vals)

        eng = ServingEngine(spy, max_batch=8, max_delay_ms=1, cache_rows=64)
        try:
            ids, vals = _rows(3)
            first = eng.submit(ids, vals)
            a = first.result(timeout=10)
            second = eng.submit(ids, vals)
            b = second.result(timeout=10)
            assert not first.cache_hit and second.cache_hit
            np.testing.assert_array_equal(a, b)   # bit-identical to flush
            assert len(calls) == 1                # no second device call
            s = eng.stats.summary()
            assert s["serving_cache_hits"] == 1
            assert s["serving_cache_misses"] == 1
            assert s["serving_cache_hit_rate"] == 0.5
            # A hit still counts as a completed request in the reservoirs.
            assert s["serving_requests"] == 2
        finally:
            eng.close()

    def test_swap_invalidates_for_free(self):
        calls = []
        fn = VersionedFn(lambda ids, vals: (calls.append(1),
                                            first_col_predict(ids, vals))[1])
        eng = ServingEngine(fn, max_batch=8, max_delay_ms=1, cache_rows=64)
        try:
            ids, vals = _rows(2)
            eng.predict(ids, vals, timeout=10)
            assert eng.submit(ids, vals).result(timeout=10) is not None
            assert len(calls) == 1                # second was a hit
            fn.version = 2                        # hot swap
            fut = eng.submit(ids, vals)
            fut.result(timeout=10)
            assert not fut.cache_hit              # stale version: miss
            assert len(calls) == 2                # recomputed under v2
            # And the v2 entry now serves v2 lookups.
            assert eng.submit(ids, vals).result(timeout=10) is not None
            assert len(calls) == 2
        finally:
            eng.close()

    def test_ttl_expiry_through_engine(self):
        clk = [0.0]
        calls = []

        def spy(ids, vals):
            calls.append(1)
            return first_col_predict(ids, vals)

        # max_delay_ms=0: the flush deadline is immediate, so the frozen
        # injected clock never strands the batcher.
        eng = ServingEngine(spy, max_batch=8, max_delay_ms=0,
                            cache_rows=64, cache_ttl_s=5.0,
                            clock=lambda: clk[0])
        try:
            ids, vals = _rows(1)
            eng.predict(ids, vals, timeout=10)
            eng.predict(ids, vals, timeout=10)
            assert len(calls) == 1
            clk[0] = 6.0                          # past the TTL
            eng.predict(ids, vals, timeout=10)
            assert len(calls) == 2
            assert eng.cache.expirations == 1
        finally:
            eng.close()

    def test_lru_eviction_under_concurrent_submit(self):
        eng = ServingEngine(first_col_predict, max_batch=8, max_delay_ms=1,
                            cache_rows=4)
        try:
            def hammer(base):
                for i in range(8):
                    eng.predict(*_rows(1, base=base + i), timeout=10)

            threads = [threading.Thread(target=hammer, args=(100 * t,))
                       for t in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert eng.cache.rows <= 4
            assert eng.cache.evictions > 0
            # The cache stayed coherent: a fresh repeat of a cached row is
            # still bit-identical to a recompute.
            ids, vals = _rows(1, base=999)
            a = eng.predict(ids, vals, timeout=10)
            b = eng.predict(ids, vals, timeout=10)
            np.testing.assert_array_equal(a, b)
        finally:
            eng.close()

    def test_bypass_never_reads_nor_warms(self):
        calls = []

        def spy(ids, vals):
            calls.append(1)
            return first_col_predict(ids, vals)

        eng = ServingEngine(spy, max_batch=8, max_delay_ms=1, cache_rows=64,
                            coalesce=True)
        try:
            ids, vals = _rows(2)
            shadow = eng.submit(ids, vals, bypass_cache=True)
            shadow.result(timeout=10)
            assert shadow.fingerprint is None     # never fingerprinted
            assert len(eng.cache) == 0            # never warmed
            # Warm via the normal lane, then bypass again: still recomputes.
            eng.predict(ids, vals, timeout=10)
            assert len(eng.cache) == 1
            again = eng.submit(ids, vals, bypass_cache=True)
            again.result(timeout=10)
            assert not again.cache_hit and not again.coalesced
            assert len(calls) == 3
            assert eng.stats.summary()["serving_cache_hits"] == 0
        finally:
            eng.close()

    def test_arms_never_share_entries(self):
        """Control and challenger engines own separate caches: warming one
        arm leaves the other arm's cache cold (the experiment-plane
        isolation the router relies on)."""
        control = ServingEngine(first_col_predict, max_batch=8,
                                max_delay_ms=1, cache_rows=64)
        challenger = ServingEngine(first_col_predict, max_batch=8,
                                   max_delay_ms=1, cache_rows=64)
        try:
            ids, vals = _rows(2)
            control.predict(ids, vals, timeout=10)
            control.predict(ids, vals, timeout=10)
            assert control.stats.summary()["serving_cache_hits"] == 1
            assert len(challenger.cache) == 0
            fut = challenger.submit(ids, vals)
            fut.result(timeout=10)
            assert not fut.cache_hit              # cold despite control hit
        finally:
            control.close()
            challenger.close()


# ---------------------------------------------------------------------------
# In-flight coalescing
# ---------------------------------------------------------------------------

class TestCoalescing:
    def test_followers_join_one_leader(self):
        calls = []

        def spy(ids, vals):
            calls.append(ids.shape[0])
            return first_col_predict(ids, vals)

        eng = ServingEngine(spy, max_batch=8, max_delay_ms=1,
                            coalesce=True, start=False)
        try:
            ids, vals = _rows(2)
            leader = eng.submit(ids, vals)
            follower = eng.submit(ids, vals)
            other = eng.submit(*_rows(2, base=50))
            assert not leader.coalesced and follower.coalesced
            assert not other.coalesced            # different bytes
            assert eng.pending_rows == 4          # follower never queued
            eng.start()
            a = leader.result(timeout=10)
            b = follower.result(timeout=10)
            other.result(timeout=10)
            np.testing.assert_array_equal(a, b)
            assert b is not a                     # fan-out copies
            assert sum(calls) == 4                # one device pass for the 3
            assert eng.stats.summary()["serving_coalesced"] == 1
        finally:
            eng.close()

    def test_leader_refuses_cancel_with_followers(self):
        eng = ServingEngine(first_col_predict, max_batch=8, max_delay_ms=1,
                            coalesce=True, start=False)
        try:
            ids, vals = _rows(1)
            leader = eng.submit(ids, vals)
            follower = eng.submit(ids, vals)
            assert follower.coalesced
            assert leader.cancel() is False       # carrying a follower
            assert not leader.cancelled()
            eng.start()
            np.testing.assert_array_equal(leader.result(timeout=10),
                                          follower.result(timeout=10))
        finally:
            eng.close()

    def test_childless_leader_cancel_still_works(self):
        eng = ServingEngine(first_col_predict, max_batch=8, max_delay_ms=1,
                            coalesce=True, start=False)
        try:
            fut = eng.submit(*_rows(1))
            assert fut.cancel() is True
            # A later identical request must NOT join the cancelled leader.
            fresh = eng.submit(*_rows(1))
            assert not fresh.coalesced
            eng.start()
            fresh.result(timeout=10)
        finally:
            eng.close()

    def test_error_propagates_to_followers(self):
        def boom(ids, vals):
            raise RuntimeError("model exploded")

        eng = ServingEngine(boom, max_batch=8, max_delay_ms=1,
                            coalesce=True, start=False)
        try:
            ids, vals = _rows(1)
            leader = eng.submit(ids, vals)
            follower = eng.submit(ids, vals)
            eng.start()
            with pytest.raises(RuntimeError, match="exploded"):
                leader.result(timeout=10)
            with pytest.raises(RuntimeError, match="exploded"):
                follower.result(timeout=10)
            assert eng.stats.summary()["serving_failed"] == 2
        finally:
            eng.close()

    def test_resolved_leader_not_joined(self):
        """Once the leader resolves, its registry entry retires — a later
        identical request recomputes (possibly via the cache, but never by
        attaching to a done future)."""
        eng = ServingEngine(first_col_predict, max_batch=8, max_delay_ms=1,
                            coalesce=True)
        try:
            ids, vals = _rows(1)
            leader = eng.submit(ids, vals)
            leader.result(timeout=10)
            late = eng.submit(ids, vals)
            assert not late.coalesced
            late.result(timeout=10)
        finally:
            eng.close()

    def test_hedge_leg_cache_hit_at_attach_does_not_deadlock(self):
        """Regression: a fired hedge leg can resolve INSIDE submit (warm
        result cache on the other replica), so ``attach_hedge`` adopts an
        ALREADY-DONE future and its done-callback runs synchronously on
        the attaching thread. That callback takes the wrapper lock —
        registering it while still holding the wrapper lock self-deadlocks
        the hedger (non-reentrant lock). The wrapper must resolve as a
        hedge win with the cached answer."""
        eng0 = ServingEngine(first_col_predict, start=False, max_batch=8,
                             max_delay_ms=1, cache_rows=64)
        eng1 = ServingEngine(first_col_predict, max_batch=8, max_delay_ms=1,
                             cache_rows=64)
        fleet = ReplicatedEngine([eng0, eng1], hedge_ms=5.0, start=False)
        try:
            ids, vals = _rows(2, base=7)
            want = eng1.submit(ids, vals).result(timeout=10)  # warm cache
            hf = fleet.submit(ids, vals, affinity=0)  # primary parks: eng0
            # hedge_pass runs on THIS thread — pre-fix it never returned.
            assert fleet.hedge_pass(now=hf.t_enqueue + 10.0) == 1
            assert hf.done()                  # resolved at attach time
            np.testing.assert_array_equal(hf.result(timeout=10), want)
            assert hf.cache_hit
            s = fleet.summary()
            assert s["hedges_won"] == 1
            assert s["serving_cache_hits"] == 1
        finally:
            eng0.start()
            fleet.close(timeout=30)


# ---------------------------------------------------------------------------
# Repeat-flood knob + tier-1 flood smoke with the extended identity
# ---------------------------------------------------------------------------

def _population(seed=5, users=2_000):
    return ZipfUserPopulation(seed, users=users, hist_len=4)


class TestRepeatFlood:
    def test_repeat_p_zero_is_bit_identical_to_legacy(self):
        a = FloodTrafficPlan(9, offered_qps=300.0, duration_s=1.0,
                             population=_population(), field_size=FIELD_SIZE,
                             feature_size=64)
        b = FloodTrafficPlan(9, offered_qps=300.0, duration_s=1.0,
                             population=_population(), field_size=FIELD_SIZE,
                             feature_size=64, repeat_p=0.0)
        assert a.fingerprint_data() == b.fingerprint_data()
        assert b.repeat_requests == 0

    def test_repeats_are_byte_identical_replays(self):
        plan = FloodTrafficPlan(9, offered_qps=300.0, duration_s=1.0,
                                population=_population(),
                                field_size=FIELD_SIZE, feature_size=64,
                                repeat_p=0.6)
        assert plan.repeat_requests > 0
        seen = {}
        replays = 0
        for r in plan.requests:
            fp = request_fingerprint(r.ids, r.vals)
            if r.user_id in seen and fp == seen[r.user_id]:
                replays += 1
            seen[r.user_id] = fp
        assert replays >= plan.repeat_requests

    def test_repeat_p_validation(self):
        with pytest.raises(ValueError, match="repeat_p"):
            FloodTrafficPlan(9, offered_qps=10.0, duration_s=0.5,
                             population=_population(), field_size=FIELD_SIZE,
                             feature_size=64, repeat_p=1.0)


# ---------------------------------------------------------------------------
# Production cache drill: bit-identity through the cascade, cache on vs off
# ---------------------------------------------------------------------------

class TestCacheDrill:
    def test_cache_drill_bit_identical_and_hits(self, tmp_path):
        """The drill serves ONE repeat-heavy plan through the cascade with
        the fast path off then on: the ON arm must actually hit the cache,
        and the audit fingerprint over every recommendation's ids AND
        probability bytes must match the OFF arm exactly."""
        r = production_drill.run_cache_drill(
            str(tmp_path), seed=7,
            params=dict(duration_s=1.0, offered_qps=60.0, users=2_000))
        assert r["bit_identical"], r
        assert r["off"]["fingerprint"] == r["on"]["fingerprint"] \
            == r["audit_fingerprint"]
        assert r["on"]["cache_hits"] > 0
        assert r["off"]["cache_hits"] == 0
        assert r["on"]["repeat_requests"] == r["off"]["repeat_requests"] > 0
        # The shadow of the fast path never changes WHAT is served, only
        # what it costs: same request count either way.
        assert r["on"]["requests"] == r["off"]["requests"]
