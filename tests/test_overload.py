"""Flood-harness + degradation-drill tests: the million-user Zipf traffic
plan (determinism, skew, per-user history continuity), the count-based
``executor_slow`` chaos seam, the overload drill's bit-replayable audit
fingerprint, and the serving accounting identity on a flooded fleet's own
counters."""

import os
import sys
import time

import numpy as np
import pytest

from deepfm_tpu.loop.traffic import FloodTrafficPlan, ZipfUserPopulation
from deepfm_tpu.serve import (AdmissionShed, ReplicatedEngine, ServeTimeout,
                              ServerOverloaded, ServingEngine)
from deepfm_tpu.serve.admission import DEGRADE_RUNGS, VALUE_CLASSES
from deepfm_tpu.utils import faults

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scripts"))
import production_drill  # noqa: E402

pytestmark = pytest.mark.overload


# --------------------------------------------------------------------------
# Zipf flood traffic plan.
# --------------------------------------------------------------------------

def _plan(seed=5, users=10_000, qps=400.0, secs=1.0, pop=None):
    pop = pop or ZipfUserPopulation(seed, users=users, hist_len=4)
    return FloodTrafficPlan(seed + 1, offered_qps=qps, duration_s=secs,
                            population=pop, field_size=3, feature_size=64)


class TestFloodTraffic:
    def test_same_seed_bit_identical(self):
        a, b = _plan(), _plan()
        assert a.fingerprint_data() == b.fingerprint_data()
        assert len(a.requests) > 100

    def test_different_seed_differs(self):
        assert _plan(seed=5).fingerprint_data() != \
            _plan(seed=6).fingerprint_data()

    def test_zipf_head_users_dominate(self):
        """rank^-q activity: the top 1% of a 100k-user population must own
        the majority of traffic — the skew DIN-style history models feed
        on, and what makes sticky affinity worth having."""
        pop = ZipfUserPopulation(0, users=100_000)
        rng = np.random.default_rng(0)
        users = pop.sample_users(rng, 20_000)
        assert users.min() >= 0 and users.max() < 100_000
        head_share = float(np.mean(users < 1_000))
        assert head_share > 0.5, f"head share only {head_share:.2f}"
        # And the single hottest user is user 0 by construction.
        ids, counts = np.unique(users, return_counts=True)
        assert ids[np.argmax(counts)] == 0

    def test_history_continuity_snapshot_before_click(self):
        """A user's Nth request carries the history of their first N-1
        clicks (snapshot taken BEFORE the request's own click lands), and
        head users accumulate toward a full mask."""
        pop = ZipfUserPopulation(1, users=50, hist_len=4)
        plan = _plan(seed=1, qps=300.0, pop=pop)
        seen = {}
        for r in plan.requests:
            expect = min(seen.get(r.user_id, 0), 4)
            assert int(r.hist_mask.sum()) == expect, (r.user_id, expect)
            item = int(r.ids[0, 0])
            if expect:
                assert r.hist_ids[expect - 1] == seen[(r.user_id, "last")]
            seen[r.user_id] = seen.get(r.user_id, 0) + 1
            seen[(r.user_id, "last")] = item
        assert any(int(r.hist_mask.sum()) == 4 for r in plan.requests)

    def test_million_user_population_is_lazy(self):
        """1M users must be cheap: one ~8MB cumsum, histories only for
        users traffic actually touched."""
        t0 = time.monotonic()
        pop = ZipfUserPopulation(2, users=1_000_000)
        assert time.monotonic() - t0 < 5.0
        assert pop.touched_users == 0
        plan = _plan(seed=2, qps=300.0, pop=pop)
        assert 0 < pop.touched_users <= len(plan.requests)

    def test_value_mix_uses_admission_classes(self):
        plan = _plan(qps=1000.0)
        got = {r.value for r in plan.requests}
        assert got == set(VALUE_CLASSES)
        # Mix roughly matches the seeded weights (normal is the mode).
        counts = {c: sum(r.value == c for r in plan.requests) for c in got}
        assert max(counts, key=counts.get) == "normal"

    def test_validation(self):
        with pytest.raises(ValueError):
            ZipfUserPopulation(0, users=0)
        with pytest.raises(ValueError):
            _plan(qps=0.0)


# --------------------------------------------------------------------------
# executor_slow chaos seam.
# --------------------------------------------------------------------------

class TestExecutorSlowChaos:
    def teardown_method(self):
        faults.set_executor_slow(0.0, 0)

    def test_count_based_consume(self):
        faults.set_executor_slow(0.5, 2)
        assert faults.executor_slow_remaining() == 2
        assert faults.executor_slow_delay() == 0.5
        assert faults.executor_slow_delay() == 0.5
        assert faults.executor_slow_delay() == 0.0   # exhausted
        assert faults.executor_slow_remaining() == 0

    def test_disarm(self):
        faults.set_executor_slow(0.5, 10)
        faults.set_executor_slow(0.0, 0)
        assert faults.executor_slow_delay() == 0.0

    def test_schedule_generates_driver_side_event(self):
        sched = faults.ChaosSchedule.generate(
            11, horizon_s=4.0, executor_slow_events=1,
            executor_slow_ms=40.0, executor_slow_calls=25)
        evs = [e for e in sched.events if e.kind == "executor_slow"]
        assert len(evs) == 1
        ev = evs[0]
        # Early in the event window so the drill can observe RECOVERY too.
        assert 0.2 * 4.0 <= ev.at_s <= 0.5 * 4.0
        assert ev.get("delay_ms") == 40.0 and ev.get("calls") == 25
        assert "executor_slow" in faults.ChaosSchedule.DRIVER_KINDS
        # Same seed -> same schedule (the replay contract).
        again = faults.ChaosSchedule.generate(
            11, horizon_s=4.0, executor_slow_events=1,
            executor_slow_ms=40.0, executor_slow_calls=25)
        assert again.fingerprint() == sched.fingerprint()


# --------------------------------------------------------------------------
# Overload drill: ladder engages under executor_slow, recovers, and the
# audit fingerprint is bit-replayable.
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cascade_artifact(tmp_path_factory):
    """ONE trained cascade artifact shared by every drill run here."""
    pub = tmp_path_factory.mktemp("overload_publish")
    os.environ["DEEPFM_TPU_SKIP_TF_EXPORT"] = "1"
    try:
        production_drill.build_cascade_artifact(str(pub))
    finally:
        os.environ.pop("DEEPFM_TPU_SKIP_TF_EXPORT", None)
    return str(pub)


class TestOverloadDrill:
    def test_ladder_engages_recovers_and_replays(self, cascade_artifact,
                                                 tmp_path):
        reports = [
            production_drill.run_overload_drill(
                str(tmp_path / f"run{k}"), seed=7,
                publish_dir=cascade_artifact)
            for k in range(2)
        ]
        r = reports[0]
        # The run_overload_drill asserts already gated engagement/recovery;
        # re-check the report surface the flood sweep embeds.
        assert r["ladder_engaged"] and r["recovered"]
        assert r["accounting_ok"]
        assert r["counters"]["failed"] == 0
        assert sum(r["counters"].values()) == r["traffic"]["requests"]
        assert r["max_rung"] >= 1
        assert r["rung_names"] == list(DEGRADE_RUNGS)
        assert r["transition_log"][0][:2] == [0, 1] or \
            r["transition_log"][0][1] >= 1
        # Ladder came back down: the last transition lands on rung 0.
        assert r["transition_log"][-1][1] == 0
        assert r["traffic"]["users"] == 1_000_000
        assert r["degrade_transitions"] == len(r["transition_log"])
        # Bit-replayable: same seed => identical audit fingerprint.
        assert reports[0]["audit_fingerprint"] == \
            reports[1]["audit_fingerprint"]
        # The slow seam never leaks out of the drill.
        assert faults.executor_slow_remaining() == 0

    def test_different_seed_different_fingerprint(self, cascade_artifact,
                                                  tmp_path):
        r7 = production_drill.run_overload_drill(
            str(tmp_path / "a"), seed=7, publish_dir=cascade_artifact)
        r8 = production_drill.run_overload_drill(
            str(tmp_path / "b"), seed=8, publish_dir=cascade_artifact)
        assert r7["audit_fingerprint"] != r8["audit_fingerprint"]


# --------------------------------------------------------------------------
# The serving accounting identity, on the engines' own counters.
# --------------------------------------------------------------------------

def _replay_open_loop(fleet, plan, resolve_timeout_s=30.0):
    """Submit every planned request at its planned time whatever has
    completed (past saturation the driver does not throttle itself), then
    resolve every admitted future: each request ends as exactly one of the
    tallied outcomes."""
    tally = dict(ok=0, coalesced=0, cache_hits=0, sheds=0, overloads=0,
                 timeouts=0, failed=0)
    futs = []
    t0 = time.monotonic()
    for r in plan.requests:
        wait = t0 + r.t_s - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        try:
            futs.append(fleet.submit(r.ids, r.vals, affinity=r.user_id,
                                     value=r.value))
        except AdmissionShed:
            tally["sheds"] += 1
        except ServerOverloaded:
            tally["overloads"] += 1
    deadline = time.monotonic() + resolve_timeout_s
    for fut in futs:
        try:
            fut.result(timeout=max(0.05, deadline - time.monotonic()))
        except ServeTimeout:
            tally["timeouts"] += 1
            fut.cancel()
            continue
        except Exception:  # noqa: BLE001 — typed into the identity
            tally["failed"] += 1
            continue
        tally["ok"] += 1
        tally["coalesced"] += bool(getattr(fut, "coalesced", False))
        tally["cache_hits"] += bool(getattr(fut, "cache_hit", False))
    return tally


def _slow_first_col_predict(feat_ids, feat_vals):
    time.sleep(0.02)   # 8 rows a flush -> ~400 rows/s a replica
    return feat_ids[:, 0].astype(np.float32) * 0.001 + feat_vals[:, 0] * 0.1


class TestServingAccountingIdentity:
    """offered == completed + coalesced + sheds + overloads + timeouts +
    failed, read from the fleet's own ``ServingStats`` — a request the
    engines lost (or counted twice) breaks the sum. The flood is offered
    past the fleet's service rate so that the refusal terms are not zero."""

    @pytest.mark.parametrize("gate,fast_path", [
        (False, False), (True, False), (True, True)],
        ids=["queue_full", "admission_gate", "gate_cache_coalesce"])
    def test_flood_reconciles_with_engine_counters(self, gate, fast_path):
        replicas = 2
        plan = FloodTrafficPlan(
            9, offered_qps=1500.0, duration_s=0.6,
            population=ZipfUserPopulation(3, users=2_000, hist_len=4),
            field_size=3, feature_size=64,
            repeat_p=0.6 if fast_path else 0.0)
        engine_kw = dict(max_batch=8, max_delay_ms=1, queue_rows=32)
        if gate:
            engine_kw.update(admission_kw=dict(shed_watermark=16))
        if fast_path:
            engine_kw.update(cache_rows=256, coalesce=True)
        fleet = ReplicatedEngine(
            [ServingEngine(_slow_first_col_predict, **engine_kw)
             for _ in range(replicas)])
        try:
            got = _replay_open_loop(fleet, plan)
        finally:
            fleet.close(timeout=30)
        s = fleet.summary()
        assert got["timeouts"] == 0 and got["failed"] == 0, got
        # Past saturation: the gate sheds by value class before the queue
        # fills; without it the full queue refuses.
        assert got["sheds" if gate else "overloads"] > 0, got
        assert gate or got["sheds"] == 0, got
        # The engines' view. A cache hit and a coalesced join are answered
        # requests (serving_requests counts them); a fleet-level refusal is
        # one refusal on EVERY replica, and a refusal followed by a spill
        # that was admitted is one refusal and one spill.
        assert s["serving_requests"] == got["ok"], (s, got)
        assert s["serving_failed"] == 0
        assert s["serving_coalesced"] == got["coalesced"]
        assert s["serving_cache_hits"] == got["cache_hits"]
        assert (s["serving_sheds"] + s["serving_overloads"]
                == replicas * (got["sheds"] + got["overloads"])
                + fleet.spills), (s, got, fleet.spills)
        completed = s["serving_requests"] - s["serving_coalesced"]
        refused = (s["serving_sheds"] + s["serving_overloads"]
                   - fleet.spills) // replicas
        assert len(plan.requests) == (
            completed + s["serving_coalesced"] + refused
            + s["serving_failed"] + got["timeouts"])
        assert sum(s["serving_sheds_by_class"].values()) == s["serving_sheds"]
        if fast_path:
            assert got["cache_hits"] > 0, got
        else:
            assert got["cache_hits"] == 0 and got["coalesced"] == 0, got
            assert s["serving_rows"] == got["ok"]   # one row a request
