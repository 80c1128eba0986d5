"""Gated-deployment plane tests: seeded hash-split arm assignment, the
shadow-lane isolation contract (a raising / NaN-emitting / slow challenger
surfaces ONLY as typed counters while the primary lane stays bit-identical),
the canary kill-switch, guardrail gate evaluation and the promotion
controller's promote/rollback/quarantine state machine, the append-only
``pointer_history.jsonl`` audit sidecar and its crash-heal idempotence, the
per-arm health window, the impression log's experiment fields, the
challenger-poisoning chaos kinds, and the experimentation drill's
bit-replayable audit fingerprint. The full-parameter drill rides behind
``slow``."""

import os
import sys
import time

import numpy as np
import pytest

from deepfm_tpu.loop import arm_health
from deepfm_tpu.loop import impressions as impressions_lib
from deepfm_tpu.serve.engine import ServeFuture
from deepfm_tpu.serve.experiment import (ARM_CHALLENGER, ARM_CONTROL,
                                         ExperimentRouter, assign_arm)
from deepfm_tpu.train import promote as promote_lib
from deepfm_tpu.utils import export as export_lib
from deepfm_tpu.utils import faults

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scripts"))
import production_drill  # noqa: E402

pytestmark = pytest.mark.experiment


# --------------------------------------------------------------------------
# Hash-split arm assignment.
# --------------------------------------------------------------------------

class TestHashSplit:
    def test_deterministic_and_replayable(self):
        arms = [assign_arm(rid, seed=3, challenger_permille=250)
                for rid in range(2000)]
        again = [assign_arm(rid, seed=3, challenger_permille=250)
                 for rid in range(2000)]
        assert arms == again
        assert set(arms) == {ARM_CONTROL, ARM_CHALLENGER}

    def test_permille_proportions(self):
        n = 20_000
        for permille in (0, 50, 500, 1000):
            frac = sum(assign_arm(rid, seed=9, challenger_permille=permille)
                       for rid in range(n)) / n
            assert abs(frac - permille / 1000.0) < 0.02, (permille, frac)

    def test_seed_changes_split_membership(self):
        a = [assign_arm(rid, seed=1, challenger_permille=500)
             for rid in range(1000)]
        b = [assign_arm(rid, seed=2, challenger_permille=500)
             for rid in range(1000)]
        assert a != b


# --------------------------------------------------------------------------
# Stub engine: the router is jax-free, so isolation tests run against a
# synchronous stand-in with the engine's submit surface.
# --------------------------------------------------------------------------

class StubEngine:
    def __init__(self, fn, *, delay_s=0.0, raise_on_submit=None,
                 error_on_resolve=None):
        self.fn = fn
        self.delay_s = delay_s
        self.raise_on_submit = raise_on_submit
        self.error_on_resolve = error_on_resolve
        self.submits = 0

    def submit(self, ids, vals, trace_id=None, value="default"):
        if self.raise_on_submit is not None:
            raise self.raise_on_submit
        self.submits += 1
        fut = ServeFuture(np.asarray(ids), np.asarray(vals),
                          time.monotonic(), trace_id=trace_id, value=value)
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.error_on_resolve is not None:
            fut.set_error(self.error_on_resolve)
        else:
            fut.set_result(self.fn(np.asarray(ids), np.asarray(vals)), 0.1)
        return fut


def _ctl_fn(ids, vals):
    return (ids[:, 0] % 7).astype(np.float32) / 10.0


def _stream(n=60, rows=3, field=4, seed=0):
    rng = np.random.default_rng(seed)
    return [(rid,
             rng.integers(0, 64, (rows, field)).astype(np.int32),
             rng.normal(size=(rows, field)).astype(np.float32))
            for rid in range(n)]


def _drive(router, stream):
    """Primary results for the whole stream, in order."""
    return [router.predict(ids, vals, rid) for rid, ids, vals in stream]


class TestShadowIsolation:
    """The contract: NOTHING a challenger does reaches the primary lane —
    primary outputs are bit-identical to a single-arm run, and trouble
    surfaces only as typed counters."""

    def _baseline(self, stream):
        return [_ctl_fn(ids, vals) for _, ids, vals in stream]

    def _assert_primary_bitexact(self, got, stream):
        for out, want in zip(got, self._baseline(stream)):
            assert out.dtype == want.dtype
            assert np.array_equal(out, want)

    def test_raising_challenger_is_typed_rejection(self):
        stream = _stream()
        r = ExperimentRouter(
            StubEngine(_ctl_fn),
            StubEngine(_ctl_fn, raise_on_submit=RuntimeError("dead arm")),
            mode="shadow", seed=3, challenger_permille=500)
        self._assert_primary_bitexact(_drive(r, stream), stream)
        expected = sum(1 for rid, _, _ in stream
                       if r.assign(rid) == ARM_CHALLENGER)
        assert expected > 0
        assert r.shadow_submit_rejected == expected
        assert r.shadow_submitted == 0 and r.shadow_errors == 0

    def test_nan_challenger_is_typed_counter(self):
        stream = _stream()
        nan_fn = lambda ids, vals: np.full(  # noqa: E731
            ids.shape[0], np.nan, np.float32)
        r = ExperimentRouter(StubEngine(_ctl_fn), StubEngine(nan_fn),
                             mode="shadow", seed=3, challenger_permille=500)
        got = _drive(r, stream)
        self._assert_primary_bitexact(got, stream)
        assert all(np.all(np.isfinite(p)) for p in got)
        expected = sum(1 for rid, _, _ in stream
                       if r.assign(rid) == ARM_CHALLENGER)
        assert r.shadow_nonfinite == expected > 0
        assert r.shadow_completed == expected

    def test_slow_challenger_is_typed_slo_miss(self):
        stream = _stream(n=20)
        r = ExperimentRouter(
            StubEngine(_ctl_fn), StubEngine(_ctl_fn, delay_s=0.01),
            mode="shadow", seed=3, challenger_permille=500,
            shadow_slo_ms=1.0)
        self._assert_primary_bitexact(_drive(r, stream), stream)
        expected = sum(1 for rid, _, _ in stream
                       if r.assign(rid) == ARM_CHALLENGER)
        assert r.shadow_slo_misses == expected > 0
        assert r.shadow_errors == 0

    def test_erroring_challenger_future_is_typed_error(self):
        stream = _stream()
        r = ExperimentRouter(
            StubEngine(_ctl_fn),
            StubEngine(_ctl_fn, error_on_resolve=ValueError("bad flush")),
            mode="shadow", seed=3, challenger_permille=500)
        self._assert_primary_bitexact(_drive(r, stream), stream)
        assert r.shadow_errors > 0 and r.shadow_nonfinite == 0

    def test_shadow_hook_observes_challenger_output(self):
        seen = []
        stream = _stream(n=30)
        r = ExperimentRouter(
            StubEngine(_ctl_fn),
            StubEngine(lambda ids, vals: np.full(ids.shape[0], 0.25,
                                                 np.float32)),
            mode="shadow", seed=3, challenger_permille=1000,
            on_shadow_result=lambda rid, probs, ms: seen.append(
                (rid, probs.copy())))
        _drive(r, stream)
        assert len(seen) == len(stream)
        assert [rid for rid, _ in seen] == [rid for rid, _, _ in stream]
        assert all(np.all(p == np.float32(0.25)) for _, p in seen)


class TestRouterModes:
    def test_off_and_shadow_always_serve_control(self):
        for mode in ("off", "shadow"):
            ctl, ch = StubEngine(_ctl_fn), StubEngine(_ctl_fn)
            r = ExperimentRouter(ctl, ch, mode=mode, seed=3,
                                 challenger_permille=1000)
            futs = [r.submit(ids, vals, rid) for rid, ids, vals in
                    _stream(n=10)]
            assert all(f.arm == ARM_CONTROL for f in futs)
            assert ctl.submits == 10

    def test_ab_serves_assigned_arm(self):
        ctl, ch = StubEngine(_ctl_fn), StubEngine(_ctl_fn)
        r = ExperimentRouter(ctl, ch, mode="ab", seed=3,
                             challenger_permille=500)
        stream = _stream(n=40)
        futs = [r.submit(ids, vals, rid) for rid, ids, vals in stream]
        want = [r.assign(rid) for rid, _, _ in stream]
        assert [f.arm for f in futs] == want
        assert ch.submits == sum(want) > 0
        assert ctl.submits == len(stream) - ch.submits
        assert r.requests_by_arm[ARM_CHALLENGER] == ch.submits

    def test_mode_and_permille_validated(self):
        with pytest.raises(ValueError):
            ExperimentRouter(StubEngine(_ctl_fn), mode="bogus")
        with pytest.raises(ValueError):
            ExperimentRouter(StubEngine(_ctl_fn), StubEngine(_ctl_fn),
                             mode="ab", challenger_permille=1001)
        with pytest.raises(ValueError):
            ExperimentRouter(StubEngine(_ctl_fn), mode="ab")  # no challenger


class TestKillSwitch:
    def test_canary_kill_collapses_to_control_and_revive_restores(self):
        ctl, ch = StubEngine(_ctl_fn), StubEngine(_ctl_fn)
        r = ExperimentRouter(ctl, ch, mode="canary", seed=3,
                             challenger_permille=1000)
        assert r.submit(*_stream(n=1)[0][1:], 0).arm == ARM_CHALLENGER
        r.kill("2: nonfinite_predictions")
        assert r.killed and r.kills == 1
        assert r.kill_reason == "2: nonfinite_predictions"
        futs = [r.submit(ids, vals, rid) for rid, ids, vals in _stream(n=8)]
        assert all(f.arm == ARM_CONTROL for f in futs)
        assert ch.submits == 1   # nothing after the kill
        r.revive()
        assert not r.killed
        assert r.submit(*_stream(n=1)[0][1:], 0).arm == ARM_CHALLENGER

    def test_shadow_kill_stops_duplication(self):
        ctl, ch = StubEngine(_ctl_fn), StubEngine(_ctl_fn)
        r = ExperimentRouter(ctl, ch, mode="shadow", seed=3,
                             challenger_permille=1000)
        _drive(r, _stream(n=5))
        assert r.shadow_submitted == 5
        r.kill("breach")
        _drive(r, _stream(n=5))
        assert r.shadow_submitted == 5
        assert ctl.submits == 10   # primary lane unaffected


# --------------------------------------------------------------------------
# Guardrail gates (pure function) + promotion controller state machine.
# --------------------------------------------------------------------------

HEALTHY = dict(arm=1, n=500, auc=0.74, p99_latency_ms=5.0, nonfinite=0,
               mean_pred=0.5, observed_ctr=0.5, calibration_err=0.0)
CONTROL = dict(HEALTHY, arm=0, auc=0.73)

GATES = dict(min_samples=10, min_auc_delta=-0.02, max_p99_ratio=3.0,
             max_p99_ms=100.0, max_nonfinite=0, max_calibration_err=0.2,
             max_candidate_age_s=600.0, windows_required=2)


def _gates(**kw):
    return promote_lib.GateConfig(**dict(GATES, **kw))


class TestGateEvaluation:
    def test_healthy_window_passes(self):
        passed, breaches, holds = promote_lib.evaluate_gates(
            HEALTHY, CONTROL, _gates(), candidate_age_s=10.0)
        assert passed and not breaches and not holds

    def test_each_breach_reason_is_typed(self):
        cases = [
            (dict(HEALTHY, nonfinite=1), promote_lib.REASON_NONFINITE),
            (dict(HEALTHY, auc=0.60), promote_lib.REASON_AUC),
            (dict(HEALTHY, p99_latency_ms=5 * CONTROL["p99_latency_ms"]
                  * 3.0), promote_lib.REASON_LATENCY),
            (dict(HEALTHY, calibration_err=0.3),
             promote_lib.REASON_CALIBRATION),
        ]
        for health, reason in cases:
            passed, breaches, _ = promote_lib.evaluate_gates(
                health, CONTROL, _gates(), candidate_age_s=10.0)
            assert not passed and breaches == [reason], (health, breaches)

    def test_absolute_p99_ceiling_is_independent_of_ratio(self):
        """The ceiling fires even when the ratio gate is parked wide open
        (the drill's configuration — ratios are timing noise on a 1-core
        host, the ceiling is detection-by-construction)."""
        slow = dict(HEALTHY, p99_latency_ms=250.0)
        passed, breaches, _ = promote_lib.evaluate_gates(
            slow, CONTROL, _gates(max_p99_ratio=1e6, max_p99_ms=150.0),
            candidate_age_s=10.0)
        assert breaches == [promote_lib.REASON_LATENCY]
        # And 0 disables the ceiling entirely.
        passed, breaches, _ = promote_lib.evaluate_gates(
            slow, CONTROL, _gates(max_p99_ratio=1e6, max_p99_ms=0.0),
            candidate_age_s=10.0)
        assert passed, breaches

    def test_staleness_breaches_on_age_alone(self):
        passed, breaches, _ = promote_lib.evaluate_gates(
            HEALTHY, CONTROL, _gates(), candidate_age_s=601.0)
        assert breaches == [promote_lib.REASON_STALE]
        # ... even on an EMPTY window: a frozen candidate that stopped
        # refreshing must not hide behind a min_samples hold.
        passed, breaches, holds = promote_lib.evaluate_gates(
            {}, {}, _gates(), candidate_age_s=601.0)
        assert promote_lib.REASON_STALE in breaches

    def test_thin_window_is_hold_not_breach(self):
        passed, breaches, holds = promote_lib.evaluate_gates(
            dict(HEALTHY, n=3), CONTROL, _gates(), candidate_age_s=10.0)
        assert not passed and not breaches
        assert holds == [promote_lib.REASON_SAMPLES]

    def test_gate_config_validation(self):
        with pytest.raises(ValueError):
            _gates(max_p99_ms=-1.0)
        with pytest.raises(ValueError):
            _gates(min_samples=0)
        with pytest.raises(ValueError):
            _gates(windows_required=0)


@pytest.fixture
def publish_dir(tmp_path):
    d = str(tmp_path / "publish")
    for version in ("1", "2"):   # read_latest refuses dangling pointers
        os.makedirs(os.path.join(d, version))
    export_lib.write_latest(d, "1")
    return d


class TestPromotionController:
    def test_promotes_after_required_windows(self, publish_dir):
        ctl = promote_lib.PromotionController(publish_dir, gates=_gates())
        assert ctl.offer("2")
        d1 = ctl.observe(HEALTHY, CONTROL)
        assert d1.action == "pass" and d1.version == "2"
        assert os.path.basename(export_lib.read_latest(publish_dir)) == "1"
        d2 = ctl.observe(HEALTHY, CONTROL)
        assert d2.action == "promote"
        assert os.path.basename(export_lib.read_latest(publish_dir)) == "2"
        assert ctl.stable_version == "2" and ctl.candidate is None
        actors = [e["actor"] for e in ctl.history()]
        assert actors[-1] == "promote"

    def test_breach_rolls_back_and_kill_switch_fires_first(
            self, publish_dir):
        calls = []

        def on_rollback(version, reason):
            # The pointer must NOT have moved yet when the hook fires:
            # traffic stops reaching the bad arm before the audit write.
            calls.append((version, reason, os.path.basename(
                export_lib.read_latest(publish_dir))))

        ctl = promote_lib.PromotionController(
            publish_dir, gates=_gates(), on_rollback=on_rollback)
        ctl.offer("2")
        ctl.observe(HEALTHY, CONTROL)   # one passing window, then poison
        d = ctl.observe(dict(HEALTHY, nonfinite=4), CONTROL)
        assert d.action == "rollback"
        assert d.reasons == (promote_lib.REASON_NONFINITE,)
        assert calls == [("2", promote_lib.REASON_NONFINITE, "1")]
        assert os.path.basename(export_lib.read_latest(publish_dir)) == "1"
        assert ctl.rollbacks == 1
        assert ctl.breaches_by_reason == {promote_lib.REASON_NONFINITE: 1}
        # A rollback resets the passing streak: the next offer starts over.
        assert ctl.passing_windows == 0

    def test_second_failure_quarantines_and_refuses_reoffer(
            self, publish_dir):
        ctl = promote_lib.PromotionController(publish_dir, gates=_gates())
        for k in range(promote_lib.QUARANTINE_FAILURES):
            assert ctl.offer("2")
            d = ctl.observe(dict(HEALTHY, calibration_err=0.5), CONTROL)
        assert d.action == "quarantine"
        assert "2" in ctl.quarantined
        assert not ctl.offer("2") and ctl.offers_refused == 1
        # History carries the audit trail: rollback line(s) + quarantine.
        actors = [e["actor"] for e in ctl.history()]
        assert actors.count("quarantine") == 1
        assert ctl.stats()["rollbacks"] == 2
        assert ctl.stats()["quarantines"] == 1

    def test_offering_stable_version_refused(self, publish_dir):
        ctl = promote_lib.PromotionController(publish_dir, gates=_gates())
        assert not ctl.offer("1")
        assert ctl.observe(HEALTHY, CONTROL).action == "hold"

    @pytest.mark.parametrize("kind,health,reason,age_s", [
        ("nan", dict(HEALTHY, nonfinite=7), promote_lib.REASON_NONFINITE,
         None),
        ("latency", dict(HEALTHY, p99_latency_ms=5000.0),
         promote_lib.REASON_LATENCY, None),
        ("calibration", dict(HEALTHY, mean_pred=0.9, calibration_err=0.4),
         promote_lib.REASON_CALIBRATION, None),
        ("stale", HEALTHY, promote_lib.REASON_STALE, 7200.0),
    ])
    def test_each_poison_rolls_back_in_one_window(
            self, publish_dir, kind, health, reason, age_s):
        """Gate evaluation is a pure function of the window, so every
        poison kind must flip the decision to ``rollback`` in the FIRST
        health window the controller observes, with its typed reason, and
        leave ``LATEST`` where it was: more than one window means a
        guardrail went soft."""
        ctl = promote_lib.PromotionController(publish_dir, gates=_gates())
        assert ctl.offer("2", now_s=0.0 if age_s is not None else None)
        kw = {"now_s": age_s} if age_s is not None else {}
        d = ctl.observe(health, CONTROL, **kw)
        assert d.action == "rollback", (kind, d)
        assert reason in d.reasons, (kind, d.reasons)
        assert ctl.rollbacks == 1 and ctl.passing_windows == 0
        assert os.path.basename(export_lib.read_latest(publish_dir)) == "1"


# --------------------------------------------------------------------------
# Pointer-history sidecar: append-then-move protocol, crash-heal
# idempotence through the publish-crash seam.
# --------------------------------------------------------------------------

class TestPointerHistory:
    def test_append_order_and_fields(self, tmp_path):
        d = str(tmp_path)
        export_lib.append_pointer_event(d, "1", "publish", wall_time=5.0)
        export_lib.append_pointer_event(d, "2", "promote",
                                        "passed 2 windows", wall_time=6.0)
        hist = export_lib.pointer_history(d)
        assert [(e["version"], e["actor"]) for e in hist] == \
            [("1", "publish"), ("2", "promote")]
        assert hist[0]["wall_time"] == 5.0
        assert hist[1]["reason"] == "passed 2 windows"
        # The reader rides on read_latest: one surface for pointer +
        # provenance.
        assert export_lib.read_latest.history(d) == hist

    def test_tail_dedupe_is_exact_triple_match(self, tmp_path):
        d = str(tmp_path)
        export_lib.append_pointer_event(d, "1", "publish")
        export_lib.append_pointer_event(d, "1", "publish")   # replay
        assert len(export_lib.pointer_history(d)) == 1
        export_lib.append_pointer_event(d, "1", "rollback", "2: breach")
        export_lib.append_pointer_event(d, "1", "publish")   # NOT the tail
        assert [e["actor"] for e in export_lib.pointer_history(d)] == \
            ["publish", "rollback", "publish"]

    def test_crash_between_history_and_pointer_heals(self, tmp_path):
        """Append-then-move: a crash after the history append but before
        the LATEST write leaves a truthful audit line and a stale pointer;
        the retried publish re-runs both steps and the tail-dedupe absorbs
        the duplicate append — exactly one line, pointer moved."""
        d = str(tmp_path)
        for version in ("1", "2"):
            os.makedirs(os.path.join(d, version))
        export_lib.write_latest(d, "1")

        def publish(version):
            export_lib.append_pointer_event(d, version, "publish")
            faults.check_publish_crash("after_history_before_latest")
            export_lib.write_latest(d, version)

        faults.set_publish_crash("after_history_before_latest")
        with pytest.raises(faults.InjectedFault):
            publish("2")
        assert os.path.basename(export_lib.read_latest(d)) == "1"
        assert len(export_lib.pointer_history(d)) == 1
        publish("2")   # the heal
        assert os.path.basename(export_lib.read_latest(d)) == "2"
        hist = export_lib.pointer_history(d)
        assert len(hist) == 1 and hist[0]["version"] == "2"

    def test_torn_tail_dropped(self, tmp_path):
        d = str(tmp_path)
        export_lib.append_pointer_event(d, "1", "publish")
        with open(os.path.join(d, export_lib.POINTER_HISTORY_FILE),
                  "a") as f:
            f.write('{"version": "2", "actor": "pro')   # crash mid-append
        hist = export_lib.pointer_history(d)
        assert len(hist) == 1 and hist[0]["version"] == "1"


# --------------------------------------------------------------------------
# Per-arm health window + the impression log's experiment fields.
# --------------------------------------------------------------------------

class TestArmHealth:
    def test_known_values(self):
        samples = [
            (0, 1.0, 0.9, 10.0), (0, 0.0, 0.1, 20.0),
            (0, 1.0, 0.8, 30.0), (0, 0.0, 0.2, 40.0),
            (1, 1.0, 0.3, 5.0), (1, 0.0, 0.7, 6.0),
        ]
        h = arm_health(samples)
        assert set(h) == {0, 1}
        ctl = h[0]
        assert ctl["n"] == 4 and ctl["auc"] == 1.0
        assert ctl["nonfinite"] == 0
        assert ctl["mean_pred"] == 0.5 and ctl["observed_ctr"] == 0.5
        assert ctl["calibration_err"] == 0.0
        assert ctl["p99_latency_ms"] == pytest.approx(40.0, abs=1.0)
        assert h[1]["auc"] == 0.0   # perfectly anti-ranked challenger

    def test_nonfinite_rows_counted_but_excluded(self):
        h = arm_health([(1, 1.0, 0.9, 1.0), (1, 0.0, 0.1, 1.0),
                        (1, 1.0, float("nan"), 1.0)])
        a = h[1]
        assert a["n"] == 3 and a["nonfinite"] == 1
        assert a["auc"] == 1.0            # the NaN row poisons no other gate
        assert a["mean_pred"] == 0.5

    def test_one_class_window_has_no_auc(self):
        h = arm_health([(0, 1.0, 0.6, 1.0), (0, 1.0, 0.7, 2.0)])
        assert h[0]["auc"] is None
        assert h[0]["observed_ctr"] == 1.0

    def test_empty_and_deterministic(self):
        assert arm_health([]) == {}
        samples = [(k % 2, float(k % 3 == 0), 0.1 * (k % 10), float(k))
                   for k in range(50)]
        assert arm_health(samples) == arm_health(list(samples))


class TestImpressionExperimentFields:
    def test_arm_and_pred_roundtrip_float32_exact(self):
        ids = np.arange(4, dtype=np.int64)
        vals = np.ones(4, np.float32)
        buf = impressions_lib.encode_impression(
            7, 1.5, ids, vals, arm=ARM_CHALLENGER, pred=0.1)
        arm, pred = impressions_lib.read_experiment(buf)
        assert arm == ARM_CHALLENGER
        assert pred == float(np.float32(0.1))   # the exact served float32
        # Unstamped records read back as None (pre-experiment writers).
        arm, pred = impressions_lib.read_experiment(
            impressions_lib.encode_impression(8, 1.5, ids, vals))
        assert arm is None and pred is None

    def test_logger_stamps_experiment_fields(self, tmp_path):
        from deepfm_tpu.data import tfrecord
        logger = impressions_lib.ImpressionLogger(str(tmp_path))
        ids = np.arange(4, dtype=np.int64)
        logger.log(11, ids, np.ones(4, np.float32), 2.0,
                   arm=ARM_CONTROL, pred=0.75)
        path = logger.close()
        (rec,) = list(tfrecord.iter_records(path))
        assert impressions_lib.read_experiment(rec) == (0, 0.75)
        iid, _, got_ids, _ = impressions_lib.decode_impression(rec)
        assert iid == 11 and np.array_equal(got_ids, ids)


# --------------------------------------------------------------------------
# Challenger-poisoning chaos kinds.
# --------------------------------------------------------------------------

class TestChallengerChaos:
    def test_new_kinds_are_driver_kinds(self):
        for kind in ("challenger_nan", "challenger_stale",
                     "challenger_slow"):
            assert kind in faults.ChaosSchedule.DRIVER_KINDS

    def test_generate_carries_kind_params_and_replays(self):
        kw = dict(horizon_s=10.0, challenger_nan_events=1,
                  challenger_nan_batches=4, challenger_slow_events=1,
                  challenger_slow_ms=250.0, challenger_stale_events=1)
        sched = faults.ChaosSchedule.generate(7, **kw)
        kinds = {e.kind: e for e in sched.events}
        assert len(kinds["challenger_nan"].get("batches")) == 4
        assert kinds["challenger_slow"].get("delay_ms") == 250.0
        assert "challenger_stale" in kinds
        assert sched.fingerprint() == \
            faults.ChaosSchedule.generate(7, **kw).fingerprint()

    def test_old_schedules_bit_identical_without_challenger_events(self):
        """Adding the challenger kinds must not perturb pre-existing
        schedules: the new rng draws happen strictly AFTER the old kinds'
        draws, so a schedule with zero challenger events is byte-for-byte
        what it was before the feature existed."""
        sched = faults.ChaosSchedule.generate(
            11, horizon_s=4.0, executor_slow_events=1,
            executor_slow_ms=40.0, executor_slow_calls=25)
        assert not any(e.kind.startswith("challenger")
                       for e in sched.events)
        assert sched.fingerprint() == faults.ChaosSchedule.generate(
            11, horizon_s=4.0, executor_slow_events=1,
            executor_slow_ms=40.0, executor_slow_calls=25).fingerprint()

    def test_nan_plan_seam_roundtrip(self):
        faults.set_nan_plan([2, 5], value=float("nan"))
        plan = faults.take_nan_plan()
        assert plan is not None and sorted(plan["batches"]) == [2, 5]
        assert faults.take_nan_plan() is None   # one-shot


# --------------------------------------------------------------------------
# The experimentation drill: healthy challenger shadow -> canary ->
# promoted; poisoned challengers detected, rolled back, quarantined — with
# zero primary-lane loss and a bit-replayable audit fingerprint.
# --------------------------------------------------------------------------

class TestExperimentDrill:
    def test_smoke_drill_end_to_end_and_bit_replayable(self, tmp_path):
        reports = [
            production_drill.run_experiment_drill(
                str(tmp_path / f"run{k}"), seed=7,
                params=production_drill.EXPERIMENT_SMOKE)
            for k in range(2)
        ]
        r = reports[0]
        assert r["ok"]
        # Zero primary-lane loss, throughout every phase.
        assert r["primary"]["failed"] == 0
        assert r["primary"]["nonfinite"] == 0
        # The healthy challenger was promoted; LATEST points at it.
        assert r["promotion"]["promotions"] == 1
        assert r["stable_version"] == "1"
        # Every poisoned challenger: detected, rolled back, quarantined,
        # with its typed reason (the drill itself also asserts the
        # re-offer of a quarantined version is refused).
        assert {s["kind"] for s in r["scenarios"]} == \
            {"challenger_nan", "challenger_slow", "challenger_stale"}
        for s in r["scenarios"]:
            actions = [d[0] for d in s["decisions"]]
            assert actions == ["rollback", "quarantine"], s
            assert all(s["expected_reason"] in d[2]
                       for d in s["decisions"]), s
        # Online per-arm health == pure offline recomputation, bit-exact.
        assert r["arm_health_offline_match"]
        # Bit-replayable: same seed => identical audit fingerprint.
        assert reports[0]["audit_fingerprint"] == \
            reports[1]["audit_fingerprint"]

    def test_different_seed_different_fingerprint(self, tmp_path):
        r7 = production_drill.run_experiment_drill(
            str(tmp_path / "a"), seed=7,
            params=production_drill.EXPERIMENT_SMOKE)
        r9 = production_drill.run_experiment_drill(
            str(tmp_path / "b"), seed=9,
            params=production_drill.EXPERIMENT_SMOKE)
        assert r7["audit_fingerprint"] != r9["audit_fingerprint"]

    @pytest.mark.slow
    def test_full_params_drill(self, tmp_path):
        r = production_drill.run_experiment_drill(str(tmp_path / "full"),
                                                  seed=7)
        assert r["ok"] and r["arm_health_offline_match"]
        assert r["primary"]["failed"] == 0
