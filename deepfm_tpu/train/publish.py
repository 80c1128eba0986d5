"""Atomic hot model publishing for the online trainer.

The reference ships fresh models by notebook-driven redeploys; an online
trainer must instead publish servable artifacts *mid-training* without ever
exposing a half-written directory. :class:`Publisher` is a fit-loop hook:

  * **Cadence** — ``--publish_every_steps`` uses boundary-crossing
    arithmetic (like ``CheckpointManager.should_save``), so the publish
    *steps* are a deterministic function of the step sequence alone — a
    resumed run republishes the same versions an uninterrupted run would
    (the drill's bit-identity check depends on this). ``--publish_every_secs``
    adds a wall-clock cadence for workloads where steps/sec varies.
  * **Off the hot path** — the hook snapshots params to host (the one
    synchronous cost: a device_get, which must happen before the next
    dispatch donates the buffers away) and hands the I/O to the shared
    :class:`~deepfm_tpu.utils.checkpoint.AsyncSaveExecutor`. While a publish
    is in flight, due cadences are counted as skipped, not queued.
  * **Atomicity** — the artifact (delta params checkpoint + servable export,
    via ``export_serving``) is staged under a dot-prefixed temp dir in the
    publish dir, completed (marker written last), fsynced, then
    ``os.replace``d to its final ``<step>/`` name; only after that does the
    ``LATEST`` pointer move (atomic pointer write, and never backwards). A
    crash at ANY point leaves either the previous artifact set intact or a
    complete new artifact — never a partially-visible one.
  * **Longevity wiring** — :meth:`drain` lets the preemption path wait for
    an in-flight publish before exiting 42; :meth:`check_wedged` (called
    every dispatch) trips the watchdog abort (exit 43) when a publish has
    been in flight longer than ``--publish_timeout_s``.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..data import fileio
from ..obs import metrics as metrics_lib
from ..obs import trace as trace_lib
from ..utils import export as export_lib
from ..utils import faults as faults_lib
from ..utils import logging as ulog
from ..utils import preempt as preempt_lib
from ..utils.checkpoint import AsyncSaveExecutor


def _default_abort(detail: str) -> None:  # pragma: no cover - kills process
    ulog.warning(f"wedged publish: {detail}; aborting (exit "
                 f"{preempt_lib.EXIT_WATCHDOG})")
    os._exit(preempt_lib.EXIT_WATCHDOG)


def _pct(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


class Publisher:
    """Fit-loop hook publishing servable artifacts on a step/time cadence."""

    def __init__(self, model, cfg, publish_dir: str, *,
                 every_steps: int = 0, every_secs: float = 0.0,
                 timeout_s: float = 600.0,
                 executor: Optional[AsyncSaveExecutor] = None,
                 clock: Callable[[], float] = time.monotonic,
                 abort: Optional[Callable[[str], None]] = None,
                 extra_export: Optional[Callable[[str], None]] = None,
                 health=None):
        self._model = model
        self._cfg = cfg
        self._dir = publish_dir
        # Ran against the staging dir BEFORE export_serving finishes it, so
        # the completion marker still certifies everything the hook wrote
        # (the cascade uses this to ship towers + candidate index alongside
        # every ranker version — rec/cascade.cascade_extra_export).
        self._extra_export = extra_export
        self.every_steps = int(every_steps)
        self.every_secs = float(every_secs)
        self.timeout_s = float(timeout_s)
        self._executor = executor if executor is not None else AsyncSaveExecutor(
            name="publisher")
        self._own_executor = executor is None
        self._clock = clock
        self._abort = abort if abort is not None else _default_abort
        self._health = health  # TrainHealth, for watchdog_aborts accounting
        fileio.makedirs(publish_dir)
        self._inflight = None          # Future of the running publish job
        self._inflight_step = -1
        self._inflight_since = 0.0
        self._last_crossed_step = 0    # step-cadence boundary bookkeeping
        self._last_pub_time = clock()  # time cadence anchors at start
        self._head_step = 0            # newest step seen (staleness metric)
        # Stats (host-side, cheap): consumed by the task result and the drills.
        self.published: List[int] = []      # versions successfully published
        self.publish_failures = 0
        self.skipped_inflight = 0           # due cadences hit while busy
        self.latencies_s: List[float] = []  # submit -> artifact visible
        self.staleness_steps: List[int] = []  # head - version at completion
        # Unified registry (obs.metrics): stats() is the metric surface.
        metrics_lib.auto_register("publisher", self)

    # ------------------------------------------------------------- cadence

    def seed_cadence(self, step: int) -> None:
        """Anchor the step cadence at a restored checkpoint step, so a
        resumed run crosses exactly the boundaries a fresh run would from
        there (same seeding rule as ``CheckpointManager.should_save``)."""
        self._last_crossed_step = max(self._last_crossed_step, int(step))
        self._head_step = max(self._head_step, int(step))

    def _due(self, step: int) -> bool:
        due = False
        if self.every_steps > 0:
            if (step // self.every_steps
                    > self._last_crossed_step // self.every_steps):
                due = True
        if not due and self.every_secs > 0:
            if self._clock() - self._last_pub_time >= self.every_secs:
                due = True
        return due

    def maybe_publish(self, state, step: int) -> bool:
        """Per-dispatch hook: snapshot + submit when a cadence is due.
        Never blocks on I/O; returns True iff a publish was started."""
        step = int(step)
        self._head_step = max(self._head_step, step)
        self.check_wedged()
        if not self._due(step):
            return False
        if self._inflight is not None and not self._inflight.done():
            # Busy: drop this cadence rather than queueing a stale snapshot.
            self.skipped_inflight += 1
            self._last_crossed_step = step
            return False
        self._reap()
        self._last_crossed_step = step
        self._last_pub_time = self._clock()
        self.publish_now(state, step)
        return True

    def publish_now(self, state, step: int) -> None:
        """Snapshot ``state`` at ``step`` and publish asynchronously."""
        # Snapshot synchronously: the fit loop donates the state buffers to
        # the next dispatch, so the background job must never touch them.
        with trace_lib.span("publish.snapshot", version=int(step)):
            params = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), state.params)
            mstate = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), state.model_state)
        self._inflight_step = int(step)
        self._inflight_since = self._clock()
        self._inflight = self._executor.submit(
            self._do_publish, params, mstate, int(step))

    # ------------------------------------------------------- background job

    def _do_publish(self, params, mstate, step: int) -> Optional[str]:
        version = str(step)
        final_dir = fileio.join(self._dir, version)
        if fileio.exists(fileio.join(final_dir, export_lib.COMPLETE_MARKER)):
            # Idempotent republish (deterministic replay after a resume hits
            # the same cadence step): the bytes would be identical. Still
            # advance LATEST — a crash between the rename and the pointer
            # write heals here on the retry.
            self._advance_latest(version)
            return final_dir
        staging = fileio.join(self._dir, f".staging-{version}-{os.getpid()}")
        if fileio.isdir(staging):
            fileio.rmtree(staging)

        class _Snap:  # duck-typed TrainState view for export_serving
            pass
        snap = _Snap()
        snap.params, snap.model_state, snap.step = params, mstate, step

        # Spans run on the executor thread — complete ("X") events are
        # thread-local, so they land on the publisher's own trace row and
        # the drill's serve-vN-while-vN+1-stages overlap reads directly
        # off the merged timeline.
        with trace_lib.span("publish.stage", version=step):
            if self._extra_export is not None:
                self._extra_export(staging)
            export_lib.export_serving(self._model, snap, self._cfg, staging)
            fileio.fsync_dir(staging)
        faults_lib.check_publish_crash("before_rename")
        with trace_lib.span("publish.rename", version=step):
            fileio.replace(staging, final_dir)
            fileio.fsync_dir(self._dir)
        faults_lib.check_publish_crash("after_rename_before_latest")
        with trace_lib.span("publish.pointer", version=step):
            self._advance_latest(version)
        return final_dir

    def _advance_latest(self, version: str) -> None:
        """Move LATEST forward, never backwards: a resumed run republishing
        an old cadence step must not regress the serving pointer. Every
        actual move is recorded in the ``pointer_history.jsonl`` sidecar
        BEFORE the pointer write — a crash between the two heals on the
        retried publish because the append is tail-deduplicated."""
        current = export_lib.read_latest(self._dir)
        if current is not None:
            try:
                if int(os.path.basename(current)) >= int(version):
                    return
            except ValueError:
                pass  # non-numeric current pointer: overwrite it
        export_lib.append_pointer_event(self._dir, version, "publish")
        faults_lib.check_publish_crash("after_history_before_latest")
        export_lib.write_latest(self._dir, version)

    def history(self) -> List[Dict[str, Any]]:
        """The publish dir's pointer-history sidecar, oldest first."""
        return export_lib.pointer_history(self._dir)

    # ------------------------------------------------------------ lifecycle

    def _reap(self) -> None:
        """Collect the finished in-flight job's outcome into the stats."""
        fut, self._inflight = self._inflight, None
        if fut is None:
            return
        step, since = self._inflight_step, self._inflight_since
        self._inflight_step = -1
        try:
            result = fut.result(timeout=0)
        except Exception as e:
            self.publish_failures += 1
            ulog.warning(f"publish of step {step} failed ({e}); the previous "
                         "artifact stays live; retrying next cadence")
            return
        if result is not None:
            self.published.append(step)
            self.latencies_s.append(self._clock() - since)
            self.staleness_steps.append(max(0, self._head_step - step))

    def check_wedged(self) -> None:
        """Trip the watchdog when a publish exceeds ``timeout_s`` in flight."""
        if self._inflight is None:
            return
        if self._inflight.done():
            self._reap()
            return
        elapsed = self._clock() - self._inflight_since
        if self.timeout_s > 0 and elapsed > self.timeout_s:
            if self._health is not None:
                self._health.record_watchdog_abort()
            self._abort(
                f"publish of step {self._inflight_step} in flight for "
                f"{elapsed:.1f}s (publish_timeout_s={self.timeout_s})")

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait for the in-flight publish (preemption path / run end).
        True iff nothing was pending or it completed within ``timeout``."""
        fut = self._inflight
        if fut is None:
            return True
        try:
            fut.result(timeout=timeout)
        except Exception:
            pass  # failure accounting happens in _reap below
        if fut.done():
            self._reap()
            return True
        ulog.warning(f"publish of step {self._inflight_step} still in "
                     f"flight after {timeout}s drain")
        return False

    def close(self) -> None:
        self.drain(timeout=self.timeout_s if self.timeout_s > 0 else None)
        if self._own_executor:
            self._executor.close()

    def stats(self) -> Dict[str, Any]:
        return {
            "published_versions": list(self.published),
            "publish_count": len(self.published),
            "publish_failures": self.publish_failures,
            "publish_skipped_inflight": self.skipped_inflight,
            "publish_latency_p50_s": _pct(self.latencies_s, 50),
            "publish_latency_p99_s": _pct(self.latencies_s, 99),
            "publish_staleness_steps_max": (
                max(self.staleness_steps) if self.staleness_steps else None),
        }
