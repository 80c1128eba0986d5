"""Checkpoint/resume on shared storage via Orbax.

TPU-native replacement for TF-Estimator checkpointing (reference semantics:
shared-storage ``model_dir`` with auto-resume from the latest checkpoint,
``1-ps-cpu/...py:434-435`` + ``README-EN.md:62``; rank-0-only ``model_dir``
under Horovod, ``2-hvd-gpu/...py:365-368``). Orbax writes the sharded train
state distributedly (every process writes its shards — the multi-host
generalization of "rank 0 saves"), asynchronously (save overlaps the next
training steps), and keeps ``max_to_keep`` checkpoints. Preemption tolerance
== resume-from-latest, exactly the reference's spot-instance story.
"""

from __future__ import annotations

import concurrent.futures
import threading
from typing import Any, Callable, Optional

import jax

from ..obs import startup

with startup.importing("orbax.checkpoint"):   # Orbax and its cloud clients
    import orbax.checkpoint as ocp

from ..data import fileio  # noqa: E402
from . import logging as ulog  # noqa: E402
from . import retry as retry_lib  # noqa: E402


# Orbax (0.11) numbers each save from a process-wide counter and reads the
# "current" number back several times while the save is being set up. Two
# saves set up from different threads at once — the trainer's checkpoint and
# the publisher thread's artifact, whose cadences coincide — can therefore
# read each other's number, and each then waits out a five-minute timeout for
# a directory-creation signal that was sent under the other's. Everything the
# background commit threads need is captured during set-up, so starting saves
# one at a time is enough; the asynchronous writes still overlap.
ORBAX_SAVE_SETUP = threading.Lock()


class AsyncSaveExecutor:
    """One background thread for artifact writes off the training hot path.

    Orbax drives its own async checkpoint writes; this executor serializes
    the *other* asynchronous writers — the online publisher's delta
    checkpoint + servable export jobs — so publish I/O never competes with
    itself and ``drain()`` gives the preemption path a single place to wait.
    The thread is created lazily on first submit and is a daemon, so an
    executor that is constructed but never used costs nothing and never
    blocks interpreter exit.
    """

    def __init__(self, name: str = "async-save"):
        self._name = name
        self._lock = threading.Lock()
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None

    def submit(self, fn: Callable, *args, **kwargs) -> concurrent.futures.Future:
        with self._lock:
            if self._pool is None:
                self._pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix=self._name)
            return self._pool.submit(fn, *args, **kwargs)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait for all submitted jobs; True iff everything finished in time.
        Submitting a no-op and waiting on it rides the FIFO guarantee of the
        single worker thread, so no job bookkeeping is needed."""
        with self._lock:
            pool = self._pool
        if pool is None:
            return True
        fence = pool.submit(lambda: None)
        try:
            fence.result(timeout=timeout)
            return True
        except concurrent.futures.TimeoutError:
            return False

    def close(self) -> None:
        with self._lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)


class CheckpointManager:
    """Thin wrapper over ocp.CheckpointManager for the TrainState pytree."""

    def __init__(self, directory: str, *, max_to_keep: int = 3,
                 save_interval_steps: int = 0, async_save: bool = True,
                 max_save_failures: int = 3,
                 retry_policy: Optional[retry_lib.RetryPolicy] = None):
        self._dir = fileio.normalize_dir(directory)
        fileio.makedirs(self._dir)
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            enable_async_checkpointing=async_save,
        )
        self._mgr = ocp.CheckpointManager(self._dir, options=options)
        self.save_interval_steps = save_interval_steps
        self._last_should_save_step: Optional[int] = None
        self._saved_steps: set = set()
        self._max_to_keep = max_to_keep
        # Save hardening: a transient interval-save failure logs and defers
        # to the next interval; only this many CONSECUTIVE failures abort.
        # (0 = abort on the first failure.) Forced saves always hard-fail.
        self.max_save_failures = max_save_failures
        self.save_failures = 0          # total failed save attempts
        self._consecutive_failures = 0
        # Read-side hardening: retry/backoff around latest_step/restore — a
        # transient storage error on restore would otherwise kill a resuming
        # job instantly (the save side has been hardened since PR 1).
        self._retry = retry_policy

    def _call_read(self, fn, *args, op_name: str):
        if self._retry is None:
            return fn(*args)
        return self._retry.call(fn, *args, op_name=op_name)

    @property
    def directory(self) -> str:
        return self._dir

    def latest_step(self) -> Optional[int]:
        return self._call_read(self._mgr.latest_step,
                               op_name=f"latest_step({self._dir})")

    def _do_save(self, step: int, state: Any, force: bool) -> bool:
        """The actual Orbax write. Seam for fault injection (FlakyFS
        patches this) — keep all failure handling in save() above it."""
        with ORBAX_SAVE_SETUP:
            return self._mgr.save(step, args=ocp.args.StandardSave(state),
                                  force=force)

    def save(self, step: int, state: Any, *, force: bool = False) -> bool:
        # Dedup against steps saved THIS session too: async saves may not yet
        # appear in all_steps() when the final forced save lands on the same
        # step as an in-flight interval save.
        if step in self._saved_steps or step in self._mgr.all_steps():
            return False  # e.g. final forced save after an interval save hit it
        try:
            saved = self._do_save(step, state, force)
        except Exception as e:
            self.save_failures += 1
            self._consecutive_failures += 1
            if force:
                # The final save is the run's deliverable — losing it
                # silently would discard the training; let it kill the job.
                raise
            if self._consecutive_failures > self.max_save_failures:
                raise IOError(
                    f"checkpoint save failed {self._consecutive_failures} "
                    f"consecutive times (max_save_failures="
                    f"{self.max_save_failures}) at step {step}: {e}") from e
            ulog.warning(
                f"checkpoint save at step {step} failed "
                f"({self._consecutive_failures} consecutive, tolerating "
                f"{self.max_save_failures}); deferring to next interval: {e}")
            return False
        self._consecutive_failures = 0
        if saved:
            self._saved_steps.add(step)
            # Steps are monotonic and Orbax only retains max_to_keep
            # checkpoints, so the session dedup set needs just the most
            # recent entries — unpruned it leaks one int per save for the
            # whole run (weeks-long jobs).
            keep_n = max(self._max_to_keep, 8)
            if len(self._saved_steps) > keep_n:
                self._saved_steps = set(sorted(self._saved_steps)[-keep_n:])
            ulog.info(f"checkpoint saved at step {step} -> {self._dir}")
        return saved

    def restore(self, state_template: Any, step: Optional[int] = None) -> Any:
        """Restore into the template's shardings (pass a freshly-initialized
        state so restored arrays land row-sharded/replicated correctly)."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint found in {self._dir}")
        abstract = jax.tree.map(_as_abstract, state_template)
        try:
            # Retry-wrapped: a transient read fault heals; a ValueError
            # (shape mismatch) is not retryable and falls through to the
            # guidance below unchanged.
            restored = self._call_read(
                lambda: self._mgr.restore(
                    step, args=ocp.args.StandardRestore(abstract)),
                op_name=f"restore(step {step}, {self._dir})")
        except ValueError as e:
            if "not compatible with the stored shape" in str(e):
                raise RuntimeError(
                    f"checkpoint at {self._dir} (step {step}) has parameter "
                    f"shapes that do not match this run's config/build: {e}. "
                    f"Common causes: changed model hyperparameters "
                    f"(feature_size/embedding_size/deep_layers) while "
                    f"reusing a model_dir, or a checkpoint saved before the "
                    f"mesh-independent vocab padding (ops/embedding.py). "
                    f"Match the original config, or start a fresh "
                    f"model_dir.") from e
            raise
        ulog.info(f"restored checkpoint step {step} from {self._dir}")
        return restored

    def should_save(self, step: int) -> bool:
        """True when ``step`` crosses a save-interval boundary since the last
        query — steps may advance by more than 1 per call (steps_per_loop).
        Seeded from the latest existing checkpoint so a resumed run does not
        save an off-schedule checkpoint on its first dispatch."""
        if not self.save_interval_steps:
            return False
        if self._last_should_save_step is None:
            self._last_should_save_step = self.latest_step() or 0
        crossed = (step // self.save_interval_steps
                   > self._last_should_save_step // self.save_interval_steps)
        self._last_should_save_step = step
        return crossed

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._mgr.close()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        if exc and exc[0] is not None:
            # Exiting on an exception (e.g. a preemption unwinding) with an
            # async save possibly in flight: drain it so the checkpoint
            # directory is never left half-written, but swallow secondary
            # close errors — the original exception must propagate.
            try:
                self.close()
            except Exception as close_exc:
                ulog.warning(
                    f"checkpoint close during exception unwind failed "
                    f"(original error propagates): {close_exc}")
            return
        self.close()


def _as_abstract(x: Any) -> Any:
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return x


def clear_model_dir(directory: str) -> None:
    """clear_existing_model semantics (reference 2-hvd-gpu/...py:60,334-340):
    wipe the checkpoint dir for a fresh run; chief only."""
    if jax.process_index() != 0:
        return
    if fileio.isdir(directory):
        fileio.rmtree(directory)
        ulog.info(f"cleared existing model dir {directory}")
