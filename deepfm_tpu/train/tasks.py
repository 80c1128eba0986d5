"""Task dispatch driver: train / eval / infer / export (the L3 layer).

Reimplements the reference ``main()`` dispatch (``1-ps-cpu/...py:341-467``,
``2-hvd-gpu/...py:289-431``) TPU-first:

  * ``train`` — per-epoch train loop with post-epoch eval (the Horovod
    file-mode shape, ``2-hvd-gpu/...py:390-394``), checkpoint every
    ``save_checkpoints_steps``, auto-resume from ``model_dir``, final
    serving export (train also exports, reference ``:451-467``).
  * ``eval`` — AUC + loss on the eval files (``DeepFM.evaluate`` analog).
  * ``infer`` — batch prediction writing one probability per line to
    ``pred.txt`` (reference ``:445-449``).
  * ``export`` — write the servable artifact (reference ``:451-467``).

File resolution follows the reference glob conventions (``tr*`` / ``va*`` /
``te*`` + ``.tfrecords``, reference ``:373-377``) with a fallback to all
``*.tfrecords`` in the directory.
"""

from __future__ import annotations

import glob as _glob
import os
import time
import warnings
from typing import Dict, List, Optional, Tuple

_IMPORT_T0_NS = time.time_ns()   # for startup.imported(), below the imports

import jax
import numpy as np

from ..config import Config
from ..data import cache as cache_lib
from ..data import fileio
from ..data import pipeline as pipe_lib
from ..data import sharding as shard_lib
from ..data import stream as stream_lib
from ..obs import metrics as obs_metrics
from ..obs import startup
from ..obs import trace as obs_trace
from ..obs.tensorboard import TensorBoardWriter as _TensorBoardWriter
from ..parallel import bootstrap
from ..utils import checkpoint as ckpt_lib
from ..utils import export as export_lib
from ..utils import faults as faults_lib
from ..utils import logging as ulog
from ..utils import preempt as preempt_lib
from ..utils import profiling as prof_lib
from ..utils import retry as retry_lib
from . import guard as guard_lib
from . import metrics as metrics_lib
from . import publish as publish_lib
from .loop import Trainer, pad_batch
from .state import TrainState

# This module's own imports (Orbax above all), for whoever imports it by
# name: the launcher stamps ``deepfm_tpu.train`` around this, the benchmark's
# driver imports it on a thread of its own and stamps nothing.
startup.imported(__name__, _IMPORT_T0_NS)


def resolve_files(directory: str, prefix: str) -> List[str]:
    """Glob `{prefix}*.tfrecords`; fall back to all *.tfrecords.
    Supports local dirs and object-store URLs (gs://...)."""
    if not directory:
        return []
    files = fileio.glob(fileio.join(directory, f"{prefix}*.tfrecords"))
    if not files:
        files = fileio.glob(fileio.join(directory, "*.tfrecords"))
    return files


def _channel_path(cfg: Config, name: str, *, require: bool = False) -> str:
    """Resolve a channel name to a directory: the SageMaker-contract env var
    ``SM_CHANNEL_<NAME>`` when set, else a ``<data_dir>/<name>`` subdirectory,
    else ``data_dir`` itself (single-dir layouts).

    ``require=True`` (multi-path channels) turns the fallback into an error:
    silently resolving every worker's private channel to the shared
    ``data_dir`` would make all local workers train identical records."""
    env_key = "SM_CHANNEL_" + "".join(
        c if c.isalnum() else "_" for c in name).upper()
    if os.environ.get(env_key):
        return os.environ[env_key]
    sub = fileio.join(cfg.data_dir, name) if cfg.data_dir else ""
    if sub and fileio.isdir(sub):
        return sub
    if require:
        raise FileNotFoundError(
            f"channel {name!r} resolves to neither ${env_key} nor "
            f"{sub or '<data_dir>/' + name!r}; enable_data_multi_path needs "
            f"a real private directory per training channel")
    return cfg.data_dir


def resolve_channel_dirs(cfg: Config, *, process_index: Optional[int] = None
                         ) -> Tuple[str, str]:
    """(train_dir, eval_dir) for this process from the channel layout.

    Reference semantics (``2-hvd-gpu/...py:376-380,403`` + README-EN.md:78-84):
    SM_CHANNELS arrives sorted with the eval channel FIRST; under
    ``enable_data_multi_path`` each local worker reads its own private
    training channel — ``channel_names[1 + local_rank]``. Without channels
    configured this degenerates to the plain data_dir/val_data_dir pair.
    """
    names = cfg.channel_names
    eval_default = cfg.val_data_dir or cfg.data_dir
    if not names:
        return cfg.data_dir, eval_default
    eval_dir = (_channel_path(cfg, names[0])
                if len(names) > 1 else eval_default)
    train_names = names[1:] if len(names) > 1 else names
    wph = max(cfg.worker_per_host, 1)
    if cfg.enable_data_multi_path:
        if len(train_names) < wph:
            raise ValueError(
                f"enable_data_multi_path needs one training channel per "
                f"local worker: have {len(train_names)} channels "
                f"{train_names} for worker_per_host={wph} "
                f"(reference contract, README-EN.md:82)")
        rank = jax.process_index() if process_index is None else process_index
        train_dir = _channel_path(cfg, train_names[rank % wph], require=True)
    else:
        train_dir = _channel_path(cfg, train_names[0])
    return train_dir, eval_dir


def _local_batch_size(cfg: Config) -> int:
    nproc = jax.process_count()
    if cfg.batch_size % max(nproc, 1) != 0:
        raise ValueError(
            f"global batch_size={cfg.batch_size} not divisible by "
            f"process_count={nproc}")
    return cfg.batch_size // nproc


def _shard_spec(cfg: Config, files: List[str],
                rank: Optional[int] = None) -> shard_lib.ShardSpec:
    rank = jax.process_index() if rank is None else rank
    return shard_lib.shard_files(
        files,
        enable_data_multi_path=cfg.enable_data_multi_path,
        enable_s3_shard=cfg.enable_s3_shard,
        rank=rank,
        local_rank=rank % max(cfg.worker_per_host, 1),
        world_size=jax.process_count(),
        workers_per_host=cfg.worker_per_host,
    )


def _validate_shard_coverage(cfg: Config, files: List[str]) -> None:
    """Startup guard for multi-process jobs: the per-rank shard specs must
    jointly cover every training file exactly once (the property the
    README decision table guarantees). Pure policy computation — every rank
    derives all ranks' specs and checks the same thing. Only meaningful
    when all ranks see the same file list (not multi-path private dirs)."""
    world = jax.process_count()
    if world <= 1 or cfg.enable_data_multi_path:
        return
    if cfg.enable_s3_shard:
        # Storage pre-sharded per host: this host's local workers must cover
        # THIS host's file list (other hosts hold other files).
        ranks = range(min(max(cfg.worker_per_host, 1), world))
    else:
        ranks = range(world)
    specs = [_shard_spec(cfg, files, rank=r) for r in ranks]
    shard_lib.validate_shard_coverage(specs, sorted(files))


def _fault_tolerance_kwargs(cfg: Config) -> Dict:
    """Bad-record policy + I/O retry knobs shared by every pipeline build."""
    return dict(
        on_bad_record=cfg.on_bad_record,
        max_bad_records=cfg.max_bad_records,
        retry_policy=retry_lib.policy_from_config(cfg),
    )


def _decoded_cache_dir(cfg: Config) -> str:
    """Disk-cache location: explicit flag, else a model_dir subdirectory
    (keeps the slabs next to the artifacts they trained)."""
    if cfg.decoded_cache != "disk":
        return ""
    if cfg.decoded_cache_dir:
        return cfg.decoded_cache_dir
    if cfg.model_dir:
        return os.path.join(cfg.model_dir, "decoded_cache")
    raise ValueError("--decoded_cache disk needs --decoded_cache_dir "
                     "or --model_dir")


def make_pipeline(cfg: Config, files: List[str], *, epochs: int = 1,
                  shuffle: bool = True, sharded: bool = True,
                  drop_remainder: Optional[bool] = None,
                  epoch_offset: int = 0,
                  skip_batches: int = 0) -> pipe_lib.CtrPipeline:
    with startup.phase("setup.pipeline", files=len(files)):
        return pipe_lib.CtrPipeline(
            files,
            decoded_cache=cfg.decoded_cache,
            decoded_cache_dir=_decoded_cache_dir(cfg),
            epoch_offset=epoch_offset,
            skip_batches=skip_batches,
            field_size=cfg.field_size,
            batch_size=_local_batch_size(cfg),
            num_epochs=epochs,
            shuffle=shuffle,
            shuffle_files=shuffle and cfg.shuffle_files,
            shuffle_buffer=cfg.shuffle_buffer,
            drop_remainder=cfg.drop_remainder if drop_remainder is None else drop_remainder,
            seed=cfg.seed,
            shard=_shard_spec(cfg, files) if sharded else None,
            prefetch_batches=cfg.prefetch_batches,
            use_native_decoder=cfg.use_native_decoder,
            native_assembly=cfg.native_assembly,
            reader_threads=cfg.reader_threads,
            input_workers=cfg.input_workers,
            stall_timeout_s=cfg.dispatch_timeout_s,
            verify_crc=cfg.verify_crc,
            num_labels=cfg.num_tasks,
            history=cfg.history_max_len > 0,
            history_max_len=max(1, cfg.history_max_len),
            **_fault_tolerance_kwargs(cfg),
        )


def _eval_pipeline(cfg: Config, va_files: List[str]) -> pipe_lib.CtrPipeline:
    """Eval reads every record: no shuffle, keep the tail batch — the
    weighted eval step pads it to the compiled shape with zero-weight rows,
    so drop_remainder would only lose data, never save a recompile."""
    return make_pipeline(cfg, va_files, shuffle=False, drop_remainder=False)


def make_streaming_pipeline(cfg: Config, files: List[str], *, epochs: int = 1,
                            skip_batches: int = 0, epoch_offset: int = 0
                            ) -> pipe_lib.StreamingCtrPipeline:
    """Pipe-mode analog (``--pipe_mode 1``): one sequential single-pass
    stream over this process's file shard, epochs replayed producer-side
    (the reference's FIFO shape, ``2-hvd-gpu/...py:403-405``). The shard's
    record-level component carries through — when ranks share the same files
    (fewer files than processes), each keeps every world-th record."""
    shard = _shard_spec(cfg, files)
    # One DataHealth shared by producer and consumer: the chained stream
    # heals transient read faults per file (so retries carry file names),
    # the consumer counts bad records against the same stats object.
    health = pipe_lib.DataHealth()
    stream = pipe_lib.ChainedFileStream(
        list(shard.files), num_epochs=epochs,
        shuffle_each_epoch=cfg.shuffle_files, seed=cfg.seed,
        epoch_offset=epoch_offset,
        retry_policy=retry_lib.policy_from_config(cfg), health=health)
    return pipe_lib.StreamingCtrPipeline(
        stream,
        field_size=cfg.field_size,
        batch_size=_local_batch_size(cfg),
        drop_remainder=cfg.drop_remainder,
        prefetch_batches=cfg.prefetch_batches,
        use_native_decoder=cfg.use_native_decoder,
        record_shard=shard.record_shard,
        skip_batches=skip_batches,
        verify_crc=cfg.verify_crc,
        on_bad_record=cfg.on_bad_record,
        max_bad_records=cfg.max_bad_records,
        num_labels=cfg.num_tasks,
        health=health,
    )


# High-water-mark sidecar for the online stream source, next to the
# checkpoints it must stay consistent with.
_STREAM_SIDECAR = "stream_manifest.json"

# Stable files-digest sentinel for online mode: the live directory listing
# grows by design, so the resume gate cannot fingerprint WHAT will be read —
# the stream's high-water-mark sidecar carries that contract instead, and
# this constant keeps the resume_meta digest comparison from spuriously
# invalidating a perfectly replayable skip.
_ONLINE_FILES_DIGEST = "online-stream-v1"


def make_online_pipeline(cfg: Config, train_dir: str, *, skip_batches: int = 0
                         ) -> Tuple[pipe_lib.StreamingCtrPipeline,
                                    stream_lib.UnboundedFileStream]:
    """Unbounded-stream producer for ``--online_mode``: the watcher tails
    ``tr*.tfrecords`` under ``train_dir`` (see data/stream.py for the
    admission/heal protocol) and the unchanged streaming consumer decodes
    it. Returns (pipeline, stream) — the stream handle lets the preemption
    path wake a blocked poll wait. Single-process for now: multi-process
    online mode needs chief-coordinated admission so every rank replays the
    same order (ROADMAP item 1's serving work is the priority first)."""
    if jax.process_count() > 1:
        raise NotImplementedError(
            "online_mode is single-process for now: shard admission order "
            "must be chief-coordinated before ranks can record-shard an "
            "unbounded stream consistently")
    health = pipe_lib.DataHealth()
    sidecar = (fileio.join(cfg.model_dir, _STREAM_SIDECAR)
               if cfg.model_dir else "")
    stream = stream_lib.UnboundedFileStream(
        train_dir, pattern="tr*.tfrecords", sidecar_path=sidecar,
        poll_secs=cfg.stream_poll_secs,
        idle_timeout_secs=cfg.stream_idle_timeout_secs,
        retry_policy=retry_lib.policy_from_config(cfg), health=health)
    pipeline = pipe_lib.StreamingCtrPipeline(
        stream,
        field_size=cfg.field_size,
        batch_size=_local_batch_size(cfg),
        drop_remainder=cfg.drop_remainder,
        prefetch_batches=cfg.prefetch_batches,
        use_native_decoder=cfg.use_native_decoder,
        skip_batches=skip_batches,
        verify_crc=cfg.verify_crc,
        on_bad_record=cfg.on_bad_record,
        max_bad_records=cfg.max_bad_records,
        num_labels=cfg.num_tasks,
        stream_label=f"<online:{train_dir}>",
        health=health,
    )
    return pipeline, stream


def _fit_epoch(trainer: Trainer, cfg: Config, state: TrainState, pipeline,
               hooks, on_log, guard=None
               ) -> Tuple[TrainState, Dict[str, float]]:
    """One epoch of training: device-resident when ``--device_dataset`` is
    set and the run qualifies, otherwise the staged host pipeline. The
    fallback warns with the disqualifier so an operator expecting device
    residency learns why the run is staged."""
    if cfg.device_dataset:
        reason = trainer.device_dataset_ineligible(pipeline)
        if reason is None:
            return trainer.fit_device_resident(
                state, pipeline, hooks=hooks, on_log=on_log, guard=guard)
        warnings.warn(
            f"--device_dataset fell back to the staged input path: {reason}",
            RuntimeWarning, stacklevel=2)
    return trainer.fit(state, pipeline, hooks=hooks, on_log=on_log,
                       guard=guard)


def _restore_or_init(trainer: Trainer, cfg: Config, require: bool,
                     mgr: Optional[ckpt_lib.CheckpointManager] = None
                     ) -> TrainState:
    """Init state, restoring from the latest checkpoint when one exists.

    For require=True tasks (eval/infer/export) a missing/empty model_dir is
    an error — checked by filesystem probe BEFORE any manager is built so a
    mistyped path is not created as a side effect. (The probe outcome is
    identical on all ranks: nothing creates the dir before this point.)
    For train, the caller passes its manager in — manager construction runs
    a cross-process barrier, so every rank must build the same managers in
    the same order; an isdir-gated construction would race.

    Hot/cold tiering: checkpoints are written DENSIFIED
    (``TieredEmbeddingRuntime.checkpoint_state``), so the restore template
    is the dense state (``init_state(tiered=False)``) and adoption into the
    hot cache happens after the restore — the restored Adam moments seed
    the cold tiers, making the round-trip bit-exact in both directions.
    """
    with startup.phase("setup.state", source="init") as ph:
        tier = getattr(trainer, "_tier", None)
        state = (trainer.init_state(tiered=False) if tier is not None
                 else trainer.init_state())

        def _adopted(s: TrainState) -> TrainState:
            return tier.adopt(s) if tier is not None else s

        if not cfg.model_dir:
            if require:
                raise FileNotFoundError(
                    f"task '{cfg.task_type}' requires model_dir")
            return _adopted(state)
        if require and not fileio.isdir(cfg.model_dir):
            raise FileNotFoundError(
                f"task '{cfg.task_type}' needs a checkpoint in model_dir="
                f"{cfg.model_dir!r}")
        own = mgr is None
        if own:
            mgr = ckpt_lib.CheckpointManager(
                cfg.model_dir, max_to_keep=cfg.keep_checkpoint_max,
                retry_policy=retry_lib.policy_from_config(cfg))
        try:
            latest = mgr.latest_step()
            if latest is not None:
                state = mgr.restore(state)
                ph.add(source="checkpoint", restored_step=int(latest))
            elif require:
                raise FileNotFoundError(
                    f"task '{cfg.task_type}' needs a checkpoint in model_dir="
                    f"{cfg.model_dir!r}")
        finally:
            if own:
                mgr.close()
        return _adopted(state)


def _ckpt_state(trainer: Trainer, state: TrainState) -> TrainState:
    """What goes INTO every checkpoint save: under hot/cold tiering the hot
    window is flushed and the tables + Adam slots densified to full shape,
    so the artifact restores bit-exactly into untiered (or differently
    sized) configs. Dense runs pass through untouched."""
    tier = getattr(trainer, "_tier", None)
    return tier.checkpoint_state(state) if tier is not None else state


def _servable_state(trainer: Trainer, state: TrainState) -> TrainState:
    """Export-time analog of :func:`_ckpt_state`: the serving artifact
    needs the full dense tables, not the hot window."""
    tier = getattr(trainer, "_tier", None)
    return tier.densified(state) if tier is not None else state


def run(cfg: Config) -> Dict[str, float]:
    """Entry point: bootstrap, dispatch on task_type, return result metrics."""
    bootstrap.initialize(cfg)
    bootstrap.start_backend()
    # Config-driven retry for every fileio op (glob/stat/open + the resume
    # sidecar reads) — not just the pipelines' own streams.
    fileio.set_retry_policy(retry_lib.policy_from_config(cfg))
    # Drill seam: env-scripted read faults reach a LAUNCHED subprocess,
    # where the in-process FlakyFS context manager can't (online_drill.py).
    faults_lib.install_env_faults()
    # Telemetry plane: span tracing (exported as Chrome-trace JSON on the
    # way out, even on preemption) plus the periodic metrics snapshotter.
    # configure() also exports env vars so spawned input workers inherit
    # the mode and write sibling per-pid trace files for merge().
    obs_dir = cfg.trace_dir or cfg.model_dir or "."
    obs_trace.configure(cfg.trace, capacity=cfg.trace_buffer,
                        trace_dir=obs_dir)
    snap_writer = None
    if cfg.metrics_snapshot_secs > 0:
        fileio.makedirs(obs_dir)
        snap_writer = obs_metrics.SnapshotWriter(
            os.path.join(obs_dir, f"metrics-{os.getpid()}.jsonl"),
            cfg.metrics_snapshot_secs)
    ulog.info(
        f"task={cfg.task_type} model={cfg.model} processes="
        f"{jax.process_count()} devices={len(jax.devices())}")
    trainer = Trainer(cfg)
    try:
        if cfg.task_type == "train":
            return _task_train(trainer, cfg)
        if cfg.task_type == "eval":
            return _task_eval(trainer, cfg)
        if cfg.task_type == "infer":
            return _task_infer(trainer, cfg)
        if cfg.task_type == "export":
            return _task_export(trainer, cfg)
        raise ValueError(f"unknown task_type {cfg.task_type!r}")
    finally:
        if snap_writer is not None:
            snap_writer.close()
        obs_trace.export()


# Multi-process ranks only consult the (rank-local) clock at agreed dispatch
# counts, then adopt the chief's verdict — keeping the eval collective in
# lockstep across processes without a per-dispatch sync.
_EVAL_CHECK_DISPATCHES = 50


def _eval_check_due(n_dispatch: int) -> bool:
    """Deterministic (rank-independent) schedule of clock-check dispatches:
    powers of two early so short runs still get mid-train evals, then every
    _EVAL_CHECK_DISPATCHES to bound sync frequency."""
    if n_dispatch < _EVAL_CHECK_DISPATCHES:
        return n_dispatch & (n_dispatch - 1) == 0  # 1, 2, 4, 8, 16, 32
    return n_dispatch % _EVAL_CHECK_DISPATCHES == 0


def _make_throttled_eval_hook(trainer: Trainer, cfg: Config,
                              va_files: List[str], result: Dict[str, float],
                              on_eval=None, evaluate=None):
    """Mid-train eval hook with TrainSpec/EvalSpec timing semantics
    (start_delay_secs / throttle_secs, reference 1-ps-cpu/...py:440-441).

    Multi-process safety: dispatch counts are identical across ranks because
    ``Trainer.fit`` min-truncates ragged shards (``_stage_multiprocess``), so every
    rank reaches each agreed check dispatch — the chief's clock verdict is
    then broadcast and the eval collective entered (or skipped) in lockstep."""
    import time as _time

    t_start = _time.time()
    last_eval_t: List[Optional[float]] = [None]
    n_dispatch = [0]
    result["mid_train_evals"] = 0.0

    def hook(state, m) -> None:
        n_dispatch[0] += 1
        multi = jax.process_count() > 1
        if multi and not _eval_check_due(n_dispatch[0]):
            return  # between agreed check points
        now = _time.time()
        due = (now - t_start >= cfg.eval_start_delay_secs
               and (last_eval_t[0] is None
                    or now - last_eval_t[0] >= cfg.eval_throttle_secs))
        if multi:
            from jax.experimental import multihost_utils  # noqa: PLC0415
            due = bool(multihost_utils.broadcast_one_to_all(
                np.asarray(due)))
        if not due:
            return
        last_eval_t[0] = _time.time()
        ev = (evaluate(state) if evaluate is not None
              else trainer.evaluate(state, _eval_pipeline(cfg, va_files)))
        result["mid_train_evals"] += 1
        result.update({"auc": ev["auc"], "eval_loss": ev["loss"],
                       "eval_examples_per_sec": ev["examples_per_sec"]})
        result.update({k: v for k, v in ev.items() if k.startswith("auc_")})
        ulog.info(f"throttled eval @ step {int(state.step)}: "
                  f"auc={ev['auc']:.5f} loss={ev['loss']:.5f}")
        if on_eval is not None:
            on_eval(ev, state)

    return hook


def _make_online_eval(trainer: Trainer, cfg: Config, va_files: List[str],
                      window, step_fn):
    """Online-mode evaluate fn: one predict pass over the held-out set,
    folded into a sliding :class:`~deepfm_tpu.train.metrics.WindowedAuc`
    tagged with the current training step — "AUC over the last N steps of
    traffic" rather than the batch job's cumulative AUC. Single-process
    (online mode is; see make_online_pipeline)."""
    import time as _time

    local_bs = _local_batch_size(cfg)
    task_names = cfg.task_names
    num_tasks = len(task_names)
    weights = cfg.task_weight_values

    def evaluate(state: TrainState) -> Dict[str, float]:
        pipeline = _eval_pipeline(cfg, va_files)
        probs: List[np.ndarray] = []
        labels: List[np.ndarray] = []
        real_rows: List[int] = []
        t0 = _time.time()

        def feed():
            for batch in pipeline:
                n = batch["label"].shape[0]
                real_rows.append(n)
                cols = [np.asarray(batch["label"]).reshape(-1)[:n]]
                if num_tasks > 1:
                    cols.append(
                        np.asarray(batch["label2"]).reshape(-1)[:n])
                labels.append(np.stack(cols, axis=1))
                yield (pad_batch(batch, local_bs)  # pad tail, trim after
                       if n < local_bs else batch)

        for i, p in enumerate(trainer.predict(state, feed())):
            arr = np.asarray(p)
            if arr.ndim == 1:
                arr = arr[:, None]
            probs.append(arr[:real_rows[i]])
        elapsed = max(_time.time() - t0, 1e-9)
        p = (np.concatenate(probs) if probs
             else np.zeros((0, num_tasks), np.float64)).astype(np.float64)
        y = (np.concatenate(labels) if labels
             else np.zeros((0, num_tasks), np.float64)).astype(np.float64)
        window.update(int(step_fn()), p, y)
        pc = np.clip(p, 1e-7, 1.0 - 1e-7)
        if len(y):
            per_task = -(y * np.log(pc)
                         + (1.0 - y) * np.log1p(-pc)).mean(axis=0)
            loss = float(sum(w * per_task[t]
                             for t, w in enumerate(weights)))
        else:
            loss = 0.0
        auc = window.compute()
        result = {"loss": loss,
                  "examples_per_sec": len(y) / elapsed,
                  "window_examples": float(window.examples)}
        if isinstance(auc, dict):
            result["auc"] = auc[task_names[0]]
            result.update({f"auc_{t}": v for t, v in auc.items()})
        else:
            result["auc"] = auc
        return result

    return evaluate


_RESUME_META = "resume_meta.json"


def _write_resume_meta(model_dir: str, meta: Dict) -> None:
    """Chief-only sidecar recording data-pipeline position alongside each
    checkpoint — the step-accurate-resume half the checkpoint itself can't
    carry (SURVEY hard-part #5; the reference punts and replays the epoch)."""
    if not bootstrap.is_chief():
        return
    import json  # noqa: PLC0415
    with fileio.open_stream(fileio.join(model_dir, _RESUME_META), "w") as f:
        json.dump(meta, f)


def _read_resume_meta(model_dir: str,
                      health: Optional[guard_lib.TrainHealth] = None
                      ) -> Optional[Dict]:
    """Read the resume sidecar; a corrupt/truncated file (a preemption can
    land mid-json.dump) degrades to checkpoint-step-only resume — warn and
    count it, never raise: the checkpoint itself is still good."""
    import json  # noqa: PLC0415
    path = fileio.join(model_dir, _RESUME_META)
    if not fileio.exists(path):
        return None
    try:
        with fileio.open_stream(path, "r") as f:
            return json.load(f)
    except (ValueError, OSError) as exc:  # torn write / unreadable
        ulog.warning(
            f"resume sidecar {path} unreadable ({exc!r}); falling back to "
            f"checkpoint-step-only resume (the interrupted epoch replays)")
        if health is not None:
            health.record_resume_meta_corrupt()
        return None


def _files_fingerprint(cfg: Config, files: List[str]) -> str:
    """Digest of WHAT the pipeline would read: the resolved training file
    list (basenames + byte sizes — robust to moving the directory wholesale,
    sensitive to any add/remove/rename/rewrite) plus the shard-mapping flags.
    If either changes between the interrupted run and the resume, the
    per-epoch shuffle order / per-rank shard assignment changes and a
    mid-epoch ``skip_batches`` would silently skip the WRONG prefix (records
    double-trained or never trained) — so ``_resume_position`` requires this
    digest to match and falls back to epoch-replay otherwise (ADVICE r3).

    Computed on the chief only (see ``_task_train``: the resume decision is
    broadcast, never derived per-rank). Under ``enable_data_multi_path``
    ``files`` (the chief's own private channel) is ignored and the digest
    covers EVERY local worker's training channel — SageMaker downloads all
    channels to every instance (README-EN.md:82), so the chief can resolve
    its siblings' channels and a sibling-channel edit invalidates the skip
    even though the chief's channel is unchanged (ADVICE r4 high).

    Stat/resolve failures degrade to a stable sentinel rather than crashing:
    ``tf.io.gfile`` raises ``tf.errors.OpError`` (an ``Exception``, NOT an
    ``OSError``) for remote paths, e.g. a file deleted between glob and
    fingerprint (ADVICE r4 low)."""
    import hashlib  # noqa: PLC0415

    h = hashlib.sha256()
    h.update(f"v1|{int(cfg.enable_data_multi_path)}|"
             f"{int(cfg.enable_s3_shard)}|{cfg.worker_per_host}|".encode())
    if cfg.enable_data_multi_path:
        tagged = []
        for r in range(max(cfg.worker_per_host, 1)):
            try:
                chan_dir, _ = resolve_channel_dirs(cfg, process_index=r)
                tagged.extend((f"c{r}", p)
                              for p in resolve_files(chan_dir, "tr"))
            except Exception:  # unresolvable sibling channel: stable marker
                tagged.append((f"c{r}", "<unresolved>"))
    else:
        tagged = [("", p) for p in files]
    for tag, path in sorted(tagged):
        try:
            n = fileio.size(path) if path != "<unresolved>" else -2
        except Exception:  # transient stat failure / gfile OpError
            n = -1
        h.update(f"{tag}={os.path.basename(path)}:{n}|".encode())
    return h.hexdigest()[:32]


def _consumption_layout(cfg: Config) -> List[int]:
    """Fingerprint of HOW batches are consumed. The pooled emission order
    and geometry depend on all of these (k-group vs per-batch drains,
    per-rank sharding, batch/pool sizes, shuffle seed), so a mid-epoch skip
    is only exact when the resuming run consumes exactly the way the
    interrupted run did; any difference falls back to epoch-replay."""
    # Leading element is a PIPELINE FORMAT VERSION: bump it whenever the
    # emission order for identical config changes (e.g. the r3 scatter
    # permutation), so a resume across framework versions falls back to
    # epoch-replay instead of silently mis-skipping.
    # decoded_cache changes chunk-arrival boundaries and therefore the pool
    # drain points whenever the pool is smaller than the epoch, so a resume
    # across cache modes must fall back to epoch-replay.
    # native_assembly does NOT change emission bytes (fused and scatter
    # paths are bit-identical), but it is consumption surface all the same:
    # including it (a list-LENGTH change old sidecars can't match) makes a
    # resume across the flag fall back to epoch-replay rather than trusting
    # a fingerprint that never recorded which path ran.
    # online_mode swaps the producer (finite file chain -> unbounded stream
    # with its own admission order), so a resume across the flag must never
    # trust a prior skip count — the list-LENGTH change guarantees that for
    # sidecars written before the flag existed too.
    # grad_accum_steps does NOT change which batches a step count covers
    # (state.step counts microbatches), but it changes which optimizer
    # trajectory produced the checkpoint, so a resume across the flag falls
    # back to epoch-replay via the list-LENGTH change rather than splicing
    # two different accumulation regimes mid-epoch.
    return [2, jax.process_count(), cfg.steps_per_loop,
            int(cfg.use_native_decoder), cfg.batch_size,
            cfg.shuffle_buffer, cfg.seed, int(cfg.drop_remainder),
            int(cfg.shuffle_files), cache_lib.MODES.index(cfg.decoded_cache),
            int(cfg.native_assembly), int(cfg.online_mode),
            cfg.grad_accum_steps]


def _resume_position(cfg: Config, restored_step: int,
                     files_digest: str = "",
                     health: Optional[guard_lib.TrainHealth] = None
                     ) -> Tuple[int, int, int]:
    """(epoch_base, start_epoch, skip_batches) for this invocation.

    The sidecar applies only when its ``step`` matches the restored
    checkpoint exactly (an async save that never became durable leaves a
    stale sidecar -> ignored, degrading to the reference's replay-the-epoch
    behavior). A cleanly-completed prior invocation advances ``epoch_base``
    so shuffle orders never repeat across resume-for-more-epochs runs; an
    interrupted invocation with the same num_epochs/pipe_mode resumes
    mid-epoch, skipping the batches already trained."""
    meta = (_read_resume_meta(cfg.model_dir, health=health)
            if cfg.model_dir else None)
    if not meta or not restored_step:
        return 0, 0, 0
    base = int(meta.get("epoch_base", 0))
    # Epochs whose shuffle order the recorded invocation may have touched.
    # A pipe-mode meta always records epoch 0 (its position is steps into
    # the stream) while the producer may have replayed up to num_epochs
    # orders, so count the full epoch budget there.
    touched = (int(meta.get("num_epochs", 0)) if meta.get("pipe_mode")
               else int(meta.get("epoch", 0)) + 1)
    if meta.get("step") != restored_step:
        # Stale sidecar (e.g. a lost async save): the position is unusable,
        # but the epoch_base is still valid knowledge — keep advancing the
        # shuffle seeds past every epoch any prior invocation touched.
        return base + touched, 0, 0
    if meta.get("completed"):
        return base + int(meta.get("num_epochs", 0)), 0, 0
    if (int(meta.get("num_epochs", -1)) == cfg.num_epochs
            and bool(meta.get("pipe_mode")) == bool(cfg.pipe_mode)
            and meta.get("layout") == _consumption_layout(cfg)
            and meta.get("files") == files_digest):
        return (base, int(meta.get("epoch", 0)),
                int(meta.get("steps_into_epoch", 0)))
    # Different invocation shape: start a fresh run but keep seeds moving.
    return base + touched, 0, 0


# _TensorBoardWriter moved to obs/tensorboard.py (imported above under its
# old name — tests monkeypatch ``tasks._TensorBoardWriter``).


def _task_train(trainer: Trainer, cfg: Config) -> Dict[str, float]:
    train_dir, eval_dir = resolve_channel_dirs(cfg)
    tr_files = resolve_files(train_dir, "tr")
    va_files = resolve_files(eval_dir, "va")
    if not tr_files and not cfg.online_mode:
        # Online mode tails the directory: starting before the first shard
        # arrives is the normal case, not an error.
        raise FileNotFoundError(f"no training tfrecords in {train_dir!r}")
    _validate_shard_coverage(cfg, tr_files)
    ulog.info(f"train dir={train_dir} files={len(tr_files)} "
              f"eval files={len(va_files)}")

    if cfg.clear_existing_model and cfg.model_dir:
        ckpt_lib.clear_model_dir(cfg.model_dir)  # chief-only rmtree
        if jax.process_count() > 1:
            # Barrier: no rank may construct its CheckpointManager (which
            # re-creates the dir) until the chief's delete has completed.
            from jax.experimental import multihost_utils  # noqa: PLC0415
            multihost_utils.sync_global_devices("clear_model_dir")

    mgr = None
    if cfg.model_dir:
        mgr = ckpt_lib.CheckpointManager(
            cfg.model_dir, max_to_keep=cfg.keep_checkpoint_max,
            save_interval_steps=cfg.save_checkpoints_steps,
            max_save_failures=cfg.max_save_failures,
            retry_policy=retry_lib.policy_from_config(cfg))
    state = _restore_or_init(trainer, cfg, require=False, mgr=mgr)

    # Runtime-resilience plumbing: ONE TrainHealth + guard for the whole run
    # (the skip/rollback budget spans rollback attempts) and the
    # process-wide preemption listener. A flag already set (a notice that
    # arrived during startup) is honored at the first dispatch.
    train_health = guard_lib.TrainHealth()
    guard = guard_lib.NonFiniteGuard.from_config(cfg, health=train_health)
    listener = preempt_lib.get_listener()

    # The resume decision is computed on the CHIEF ONLY and broadcast to all
    # ranks: a rank deciding from its own filesystem view (transient stat
    # failure, eventually-consistent object-store metadata, or a multi-path
    # private channel) could derive a divergent (epoch_base, start_epoch,
    # skip_batches) and desynchronize the lockstep collectives — a hang or
    # silent mis-training (ADVICE r4 high+medium). restored_step itself is
    # rank-consistent (all ranks restore the same global checkpoint).
    files_digest = ""
    if bootstrap.is_chief():
        # Online mode: the listing grows by design — a stable sentinel keeps
        # the resume gate from invalidating a replayable skip; the stream
        # sidecar (not the digest) carries WHAT-will-be-read exactness.
        files_digest = (_ONLINE_FILES_DIGEST if cfg.online_mode
                        else _files_fingerprint(cfg, tr_files))

    def _resume_for(restored_step: int) -> Tuple[int, int, int]:
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils  # noqa: PLC0415
            pos = (_resume_position(cfg, restored_step, files_digest,
                                    health=train_health)
                   if bootstrap.is_chief() else (0, 0, 0))
            pos = multihost_utils.broadcast_one_to_all(
                np.asarray(pos, np.int64))
            epoch_base, start_epoch, skip_batches = (int(x) for x in pos)
        else:
            epoch_base, start_epoch, skip_batches = _resume_position(
                cfg, restored_step, files_digest, health=train_health)
        if start_epoch or skip_batches:
            ulog.info(f"step-accurate resume: epoch {start_epoch} "
                      f"(+{skip_batches} batches already trained), "
                      f"epoch_base={epoch_base}")
        return epoch_base, start_epoch, skip_batches

    # train_and_evaluate semantics (reference 1-ps-cpu/...py:440-442,
    # REQUIRED there per README-EN.md:36-38): mid-train eval no earlier than
    # eval_start_delay_secs, then at most every eval_throttle_secs. With both
    # 0 (default) the loop keeps the Horovod file-mode shape instead:
    # eval after every epoch (2-hvd-gpu/...py:390-394).
    eval_throttled = bool(va_files) and (
        cfg.eval_start_delay_secs > 0 or cfg.eval_throttle_secs > 0)

    result: Dict[str, float] = {}

    # Cross-epoch fault accounting: each pipeline (train AND eval) owns a
    # DataHealth; fold them into one total so the run reports exact
    # retry/skip counts (asserted by scripts/fault_drill.py).
    health_totals: Dict[str, int] = {}

    def _log_health(pipeline, where: str) -> None:
        health = getattr(pipeline, "health", None)
        if health is None:
            return
        if health.total_events:
            ulog.info(f"data health ({where}): {health.summary()}")
        health.merge_into(health_totals)

    def _run_eval(at_state: TrainState, where: str) -> Dict[str, float]:
        pipe = _eval_pipeline(cfg, va_files)
        ev = trainer.evaluate(at_state, pipe)
        _log_health(pipe, where)
        return ev

    tb = _TensorBoardWriter(cfg.tensorboard_dir)

    def _tb_log(step: int, loss: float, eps: float) -> None:
        tb.scalars(step, loss=loss, examples_per_sec=eps)

    def _tb_eval(ev: Dict[str, float], at_state: TrainState) -> None:
        tb.scalars(int(at_state.step), eval_auc=ev["auc"],
                   eval_loss=ev["loss"])

    def _tb_health(step: int) -> None:
        tb.scalars(step, **{f"health/{name}": float(v)
                            for name, v in train_health.snapshot().items()})

    def _log_train_health(where: str) -> None:
        if train_health.consume_dirty():
            ulog.info(f"train health ({where}): {train_health.summary()}")

    def _maybe_poison(pipeline):
        """Test seam: an armed NaN plan (utils.faults.set_nan_plan) wraps
        the pipeline once; the plan is consumed on pickup, so a rollback
        replay (or the next epoch) trains clean data."""
        plan = faults_lib.take_nan_plan()
        if plan is not None:
            return faults_lib.BatchPoisoner(pipeline, **plan)
        return pipeline

    def _env_steps(name: str) -> int:
        raw = os.environ.get(name, "").strip()
        try:
            return int(raw) if raw else 0
        except ValueError:
            raise ValueError(
                f"{name} must be an integer step count, got {raw!r}"
            ) from None

    # Fault injection (drill hooks): DEEPFM_TPU_FAULT_AFTER_STEPS=N kills
    # training after >= N optimizer steps, AFTER the checkpoint hook has
    # run — a deterministic spot-kill for exercising the crash-resume path
    # end-to-end (the reference had no fault injection; SURVEY.md §5).
    # PREEMPT_AFTER pulls the injectable preemption trigger instead (the
    # graceful path: force-save + exit 42); PREEMPT_HOLD writes a sentinel
    # file and blocks until a real signal arrives — scripts/preempt_drill.py
    # uses it to SIGTERM a live run at a deterministic step. Every rank
    # reads the same env via the launcher, so each fault is cluster-wide
    # like a real slice preemption.
    fault_after = _env_steps("DEEPFM_TPU_FAULT_AFTER_STEPS")
    preempt_after = _env_steps("DEEPFM_TPU_PREEMPT_AFTER_STEPS")
    hold_after = _env_steps("DEEPFM_TPU_PREEMPT_HOLD_AFTER_STEPS")

    def _attempt(state: TrainState) -> TrainState:
        """One full training attempt: resume-position computation, hook
        stack, train loops, final forced save. A RollbackSignal (guard
        policy ``rollback``) aborts the attempt; the driver loop below
        restores the latest checkpoint and calls back in — the fresh
        ``_resume_for`` then replays from that checkpoint's recorded
        offset."""
        restored_step = int(state.step)
        epoch_base, start_epoch, skip_batches = _resume_for(restored_step)

        # Data-pipeline position for the resume sidecar; epoch_start is the
        # global step at which the current epoch's batch 0 was (or would
        # have been) trained, so steps_into_epoch == batches consumed this
        # epoch.
        progress = {"epoch": start_epoch,
                    "epoch_start": restored_step - skip_batches}

        def _meta(step: int, completed: bool) -> Dict:
            return {"step": step, "epoch": progress["epoch"],
                    "steps_into_epoch": step - progress["epoch_start"],
                    "epoch_base": epoch_base, "num_epochs": cfg.num_epochs,
                    "pipe_mode": int(cfg.pipe_mode),
                    "layout": _consumption_layout(cfg),
                    "files": files_digest, "completed": completed}

        # Online hot publishing: per attempt, so a rollback replay starts
        # with a clean in-flight ledger (the publish DIR persists — already-
        # published versions are skipped idempotently).
        publisher = None
        online_stream = [None]  # UnboundedFileStream handle for preempt wake
        if cfg.online_mode and (cfg.publish_every_steps
                                or cfg.publish_every_secs):
            pdir = cfg.publish_dir or (
                fileio.join(cfg.model_dir, "publish") if cfg.model_dir
                else "")
            if not pdir:
                raise ValueError("--publish_every_steps/secs needs "
                                 "--publish_dir or --model_dir")
            publisher = publish_lib.Publisher(
                trainer.model, cfg, pdir,
                every_steps=cfg.publish_every_steps,
                every_secs=cfg.publish_every_secs,
                timeout_s=cfg.publish_timeout_s,
                health=train_health)
            # Resumed runs cross the same publish boundaries a fresh run
            # would (the drill's version-set determinism rests on this).
            publisher.seed_cadence(restored_step)

        hooks = []
        # Host-side step counter: reading s.step would force a device sync
        # every step (it blocks on the async-dispatched update), collapsing
        # throughput — one sync at restore time instead. First hook, so
        # every later hook reads the post-dispatch count.
        step_counter = [restored_step]
        hooks.append(lambda s, m: step_counter.__setitem__(
            0, step_counter[0] + int(m.get("steps_done", 1))))

        if publisher is not None:
            # Cadence check + host snapshot + async submit; never blocks on
            # publish I/O. Also the wedged-publish watchdog (exit 43).
            hooks.append(lambda s, m: publisher.maybe_publish(
                s, step_counter[0]))

        last_saved = [-1]
        if mgr is not None:
            def ckpt_hook(s: TrainState, m) -> None:
                if mgr.should_save(step_counter[0]):
                    if mgr.save(step_counter[0], _ckpt_state(trainer, s)):
                        last_saved[0] = step_counter[0]
                        _write_resume_meta(
                            cfg.model_dir, _meta(step_counter[0], False))
            hooks.append(ckpt_hook)

        if preempt_after:
            def trigger_hook(s: TrainState, m) -> None:
                if step_counter[0] - restored_step >= preempt_after:
                    listener.trigger(
                        f"env trigger after "
                        f"{step_counter[0] - restored_step} steps")
            hooks.append(trigger_hook)

        if hold_after:
            held = [False]

            def hold_hook(s: TrainState, m) -> None:
                if held[0] or step_counter[0] - restored_step < hold_after:
                    return
                held[0] = True
                sentinel = fileio.join(cfg.model_dir or ".", ".preempt_hold")
                with fileio.open_stream(sentinel, "w") as f:
                    f.write(str(step_counter[0]))
                deadline = time.time() + 120.0
                while not listener.triggered():
                    if time.time() > deadline:
                        raise RuntimeError(
                            "preempt hold: no signal arrived within 120s")
                    time.sleep(0.05)
            hooks.append(hold_hook)

        # Preemption poll: once per dispatch single-process; multi-process
        # ranks consult their local flag only at the agreed _eval_check_due
        # dispatches and OR it across ranks, so every rank checkpoints and
        # raises at the SAME dispatch — the lockstep collectives stay
        # aligned (same pattern as the throttled-eval clock checks).
        pc_dispatch = [0]

        def preempt_hook(s: TrainState, m) -> None:
            pc_dispatch[0] += 1
            trig = listener.triggered()
            if jax.process_count() > 1:
                if not _eval_check_due(pc_dispatch[0]):
                    return
                from jax.experimental import multihost_utils  # noqa: PLC0415
                trig = bool(np.asarray(multihost_utils.process_allgather(
                    np.asarray([trig]))).any())
            if not trig:
                return
            step = step_counter[0]
            train_health.record_preemption()
            ulog.warning(
                f"preemption ({listener.reason or 'peer rank'}): force-"
                f"saving checkpoint at step {step}, then exiting with code "
                f"{preempt_lib.EXIT_PREEMPTED}")
            if mgr is not None:
                # An interval save may have just landed on this exact step
                # (mgr.save dedups); the resume sidecar makes the mid-epoch
                # position replay-exact on restart.
                mgr.save(step, _ckpt_state(trainer, s), force=True)
                _write_resume_meta(cfg.model_dir, _meta(step, False))
            if online_stream[0] is not None:
                online_stream[0].request_stop()  # wake a blocked poll wait
            if publisher is not None:
                # Drain the in-flight publish before exit 42: a published
                # artifact must never be abandoned half-staged by a graceful
                # preemption (a wedged one still trips the 43 watchdog).
                publisher.drain(timeout=cfg.publish_timeout_s or None)
            raise preempt_lib.Preempted(step, listener.reason)
        hooks.append(preempt_hook)

        if fault_after:
            def fault_hook(s: TrainState, m) -> None:
                if step_counter[0] - restored_step >= fault_after:
                    raise RuntimeError(
                        f"fault injection: simulated preemption after "
                        f"{step_counter[0] - restored_step} steps")
            hooks.append(fault_hook)

        tracer = prof_lib.StepWindowTracer(
            cfg.profile_dir, num_steps=cfg.profile_steps)
        hooks.append(lambda s, m: tracer.on_step(int(m.get("steps_done", 1))))
        # Online windowed eval: the throttled-eval machinery drives WHEN;
        # the evaluate override swaps the cumulative batch AUC for the
        # sliding-window streaming AUC (metrics.WindowedAuc).
        online_eval_fn = None
        if (cfg.online_mode and va_files
                and cfg.online_eval_window_steps > 0):
            if cfg.num_tasks > 1:
                window = metrics_lib.WindowedAucDict(
                    cfg.task_names, cfg.online_eval_window_steps,
                    num_bins=cfg.auc_num_thresholds)
            else:
                window = metrics_lib.WindowedAuc(
                    cfg.online_eval_window_steps,
                    num_bins=cfg.auc_num_thresholds)
            online_eval_fn = _make_online_eval(
                trainer, cfg, va_files, window, lambda: step_counter[0])
        if eval_throttled:
            hooks.append(_make_throttled_eval_hook(
                trainer, cfg, va_files, result, on_eval=_tb_eval,
                evaluate=(online_eval_fn
                          or (lambda s: _run_eval(s, "throttled eval")))))
        try:
            if cfg.pipe_mode:
                # Streaming (Pipe-mode analog): ONE train call consuming a
                # single-pass stream with all epochs replayed producer-side —
                # the reference pipe-mode shape (``2-hvd-gpu/...py:403-405``,
                # FIFO not reusable per epoch). Eval afterwards, file-mode.
                # Resume: the already-trained stream prefix is skipped
                # (epoch index stays 0 — position is steps into the stream).
                # online_mode swaps the finite file chain for the unbounded
                # directory watcher; the consumer is identical.
                if cfg.online_mode:
                    pipeline, ustream = make_online_pipeline(
                        cfg, train_dir, skip_batches=skip_batches)
                    online_stream[0] = ustream
                    pipeline = _maybe_poison(pipeline)
                else:
                    pipeline = _maybe_poison(make_streaming_pipeline(
                        cfg, tr_files, epochs=cfg.num_epochs,
                        skip_batches=skip_batches, epoch_offset=epoch_base))
                state, fit_m = trainer.fit(state, pipeline, hooks=hooks,
                                           on_log=_tb_log, guard=guard)
                _log_health(pipeline, "stream end")
                _log_train_health("stream end")
                if fit_m["steps"]:
                    result["loss"] = fit_m["loss"]
                    result["examples_per_sec"] = fit_m.get(
                        "examples_per_sec", 0.0)
                    result.update(
                        {k: v for k, v in fit_m.items()
                         if k.startswith("collective_")})
                if publisher is not None:
                    # Stream ended (idle timeout / stop): force one final
                    # publish at the terminal step. Deterministic — both an
                    # interrupted-and-resumed run and a clean run end at the
                    # same step over the same admitted shards, so the drill
                    # always has a common version to bit-compare.
                    publisher.drain(timeout=cfg.publish_timeout_s or None)
                    final_step = step_counter[0]
                    if final_step and final_step not in publisher.published:
                        publisher.publish_now(state, final_step)
                        publisher.drain(
                            timeout=cfg.publish_timeout_s or None)
                    pub_stats = publisher.stats()
                    result.update(pub_stats)
                    # Publisher scalars ride the same TB writer as training
                    # loss/eval (obs.tensorboard) — one place to look.
                    tb.scalar_dict(final_step, "publish/", pub_stats)
                if va_files:
                    ev = (online_eval_fn(state) if online_eval_fn is not None
                          else _run_eval(state, "stream eval"))
                    ulog.info(f"streaming train done: eval auc={ev['auc']:.5f} "
                              f"loss={ev['loss']:.5f}")
                    result.update({"auc": ev["auc"], "eval_loss": ev["loss"],
                                   "eval_examples_per_sec":
                                       ev["examples_per_sec"]})
                    result.update({k: v for k, v in ev.items()
                                   if k.startswith("auc_")})
                    if "window_examples" in ev:  # online windowed AUC
                        result["window_examples"] = ev["window_examples"]
                    _tb_eval(ev, state)
            else:
                for epoch in range(start_epoch, cfg.num_epochs):
                    # Per-epoch loop in the driver, per the reference's
                    # file-mode shape (``2-hvd-gpu/...py:390-394``). The
                    # epoch index (offset by epoch_base across invocations)
                    # feeds the shuffle seed so each epoch sees a fresh
                    # order (tf.data reshuffle_each_iteration analog) —
                    # which is also what makes mid-epoch resume exact: the
                    # resumed epoch replays the identical permutation and
                    # skips the already-trained prefix.
                    progress["epoch"] = epoch
                    progress["epoch_start"] = step_counter[0] - (
                        skip_batches if epoch == start_epoch else 0)
                    pipeline = _maybe_poison(make_pipeline(
                        cfg, tr_files, epochs=1, shuffle=True,
                        epoch_offset=epoch_base + epoch,
                        skip_batches=(skip_batches if epoch == start_epoch
                                      else 0)))
                    state, fit_m = _fit_epoch(trainer, cfg, state, pipeline,
                                              hooks, _tb_log, guard=guard)
                    _log_health(pipeline, f"epoch {epoch + 1} end")
                    _log_train_health(f"epoch {epoch + 1}")
                    if fit_m["steps"]:
                        # (a fully-skipped resumed epoch reports no loss)
                        result["loss"] = fit_m["loss"]
                        result["examples_per_sec"] = fit_m.get(
                            "examples_per_sec", 0.0)
                        result.update(
                            {k: v for k, v in fit_m.items()
                             if k.startswith("collective_")})
                    if (mgr is not None and last_saved[0] == step_counter[0]
                            and epoch + 1 < cfg.num_epochs):
                        # A checkpoint landed exactly on this epoch's last
                        # step: roll the sidecar to the next epoch so resume
                        # starts there instead of decode-skipping a fully
                        # trained epoch.
                        progress["epoch"] = epoch + 1
                        progress["epoch_start"] = step_counter[0]
                        _write_resume_meta(
                            cfg.model_dir, _meta(step_counter[0], False))
                    if va_files and not eval_throttled:
                        ev = _run_eval(state, f"epoch {epoch + 1} eval")
                        ulog.info(
                            f"epoch {epoch + 1}/{cfg.num_epochs}: eval auc="
                            f"{ev['auc']:.5f} loss={ev['loss']:.5f}")
                        result.update({"auc": ev["auc"], "eval_loss": ev["loss"],
                                       "eval_examples_per_sec":
                                           ev["examples_per_sec"]})
                        result.update({k: v for k, v in ev.items()
                                       if k.startswith("auc_")})
                        _tb_eval(ev, state)
                if va_files and eval_throttled:
                    # Final eval at completion (train_and_evaluate does one).
                    ev = _run_eval(state, "final eval")
                    ulog.info(f"final eval: auc={ev['auc']:.5f} "
                              f"loss={ev['loss']:.5f}")
                    result.update({"auc": ev["auc"], "eval_loss": ev["loss"],
                                   "eval_examples_per_sec":
                                       ev["examples_per_sec"]})
                    result.update({k: v for k, v in ev.items()
                                   if k.startswith("auc_")})
                    _tb_eval(ev, state)
        finally:
            tracer.close()
            if publisher is not None:
                publisher.close()
            if online_stream[0] is not None:
                online_stream[0].close()
                online_stream[0] = None
        if mgr is not None:
            final_step = int(state.step)
            mgr.save(final_step, _ckpt_state(trainer, state), force=True)
            _write_resume_meta(cfg.model_dir, _meta(final_step, True))
        return state

    try:
        while True:
            try:
                state = _attempt(state)
                break
            except guard_lib.RollbackSignal as rs:
                # on_nonfinite=rollback: restore the latest checkpoint and
                # replay from its recorded offset. The guard's shared event
                # budget (max_rollbacks, spanning skips AND rollbacks)
                # already bounded how often we can get here — a run whose
                # data keeps poisoning the same step exhausts it and aborts.
                if mgr is None or mgr.latest_step() is None:
                    raise guard_lib.NonFiniteError(
                        f"rollback requested at step {rs.step} but no "
                        f"checkpoint exists to roll back to (set model_dir "
                        f"or use on_nonfinite=skip)") from rs
                train_health.record_rollback()
                mgr.wait()  # an async interval save may still be landing
                state = mgr.restore(trainer.init_state())
                ulog.warning(
                    f"rolled back: restored checkpoint step "
                    f"{int(state.step)} after non-finite at step {rs.step}; "
                    f"replaying from the recorded offset")
        _log_train_health("run end")
        _tb_health(int(state.step))
    finally:
        tb.close()
        if mgr is not None:
            mgr.close()

    if cfg.servable_model_dir and bootstrap.is_chief():
        out = fileio.join(cfg.servable_model_dir, str(int(state.step)))
        export_lib.export_serving(
            trainer.model, _servable_state(trainer, state), cfg, out)
        result["saved_model"] = export_lib.saved_model_status(out)
    result["steps"] = float(int(state.step))
    result["read_retries"] = float(health_totals.get("read_retries", 0))
    result["bad_records"] = float(health_totals.get("bad_records", 0))
    for name, v in train_health.snapshot().items():
        result[name] = float(v)
    return result


def _task_eval(trainer: Trainer, cfg: Config) -> Dict[str, float]:
    _, eval_dir = resolve_channel_dirs(cfg)
    va_files = resolve_files(eval_dir, "va")
    if not va_files:
        raise FileNotFoundError("no eval tfrecords found")
    state = _restore_or_init(trainer, cfg, require=True)
    ev = trainer.evaluate(state, _eval_pipeline(cfg, va_files))
    ulog.info(f"eval: auc={ev['auc']:.5f} loss={ev['loss']:.5f}")
    return ev


def _interleave_rank_shards(gathered: np.ndarray, counts: np.ndarray
                            ) -> np.ndarray:
    """Reassemble global record order from per-rank record-sharded results:
    rank r held records r, r+world, r+2*world, ... so global index
    ``i * world + r`` maps to ``gathered[r, i]``. Trailing dims (per-task
    probability columns) carry through unchanged."""
    world = gathered.shape[0]
    out = np.empty((int(counts.sum()),) + gathered.shape[2:],
                   dtype=gathered.dtype)
    for r in range(world):
        n = int(counts[r])
        out[r:(n - 1) * world + r + 1:world] = gathered[r, :n]
    return out


def _task_infer(trainer: Trainer, cfg: Config) -> Dict[str, float]:
    te_files = resolve_files(cfg.val_data_dir or cfg.data_dir, "te")
    if not te_files:
        raise FileNotFoundError("no inference tfrecords found")
    state = _restore_or_init(trainer, cfg, require=True)
    world = jax.process_count()
    rank = jax.process_index()
    local_bs = _local_batch_size(cfg)
    files = tuple(sorted(te_files))
    # Record-level shard: each process predicts every world-th record (wall
    # clock ~1/world of the set) and the chief re-interleaves global order
    # before writing. (The reference had every worker predict the full set,
    # :445-449 — O(world) redundant compute at pod scale.)
    shard = shard_lib.ShardSpec(
        files, record_shard=(world, rank) if world > 1 else None)
    pipeline = pipe_lib.CtrPipeline(
        files, field_size=cfg.field_size, batch_size=local_bs, num_epochs=1,
        shuffle=False, shuffle_files=False, drop_remainder=False,
        seed=cfg.seed, shard=shard, prefetch_batches=cfg.prefetch_batches,
        use_native_decoder=cfg.use_native_decoder,
        reader_threads=cfg.reader_threads, verify_crc=cfg.verify_crc,
        num_labels=cfg.num_tasks, **_fault_tolerance_kwargs(cfg))

    # Collectives inside predict_step require every process to run the same
    # number of rounds, but per-rank record counts can differ by one. Rather
    # than a full counting pre-pass over the data (2x I/O), ranks advance in
    # lockstep rounds (Trainer.lockstep_batches — the same mechanism eval
    # uses); an exhausted rank feeds dummy batches whose output is discarded.
    # Batches are padded to the compiled shape and STREAMED through
    # Trainer.predict, which groups steps_per_loop of them into one stacked
    # transfer + one scanned program (VERDICT r3 #2 — previously one
    # program per batch). ``real_rows`` records each fed batch's true row
    # count; predict preserves per-batch yield order, and it only runs
    # ahead of the consumer by one group, so the list index is always
    # populated before its output arrives.
    probs: List[np.ndarray] = []
    n_local = 0
    real_rows: List[int] = []
    if world > 1:
        from jax.experimental import multihost_utils  # noqa: PLC0415

        from .loop import zero_batch  # noqa: PLC0415

        def make_dummy():
            return zero_batch(cfg.field_size, local_bs,
                              num_labels=cfg.num_tasks)

        def feed():
            # Lockstep rounds keep every rank's fed-stream length identical
            # (dummies where a shard is exhausted), so predict's k-grouping
            # — and therefore its program sequence — aligns across ranks.
            for batch, real in trainer.lockstep_batches(pipeline, make_dummy):
                n = batch["label"].shape[0] if real else 0
                real_rows.append(n)
                yield (pad_batch(batch, local_bs)
                       if real and n < local_bs else batch)

        for i, p in enumerate(trainer.predict(state, feed())):
            n = real_rows[i]
            if n:
                probs.append(p[:n])
                n_local += n
        counts = np.asarray(multihost_utils.process_allgather(
            np.asarray([n_local]))).reshape(-1)
    else:

        def feed():
            for batch in pipeline:
                n = batch["label"].shape[0]
                real_rows.append(n)
                yield (pad_batch(batch, local_bs)  # pad tail, trim after
                       if n < local_bs else batch)

        for i, p in enumerate(trainer.predict(state, feed())):
            n = real_rows[i]
            n_local += n
            probs.append(p[:n])
    # Single-task probs are [n]; multitask [n, T] (one column per task, in
    # cfg.task_names order).
    tail = (cfg.num_tasks,) if cfg.num_tasks > 1 else ()
    local = (np.concatenate(probs) if probs
             else np.zeros((0,) + tail, np.float32)).astype(np.float32)

    if world > 1:
        padded = np.zeros((max(int(counts.max()), 1),) + tail, np.float32)
        padded[:len(local)] = local
        gathered = np.asarray(multihost_utils.process_allgather(padded))
        all_probs = _interleave_rank_shards(gathered, counts)
    else:
        all_probs = local

    out_path = fileio.join(cfg.val_data_dir or cfg.data_dir, "pred.txt")
    if bootstrap.is_chief():
        with fileio.open_stream(out_path, "w") as f:
            # One line per record (ref :447-449); multitask writes one
            # space-separated column per task.
            for p in all_probs:
                row = np.atleast_1d(p)
                f.write(" ".join(f"{float(v):.6f}" for v in row) + "\n")
        ulog.info(f"wrote {len(all_probs)} predictions to {out_path}")
    return {"num_predictions": float(len(all_probs))}


def _task_export(trainer: Trainer, cfg: Config) -> Dict[str, float]:
    if not cfg.servable_model_dir:
        raise ValueError("export task requires servable_model_dir")
    state = _restore_or_init(trainer, cfg, require=True)
    result: Dict[str, float] = {"step": float(int(state.step))}
    if bootstrap.is_chief():
        out = fileio.join(cfg.servable_model_dir, str(int(state.step)))
        export_lib.export_serving(
            trainer.model, _servable_state(trainer, state), cfg, out)
        result["saved_model"] = export_lib.saved_model_status(out)
    return result
