"""Serving export: the SavedModel-analog artifact for TPU-native serving.

The reference exports a SavedModel with a raw serving signature
``{feat_ids: int64[None,F], feat_vals: float32[None,F]} -> {prob}``
(``1-ps-cpu/...py:451-467``, PREDICT branch ``:234-241``), chief/rank-0 only.

Here the servable artifact is a directory containing:
  * ``serving_fn.stablehlo`` — the predict function serialized with
    ``jax.export`` (StableHLO, batch-dim symbolic, lowered for CPU+TPU in
    one module, so an artifact written on either platform serves on both)
  * ``params.ckpt/`` — the inference parameters (Orbax standard format)
  * ``model_config.json`` — the model hyperparameters + signature schema

``load_serving`` reloads the artifact into a callable — the TF-Serving
round-trip analog used by tests and the infer benchmark.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jax_export

from ..config import Config
from ..data import fileio
from ..obs import startup
from . import logging as ulog

with startup.importing("orbax.checkpoint"):   # whichever of the two is first
    import orbax.checkpoint as ocp

from . import checkpoint as ckpt_lib  # noqa: E402

_SERVING_FILE = "serving_fn.stablehlo"
_PARAMS_DIR = "params.ckpt"
_CONFIG_FILE = "model_config.json"
_SAVEDMODEL_DIR = "saved_model"

# Platforms every artifact is lowered for, whichever backend writes it.
SERVING_PLATFORMS = ("cpu", "tpu")

# Written LAST by export_serving: its presence certifies every other file in
# the artifact dir is complete. load_serving refuses dirs without it — a
# crashed or in-flight export must fail with a typed error, not a cryptic
# deserialization traceback halfway through restore.
COMPLETE_MARKER = "ARTIFACT_COMPLETE"

# Pointer file maintained next to published artifact dirs: its content is
# the basename of the newest complete artifact. Updated via write_atomic so
# readers only ever see a fully-published version.
LATEST_FILE = "LATEST"


class ArtifactIncomplete(RuntimeError):
    """A servable artifact dir is missing its completion marker (export
    crashed mid-write, or the caller raced an in-flight publish) or the
    serialized serving function the marker certifies."""


def _task_names(model) -> Tuple[str, ...]:
    return tuple(getattr(model, "task_names", ()) or ())


def _serving_hist_len(model, cfg: Config) -> int:
    """History columns in the serving signature: > 0 only for sequence
    models exported from a history-enabled config."""
    if getattr(model, "uses_history", False) and cfg.history_max_len > 0:
        return int(cfg.history_max_len)
    return 0


def serving_input_cols(model, cfg: Config) -> int:
    """Width of the artifact's feat_ids/feat_vals inputs. History-aware
    artifacts use the pipeline's packed-column convention — ids carry
    ``feat_ids ‖ hist_ids`` and vals carry ``feat_vals ‖ hist_mask``, width
    ``field_size + history_max_len`` — so the whole engine stack (buckets,
    padded_predict, dynamic batcher) serves them unchanged."""
    return cfg.field_size + _serving_hist_len(model, cfg)


def _serving_fn(model, cfg: Config) -> Callable:
    """Single-task: ``probs`` float32[B] (the reference signature, kept
    bit-for-bit). Multitask: ``{task_name: float32[B]}`` — one named
    probability head per task, in the model's declared task order.
    History-aware models split the packed input columns back into
    (feat, hist) before apply."""
    names = _task_names(model)
    multitask = len(names) > 1
    hist_len = _serving_hist_len(model, cfg)
    fs = cfg.field_size

    def serve(params, model_state, feat_ids, feat_vals):
        kwargs = {}
        if hist_len:
            kwargs = {"hist_ids": feat_ids[:, fs:].astype(jnp.int32),
                      "hist_mask": feat_vals[:, fs:].astype(jnp.float32)}
            feat_ids = feat_ids[:, :fs]
            feat_vals = feat_vals[:, :fs]
        logits, _ = model.apply(
            params, model_state, feat_ids.astype(jnp.int32),
            feat_vals.astype(jnp.float32), train=False, rng=None,
            shard_axis=None, data_axis=None, **kwargs)
        if multitask:
            probs = model.probs_from_logits(logits)  # [B, T]
            return {name: probs[:, t] for t, name in enumerate(names)}
        return jax.nn.sigmoid(logits)
    return serve


def export_serving(model, state, cfg: Config, out_dir: str) -> str:
    """Write the servable artifact; returns the artifact path.

    Chief-only by caller convention (reference rank-0 export,
    ``2-hvd-gpu/...py:429-431``). Params are fetched to host and saved
    unsharded so any single-device server can load them.
    """
    fileio.makedirs(out_dir)

    # 1. Params (device-gathered, unsharded).
    params = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state.params)
    model_state = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x)), state.model_state)
    ckptr = ocp.StandardCheckpointer()
    params_path = fileio.join(fileio.normalize_dir(out_dir), _PARAMS_DIR)
    with ckpt_lib.ORBAX_SAVE_SETUP:  # see its comment: publisher thread here
        ckptr.save(params_path,
                   {"params": params, "model_state": model_state}, force=True)
    ckptr.wait_until_finished()

    # 2. Serialized serving function with symbolic batch dim. History-aware
    # models take packed columns (field_size + history_max_len wide).
    serve = _serving_fn(model, cfg)
    in_cols = serving_input_cols(model, cfg)
    b = jax_export.symbolic_shape("b")[0]
    ids_spec = jax.ShapeDtypeStruct((b, in_cols), jnp.int32)
    vals_spec = jax.ShapeDtypeStruct((b, in_cols), jnp.float32)
    params_spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    mstate_spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), model_state)
    # Lowered for both platforms whichever one exports: trainers publish on
    # the accelerator, and tests, drills and CPU frontends load the same
    # artifact. A lowering failure propagates — a params-only artifact would
    # serve through a different program than the one that was exported.
    exported = jax_export.export(
        jax.jit(serve), platforms=SERVING_PLATFORMS)(
            params_spec, mstate_spec, ids_spec, vals_spec)
    with fileio.open_stream(fileio.join(out_dir, _SERVING_FILE), "wb") as f:
        f.write(exported.serialize())

    # 3. TF SavedModel (optional): the reference's actual serving artifact
    # (``export_savedmodel`` with the raw feat_ids/feat_vals signature,
    # ``1-ps-cpu/...py:458-467``) — a user's existing TF-Serving deployment
    # can load this directly. Emitted via jax2tf when TF is importable, for
    # the same platform set as the StableHLO file; what became of it is
    # recorded in the metadata below (and from there in the task result).
    saved_model = _export_tf_savedmodel(serve, params, model_state, cfg,
                                        out_dir, in_cols=in_cols)

    # 4. Signature/config metadata. Single-task keeps the historical "prob"
    # output name; multitask artifacts advertise one output per task name.
    names = _task_names(model)
    outputs = ({name: ["batch", "float32"] for name in names}
               if len(names) > 1 else {"prob": ["batch", "float32"]})
    meta = {
        "signature": {
            "inputs": {
                "feat_ids": ["batch", in_cols, "int32"],
                "feat_vals": ["batch", in_cols, "float32"],
            },
            "outputs": outputs,
        },
        "model": cfg.model,
        "history_len": _serving_hist_len(model, cfg),
        "config": cfg.to_dict(),
        "step": int(jax.device_get(state.step)),
        "saved_model": saved_model,
    }
    with fileio.open_stream(fileio.join(out_dir, _CONFIG_FILE), "w") as f:
        json.dump(meta, f, indent=2)

    # 5. Completion marker — strictly last, atomically: the artifact is not
    # loadable until every byte above it is on disk.
    fileio.write_atomic(fileio.join(out_dir, COMPLETE_MARKER),
                        json.dumps({"step": meta["step"]}))
    ulog.info(f"exported servable model to {out_dir}")
    return out_dir


def _export_tf_savedmodel(serve: Callable, params, model_state, cfg: Config,
                          out_dir: str,
                          in_cols: Optional[int] = None) -> str:
    """Write ``<out_dir>/saved_model`` loadable by TF Serving / tf.saved_model;
    returns what happened (``"written"`` or ``"skipped: <why>"``).

    The serving signature mirrors the reference exactly: inputs
    ``feat_ids`` int64[None, F] / ``feat_vals`` float32[None, F] (int64 per
    the reference's raw placeholders, ``1-ps-cpu/...py:458-461``), output
    ``prob`` float32[None].

    Weights are held as ``tf.Variable``s on the module (the jax2tf
    deployment pattern), NOT closed over as Python values — closure would
    freeze the embedding table into GraphDef constants and hit the 2GB
    proto limit at CTR scale. The function is lowered for
    ``SERVING_PLATFORMS``, not for whatever backend exports it: a sidecar
    written on a TPU host must load in a CPU TF-Serving. A lowering or
    write failure propagates; the sidecar is skipped only where it cannot
    exist — the seam is set, TensorFlow is not installed, or TF's
    filesystem layer does not know the destination's scheme.
    """
    if os.environ.get("DEEPFM_TPU_SKIP_TF_EXPORT", ""):
        # Drill/test seam (docs/TUNING.md seam table): the TF SavedModel
        # sidecar costs ~10s per publish and the jax-native serving runtime
        # never reads it — subprocess drills set this to keep the publish
        # cadence realistic. Production publishes leave it unset.
        return "skipped: DEEPFM_TPU_SKIP_TF_EXPORT is set"
    try:
        import tensorflow as tf  # noqa: PLC0415 (lazy, heavy)
        from jax.experimental import jax2tf  # noqa: PLC0415
    except ImportError as e:  # pragma: no cover - env without TF
        return f"skipped: tensorflow not importable ({e})"
    variables = tf.nest.map_structure(tf.Variable, (params, model_state))
    tf_fn = jax2tf.convert(
        lambda pv, ids, vals: serve(pv[0], pv[1], ids, vals),
        polymorphic_shapes=[None, "(b, _)", "(b, _)"],
        with_gradient=False,
        native_serialization_platforms=SERVING_PLATFORMS)
    module = tf.Module()
    module.model_variables = variables  # tracked -> variables shard

    def _sig_out(feat_ids, feat_vals):
        out = tf_fn(variables, tf.cast(feat_ids, tf.int32), feat_vals)
        # Multitask serve fns already return a {task: probs} dict;
        # single-task keeps the reference's "prob" key.
        return out if isinstance(out, dict) else {"prob": out}

    cols = in_cols if in_cols is not None else cfg.field_size
    module.f = tf.function(
        _sig_out,
        input_signature=[
            tf.TensorSpec([None, cols], tf.int64, name="feat_ids"),
            tf.TensorSpec([None, cols], tf.float32, name="feat_vals"),
        ])
    concrete = module.f.get_concrete_function()
    sm_dir = fileio.join(out_dir, _SAVEDMODEL_DIR)
    try:
        tf.saved_model.save(module, sm_dir,
                            signatures={"serving_default": concrete})
    except tf.errors.UnimplementedError as e:
        # Storage scheme TF's filesystem layer doesn't support: a capability
        # gap of the destination, not of the program. (Real I/O errors —
        # permissions, 5xx — are other types and raise.)
        return f"skipped: TF cannot write to this storage scheme ({e})"
    ulog.info(f"wrote TF SavedModel to {sm_dir}")
    return "written"


def saved_model_status(artifact_dir: str) -> str:
    """What ``export_serving`` recorded about the TF SavedModel sidecar of
    ``artifact_dir`` (``"written"`` or ``"skipped: <why>"``)."""
    with fileio.open_stream(fileio.join(artifact_dir, _CONFIG_FILE), "r") as f:
        return json.load(f)["saved_model"]


# --------------------------------------------------------------------------
# Bucketed prediction: the explicit per-shape compile cache
# --------------------------------------------------------------------------
#
# Both reload paths below compile one program per distinct batch shape they
# see (``exported.call`` specializes the symbolic batch dim per concrete
# shape; ``jax.jit`` caches per shape) — an implicit, unbounded compile
# cache. A serving engine flushing arbitrary batch sizes would compile
# arbitrarily many variants; bucketing makes the cache explicit and bounded:
# every call pads to the next bucket size, so at most ``len(buckets)``
# programs ever compile, and which sizes compile is a deployment decision
# instead of an accident of traffic.

def serving_buckets(max_batch: int) -> Tuple[int, ...]:
    """Power-of-two bucket ladder ``(1, 2, 4, ..., max_batch)``.

    ``max_batch`` itself is always the last bucket, even when it is not a
    power of two — the engine's largest flush must have a home.
    """
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    buckets = []
    b = 1
    while b < max_batch:
        buckets.append(b)
        b <<= 1
    buckets.append(int(max_batch))
    return tuple(buckets)


def next_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= ``n`` (buckets ascending)."""
    if n < 1:
        raise ValueError(f"batch of {n} rows cannot be bucketed")
    for b in buckets:
        if b >= n:
            return int(b)
    raise ValueError(
        f"batch of {n} rows exceeds the largest bucket ({buckets[-1]}); "
        "raise serve_max_batch or split the request")


def padded_predict(fn: Callable[[np.ndarray, np.ndarray], np.ndarray],
                   feat_ids: np.ndarray, feat_vals: np.ndarray,
                   buckets: Sequence[int]) -> np.ndarray:
    """Run ``fn`` on the batch padded up to its bucket; return the real rows.

    Pad rows are zeros (id 0 is a valid embedding row; the serve path runs
    ``train=False`` so no batch statistic couples rows) and their outputs
    are sliced away before returning — output is row-for-row equal to the
    unpadded call (pinned by ``tests/test_serving.py``).
    """
    n = int(feat_ids.shape[0])
    b = next_bucket(n, buckets)
    if b == n:
        out = fn(feat_ids, feat_vals)
        if isinstance(out, dict):  # multitask: {task: probs}
            return {k: np.asarray(v) for k, v in out.items()}
        return np.asarray(out)
    ids = np.zeros((b,) + feat_ids.shape[1:], feat_ids.dtype)
    vals = np.zeros((b,) + feat_vals.shape[1:], feat_vals.dtype)
    ids[:n] = feat_ids
    vals[:n] = feat_vals
    out = fn(ids, vals)
    if isinstance(out, dict):
        return {k: np.asarray(v)[:n] for k, v in out.items()}
    return np.asarray(out)[:n]


class BucketedPredict:
    """``load_serving``-shaped callable with the bounded compile cache.

    Wraps a raw ``f(feat_ids, feat_vals) -> probs`` so only bucket shapes
    ever reach it. ``calls_per_bucket`` is observability for the serving
    stats (which bucket a deployment actually exercises).
    """

    def __init__(self, fn: Callable, buckets: Sequence[int]):
        bs = tuple(sorted({int(b) for b in buckets}))
        if not bs or bs[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        self.fn = fn
        self.buckets = bs
        self.calls_per_bucket: Dict[int, int] = {b: 0 for b in bs}

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    def __call__(self, feat_ids: np.ndarray,
                 feat_vals: np.ndarray) -> np.ndarray:
        self.calls_per_bucket[next_bucket(len(feat_ids), self.buckets)] += 1
        return padded_predict(self.fn, feat_ids, feat_vals, self.buckets)


def load_serving(artifact_dir: str, *,
                 buckets: Optional[Sequence[int]] = None
                 ) -> Callable[[np.ndarray, np.ndarray], np.ndarray]:
    """Reload a servable artifact as ``f(feat_ids, feat_vals) -> probs``.

    With ``buckets`` the result is a :class:`BucketedPredict` — every call
    pads to the next bucket size so at most ``len(buckets)`` predict
    programs ever compile (the serving engine's shape policy).

    Raises :class:`ArtifactIncomplete` when the dir lacks its completion
    marker — the dir is mid-write, or an export crashed into it — or its
    serialized serving function. Callers that poll (``watch_latest``) treat
    this as "try again later"; everything else should treat it as a corrupt
    deployment.
    """
    if not fileio.exists(fileio.join(artifact_dir, COMPLETE_MARKER)):
        raise ArtifactIncomplete(
            f"{artifact_dir} has no {COMPLETE_MARKER} marker — the artifact "
            "is incomplete (crashed or in-flight export); refusing to load")
    with fileio.open_stream(fileio.join(artifact_dir, _CONFIG_FILE), "r") as f:
        meta = json.load(f)
    ckptr = ocp.StandardCheckpointer()
    restored = ckptr.restore(
        fileio.join(fileio.normalize_dir(artifact_dir), _PARAMS_DIR))
    params, model_state = restored["params"], restored["model_state"]

    hlo_path = fileio.join(artifact_dir, _SERVING_FILE)
    if not fileio.exists(hlo_path):
        # export_serving writes the program or raises, so a marker without
        # it is a damaged artifact. Rebuilding a predict function from the
        # config would serve through a program nobody exported.
        raise ArtifactIncomplete(
            f"{artifact_dir} has no {_SERVING_FILE}; refusing to serve a "
            "program rebuilt from its config")
    with fileio.open_stream(hlo_path, "rb") as f:
        exported = jax_export.deserialize(f.read())

    def serve(feat_ids: np.ndarray, feat_vals: np.ndarray) -> np.ndarray:
        out = exported.call(
            params, model_state, feat_ids.astype(np.int32),
            feat_vals.astype(np.float32))
        if isinstance(out, dict):  # multitask: {task: probs}
            return {k: np.asarray(v) for k, v in out.items()}
        return np.asarray(out)

    # Traceable predict for callers that fuse the ranker into a larger
    # jitted program (the cascade fast path): ``exported.call`` is
    # jax-traceable, so this composes under an outer ``jax.jit``.
    # Inputs must already be int32/float32 tracers of a bucket shape.
    def raw_call(feat_ids, feat_vals):
        return exported.call(params, model_state, feat_ids, feat_vals)

    # Input width from the signature metadata: what a pre-warm caller (the
    # hot-swap watcher) needs to drive every bucket shape before the swap.
    in_cols = int(meta["signature"]["inputs"]["feat_ids"][1])
    serve.input_cols = in_cols
    serve.raw_call = raw_call
    if buckets is not None:
        wrapped = BucketedPredict(serve, buckets)
        wrapped.input_cols = in_cols
        wrapped.raw_call = raw_call
        return wrapped
    return serve


# --------------------------------------------------------------------------
# LATEST pointer + hot-swap consumer
# --------------------------------------------------------------------------

def write_latest(publish_dir: str, version: str) -> None:
    """Point ``<publish_dir>/LATEST`` at artifact dir ``version`` (basename).
    Atomic: a crashed update leaves the previous pointer intact."""
    fileio.write_atomic(fileio.join(publish_dir, LATEST_FILE), str(version))


def read_latest(publish_dir: str) -> Optional[str]:
    """Full path of the newest published artifact, or None when no pointer
    exists yet (or it dangles — points at a dir that is gone)."""
    pointer = fileio.join(publish_dir, LATEST_FILE)
    if not fileio.exists(pointer):
        return None
    with fileio.open_stream(pointer, "rb") as f:
        version = f.read().decode("utf-8").strip()
    if not version:
        return None
    path = fileio.join(publish_dir, version)
    return path if fileio.exists(path) else None


# Append-only audit sidecar next to LATEST: one JSON line per pointer move
# (publish / promote / rollback, plus quarantine bookkeeping), so every
# deployment decision is replayable from the publish dir alone.
POINTER_HISTORY_FILE = "pointer_history.jsonl"


def append_pointer_event(publish_dir: str, version: str, actor: str,
                         reason: str = "", *,
                         wall_time: Optional[float] = None) -> Dict[str, Any]:
    """Append one pointer-history event; returns the entry written (or the
    existing tail entry when this is a replay).

    Idempotent by design: the write protocol everywhere in this repo is
    *append history, then move the pointer* — a crash between the two means
    the healing retry re-runs both steps, so an append whose
    ``(version, actor, reason)`` exactly matches the current tail entry is
    skipped instead of duplicated. ``wall_time`` is injectable (the drill
    passes its logical clock; audit fingerprints exclude it either way).
    """
    entry = {"version": str(version), "actor": str(actor),
             "reason": str(reason),
             "wall_time": float(wall_time if wall_time is not None
                                else time.time())}
    path = fileio.join(publish_dir, POINTER_HISTORY_FILE)
    history = pointer_history(publish_dir)
    if history:
        tail = history[-1]
        if (tail.get("version") == entry["version"]
                and tail.get("actor") == entry["actor"]
                and tail.get("reason") == entry["reason"]):
            return tail
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(entry, sort_keys=True,
                           separators=(",", ":")) + "\n")
        f.flush()
        os.fsync(f.fileno())
    return entry


def pointer_history(publish_dir: str) -> list:
    """All pointer-history events, oldest first. Tolerant of a torn final
    line (a crash mid-append): the unparseable tail is dropped, matching
    the heal contract — the retried append rewrites it whole."""
    path = fileio.join(publish_dir, POINTER_HISTORY_FILE)
    if not os.path.exists(path):
        return []
    out = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                break   # torn tail — everything after it is suspect
    return out


# The pointer reader's companion (satellite contract: reading the pointer
# and reading its provenance are one surface).
read_latest.history = pointer_history


class LatestWatcher:
    """Hot-swap serving consumer: follow ``LATEST`` without dropping requests.

    Callable with the same ``(feat_ids, feat_vals) -> probs`` signature as
    :func:`load_serving`'s result. A poll (background thread, or
    :meth:`check_once` for callers that drive it themselves) notices a new
    ``LATEST`` pointer, loads the NEW artifact completely off to the side,
    then swaps it in with one attribute assignment — requests in flight keep
    executing the old function; requests after the swap get the new one; no
    request ever observes a half-loaded model. A load failure (incomplete or
    vanished artifact — e.g. the watcher raced a publish) keeps the current
    model and retries next poll.
    """

    def __init__(self, publish_dir: str, *, poll_secs: float = 2.0,
                 on_swap: Optional[Callable[[str], None]] = None,
                 on_error: Optional[Callable[[BaseException], None]] = None,
                 loader: Callable[[str], Callable] = load_serving,
                 start: bool = True,
                 prewarm: bool = True,
                 sleep: Optional[Callable[[float], None]] = None):
        self._publish_dir = publish_dir
        self._poll_secs = float(poll_secs)
        self._on_swap = on_swap
        self._on_error = on_error
        self._loader = loader
        self._prewarm = bool(prewarm)
        # Guards the (fn, current_path, swap_count) triple so current()
        # returns a CONSISTENT snapshot: a pipelined serving engine stamps
        # each flush with the version that executed it (the blackout
        # measure), and a torn read (new fn, old count) would mislabel the
        # first post-swap flush as pre-swap.
        self._swap_lock = threading.Lock()
        self._stop = threading.Event()
        self._sleep = sleep if sleep is not None else self._stop.wait
        self._fn: Optional[Callable] = None
        self.current_path: Optional[str] = None
        self.swap_count = 0
        # Buckets compiled off-thread before each swap (observability for
        # the blackout drill: prewarmed > 0 means the first post-swap
        # request of any bucket shape hits a warm compile cache).
        self.prewarmed_buckets = 0
        # Failed swap attempts (torn/marker-less/vanished artifact seen at
        # LATEST): the current model stayed live each time. A counter, not
        # just a warning — a serving drill asserting "zero dropped requests
        # across N swaps" also wants to know how many swaps never happened.
        self.swap_failures = 0
        # Unexpected poll-loop exceptions (loader bugs, filesystem faults
        # outside the anticipated ArtifactIncomplete/OSError/ValueError
        # classes). The poll thread NEVER dies on these — it keeps serving
        # the current model and retries — but dying silently and counting
        # are different things: this is the counter, surfaced through
        # ``ServingStats`` so a drill (or production alerting) can see a
        # watcher that is alive but failing.
        self.watcher_errors = 0
        self._thread: Optional[threading.Thread] = None
        self.check_once()
        if start:
            self._thread = threading.Thread(
                target=self._run, name="latest-watcher", daemon=True)
            self._thread.start()

    def check_once(self) -> bool:
        """Poll LATEST; swap if it moved. Returns True iff a swap happened."""
        path = read_latest(self._publish_dir)
        if path is None or path == self.current_path:
            return False
        try:
            fn = self._loader(path)
            if self._prewarm:
                self._warm_buckets(fn)
        except (ArtifactIncomplete, OSError, ValueError) as e:
            self.swap_failures += 1
            ulog.warning(f"hot-swap to {path} deferred ({e}); "
                         "keeping current model")
            return False
        with self._swap_lock:
            self._fn = fn  # the swap: one reference assignment
            self.current_path = path
            self.swap_count += 1
        if self._on_swap is not None:
            self._on_swap(path)
        return True

    def current(self):
        """Consistent ``(predict_fn, version)`` snapshot, where version is
        the ``swap_count`` that installed the function. Before the first
        artifact loads, the fn slot is the watcher itself (calling it
        raises the typed "no artifact published" error) at version 0. A
        versioned executor (the pipelined serving engine) uses this to
        stamp each flush with the model that actually ran it."""
        with self._swap_lock:
            fn = self._fn if self._fn is not None else self
            return fn, self.swap_count

    def _warm_buckets(self, fn: Callable) -> None:
        """Drive every serving bucket through the NEW function before it is
        swapped in, still off to the side: each bucket's predict program
        compiles here, on the watcher thread, so the swap costs live
        traffic one pointer assignment instead of len(buckets) compiles
        (the near-zero-blackout property the serving drill asserts).
        Needs a bucketed loader result that advertises its input width
        (``load_serving(buckets=...)`` does); anything else warms nothing."""
        buckets = getattr(fn, "buckets", None)
        cols = getattr(fn, "input_cols", None)
        if not buckets or not cols:
            return
        for b in buckets:
            fn(np.zeros((int(b), int(cols)), np.int32),
               np.zeros((int(b), int(cols)), np.float32))
            self.prewarmed_buckets += 1

    def _run(self) -> None:
        while not self._stop.is_set():
            self._sleep(self._poll_secs)
            if self._stop.is_set():
                return
            try:
                self.check_once()
            except Exception as e:  # never kill the serving thread
                self.watcher_errors += 1
                ulog.warning(f"LATEST poll failed ({e}); retrying")
                if self._on_error is not None:
                    try:
                        self._on_error(e)
                    except Exception:
                        pass

    def __call__(self, feat_ids: np.ndarray,
                 feat_vals: np.ndarray) -> np.ndarray:
        fn = self._fn
        if fn is None:
            raise RuntimeError(
                f"no artifact published under {self._publish_dir} yet")
        return fn(feat_ids, feat_vals)

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)


def watch_latest(publish_dir: str, **kwargs) -> LatestWatcher:
    """``load_serving`` that follows the LATEST pointer: returns a callable
    that hot-swaps to each newly published artifact without dropping a
    request. See :class:`LatestWatcher` (kwargs forwarded)."""
    return LatestWatcher(publish_dir, **kwargs)
