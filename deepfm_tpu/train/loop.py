"""Trainer: jitted/shard_mapped train-eval-predict step functions + fit loop.

TPU-native replacement for the reference's Estimator driver (L3):

  * One *synchronous SPMD* mechanism replaces both reference backends: the
    step function is ``shard_map``-ped over the ``('data','model')`` mesh —
    gradients are ``pmean``-ed over 'data' (vs Horovod's NCCL ring allreduce,
    X2) and embedding lookups are masked-gather + ``psum`` over 'model'
    row-shards (vs the gRPC parameter server, X1). On one device it's a plain
    ``jax.jit``.
  * Replicated initialization from one PRNG key == Horovod's
    ``BroadcastGlobalVariablesHook(0)`` (reference 2-hvd-gpu/...py:372).
  * Everything under jit is static-shaped; one compiled program per task.

The fit loop feeds host batches via ``jax.make_array_from_process_local_data``
(multi-host-correct) and logs loss/examples-per-sec every ``log_steps``
(reference flag :47).
"""

from __future__ import annotations

import math
import queue
import threading
import time
from typing import (Any, Callable, Dict, Iterable, Iterator, List, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..obs import startup

# Over a second on the chip's host (PERF.md §5): stamped by name.
with startup.importing("optax"):
    import optax

from ..config import Config
from ..obs import trace as trace_lib
from ..ops import embedding as emb_ops
from ..ops import pallas_embedding as pemb
from ..ops import pallas_put_rows
from ..parallel import mesh as mesh_lib
from ..utils import logging as ulog
from ..utils import profiling as prof_lib
from . import guard as guard_lib
from . import metrics as metrics_lib
from . import optimizers as opt_lib
from .state import TrainState

with startup.importing("deepfm_tpu.models"):   # the zoo: every model's module
    from ..models import get_model


# Stand-in device memory for the CPU backend (which reports none): a test
# fixture for the device-dataset budget check, never a claim about a device.
_CPU_TEST_MEMORY_BYTES = 16 << 30

# Distinct rows one trip of the row-local table update gathers, updates and
# writes back (``Trainer._update_rows``): a batch with more distinct rows
# takes another trip. Chosen on the chip, once for each way the rows go
# back. By XLA's scatter (PERF.md §6, PR 28): a trip costs its slots, filled
# or spare (a dropped row costs 0.87 of a written one) and nothing beside,
# so a small capacity only wastes less of the last trip (15 trips of 2,048
# read 15.86 ms a step where 1 of 32,768 reads 16.16). By the
# ``embed_put_rows`` kernel (PERF.md §6, PR 30): the same holds — a spare
# slot costs the kernel's loop what a written one does, a launch nothing
# that shows (15 trips of 2,048 read 12.33 ms a step; 8 / 4 / 2 trips of
# 4,096 / 8,192 / 16,384, 32,768 slots each, 12.50 / 12.49 / 12.49; read
# before the new rows were held in HBM, which took 0.54 ms off every one)
# — so both share the one constant.
ROW_UPDATE_CAPACITY = 2048
#: What that update reports beside the step's loss: distinct rows, trips.
#: A dense-gradient step that builds its table gradient from rows
#: (``Trainer._grad_by_rows``) reports the same two.
ROW_COUNTS = ("embed_distinct_rows", "embed_row_trips")
#: Under data replicas that step exchanges its rows (``_table_grads``) and
#: reports a third: the rows every chip scatter-added, all replicas' together.
EXCHANGED_ROWS = "embed_exchanged_rows"
#: And how its rows went back: "dma", "scatter", or "dma+scatter" where a
#: model's tables differ in row shape (``Trainer.row_writeback``).
ROW_WRITEBACK = "embed_row_writeback"
#: How a dense-gradient step made its table-shaped gradient: "rows" (the
#: batch's distinct rows, each the sum of its positions' cotangents), "rows,
#: exchanged over data" (the same under data replicas: every chip scatters
#: all replicas' rows, no wide table crosses the interconnect) or "positions"
#: (AD's scatter-add of every position); ``Trainer.embed_grad``.
EMBED_GRAD = "embed_grad"
#: And which tables' gradient that step sums over the data replicas as a
#: table, names joined by "," ("" where none does: one device, or every
#: table's rows exchanged): under the exchange the tables whose row is one
#: word, whose all-reduce costs less than their slots in the trips; with the
#: tables left to AD every one; ``Trainer.embed_grad_by_table``.
EMBED_GRAD_BY_TABLE = "embed_grad_by_table"
#: How a step that differentiates its tables' ``[B, F, ...]`` views (that
#: one, and the row-local update) read them: "rows" (each distinct row of the
#: batch gathered from its table once, the positions copied from those),
#: "positions" (one table row gathered a position), or per table
#: ("fm_w:rows,fm_v:positions") where a model's tables differ in row shape;
#: ``Trainer.embed_lookup``.
EMBED_LOOKUP = "embed_lookup"


def pad_batch(batch: Dict[str, np.ndarray], bs: int) -> Dict[str, np.ndarray]:
    """Pad a short tail batch up to the compiled shape by repeating the last
    row. Callers either trim the padded rows from the output (predict) or
    mask them with a zero weight (evaluate)."""
    n = batch["label"].shape[0]
    pad = bs - n
    return {k: np.concatenate([v, np.tile(v[-1:], (pad,) + (1,) * (v.ndim - 1))])
            for k, v in batch.items()}


def zero_batch(field_size: int, bs: int, num_labels: int = 1,
               hist_len: int = 0) -> Dict[str, np.ndarray]:
    """All-zero batch with the canonical CTR schema — the single source of
    the batch keys/dtypes for dummy (lockstep filler) batches. Multi-task
    runs carry a second label column (``label2``); history runs carry the
    fixed-shape ``hist_ids``/``hist_mask`` pair (all-masked here, so the
    attention blocks see an empty history)."""
    batch = {
        "feat_ids": np.zeros((bs, field_size), np.int32),
        "feat_vals": np.zeros((bs, field_size), np.float32),
        "label": np.zeros((bs, 1), np.float32),
    }
    if num_labels > 1:
        batch["label2"] = np.zeros((bs, 1), np.float32)
    if hist_len > 0:
        batch["hist_ids"] = np.zeros((bs, hist_len), np.int32)
        batch["hist_mask"] = np.zeros((bs, hist_len), np.float32)
    return batch


def _with_weight(batch: Dict[str, np.ndarray], bs: int) -> Dict[str, np.ndarray]:
    """Attach a per-row validity weight and pad to the compiled batch shape.
    Real rows weigh 1, padding weighs 0 — the weights flow into the AUC
    histograms and the loss sum, so tail records count exactly once and
    padding not at all."""
    n = batch["label"].shape[0]
    bs = max(bs, n)  # oversize batches pass through un-padded (jit re-specializes)
    w = np.zeros((bs, 1), np.float32)
    w[:n] = 1.0
    if n < bs:
        batch = pad_batch(batch, bs)
    return {**batch, "weight": w}


def _staged_size(args) -> Tuple[int, int]:
    """(records, bytes) of a staged transfer's host-side payload (batch dict
    or list of batch dicts). Records count 0 for layouts without a 'label'
    column (columnar input-service rows)."""
    records = nbytes = 0
    for a in args:
        for b in ([a] if isinstance(a, dict) else
                  a if isinstance(a, (list, tuple)) else ()):
            if isinstance(b, dict):
                nbytes += sum(getattr(v, "nbytes", 0) for v in b.values())
                if "label" in b:
                    records += int(b["label"].shape[0])
    return records, nbytes


class _StagingRing:
    """Bounded device staging area: at most ``n_slots`` superbatches may be
    transferred ahead of the dispatches that consume them (TUNING §2.13).

    The staging thread calls :meth:`put` around each host->device transfer;
    the fit loop calls :meth:`retire` with a device value from each dispatch
    (its readiness marks that dispatch complete ON DEVICE). Transfer j
    fences on dispatch j - n_slots: with 2 slots dispatch k+1's transfer
    runs while dispatch k computes (double buffering), with 1 slot every
    transfer waits out the previous dispatch — H2D serializes with compute
    (the A/B baseline, and the memory floor when two staged superbatches
    don't fit). Purely a scheduling constraint: the trajectory is
    bit-identical across slot counts. The ``stage.wait`` and
    ``stage.transfer`` spans time the fence and the transfer.
    """

    def __init__(self, n_slots: int):
        self.n_slots = max(int(n_slots), 1)
        self._fences: "queue.Queue[Any]" = queue.Queue()
        self._closed = threading.Event()
        # Transfers begun / dispatches retired: the same superbatch gets the
        # same number from both, the ``seq`` its spans share.
        self.staged = 0
        self.dispatched = 0

    def put(self, transfer: Callable[[], Any], n_records: int = 0,
            n_bytes: int = 0) -> Any:
        """Run one transfer under the slot discipline (staging thread)."""
        self.staged += 1
        if self.staged > self.n_slots:
            with trace_lib.span("stage.wait", seq=self.staged):
                fence = None
                # Poll against close so an abandoned fit (exception, early
                # return) can never strand the staging thread on this queue.
                while not self._closed.is_set():
                    try:
                        fence = self._fences.get(timeout=0.1)
                        break
                    except queue.Empty:
                        continue
                if fence is not None:
                    jax.block_until_ready(fence)
        with trace_lib.span("stage.transfer", seq=self.staged,
                            records=n_records, bytes=n_bytes):
            return transfer()

    def retire(self, fence: Any) -> None:
        """Mark one dispatch's slot reusable once ``fence`` is ready
        (fit thread; the fence is any device value the dispatch produced)."""
        self._fences.put(fence)
        self.dispatched += 1

    def close(self) -> None:
        self._closed.set()


class Trainer:
    """Builds and runs the compiled train/eval/predict step functions."""

    def __init__(self, cfg: Config, mesh_info: Optional[mesh_lib.MeshInfo] = None):
        # JAX's compile timings become ``compile.*`` spans from the first
        # program this trainer traces (the entry points' compile-cache
        # set-up registered them already; a caller that built none has not).
        startup.listen_to_jax()
        with startup.phase("setup.trainer", model=cfg.model):
            self._build(cfg, mesh_info)

    def _build(self, cfg: Config,
               mesh_info: Optional[mesh_lib.MeshInfo]) -> None:
        self.cfg = cfg
        self.model = get_model(cfg)
        # Multi-task contract: the model emits [B, T] logits and owns the
        # per-task loss combination; single-task models keep the legacy [B]
        # path byte-for-byte (bit-exactness tests pin it).
        self._task_names = tuple(getattr(self.model, "task_names", ("ctr",)))
        self._multitask = len(self._task_names) > 1
        # A model that owns its loss (a loss over the positions of a
        # sequence: models.sdar_moe) hands the trainer per-example values
        # where a ranker hands it one logit an example.
        self._model_loss = getattr(self.model, "owns_loss", False)
        self.mesh_info = mesh_info if mesh_info is not None else mesh_lib.build_mesh(cfg)
        self.tx = opt_lib.build_optimizer(cfg, world_size=self.mesh_info.data_size)
        self._specs: Optional[Dict[str, Any]] = None
        self._train_step: Optional[Callable] = None
        self._multi_step: Optional[Callable] = None
        self._eval_step: Optional[Callable] = None
        self._eval_multi_step: Optional[Callable] = None
        self._predict_step: Optional[Callable] = None
        self._predict_multi_step: Optional[Callable] = None
        # Device-resident dataset mode: uploaded columns keyed by the
        # decoded-cache fingerprint, and one compiled program per
        # (steps, batch) shape.
        self._dd_cols: Optional[Tuple[str, Dict[str, jax.Array]]] = None
        self._dd_programs: Dict[Tuple[int, int], Callable] = {}
        # on_nonfinite=skip must keep the pre-dispatch state alive to drop a
        # poisoned update, so the step programs cannot donate their input
        # state buffer under that policy (the cost of the safety net; see
        # TUNING §2.8).
        self._donate_state = cfg.on_nonfinite != "skip"
        # Injectable watchdog abort (tests); None = os._exit(EXIT_WATCHDOG).
        self.watchdog_abort: Optional[Callable[[str], None]] = None
        # Sparse (touched-rows-only) embedding updates. Two legs: the
        # single-device jit path, and — with --embedding_shard rows — the
        # row-exchange mesh program (_sharded_sparse_step_impl), where
        # tables and Adam moments live sharded over 'model' and grads sync
        # over 'data' in owner-local table space. A mesh WITHOUT the rows
        # plane still falls back to dense: replicated tables with
        # per-shard sparse plans would desync.
        self.sparse_embed = cfg.embedding_update == "sparse"
        self._shard_rows = cfg.embedding_shard == "rows"
        if (self.sparse_embed and self.mesh_info.mesh is not None
                and not self._shard_rows):
            ulog.warning(
                "embedding_update=sparse under a mesh needs the row "
                "exchange plane (--embedding_shard rows) -> falling back "
                "to dense embedding updates")
            self.sparse_embed = False
        self._embed_names = tuple(self.model.embedding_param_names())
        # Embedding rows follow the same world-LR rule as the optax base
        # optimizer (opt_lib.build_optimizer).
        self._sparse_lr = cfg.learning_rate
        if cfg.scale_lr_by_world and self.mesh_info.data_size > 1:
            self._sparse_lr = cfg.learning_rate * self.mesh_info.data_size
        # Kernel-leg selection for the sparse embedding plane (see
        # ops.pallas_embedding): "off" is the kill switch that also
        # disables the fused one-leaf backward below.
        self._emb_kernels = cfg.embedding_kernels
        # Hot/cold tiered embedding storage (requires the sparse path).
        self._tier: Optional[Any] = None
        if cfg.embedding_tiering == "hot_cold":
            if not self.sparse_embed:
                raise ValueError(
                    "embedding_tiering=hot_cold requires the sparse "
                    "single-device update path (a mesh forced the dense "
                    "fallback)")
            from ..data import hot_cold  # noqa: PLC0415
            self._tier = hot_cold.TieredEmbeddingRuntime(cfg, self.model)
        # Gradient accumulation factor (config-validated; 1 = off). The
        # scanned dispatch regroups its K microbatches into K//a optimizer
        # applies plus K%a single-microbatch full steps for ragged tails.
        self._accum = max(cfg.grad_accum_steps, 1)
        # DCN-aware two-stage gradient reduction over 'data': derived from
        # the mesh's host layout — None on single-host meshes (every
        # virtual mesh included) and on layouts that don't decompose into
        # equal per-host blocks. Tests override this seam to exercise the
        # hierarchical program on a single-host virtual mesh.
        self._hier_groups = mesh_lib.data_axis_host_groups(self.mesh_info)
        # Active fit's device staging ring (slot fences);
        # None outside fit so eval/predict transfers pass through untouched.
        self._ring: Optional[_StagingRing] = None
        self._grad_bytes_cache: Optional[int] = None
        # How the row-local update writes its rows back (ROW_WRITEBACK):
        # static per compiled step, known once ``_update_rows`` is traced.
        self.row_writeback: Optional[str] = None
        # How the dense-gradient step makes its table gradient (EMBED_GRAD):
        # known once ``_dense_value_and_grad`` is traced.
        self.embed_grad: Optional[str] = None
        self.embed_grad_by_table: Optional[str] = None
        # How a step that differentiates the tables' views read them:
        # known once ``_value_and_view_grads`` is traced.
        self.embed_lookup: Optional[str] = None

    # ------------------------------------------------------------------
    # State creation / placement
    # ------------------------------------------------------------------
    def init_state(self, seed: Optional[int] = None, *,
                   tiered: bool = True) -> TrainState:
        """Replicated-by-construction init: every process derives identical
        params from the same seed (broadcast-hook analog).

        ``tiered=False`` skips hot/cold adoption and returns the DENSE
        state — the restore template for tiered runs, whose checkpoints are
        written densified (``TieredEmbeddingRuntime.checkpoint_state``).
        The caller restores into it, then calls ``self._tier.adopt``."""
        seed = self.cfg.seed if seed is None else seed
        rng = jax.random.PRNGKey(seed)
        k_init, k_state = jax.random.split(rng)
        params, model_state = self.model.init(k_init)
        opt_state = self._init_opt_state(params)
        state = TrainState.create(params, opt_state, model_state, k_state)
        state = self._place(state)
        if tiered and self._tier is not None:
            state = self._tier.adopt(state)
        return state

    def _init_opt_state(self, params) -> Any:
        """Dense: the optax state over all params. Sparse: the optax state
        over the NON-embedding params plus per-table lazy-Adam slots
        (m/v/tau) and one global step counter for the embeddings."""
        if not self.sparse_embed:
            return self.tx.init(params)
        rest = {k: v for k, v in params.items()
                if k not in self._embed_names}
        embed = {
            name: {k: opt_lib.embed_adam_init(t)
                   for k, t in self.model.emb.tables(params[name]).items()}
            for name in self._embed_names}
        return {"base": self.tx.init(rest), "embed": embed,
                "count": jnp.zeros((), jnp.int32)}

    def _state_specs(self, state: TrainState) -> TrainState:
        param_specs = mesh_lib.param_pspecs(
            state.params, self.model.embedding_param_names(),
            self.mesh_info.model_size)
        if self.sparse_embed:
            # Sparse opt layout {"base", "embed", "count"}: the lazy-Adam
            # m/v mirror their table's spec; tau is a [rows] int vector
            # that shards with the rows — opt_state_pspecs's shape
            # matching would only catch it by accidental collision with a
            # 1-D param, so the layout is spelled out here.
            emb = self.model.emb
            rest = {k: v for k, v in state.params.items()
                    if k not in self._embed_names}
            rest_specs = {k: param_specs[k] for k in rest}
            row_spec = (P(mesh_lib.MODEL_AXIS)
                        if self.mesh_info.model_size > 1 else P())
            embed_specs = {
                name: {key: opt_lib.EmbedAdamEntry(m=s, v=s, tau=row_spec)
                       for key, s in emb.tables(param_specs[name]).items()}
                for name in self._embed_names}
            opt_specs = {
                "base": mesh_lib.opt_state_pspecs(
                    state.opt_state["base"], rest, rest_specs),
                "embed": embed_specs,
                "count": P(),
            }
        else:
            opt_specs = mesh_lib.opt_state_pspecs(
                state.opt_state, state.params, param_specs)
        mstate_specs = jax.tree.map(lambda _: P(), state.model_state)
        return TrainState(
            step=P(), params=param_specs, opt_state=opt_specs,
            model_state=mstate_specs, rng=P())

    def _state_shardings(self, state: TrainState) -> TrainState:
        """The NamedSharding of every leaf of ``state`` (row-sharded
        embeddings, replicated rest), real or abstract; needs a mesh."""
        mi = self.mesh_info
        return jax.tree.map(lambda _, s: mi.sharding(s), state,
                            self._state_specs(state))

    def _place(self, state: TrainState) -> TrainState:
        """Apply NamedShardings (row-sharded embeddings, replicated rest)."""
        if self.mesh_info.mesh is None:
            return jax.device_put(state)
        return jax.tree.map(jax.device_put, state,
                            self._state_shardings(state))

    def put_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        """Host numpy batch -> device array sharded over the data axis.

        Under multi-process each process passes its local shard of the global
        batch; ``make_array_from_process_local_data`` assembles the global
        array (the pod-sharded tf.data->device-iterator analog, X3)."""
        if self.mesh_info.mesh is None:
            return jax.device_put(batch)
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                self._batch_sharding(x.ndim), x),
            dict(batch))

    def _batch_sharding(self, ndim: int):
        """One batch's arrays: the leading (batch) dim over 'data'."""
        return self.mesh_info.sharding(
            P(mesh_lib.DATA_AXIS, *([None] * (ndim - 1))))

    # ------------------------------------------------------------------
    # Step functions
    # ------------------------------------------------------------------
    def _per_example_loss(self, logits, labels):
        """Per-example loss by cfg.loss_type — the ONE place the loss_type
        branch lives (train takes the mean; eval the weighted sum). Multi-
        task models own their weighted per-task combination ([B,T] -> [B])."""
        if self._multitask:
            return self.model.per_example_loss(logits, labels)
        if self.cfg.loss_type == "log_loss":
            return optax.sigmoid_binary_cross_entropy(logits, labels)
        return jnp.square(jax.nn.sigmoid(logits) - labels)  # square_loss

    def _batch_labels(self, batch):
        """[B] labels (single-task, legacy path) or the [B,T] label matrix:
        task 0 reads ``label``, task 1 the ``label2`` column."""
        if not self._multitask:
            return batch["label"].reshape(-1).astype(jnp.float32)
        cols = [batch["label"].reshape(-1), batch["label2"].reshape(-1)]
        return jnp.stack(cols[:len(self._task_names)],
                         axis=1).astype(jnp.float32)

    @jax.named_scope("loss")
    def _mean_loss(self, logits, batch):
        """Mean per-example loss of one (micro)batch, under the ``loss``
        scope (TUNING §17)."""
        return jnp.mean(self._per_example_loss(
            logits, self._batch_labels(batch)))

    def _hist_kwargs(self, batch):
        """hist_ids/hist_mask forwarding for sequence models: only when the
        model opts in (``uses_history``) AND the batch carries the columns
        (zoo/dummy batches don't — the models then default to an empty
        history). Trace-time pytree-key check, jit-safe."""
        if getattr(self.model, "uses_history", False) and "hist_ids" in batch:
            return {"hist_ids": batch["hist_ids"],
                    "hist_mask": batch["hist_mask"]}
        return {}

    def _loss_terms(self, params, model_state, batch, *, train, rng,
                    shard_axis, data_axis, **emb):
        if self._model_loss:
            per_example, new_mstate = self.model.per_example_loss(
                params, model_state, batch, train=train, rng=rng,
                shard_axis=shard_axis, data_axis=data_axis, **emb)
            return None, jnp.mean(per_example), new_mstate
        logits, new_mstate = self.model.apply(
            params, model_state, batch["feat_ids"], batch["feat_vals"],
            train=train, rng=rng, shard_axis=shard_axis, data_axis=data_axis,
            **self._hist_kwargs(batch), **emb)
        xent = self._mean_loss(logits, batch)
        return logits, xent, new_mstate

    def _step_impl(self, state: TrainState, batch, *, data_axis, shard_axis
                   ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        """One optimizer step (raw, mesh-axis-aware; wrapped by jit/shard_map
        in _make_train_step and scanned in _make_train_multi_step)."""
        if self.sparse_embed:
            if data_axis is None and shard_axis is None:
                return self._sparse_step_impl(state, batch)
            return self._sharded_sparse_step_impl(
                state, batch, data_axis=data_axis, shard_axis=shard_axis)
        rng = jax.random.fold_in(state.rng, state.step)
        if data_axis is not None:
            # Distinct dropout per data shard; identical across model
            # shards (keeps activations replicated over 'model').
            rng = jax.random.fold_in(rng, jax.lax.axis_index(data_axis))

        def data_loss(params, **emb):
            _, xent, new_mstate = self._loss_terms(
                params, state.model_state, batch, train=True, rng=rng,
                shard_axis=shard_axis, data_axis=data_axis, **emb)
            return xent, new_mstate

        counts: Dict[str, jnp.ndarray] = {}
        if self._row_local_eligible():
            l2 = jnp.zeros((), jnp.float32)
            xent, new_mstate, new_params, new_opt, counts = (
                self._row_local_apply(data_loss, state, batch))
        else:
            ids = (self.model.lookup_ids(batch["feat_ids"])
                   if self._grad_by_rows() else None)
            xent, l2, new_mstate, grads, counts = self._dense_value_and_grad(
                data_loss, state.params, data_axis=data_axis,
                shard_axis=shard_axis, ids=ids)
            new_params, new_opt = self._optax_apply(
                grads, state.opt_state, state.params)
        if self._model_loss:
            # the model's counts ride beside the loss; a model whose loss
            # has parts (``loss_parts``) says them among its counts, the
            # main one as ``xent`` in place of the mean differentiated here
            counts = self.model.step_counts(new_mstate)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt,
            model_state=new_mstate)
        return new_state, {"loss": xent + l2, "xent": xent, **counts}

    def _row_local_eligible(self) -> bool:
        """Whether the dense-semantics step may update the embedding tables
        on the batch's distinct rows alone (``_row_local_apply``) and still
        compute the dense update exactly: an optimizer that leaves a row
        with a zero gradient bit for bit, parameter and state
        (``opt_lib.zero_grad_keeps_row``), and nothing else that moves
        every row (dense L2) or needs the gradient as a table (a sync over
        data replicas, row shards, accumulation over microbatches); tables
        the model reads through ``_emb_lookup`` alone (history models also
        read them by ``hist_ids``), one array each. Read from what the
        trainer was built with; anything else compiles the step with the
        table-shaped gradient."""
        return (self._grad_by_rows()
                and opt_lib.zero_grad_keeps_row(self.cfg)
                and not self.cfg.l2_reg
                and self.mesh_info.mesh is None)

    def _grad_by_rows(self) -> bool:
        """Whether a dense-update step may differentiate the ``[B, F, ...]``
        views of its tables in place of the tables, and so hold the table
        gradient as the batch's distinct rows, each the float32 sum of its
        positions' cotangents (``_view_row_sums``): tables the model reads
        through ``_emb_lookup`` alone (history models also read them by
        ``hist_ids``), one whole array each (not hashed into several, no
        row shards), one batch an apply. The row-local step goes on from
        the rows; every other such step scatters them into the table-shaped
        gradient (``_table_grads``), which is AD's up to the order of a
        float32 sum. Read from what the trainer was built with; anything
        else leaves the tables to AD, a scatter-add of every position."""
        return (self.cfg.embedding_update == "dense"
                and self._accum == 1
                and self.mesh_info.model_size == 1
                and not self.model.emb.hashed
                and not getattr(self.model, "uses_history", False))

    def _row_local_apply(self, data_loss, state: TrainState, batch):
        """(xent, new model state, new params, new optimizer state, row
        counts) of a dense-semantics step whose table gradient is never a
        table. The ``[B, F, ...]`` views of the tables are what is
        differentiated (with the dense leaves); their cotangents are summed
        per distinct row in float32 (``emb_ops.sum_rows``); then, a
        ``ROW_UPDATE_CAPACITY`` of distinct rows a trip, those rows of each
        table and of its share of the optimizer state are gathered, given
        the trainer's own ``tx.update`` — elementwise, so on rows it is the
        dense formula by construction — and written back in place. The
        state's tree and shapes are the dense step's."""
        tabs, rest = self._tables_and_rest(state.params)
        ids = self.model.lookup_ids(batch["feat_ids"])
        xent, new_mstate, g_views, g_rest, plan = (
            self._value_and_view_grads(data_loss, tabs, rest, ids))
        opt_rest = opt_lib.select_params(state.opt_state, state.params, rest)
        opt_tabs = opt_lib.select_params(state.opt_state, state.params, tabs)
        new_rest, opt_rest = self._optax_apply(g_rest, opt_rest, rest)

        tabs, opt_tabs, counts = self._update_rows(tabs, opt_tabs, ids,
                                                   g_views, plan)
        return (xent, new_mstate, {**new_rest, **tabs},
                opt_lib.join_params(opt_rest, opt_tabs, rest), counts)

    def _tables_and_rest(self, params):
        """``params`` split into the embedding tables and the other leaves."""
        names = self._embed_names
        return ({n: params[n] for n in names},
                {k: v for k, v in params.items() if k not in names})

    def _value_and_view_grads(self, data_loss, tabs, rest, ids, *,
                              mean_axis=None):
        """(xent, new model state, cotangents of the tables' ``[*ids.shape,
        *row]`` views at ``ids``, gradient of the other leaves ``rest``, the
        ``emb_ops.RowPlan`` of ``ids`` if the views were read by it) of
        ``data_loss``, the tables ``tabs`` themselves not differentiated:
        the model reads the views (``emb_rows``) where it would have looked
        ``ids`` up. With ``mean_axis`` the loss is its mean over that mesh
        axis (``_dense_value_and_grad``'s sync point): the other leaves'
        gradient comes back reduced over it, the views' cotangents — the
        views vary over the axis — this shard's own, scaled by the mean.

        The views are ``jnp.take(table, ids, axis=0)`` bit for bit. A table
        whose row is narrower than one lane line (``_looked_up_by_rows``)
        is read once a distinct row of the batch, not once a position
        (``emb_ops.take_planned``), by the one sort of (id, position) that
        the cotangents are then summed by (``_view_row_sums``)."""
        emb, names = self.model.emb, self._embed_names
        by_rows = self._looked_up_by_rows(tabs)
        lookup = ("rows" if len(by_rows) == len(names) else
                  "positions" if not by_rows else
                  ",".join(f"{n}:{'rows' if n in by_rows else 'positions'}"
                           for n in names))
        if lookup != self.embed_lookup:     # said once a trainer, at trace
            self.embed_lookup = lookup      # time
            ulog.info(f"table views of a step differentiated by views: "
                      f"looked up by {lookup}")
        plan = None
        with jax.named_scope("embed"):
            views = {n: jnp.take(tabs[n], ids, axis=0) for n in names
                     if n not in by_rows}
            if by_rows:
                plan = emb_ops.plan_rows(
                    ids, self.model.padded_vocab, self.cfg.feature_size,
                    multiple=ROW_UPDATE_CAPACITY, keep_pad_rows=True)
                views.update(zip(by_rows, emb_ops.take_planned(
                    [tabs[n] for n in by_rows], plan, ids.shape,
                    ROW_UPDATE_CAPACITY)))

        def loss_fn(diff):
            views, rest = diff
            xent, new_mstate = data_loss(
                {**rest, **tabs}, emb_plan=None,
                emb_rows={n: {emb.MONO: views[n]} for n in names})
            if mean_axis is not None:
                xent = jax.lax.pmean(xent, mean_axis)
            return xent, (xent, new_mstate)

        (_, (xent, new_mstate)), (g_views, g_rest) = jax.value_and_grad(
            loss_fn, has_aux=True)((views, rest))
        return xent, new_mstate, g_views, g_rest, plan

    def _looked_up_by_rows(self, tabs) -> Tuple[str, ...]:
        """The tables of ``tabs`` whose views ``_value_and_view_grads``
        reads once a distinct row: those whose row is narrower than one
        128-lane line (``emb_ops.narrow_rows``: read from the table's shape,
        as ``_summed_as_tables`` reads it)."""
        return tuple(n for n in self._embed_names
                     if emb_ops.narrow_rows(tabs[n]))

    def _view_row_sums(self, tabs, ids, g_views, plan=None, *,
                       gather_axis=None, apart=()):
        """(``emb_ops.RowSums`` of the distinct rows of ``ids``: per row the
        float32 sum of its positions' cotangents ``g_views``, every table's
        columns side by side, slots a whole number of trips; the trips of
        ``ROW_UPDATE_CAPACITY`` rows that hold them; ``rows_of(i)`` = the
        ids of trip ``i`` and each table's rows of sums there; the tables
        ``apart``, which the trips leave out: each one's rows of sums at
        every slot of ``rows.uids``). Summed by ``plan`` where the forward
        made one of ``ids`` (``_value_and_view_grads``), else by a sort of
        its own. Sorted, so the last trip's spare
        slots lie past the table: read as fill, dropped or skipped by a
        write. With ``gather_axis`` (data replicas: the rows are this
        shard's) the trips are the fullest shard's and ``rows_of(i)`` is
        every shard's trip ``i``, one after another in the axis's order,
        the same on every shard (``emb_ops.all_gather_invariant``); a shard
        with fewer trips hands in spare slots."""
        names, cap = self._embed_names, ROW_UPDATE_CAPACITY
        widths = [math.prod(tabs[n].shape[1:]) for n in names]
        cuts = np.cumsum([0] + widths)
        cots = jnp.concatenate([g_views[n].reshape(ids.size, w)
                                for n, w in zip(names, widths)], axis=1)
        num_rows = self.model.padded_vocab
        rows = (emb_ops.sum_planned(plan, cots, num_rows)
                if plan is not None else    # every view was read a position:
                emb_ops.sum_rows(           # the ids are sorted here
                    ids, cots, num_rows, self.cfg.feature_size, multiple=cap))
        trips = (rows.count + cap - 1) // cap
        if gather_axis is not None:     # before the loop: a collective in
            # its body needs every shard to make the same trips
            trips = jax.lax.pmax(trips, gather_axis)
        columns = {n: slice(cuts[j], cuts[j + 1])
                   for j, n in enumerate(names)}

        def split(g, of):
            """Per table of ``of``, its columns of ``g`` as rows of it."""
            return {n: g[:, columns[n]].reshape(
                (len(g),) + tabs[n].shape[1:]) for n in of}

        def rows_of(i):
            uids = jax.lax.dynamic_slice_in_dim(rows.uids, i * cap, cap)
            g = split(jax.lax.dynamic_slice_in_dim(rows.sums, i * cap, cap),
                      [n for n in names if n not in apart])
            if gather_axis is not None:     # the trips' tables alone cross
                uids, *gathered = (
                    emb_ops.all_gather_invariant(x, gather_axis)
                    for x in (uids, *g.values()))
                g = dict(zip(g, gathered))
            return uids, g

        return rows, trips, rows_of, split(rows.sums, apart)

    @jax.named_scope("embed")
    def _update_rows(self, tabs, opt_tabs, ids, g_views, plan=None):
        """(tables, their optimizer state, row counts) after the optimizer's
        update of the distinct rows of ``ids``, for the cotangents
        ``g_views`` of the tables' ``[..., *row]`` views at ``ids``; rows no
        id names are not read. A trip handles ``ROW_UPDATE_CAPACITY``
        distinct rows (``_view_row_sums``), and the trips are as many as
        the batch needs: exact for any batch at static shapes.
        All of it is the ``embed`` scope's but the rows' arithmetic
        (``_optax_apply``: ``opt``, the innermost scope wins)."""
        names, cap = self._embed_names, ROW_UPDATE_CAPACITY
        how = "+".join(sorted({
            "dma" if pallas_put_rows.supported(t) else "scatter"
            for t in jax.tree.leaves((tabs, opt_tabs))}))
        if how != self.row_writeback:   # said once a trainer, at trace time
            self.row_writeback = how
            ulog.info(f"row-local table update: {cap} rows a trip, written "
                      f"back by {how}")
        rows, trips, rows_of, _ = self._view_row_sums(tabs, ids, g_views,
                                                      plan)

        def take(table, uids):
            return jnp.take(table, uids, axis=0, mode="fill", fill_value=0)

        def put(old, new, uids):
            """The tree ``old`` of table-tall arrays with rows ``uids`` of
            each set to ``new``'s. Distinct, ascending, in bounds or past
            the table. Where a row is whole 128-lane lines on a TPU, one
            DMA a slot (spare slots skipped), one launch for the arrays of
            one shape; else XLA's scatter, which is told neither property:
            with indices_are_sorted the TPU compiler sweeps the whole table
            (9.7 ms whatever the rows; PERF.md §6, PR 28)."""
            olds, tree = jax.tree.flatten(old)
            news = tree.flatten_up_to(new)
            by_dma: Dict[Tuple[int, ...], List[int]] = {}
            for k, table in enumerate(olds):
                if pallas_put_rows.supported(table):
                    by_dma.setdefault(table.shape, []).append(k)
                else:
                    olds[k] = table.at[uids].set(news[k], mode="drop")
            for at in by_dma.values():
                done = pallas_put_rows.put_rows_many(
                    [olds[k] for k in at], uids, [news[k] for k in at])
                for k, table in zip(at, done):
                    olds[k] = table
            return jax.tree.unflatten(tree, olds)

        def trip(carry):
            i, tabs, opt_tabs = carry
            uids, g = rows_of(i)
            new, new_opt = self._optax_apply(
                g, jax.tree.map(lambda t: take(t, uids), opt_tabs),
                {n: take(tabs[n], uids) for n in names})
            return (i + 1, *put((tabs, opt_tabs), (new, new_opt), uids))

        _, tabs, opt_tabs = jax.lax.while_loop(
            lambda carry: carry[0] < trips, trip,
            (jnp.zeros((), jnp.int32), tabs, opt_tabs))
        return tabs, opt_tabs, dict(zip(ROW_COUNTS, (rows.count, trips)))

    @jax.named_scope("embed")
    def _table_grads(self, tabs, ids, g_views, plan=None, *, sum_axis=None):
        """(the table-shaped gradient of every table, row counts) from the
        cotangents ``g_views`` of the tables' views at ``ids``: zeros, and
        the batch's distinct rows added, each the float32 sum of its
        positions' cotangents (``_view_row_sums``) — AD's scatter-add of
        every position up to the order of those sums, at a sixth of the
        table row updates where a row is looked up six times a batch (a
        row update costs the table's height, 122 ns at 16.9M rows, a
        position summed in a batch-tall array a tenth of it: PERF.md §6,
        PR 36). Only the rows really held are scattered, a trip of
        ``ROW_UPDATE_CAPACITY`` at a time (a dropped spare slot costs what
        a written one does), the gradient the loop's carry: exact for any
        batch at static shapes. The scatter is told nothing of its ids
        (``indices_are_sorted`` makes the TPU compiler sweep the table).
        With ``sum_axis`` (data replicas: the cotangents are this shard's)
        the gradient is the sum over that mesh axis, and what crosses the
        interconnect is rows, not tables: a trip gathers every shard's
        ``ROW_UPDATE_CAPACITY`` (id, sum) pairs and every chip scatter-adds
        them all, the same operands in the same order, so the replicas stay
        bit-identical as under the all-reduce AD would have put after its
        own scatter (a row two shards hold is added twice, which is the
        sum). The trips and the distinct rows counted are the fullest
        shard's; ``EXCHANGED_ROWS`` is all shards' rows together, what
        each chip scattered (PERF.md §6, PR 38). But a table whose row is
        one word (``_summed_as_tables``) stays out of the trips: its
        gradient is this shard's own rows in one scatter-add of every slot
        beside the loop, all-reduced — the same sum, the shards' added by
        the collective, whose one result every replica receives. An (id,
        sum) pair is 8 bytes a shard and a slot of every chip's scatter, 91
        ns at 16.9M rows inside the loop; the table's all-reduce is 4 bytes
        a row, 67.5 MB where the exchange saved 2.16 GB for the 32-wide
        table (PERF.md §6, PR 41)."""
        apart = self._summed_as_tables(tabs, sum_axis)
        rows, trips, rows_of, own = self._view_row_sums(
            tabs, ids, g_views, plan, gather_axis=sum_axis, apart=apart)

        def trip(carry):
            i, grads = carry
            uids, g = rows_of(i)
            return i + 1, {n: grads[n].at[uids].add(
                g[n].astype(grads[n].dtype), mode="drop") for n in grads}

        _, grads = jax.lax.while_loop(
            lambda carry: carry[0] < trips, trip,
            (jnp.zeros((), jnp.int32),
             {n: jnp.zeros_like(t) for n, t in tabs.items()
              if n not in apart}))
        for n in apart:
            grads[n] = jax.lax.psum(jnp.zeros_like(tabs[n]).at[rows.uids].add(
                own[n].astype(tabs[n].dtype), mode="drop"), sum_axis)
        distinct, exchanged = rows.count, {}
        if sum_axis is not None:
            exchanged = {EXCHANGED_ROWS: jax.lax.psum(distinct, sum_axis)}
            distinct = jax.lax.pmax(distinct, sum_axis)
        return grads, {**dict(zip(ROW_COUNTS, (distinct, trips))),
                       **exchanged}

    def _summed_as_tables(self, tabs, sum_axis) -> Tuple[str, ...]:
        """The tables of ``tabs`` whose gradient ``_table_grads`` sums over
        ``sum_axis`` by an all-reduce of the table and not by the exchange
        of rows: under data replicas those whose row is one word. Read from
        the table's shape: it is one sum either way, and which carrier is
        cheaper is a matter of the row's width alone."""
        if sum_axis is None:
            return ()
        return tuple(n for n in self._embed_names if tabs[n].ndim == 1)

    def _dense_value_and_grad(self, data_loss, params, *, data_axis,
                              shard_axis, ids=None):
        """(xent, l2, new_model_state, grads, row counts) of a dense-update
        step, for ``data_loss(params, **emb) -> (mean data loss of this
        shard, new model state)``. The plain and the accumulating step
        share it: the gradient sync over the data axis, the L2 term and the
        pad-row mask are defined here once. With ``ids`` (the ids the model
        looks up in its tables, from a step for which ``_grad_by_rows``
        holds) the tables' gradient is built from the batch's distinct rows
        (``_table_grads``; the counts are its); without, by AD, from every
        position (no counts)."""
        flat_sync = data_axis is not None and self._hier_groups is None
        how = ("positions" if ids is None else
               "rows, exchanged over data" if flat_sync else "rows")
        tabs, rest = self._tables_and_rest(params)
        if data_axis is None:
            by_table = ""
        elif ids is not None and flat_sync:
            by_table = ",".join(self._summed_as_tables(tabs, data_axis))
        else:   # AD's psum, or the hierarchical mean, of every table
            by_table = ",".join(tabs)
        if (how, by_table) != (self.embed_grad, self.embed_grad_by_table):
            # said once a trainer, at trace time
            self.embed_grad, self.embed_grad_by_table = how, by_table
            ulog.info(f"dense-gradient step: table gradient from {how}"
                      + (f"; summed over data as tables: {by_table}"
                         if by_table else ""))

        counts: Dict[str, jnp.ndarray] = {}
        # THE gradient sync point: the loss is made a *global* scalar (mean
        # over the data axis); differentiating it under shard_map's
        # replication-aware AD yields gradients with the cross-replica psum
        # already inserted by XLA — this replaces
        # hvd.DistributedOptimizer's NCCL allreduce (2-hvd-gpu/...py:262)
        # and the PS push/pull (X1).
        sync = data_axis if flat_sync else None
        if ids is None:
            def loss_fn(params):
                xent, new_mstate = data_loss(params)
                if sync is not None:
                    xent = jax.lax.pmean(xent, sync)
                return xent, (xent, new_mstate)

            (_, (xent, new_mstate)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        else:
            # The same sync point: the dense leaves' gradient comes reduced
            # out of AD; the views' cotangents are local, so the tables
            # made of them are summed over the axis explicitly, by an
            # exchange of the shards' rows.
            xent, new_mstate, g_views, g_rest, plan = (
                self._value_and_view_grads(data_loss, tabs, rest, ids,
                                           mean_axis=sync))
            g_tabs, counts = self._table_grads(tabs, ids, g_views, plan,
                                               sum_axis=sync)
            grads = {**g_rest, **g_tabs}
        if data_axis is not None and not flat_sync:
            # Hierarchical sync point (TUNING §2.13): the loss stayed
            # per-shard above, so the raw grads carry no psum; average
            # them intra-host then inter-host — the DCN stage moves 1/L
            # of the flat-ring traffic (L = data rows per host).
            grads = mesh_lib.hierarchical_pmean(
                grads, data_axis, self._hier_groups,
                self.mesh_info.data_size)
            xent = jax.lax.pmean(xent, data_axis)  # metrics only
            counts = jax.lax.pmax(counts, data_axis)
        l2, grads = self._add_dense_l2(params, grads, shard_axis=shard_axis)
        # Structural guarantee: padded_vocab pad rows never receive a
        # gradient (they are zero already — unreachable ids, masked l2 —
        # so this is bit-neutral; the regression test pins it).
        with jax.named_scope("opt"):
            grads = {**grads, **{
                n: self.model.emb.mask_pad_grads(grads[n],
                                                 axis_name=shard_axis)
                for n in self._embed_names}}
        return xent, l2, new_mstate, grads, counts

    def _add_dense_l2(self, params, grads, *, shard_axis):
        """(l2 value, grads + the L2 term's gradient on the embedding
        tables): dense L2, once per optimizer apply, on every real row.

        The term stays out of the differentiated data loss and its gradient
        ``l2_reg * mask * w`` (AD of ``Model.l2_loss`` over the tables
        alone: elementwise, no scatter) is added to the *fenced* data
        gradient. Without the fence XLA rewrites ``scatter-add(zeros, rows)
        + X`` into ``scatter-add(X, rows)``, and X, a product that would
        have been arithmetic inside Adam's sweep, becomes a table in HBM
        that costs a write and a read pass of its own (PERF.md §6, PR 26).
        Across data replicas the gradient's all-reduce already stands
        between the two, so that program is left as it compiles."""
        if not self.cfg.l2_reg:
            return jnp.zeros((), jnp.float32), grads
        tabs = {n: params[n] for n in self._embed_names}
        l2, l2_grads = jax.value_and_grad(
            lambda t: self.model.l2_loss({**params, **t},
                                         shard_axis=shard_axis))(tabs)
        if shard_axis is not None:
            # l2 over the full row-sharded table (invariant scalar).
            l2 = jax.lax.psum(l2, shard_axis)
        data_grads = {n: grads[n] for n in self._embed_names}
        if self.mesh_info.data_size == 1:
            data_grads = jax.lax.optimization_barrier(data_grads)
        return l2, {**grads, **jax.tree.map(jnp.add, data_grads, l2_grads)}

    @jax.named_scope("opt")
    def _optax_apply(self, grads, opt_state, params):
        """The optax update and its application: (new_params, new_opt)."""
        updates, new_opt = self.tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt

    # -- sparse-plane helpers (fused vocab-space backward) --------------
    def _use_fused_backward(self) -> bool:
        """The fused formulation differentiates the [B, F, D] BATCH VIEWS
        of each embedding table (a direct gather — no plan, no inverse
        remap), accumulates all names' cotangents plus an occupancy column
        in ONE table-shaped scatter-add, and applies lazy Adam as a masked
        table-space sweep (optimizers.sparse_adam_masked). Structurally
        that is the dense step's cost profile with lazy-Adam semantics —
        it needs the monolithic layout, same-height 2-D tables, and a
        table small enough to sweep; ``--embedding_kernels off`` is the
        kill switch back to the plan-based seed formulation."""
        return self._emb_kernels != "off" and not self.model.emb.hashed

    def _fused_tables_ok(self, tabs: Dict[str, jax.Array]) -> bool:
        heights = {t.shape[0] for t in tabs.values()}
        return (len(heights) == 1
                and all(t.ndim in (1, 2) for t in tabs.values())
                and heights.pop() <= pemb.PLAN_COUNT_MAX_ROWS)

    @jax.named_scope("embed")
    def _fused_grad_ext(self, tabs, ids, g_views):
        """ONE table-shaped scatter-add for the whole embedding plane:
        column 0 accumulates an occupancy count (touch marks — exact
        integers in f32 up to 2^24 positions; a separate boolean
        scatter-set benches ~2 ms SLOWER than riding in the one scatter),
        the rest accumulate every name's per-position cotangents.
        Per-(row, column) addition order is batch-position order — the
        same order XLA's per-name gather transpose uses — so the per-name
        gradient slices are bit-identical to the seed path's
        segment-sums."""
        flat = ids.reshape(-1)
        n_pos = flat.shape[0]
        rows = next(iter(tabs.values())).shape[0]
        cols = [jnp.ones((n_pos, 1), jnp.float32)]
        cols += [g_views[n].reshape(n_pos, -1).astype(jnp.float32)
                 for n in self._embed_names]
        gcat = jnp.concatenate(cols, axis=1)
        gext = jnp.zeros((rows, gcat.shape[1]), jnp.float32)
        return gext.at[flat].add(gcat)

    @staticmethod
    @jax.named_scope("l2")
    def _touched_l2(tab, touched):
        """0.5 * sum(x^2) over the touched rows of one table."""
        sq = jnp.square(tab.astype(jnp.float32))
        keep = touched.reshape(touched.shape + (1,) * (sq.ndim - 1))
        return 0.5 * jnp.sum(jnp.where(keep, sq, jnp.zeros((), sq.dtype)))

    @staticmethod
    @jax.named_scope("opt")
    def _lazy_decay(count, tau):
        """(0.9, 0.999) ** (steps since each row's last touch), behind a
        barrier. exp2 formulation: benches ~11x faster than jnp.power on
        XLA:CPU (pow lowers to a libm call) at ~1 ULP from pow — inside the
        tolerance already pinned for the masked sweep (sparse_adam_masked
        doc)."""
        idle = (count - tau).astype(jnp.float32)
        return jax.lax.optimization_barrier(
            (jnp.exp2(idle * np.float32(np.log2(0.9))),
             jnp.exp2(idle * np.float32(np.log2(0.999)))))

    @jax.named_scope("opt")
    def _fused_apply(self, state: TrainState, tabs, gext, count):
        """Masked lazy-Adam sweep per name over the gradient columns of
        ``gext`` (+ the touched-rows-only L2 term, added here exactly as
        AD adds it on the seed path). Returns (new_params_embed,
        new_embed_opt, l2_value)."""
        touched = gext[:, 0] > 0
        opt_embed = state.opt_state["embed"]
        emb = self.model.emb
        l2_reg = self.cfg.l2_reg
        new_params_embed: Dict[str, Any] = {}
        new_embed: Dict[str, Any] = {}
        l2 = jnp.zeros((), jnp.float32)
        # tau is identical across tables (same touched set every step), so
        # the lazy-decay pows — the sweep's hot spot — are computed once
        # and shared by every table (see sparse_adam_masked's decay note).
        decay = self._lazy_decay(
            count, opt_embed[self._embed_names[0]][emb.MONO].tau)
        o = 1
        for name in self._embed_names:
            tab = tabs[name]
            d = 1 if tab.ndim == 1 else tab.shape[-1]
            g_eff = gext[:, o:o + d].reshape(tab.shape)
            if l2_reg:
                g_eff = g_eff + l2_reg * tab.astype(jnp.float32)
            o += d
            new_tab, new_oe = opt_lib.sparse_adam_masked(
                tab, g_eff, touched, opt_embed[name][emb.MONO], count,
                lr=self._sparse_lr, decay=decay)
            new_params_embed[name] = new_tab
            new_embed[name] = {emb.MONO: new_oe}
            if l2_reg:
                l2 = l2 + self._touched_l2(tab, touched)
        return new_params_embed, new_embed, l2_reg * l2

    @jax.named_scope("opt")
    def _sparse_apply(self, state: TrainState, plan, rows0, g_rows, count):
        """Lazy-Adam apply + writeback for every (name, table): returns
        ({name: new_entry_params}, {name: new_opt_tables}).

        The counting plans' select-writeback companions are STRIPPED here:
        a vocab-shaped ``where`` in the update graph perturbs XLA:CPU's
        fusion of the model backward (~1 ULP cotangent drift), breaking
        the kill-switch bit-parity pin. The scatter writeback is
        bit-exact, so the trainer always takes it; the select leg stays
        available through ``ops.embedding.scatter_rows`` directly, held
        element-identical to the scatter by ``test_pallas_embedding.py``
        (``test_select_writeback_matches_scatter_writeback``)."""
        plan = {key: e._replace(touched=None, rank=None)
                for key, e in plan.items()}
        emb = self.model.emb
        opt_embed = state.opt_state["embed"]
        new_params_embed: Dict[str, Any] = {}
        new_embed: Dict[str, Any] = {}
        for name in self._embed_names:
            tabs = emb.tables(state.params[name])
            new_tabs: Dict[str, jax.Array] = {}
            new_opt_t: Dict[str, Any] = {}
            for key, e in plan.items():
                new_tabs[key], new_opt_t[key] = opt_lib.sparse_apply_rows(
                    rows0[name][key], g_rows[name][key], e,
                    opt_embed[name][key], count, lr=self._sparse_lr,
                    table=tabs[key])
            new_params_embed[name] = emb.from_tables(new_tabs)
            new_embed[name] = new_opt_t
        return new_params_embed, new_embed

    def _sparse_step_impl(self, state: TrainState, batch
                          ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        """One sparse-update optimizer step (single-device path).

        The batch's ids are deduped into a per-table plan; the TOUCHED ROWS
        — not the tables — are the differentiated leaf, so AD of the
        inverse-index gather in the forward lowers to a batch-sized
        segment-sum scatter-add instead of a [vocab, ...] cotangent, and
        lazy timestamped Adam (optimizers.sparse_adam_rows) touches only
        those rows. Per-step cost scales with unique-ids-per-batch, never
        with vocab size.

        With the embedding kernels enabled (default) the monolithic layout
        takes the FUSED vocab-space formulation: the [B, F, D] batch views
        are the gradient leaves (no dedup plan at all), every name's
        cotangents land in one table-shaped scatter-add alongside an
        occupancy column, and lazy Adam runs as a masked table sweep —
        at the dense step's cost profile. Gradients are bit-identical to
        the seed formulation; the Adam tail rounds 1–2 ULP apart between
        the row-space and table-sweep programs (see sparse_adam_masked),
        so the kill-switch parity test pins a tight tolerance there and
        bit equality everywhere else."""
        emb = self.model.emb
        rng = jax.random.fold_in(state.rng, state.step)
        tabs = {n: state.params[n] for n in self._embed_names}
        rest0 = {k: v for k, v in state.params.items()
                 if k not in self._embed_names}
        fused = self._use_fused_backward() and self._fused_tables_ok(tabs)

        if fused:
            ids = batch["feat_ids"]
            with jax.named_scope("embed"):
                views0 = {n: jnp.take(tabs[n], ids, axis=0)
                          for n in self._embed_names}

            def loss_fn(diff):
                views, rest = diff
                params = {**rest, **tabs}
                logits, new_mstate = self.model.apply(
                    params, state.model_state, batch["feat_ids"],
                    batch["feat_vals"], train=True, rng=rng,
                    shard_axis=None, data_axis=None,
                    emb_rows={n: {emb.MONO: views[n]}
                              for n in self._embed_names}, emb_plan=None)
                xent = self._mean_loss(logits, batch)
                return xent, (xent, new_mstate)

            (_, (xent, new_mstate)), (g_views, g_rest) = (
                jax.value_and_grad(loss_fn, has_aux=True)((views0, rest0)))
            gext = self._fused_grad_ext(tabs, ids, g_views)
        else:
            plan = emb.sparse_plan(batch["feat_ids"])
            rows0 = {n: emb.gather_rows(state.params[n], plan)
                     for n in self._embed_names}

            def loss_fn(diff):
                rows, rest = diff
                params = {**rest, **tabs}
                logits, new_mstate = self.model.apply(
                    params, state.model_state, batch["feat_ids"],
                    batch["feat_vals"], train=True, rng=rng,
                    shard_axis=None, data_axis=None,
                    emb_rows=rows, emb_plan=plan)
                xent = self._mean_loss(logits, batch)
                # Touched-rows-only L2 (deliberate deviation from dense L2
                # — idle rows do not decay between touches; TUNING §2.11).
                l2 = self.model.l2_loss(params, emb_rows=rows, emb_plan=plan)
                return xent + l2, (xent, l2, new_mstate)

            (_, (xent, l2, new_mstate)), (g_rows, g_rest) = (
                jax.value_and_grad(loss_fn, has_aux=True)((rows0, rest0)))

        opt = state.opt_state
        new_rest, new_base = self._optax_apply(g_rest, opt["base"], rest0)
        count = opt["count"] + 1
        new_params = dict(new_rest)
        if fused:
            emb_params, new_embed, l2 = self._fused_apply(
                state, tabs, gext, count)
        else:
            emb_params, new_embed = self._sparse_apply(
                state, plan, rows0, g_rows, count)
        new_params.update(emb_params)
        new_opt = {"base": new_base, "embed": new_embed, "count": count}
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt,
            model_state=new_mstate)
        return new_state, {"loss": xent + l2, "xent": xent}

    def _sharded_sparse_step_impl(self, state: TrainState, batch, *,
                                  data_axis, shard_axis
                                  ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        """One sparse optimizer step under the ('data','model') mesh with
        row-sharded tables (``--embedding_shard rows``).

        Topology per step (runs inside shard_map; tables + Adam moments
        live as [rows/D, ...] shards over 'model', the batch is sharded
        over 'data' and replicated over 'model'):

          1. The local batch's dedup plan is built exactly as on the
             single-device path — model peers see the same batch, so the
             plan (and its sorted uid list) is model-replicated for free.
          2. ``build_exchange`` splits request responsibility by uid
             position across the D model peers (C = ceil(U/D) ids each),
             ``exchange_rows`` moves requests/responses via two tiled
             ``all_to_all``s and reassembles the [U, ...] row block with a
             psum — bit-identical to gathering from the full table.
          3. The TOUCHED ROWS are the gradient leaf (same AD shape as the
             single-device plan leg); the in-loss pmean over 'data' is THE
             gradient sync for the dense params, and scales the row
             cotangents by 1/dp.
          4. ``owner_scatter_add`` lands each replica's cotangents in
             owner-local table space; a psum over 'data' then sums the
             1/dp-scaled contributions — i.e. the cross-replica pmean —
             and unions the touched masks. Each owner lazy-Adam-sweeps
             only its own rows (sparse_adam_masked), so optimizer work
             and moment HBM both scale 1/D.

        Touched-rows L2 is applied post-hoc against the UNION touched mask
        (fused-apply style): putting it in the per-replica loss would
        weight a row by how many replicas touched it (k/dp), diverging
        from the single-device semantics this path is pinned against.

        Unlike the dense step, the loss here carries NO collectives at
        all: the gradients come out per-replica LOCAL and the pmeans are
        explicit, after AD (the hierarchical dense leg's idiom). That
        sidesteps the in-loss-pmean transpose entirely — whose scaling
        shifted between the legacy shard_map AD and the vma-typed one —
        so this program means the same thing on either. The hierarchical
        two-stage 'data' reduce is NOT composed with this path (grads
        never materialize as one dense tree to stage)."""
        emb = self.model.emb
        d = self.mesh_info.model_size if shard_axis is not None else 1
        rng = jax.random.fold_in(state.rng, state.step)
        if data_axis is not None:
            rng = jax.random.fold_in(rng, jax.lax.axis_index(data_axis))
        tabs = {n: state.params[n] for n in self._embed_names}  # local shards
        rest0 = {k: v for k, v in state.params.items()
                 if k not in self._embed_names}
        plan = emb.sparse_plan(batch["feat_ids"])
        if d > 1:
            ex = {key: emb_ops.build_exchange(e, d, shard_axis)
                  for key, e in plan.items()}
            rows0 = {n: {key: emb_ops.exchange_rows(
                             emb.tables(tabs[n])[key], ex[key], shard_axis)
                         for key in plan}
                     for n in self._embed_names}
        else:
            rows0 = {n: emb.gather_rows(tabs[n], plan)
                     for n in self._embed_names}

        def loss_fn(diff):
            rows, rest = diff
            params = {**rest, **tabs}
            logits, new_mstate = self.model.apply(
                params, state.model_state, batch["feat_ids"],
                batch["feat_vals"], train=True, rng=rng,
                shard_axis=None, data_axis=data_axis,
                emb_rows=rows, emb_plan=plan, **self._hist_kwargs(batch))
            xent = self._mean_loss(logits, batch)
            return xent, (xent, new_mstate)

        (_, (xent, new_mstate)), (g_rows, g_rest) = (
            jax.value_and_grad(loss_fn, has_aux=True)((rows0, rest0)))
        if data_axis is not None:
            # THE gradient sync point, explicit and post-AD: per-replica
            # local-mean grads -> the global-batch mean (row leaves sync
            # below, in owner table space).
            g_rest = jax.tree.map(
                lambda g: jax.lax.pmean(g, data_axis), g_rest)
            xent = jax.lax.pmean(xent, data_axis)

        opt = state.opt_state
        new_rest, new_base = self._optax_apply(g_rest, opt["base"], rest0)
        count = opt["count"] + 1
        opt_embed = opt["embed"]
        l2_reg = self.cfg.l2_reg
        new_tabs: Dict[str, Dict[str, jax.Array]] = {
            n: {} for n in self._embed_names}
        new_embed: Dict[str, Dict[str, Any]] = {
            n: {} for n in self._embed_names}
        l2 = jnp.zeros((), jnp.float32)
        for key, e in plan.items():
            scat = {n: emb_ops.owner_scatter_add(
                        g_rows[n][key], e, d,
                        shard_axis if d > 1 else None)
                    for n in self._embed_names}
            grads = {n: scat[n][0] for n in self._embed_names}
            touched = scat[self._embed_names[0]][1]
            if data_axis is not None:
                # pmean of the owner-local scatters == the global-batch
                # mean grad per owned row; touched becomes the UNION.
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, data_axis), grads)
                touched = jax.lax.psum(
                    touched.astype(jnp.int32), data_axis) > 0
            # Shared per physical table: tau is identical across names
            # (same touched set every step).
            decay = self._lazy_decay(
                count, opt_embed[self._embed_names[0]][key].tau)
            for name in self._embed_names:
                tab = emb.tables(tabs[name])[key]
                g_eff = grads[name]
                if l2_reg:
                    g_eff = g_eff + l2_reg * tab.astype(jnp.float32)
                new_tab, new_oe = opt_lib.sparse_adam_masked(
                    tab, g_eff, touched, opt_embed[name][key], count,
                    lr=self._sparse_lr, decay=decay)
                new_tabs[name][key] = new_tab
                new_embed[name][key] = new_oe
                if l2_reg:
                    l2 = l2 + self._touched_l2(tab, touched)
        l2 = l2_reg * l2
        if l2_reg and shard_axis is not None:
            # Per-shard partials -> the full-table touched-L2 scalar.
            l2 = jax.lax.psum(l2, shard_axis)
        new_params = dict(new_rest)
        for name in self._embed_names:
            new_params[name] = emb.from_tables(new_tabs[name])
        new_opt = {"base": new_base, "embed": new_embed, "count": count}
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt,
            model_state=new_mstate)
        return new_state, {"loss": xent + l2, "xent": xent}

    def _accum_step_impl(self, state: TrainState, batches, *, data_axis,
                         shard_axis
                         ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        """ONE optimizer apply over ``a`` stacked microbatches [a, B, ...].

        The loss is the mean of per-microbatch mean losses — for equal-size
        microbatches exactly the big-batch mean over a*B examples — so the
        accumulated gradient equals the single big-batch gradient up to
        float reassociation (the parity test pins the tolerance). The inner
        scan re-walks the forward once per microbatch, so activation memory
        peaks at ONE microbatch while the effective batch is
        batch_size * a * data parallelism. ``state.step`` advances by ``a``
        (it counts MICROBATCHES: resume bookkeeping equates steps with
        batches consumed); the optimizer's count — Adam bias correction
        included — ticks ONCE per apply.
        """
        if self.sparse_embed and data_axis is None and shard_axis is None:
            return self._sparse_accum_step_impl(state, batches)
        a = batches["label"].shape[0]
        base_rng = jax.random.fold_in(state.rng, state.step)

        def data_loss(params):
            def micro(carry, inp):
                mstate, xent_sum = carry
                i, batch = inp
                rng = jax.random.fold_in(base_rng, i)
                if data_axis is not None:
                    rng = jax.random.fold_in(
                        rng, jax.lax.axis_index(data_axis))
                logits, new_mstate = self.model.apply(
                    params, mstate, batch["feat_ids"], batch["feat_vals"],
                    train=True, rng=rng, shard_axis=shard_axis,
                    data_axis=data_axis, **self._hist_kwargs(batch))
                xent = self._mean_loss(logits, batch)
                return (new_mstate, xent_sum + xent), None

            xent0 = jnp.zeros((), jnp.float32)
            if data_axis is not None:
                # Each data shard sums its own microbatches: the carry
                # varies over the axis from the start (shard_map's typing).
                xent0 = jax.lax.pcast(xent0, (data_axis,), to="varying")
            (new_mstate, xent_sum), _ = jax.lax.scan(
                micro, (state.model_state, xent0), (jnp.arange(a), batches))
            return xent_sum / a, new_mstate

        # L2 charged once per APPLY, not per microbatch — matching the
        # equivalent big-batch step, where it also appears once.
        xent, l2, new_mstate, grads, _ = self._dense_value_and_grad(
            data_loss, state.params, data_axis=data_axis,
            shard_axis=shard_axis)
        new_params, new_opt = self._optax_apply(
            grads, state.opt_state, state.params)
        new_state = state.replace(
            step=state.step + a, params=new_params, opt_state=new_opt,
            model_state=new_mstate)
        return new_state, {"loss": xent + l2, "xent": xent}

    def _sparse_accum_step_impl(self, state: TrainState, batches
                                ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        """Sparse-update accumulation: ONE merged plan across the group.

        The group's a*B batches of ids dedup into a single PlanEntry per
        table (``make_plan`` over the flattened group — the same machinery
        as the per-batch plan), so the touched-rows gradient leaf is
        gathered ONCE; each microbatch forward reads it through its [B, F]
        slice of the shared inverse index, and AD accumulates the
        per-microbatch cotangents into the same [U] row slots
        automatically. One ``sparse_adam_rows`` apply per group (count
        ticks once), touched-row L2 charged once per apply.
        """
        emb = self.model.emb
        a, bsz = batches["feat_ids"].shape[:2]
        base_rng = jax.random.fold_in(state.rng, state.step)
        tabs = {n: state.params[n] for n in self._embed_names}
        rest0 = {k: v for k, v in state.params.items()
                 if k not in self._embed_names}
        fused = self._use_fused_backward() and self._fused_tables_ok(tabs)

        if fused:
            # Fused vocab-space formulation over the whole group: the
            # [a, B, F, D] stacked views are the leaves; the scan slices
            # one microbatch's view per iteration and AD stacks the
            # per-microbatch cotangents back into [a, B, F, D] — flattened
            # into ONE table-shaped scatter-add below (group-position
            # order == the merged plan's segment-sum order, bit-for-bit).
            ids = batches["feat_ids"]
            with jax.named_scope("embed"):
                views0 = {n: jnp.take(tabs[n], ids, axis=0)
                          for n in self._embed_names}

            def loss_fn(diff):
                views, rest = diff
                params = {**rest, **tabs}

                def micro(carry, inp):
                    mstate, xent_sum = carry
                    i, batch, views_i = inp
                    rng = jax.random.fold_in(base_rng, i)
                    logits, new_mstate = self.model.apply(
                        params, mstate, batch["feat_ids"],
                        batch["feat_vals"], train=True, rng=rng,
                        shard_axis=None, data_axis=None,
                        emb_rows={n: {emb.MONO: views_i[n]}
                                  for n in self._embed_names},
                        emb_plan=None)
                    xent = self._mean_loss(logits, batch)
                    return (new_mstate, xent_sum + xent), None

                (new_mstate, xent_sum), _ = jax.lax.scan(
                    micro, (state.model_state, jnp.zeros((), jnp.float32)),
                    (jnp.arange(a), batches, views))
                xent = xent_sum / a
                return xent, (xent, new_mstate)

            (_, (xent, new_mstate)), (g_views, g_rest) = (
                jax.value_and_grad(loss_fn, has_aux=True)((views0, rest0)))
            gext = self._fused_grad_ext(tabs, ids, g_views)
        else:
            ids_flat = batches["feat_ids"].reshape(
                (a * bsz,) + batches["feat_ids"].shape[2:])
            plan = emb.sparse_plan(ids_flat)
            # Per-microbatch plan views: merged uids, inverse index (and
            # the hashed-mode position mask) sliced back to [B, F].
            inv_stack = {key: e.inv.reshape((a, bsz) + e.inv.shape[1:])
                         for key, e in plan.items()}
            mask_stack = {key: e.mask.reshape((a, bsz) + e.mask.shape[1:])
                          for key, e in plan.items() if e.mask is not None}
            rows0 = {n: emb.gather_rows(state.params[n], plan)
                     for n in self._embed_names}

            def loss_fn(diff):
                rows, rest = diff
                params = {**rest, **tabs}

                def micro(carry, inp):
                    mstate, xent_sum = carry
                    i, batch, inv_i, mask_i = inp
                    plan_i = {key: e._replace(inv=inv_i[key],
                                              mask=mask_i.get(key))
                              for key, e in plan.items()}
                    rng = jax.random.fold_in(base_rng, i)
                    logits, new_mstate = self.model.apply(
                        params, mstate, batch["feat_ids"],
                        batch["feat_vals"], train=True, rng=rng,
                        shard_axis=None, data_axis=None,
                        emb_rows=rows, emb_plan=plan_i)
                    xent = self._mean_loss(logits, batch)
                    return (new_mstate, xent_sum + xent), None

                (new_mstate, xent_sum), _ = jax.lax.scan(
                    micro, (state.model_state, jnp.zeros((), jnp.float32)),
                    (jnp.arange(a), batches, inv_stack, mask_stack))
                xent = xent_sum / a
                l2 = self.model.l2_loss(params, emb_rows=rows, emb_plan=plan)
                return xent + l2, (xent, l2, new_mstate)

            (_, (xent, l2, new_mstate)), (g_rows, g_rest) = (
                jax.value_and_grad(loss_fn, has_aux=True)((rows0, rest0)))

        opt = state.opt_state
        new_rest, new_base = self._optax_apply(g_rest, opt["base"], rest0)
        count = opt["count"] + 1
        new_params = dict(new_rest)
        if fused:
            emb_params, new_embed, l2 = self._fused_apply(
                state, tabs, gext, count)
        else:
            emb_params, new_embed = self._sparse_apply(
                state, plan, rows0, g_rows, count)
        new_params.update(emb_params)
        new_opt = {"base": new_base, "embed": new_embed, "count": count}
        new_state = state.replace(
            step=state.step + a, params=new_params, opt_state=new_opt,
            model_state=new_mstate)
        return new_state, {"loss": xent + l2, "xent": xent}

    def _make_train_step(self) -> Callable:
        mi = self.mesh_info
        shard_axis = mi.model_axis if mi.model_size > 1 else None
        data_axis = mi.data_axis

        def step(state: TrainState, batch):
            return self._step_impl(
                state, batch, data_axis=data_axis, shard_axis=shard_axis)

        donate = (0,) if self._donate_state else ()
        if mi.mesh is None:
            return jax.jit(step, donate_argnums=donate)
        specs = self._dummy_specs()
        return jax.jit(
            shard_map(
                step, mesh=mi.mesh,
                in_specs=(specs["state"], specs["batch"]),
                out_specs=(specs["state"], P()),
                # Grouped psums defeat static replication inference; the
                # hierarchical program opts out of the check.
                check_vma=self._hier_groups is None),
            donate_argnums=donate)

    def _make_train_multi_step(self) -> Callable:
        """K optimizer steps in ONE dispatch: lax.scan over a stacked batch
        [K, B, ...] (K comes from the batch's leading dim; jit specializes
        per shape). Bit-identical to K sequential train_step calls (same rng
        folding, same update order) but amortizes the per-step host dispatch
        and host->device transfer overhead — the dominant e2e cost on a
        single-core host (see README Performance).

        Under ``--grad_accum_steps a`` > 1 the K scanned microbatches
        regroup at trace time into K//a accumulated optimizer applies
        (``_accum_step_impl``) plus K%a single-microbatch FULL optimizer
        steps for a ragged tail group — a tail never stalls on a partial
        accumulation group. ``state.step`` still counts microbatches either
        way (resume bookkeeping equates steps with batches consumed)."""
        mi = self.mesh_info
        shard_axis = mi.model_axis if mi.model_size > 1 else None
        data_axis = mi.data_axis
        a = self._accum

        def multi(state: TrainState, batches):
            def body(st, batch):
                new_st, m = self._step_impl(
                    st, batch, data_axis=data_axis, shard_axis=shard_axis)
                # the row-local update's counts ride beside (none otherwise)
                return new_st, (jnp.stack((m.pop("loss"), m.pop("xent"))), m)

            if a > 1:
                k_steps = batches["label"].shape[0]
                n_macro, left = divmod(k_steps, a)
                ms = None
                if n_macro:
                    groups = jax.tree.map(
                        lambda x: x[:n_macro * a].reshape(
                            (n_macro, a) + x.shape[1:]), batches)

                    def macro_body(st, group):
                        new_st, m = self._accum_step_impl(
                            st, group, data_axis=data_axis,
                            shard_axis=shard_axis)
                        return new_st, jnp.stack((m["loss"], m["xent"]))

                    state, ms = jax.lax.scan(macro_body, state, groups)
                if left:
                    tail = jax.tree.map(lambda x: x[k_steps - left:], batches)
                    state, (ms_tail, _) = jax.lax.scan(body, state, tail)
                    ms = ms_tail if ms is None else jnp.concatenate(
                        [ms, ms_tail])
                return state, {"loss": ms[-1, 0], "xent": ms[-1, 1]}
            state2, (ms, counts) = jax.lax.scan(body, state, batches)
            # Last-step metrics: matches what a sequential loop would report.
            return state2, {"loss": ms[-1, 0], "xent": ms[-1, 1],
                            **{key: v[-1] for key, v in counts.items()}}

        # Donate only the state: scanned batch buffers are not reusable as
        # outputs (XLA reports them unusable and warns).
        donate = (0,) if self._donate_state else ()
        if mi.mesh is None:
            return jax.jit(multi, donate_argnums=donate)
        specs = self._dummy_specs()
        sb_specs = jax.tree.map(lambda s: P(None, *s), specs["batch"])
        return jax.jit(
            shard_map(
                multi, mesh=mi.mesh,
                in_specs=(specs["state"], sb_specs),
                out_specs=(specs["state"], P()),
                check_vma=self._hier_groups is None),
            donate_argnums=donate)

    @property
    def multi_step(self) -> Callable:
        if self._multi_step is None:
            self._multi_step = self._make_train_multi_step()
        return self._multi_step

    def step_hlo_text(self, device=None) -> str:
        """``step_compiled`` as optimized HLO text, an instruction a line
        (``profiling.whole_instructions``), the kernels the compiler names
        itself under the scopes the model declares for them
        (``kernel_scopes``; ``profiling.scope_kernels``)."""
        return prof_lib.scope_kernels(
            prof_lib.whole_instructions(self.step_compiled(device).as_text()),
            getattr(self.model, "kernel_scopes", ()))

    def step_compiled(self, device=None):
        """The compiled K-step dispatch (``steps_per_loop`` steps of
        ``batch_size``, as ``fit`` dispatches them: ``multi_step``, or
        ``train_step`` where K is 1; ``as_text()`` is its optimized HLO,
        ``memory_analysis()`` its footprint), from abstract arguments laid
        out as ``_place`` and ``_put_stacked`` lay out the real ones; a
        trainer without a mesh compiles for ``device`` where one is given
        (a described chip: ``scripts/step_table_ops.py``). This
        compiles the program a fit runs once more; the compiler is
        deterministic, so the instructions are the fit's. Where the
        persistent cache answers instead (its key leaves debug info out),
        an executable cached before a scope was renamed comes back with its
        old ``op_name``s (TUNING §17)."""
        cfg = self.cfg
        state = jax.eval_shape(self._abstract_state_for_specs)
        hl = (cfg.history_max_len
              if getattr(self.model, "uses_history", False) else 0)
        batch = zero_batch(cfg.field_size, cfg.batch_size,
                           len(self._task_names), hl)
        k = max(cfg.steps_per_loop, 1)
        lead = (k,) if k > 1 else ()    # one step a dispatch: train_step
        batches = {key: jax.ShapeDtypeStruct(lead + v.shape, v.dtype)
                   for key, v in batch.items()}
        if self.mesh_info.mesh is not None:
            state = jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                  sharding=s),
                state, self._state_shardings(state))
            sharding = self._stacked_sharding if k > 1 \
                else self._batch_sharding
            batches = {key: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=sharding(x.ndim))
                for key, x in batches.items()}
        elif device is not None:
            one = jax.sharding.SingleDeviceSharding(device)
            state, batches = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
                (state, batches))
        step = self.multi_step if k > 1 else self.train_step
        return step.lower(state, batches).compile()

    def step_op_scopes(self) -> Dict[str, str]:
        """{HLO instruction name: named scope, "" for none} of the compiled
        K-step dispatch, for charging a device trace's ops to the step's
        phases: a profiler event carries its instruction's text and nothing
        of the ``op_name`` the scopes are in (``profiling.hlo_op_scopes``
        over ``step_hlo_text``, which costs a compilation)."""
        return prof_lib.hlo_op_scopes(self.step_hlo_text())

    def put_superbatch(self, batches) -> Dict[str, jax.Array]:
        """Stack K host batches into [K, B, ...] arrays and transfer in one
        host->device move (batch dim sharded over 'data', K replicated)."""
        stacked = {
            key: np.stack([b[key] for b in batches]) for key in batches[0]}
        return self._put_stacked(stacked)

    def put_superbatch_rows(self, rows: Dict[str, np.ndarray], k: int
                            ) -> Dict[str, jax.Array]:
        """[k*B, ...] contiguous rows -> [k, B, ...] device arrays. The
        reshape is free (contiguous view), so a pipeline emitting pool
        slices (CtrPipeline.iter_superbatches) reaches the device with zero
        host-side stacking copies."""
        stacked = {key: v.reshape(k, v.shape[0] // k, *v.shape[1:])
                   for key, v in rows.items()}
        return self._put_stacked(stacked)

    def _stacked_sharding(self, ndim: int):
        """[K, B, ...]: K replicated, the batch dim over 'data'."""
        return self.mesh_info.sharding(
            P(None, mesh_lib.DATA_AXIS, *([None] * (ndim - 2))))

    def _put_stacked(self, stacked: Dict[str, np.ndarray]
                     ) -> Dict[str, jax.Array]:
        if self.mesh_info.mesh is None:
            return jax.device_put(stacked)
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                self._stacked_sharding(x.ndim), x),
            stacked)

    def _eval_update(self, state: TrainState, batch, acc, *, data_axis,
                     shard_axis):
        """One weighted eval update (shared by the single-batch and scanned
        eval steps): ``batch['weight']`` ([B,1], 1=real row, 0=tail padding)
        flows into the AUC histograms and the loss sum, so every record
        counts exactly once regardless of how the tail was padded — and all
        ranks can run the same compiled shape on ragged shards."""
        auc_state, loss_state = acc
        if self._model_loss:
            # The model's own loss, weighted like any other; there is no
            # one probability an example for an AUC, whose state stays empty.
            per_ex, _ = self.model.per_example_loss(
                state.params, state.model_state, batch, train=False,
                rng=None, shard_axis=shard_axis, data_axis=data_axis)
            w = batch["weight"].reshape(-1).astype(jnp.float32)
            loss_total, n = jnp.sum(per_ex * w), jnp.sum(w)
            if data_axis is not None:
                loss_total = jax.lax.psum(loss_total, data_axis)
                n = jax.lax.psum(n, data_axis)
            return (auc_state, metrics_lib.MeanState(
                total=loss_state.total + loss_total,
                count=loss_state.count + n))
        logits, _ = self.model.apply(
            state.params, state.model_state, batch["feat_ids"],
            batch["feat_vals"], train=False, rng=None,
            shard_axis=shard_axis, data_axis=data_axis,
            **self._hist_kwargs(batch))
        if self._multitask:
            # Per-task dict accumulator: one psum-reducible histogram pair
            # per named task; the combined weighted loss mirrors training.
            labels_m = self._batch_labels(batch)
            w = batch["weight"].reshape(-1).astype(jnp.float32)
            per_ex = self._per_example_loss(logits, labels_m)
            probs = self.model.probs_from_logits(logits)
            deltas = {
                name: metrics_lib.auc_update(
                    metrics_lib.auc_init(self.cfg.auc_num_thresholds),
                    probs[:, t], labels_m[:, t], w)
                for t, name in enumerate(self._task_names)}
            loss_total = jnp.sum(per_ex * w)
            n = jnp.sum(w)
            if data_axis is not None:
                deltas = {name: metrics_lib.auc_psum(d, data_axis)
                          for name, d in deltas.items()}
                loss_total = jax.lax.psum(loss_total, data_axis)
                n = jax.lax.psum(n, data_axis)
            new_auc = {name: metrics_lib.auc_merge(auc_state[name], d)
                       for name, d in deltas.items()}
            new_loss = metrics_lib.MeanState(
                total=loss_state.total + loss_total,
                count=loss_state.count + n)
            return (new_auc, new_loss)
        labels = batch["label"].reshape(-1).astype(jnp.float32)
        w = batch["weight"].reshape(-1).astype(jnp.float32)
        per_ex = self._per_example_loss(logits, labels)
        probs = jax.nn.sigmoid(logits)
        delta = metrics_lib.auc_update(
            metrics_lib.auc_init(self.cfg.auc_num_thresholds), probs,
            labels, w)
        loss_total = jnp.sum(per_ex * w)
        n = jnp.sum(w)
        if data_axis is not None:
            delta = metrics_lib.auc_psum(delta, data_axis)
            loss_total = jax.lax.psum(loss_total, data_axis)
            n = jax.lax.psum(n, data_axis)
        new_auc = metrics_lib.auc_merge(auc_state, delta)
        new_loss = metrics_lib.MeanState(
            total=loss_state.total + loss_total, count=loss_state.count + n)
        return (new_auc, new_loss)

    def _make_eval_step(self) -> Callable:
        mi = self.mesh_info
        shard_axis = mi.model_axis if mi.model_size > 1 else None
        data_axis = mi.data_axis

        def step(state: TrainState, batch, acc):
            return self._eval_update(state, batch, acc, data_axis=data_axis,
                                     shard_axis=shard_axis)

        if mi.mesh is None:
            return jax.jit(step)
        specs = self._dummy_specs()
        return jax.jit(shard_map(
            step, mesh=mi.mesh,
            in_specs=(specs["state"], specs["eval_batch"], P()),
            out_specs=P(),
            check_vma=True))

    def _make_eval_multi_step(self) -> Callable:
        """K weighted eval updates in ONE dispatch: lax.scan over stacked
        [K, B, ...] batches (the eval twin of ``multi_step``, VERDICT r3
        #2). The scan merges into the accumulator in batch order; on CPU
        that reproduces K sequential ``eval_step`` calls bit-for-bit (the
        property the tests pin), while on TPU the scanned program may fuse
        or reassociate float reductions differently, so expect agreement
        to rounding there, not bit-identity. Only the per-batch host
        dispatch + transfer overhead is amortized."""
        mi = self.mesh_info
        shard_axis = mi.model_axis if mi.model_size > 1 else None
        data_axis = mi.data_axis

        def multi(state: TrainState, batches, acc):
            def body(a, batch):
                return self._eval_update(
                    state, batch, a, data_axis=data_axis,
                    shard_axis=shard_axis), None
            acc2, _ = jax.lax.scan(body, acc, batches)
            return acc2

        if mi.mesh is None:
            return jax.jit(multi)
        specs = self._dummy_specs()
        sb_specs = jax.tree.map(lambda s: P(None, *s), specs["eval_batch"])
        return jax.jit(shard_map(
            multi, mesh=mi.mesh,
            in_specs=(specs["state"], sb_specs, P()),
            out_specs=P(),
            check_vma=True))

    def _predict_logits(self, state: TrainState, batch, *, data_axis,
                        shard_axis):
        logits, _ = self.model.apply(
            state.params, state.model_state, batch["feat_ids"],
            batch["feat_vals"], train=False, rng=None,
            shard_axis=shard_axis, data_axis=data_axis,
            **self._hist_kwargs(batch))
        if self._multitask:
            return self.model.probs_from_logits(logits)  # [B, T]
        return jax.nn.sigmoid(logits)

    def _make_predict_step(self) -> Callable:
        mi = self.mesh_info
        shard_axis = mi.model_axis if mi.model_size > 1 else None

        def step(state: TrainState, batch):
            return self._predict_logits(
                state, batch, data_axis=mi.data_axis, shard_axis=shard_axis)

        if mi.mesh is None:
            return jax.jit(step)
        specs = self._dummy_specs()
        return jax.jit(shard_map(
            step, mesh=mi.mesh,
            in_specs=(specs["state"], specs["batch"]),
            out_specs=P(mesh_lib.DATA_AXIS),
            check_vma=True))

    def _make_predict_multi_step(self) -> Callable:
        """K forward passes in ONE dispatch: scan over stacked [K, B, ...]
        batches returning [K, B] probabilities (the infer twin of
        ``multi_step``)."""
        mi = self.mesh_info
        shard_axis = mi.model_axis if mi.model_size > 1 else None

        def multi(state: TrainState, batches):
            def body(carry, batch):
                return carry, self._predict_logits(
                    state, batch, data_axis=mi.data_axis,
                    shard_axis=shard_axis)
            _, probs = jax.lax.scan(body, 0, batches)
            return probs

        if mi.mesh is None:
            return jax.jit(multi)
        specs = self._dummy_specs()
        sb_specs = jax.tree.map(lambda s: P(None, *s), specs["batch"])
        return jax.jit(shard_map(
            multi, mesh=mi.mesh,
            in_specs=(specs["state"], sb_specs),
            out_specs=P(None, mesh_lib.DATA_AXIS),
            check_vma=True))

    def _dummy_specs(self) -> Dict[str, Any]:
        if self._specs is None:
            # Build spec trees from an abstract state (no device memory).
            abstract = jax.eval_shape(
                lambda: self._abstract_state_for_specs())
            state_specs = self._state_specs(abstract)
            batch = {
                "feat_ids": jax.ShapeDtypeStruct(
                    (self.cfg.batch_size, self.cfg.field_size), jnp.int32),
                "feat_vals": jax.ShapeDtypeStruct(
                    (self.cfg.batch_size, self.cfg.field_size), jnp.float32),
                "label": jax.ShapeDtypeStruct(
                    (self.cfg.batch_size, 1), jnp.float32),
            }
            if self._multitask:
                batch["label2"] = jax.ShapeDtypeStruct(
                    (self.cfg.batch_size, 1), jnp.float32)
            if (getattr(self.model, "uses_history", False)
                    and self.cfg.history_max_len > 0):
                # History runs (history_max_len > 0) carry the fixed-shape
                # pair in every batch (zero_batch emits all-masked fillers
                # for lockstep) — the shard_map in_specs tree must include
                # them or any DIN/BST mesh run dies on pytree structure
                # mismatch. At history_max_len == 0 the zoo feeds plain
                # batches and the models default to an empty history.
                hl = self.cfg.history_max_len
                batch["hist_ids"] = jax.ShapeDtypeStruct(
                    (self.cfg.batch_size, hl), jnp.int32)
                batch["hist_mask"] = jax.ShapeDtypeStruct(
                    (self.cfg.batch_size, hl), jnp.float32)
            eval_batch = dict(batch)
            eval_batch["weight"] = jax.ShapeDtypeStruct(
                (self.cfg.batch_size, 1), jnp.float32)
            self._specs = {
                "state": state_specs,
                "batch": mesh_lib.batch_pspecs(batch),
                "eval_batch": mesh_lib.batch_pspecs(eval_batch),
            }
        return self._specs

    def _abstract_state_for_specs(self) -> TrainState:
        rng = jax.random.PRNGKey(0)
        params, model_state = self.model.init(rng)
        opt_state = self._init_opt_state(params)
        return TrainState.create(params, opt_state, model_state, rng)

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------
    @property
    def train_step(self) -> Callable:
        if self._train_step is None:
            self._train_step = self._make_train_step()
        return self._train_step

    @property
    def eval_step(self) -> Callable:
        if self._eval_step is None:
            self._eval_step = self._make_eval_step()
        return self._eval_step

    @property
    def eval_multi_step(self) -> Callable:
        if self._eval_multi_step is None:
            self._eval_multi_step = self._make_eval_multi_step()
        return self._eval_multi_step

    @property
    def predict_step(self) -> Callable:
        if self._predict_step is None:
            self._predict_step = self._make_predict_step()
        return self._predict_step

    @property
    def predict_multi_step(self) -> Callable:
        if self._predict_multi_step is None:
            self._predict_multi_step = self._make_predict_multi_step()
        return self._predict_multi_step

    def _staged_put(self, put: Callable, *args) -> Any:
        """Route a staging-thread host->device transfer through the active
        fit's staging ring (slot fence + transfer/wait timing). Identity
        passthrough outside fit, so eval/predict transfers are untouched."""
        ring = self._ring
        if ring is None:
            return put(*args)
        return ring.put(lambda: put(*args), *_staged_size(args))

    def _pull(self, source: Iterable) -> Iterator:
        """``source``'s items, each ``next()`` under a ``stage.input_wait``
        span on the staging thread: what the trainer waits for its input.
        ``seq`` is the superbatch the item goes into."""
        it = iter(source)
        try:
            while True:
                ring = self._ring
                with trace_lib.span(
                        "stage.input_wait",
                        seq=ring.staged + 1 if ring is not None else 0):
                    try:
                        item = next(it)
                    except StopIteration:
                        return
                yield item
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()

    def _grad_payload_bytes(self) -> int:
        """Analytic per-device payload of ONE gradient reduce over 'data'
        (row-sharded embedding leaves count 1/model_size; see
        mesh.grad_payload_bytes). Computed once from abstract shapes."""
        if self._grad_bytes_cache is None:
            abstract = jax.eval_shape(
                lambda: self._abstract_state_for_specs())
            self._grad_bytes_cache = mesh_lib.grad_payload_bytes(
                abstract.params, self._embed_names,
                self.mesh_info.model_size,
                embedding_shard=("rows" if self.sparse_embed
                                 and self._shard_rows else "off"))
        return self._grad_bytes_cache

    def _stage(self, batches: Iterable[Dict[str, np.ndarray]], k: int,
               depth: int):
        """Group host batches into K-step superbatches and move them to device
        on a background thread, ``depth`` dispatch-groups ahead — overlapping
        the host->device transfer with step dispatch (the prefetch-to-device
        iterator analog of X3). Yields (device_batches, n_steps, n_local_ex).
        A tail group smaller than K is staged as single steps (no recompile
        for odd sizes).

        Fast path: a source exposing ``iter_superbatches`` (CtrPipeline)
        emits pre-grouped contiguous rows, skipping the np.stack copy."""
        sb_iter = getattr(batches, "iter_superbatches", None)

        def gen():
            if sb_iter is not None and k > 1:
                for rows, m, n_ex in self._pull(sb_iter(k)):
                    if m == 1:
                        yield self._staged_put(self.put_batch, rows), 1, n_ex
                    else:
                        yield self._staged_put(
                            self.put_superbatch_rows, rows, m), m, n_ex
                return
            group = []
            for b in self._pull(batches):
                group.append(b)
                if len(group) == k:
                    n_ex = sum(g["label"].shape[0] for g in group)
                    if k == 1:
                        yield self._staged_put(
                            self.put_batch, group[0]), 1, n_ex
                    else:
                        yield self._staged_put(
                            self.put_superbatch, group), k, n_ex
                    group = []
            for b in group:
                yield (self._staged_put(self.put_batch, b), 1,
                       b["label"].shape[0])

        if depth <= 0:
            return gen()
        from ..data.pipeline import _prefetch  # noqa: PLC0415
        return _prefetch(gen(), depth)

    def _stage_tiered(self, batches: Iterable[Dict[str, np.ndarray]],
                      k: int, depth: int):
        """Tiered staging: same grouping contract as ``_stage``, but every
        group is routed through the hot/cold runtime on the staging thread
        — plan the cache transaction, PREFETCH missing cold rows (the fetch
        for dispatch t+1 overlaps the device computing dispatch t when
        ``depth`` > 0), and remap ``feat_ids`` to hot slot ids — before the
        host->device transfer. Plan order == yield order == dispatch order;
        the fit loop pops one plan per yielded group via
        ``_tier.apply_next``."""

        def stage_group(group):
            n_ex = sum(g["label"].shape[0] for g in group)
            remapped = self._tier.plan_group(group)
            if len(remapped) == 1:
                return self._staged_put(self.put_batch, remapped[0]), 1, n_ex
            return (self._staged_put(self.put_superbatch, remapped),
                    len(remapped), n_ex)

        def gen():
            group = []
            for b in self._pull(batches):
                group.append(b)
                if len(group) == k:
                    yield stage_group(group)
                    group = []
            for b in group:
                yield stage_group([b])

        if depth <= 0:
            return gen()
        from ..data.pipeline import _prefetch  # noqa: PLC0415
        return _prefetch(gen(), depth)

    def _stage_rounds(self, batches: Iterable[Dict[str, np.ndarray]],
                      k: int, depth: int):
        """Background staging for the multi-process fit loop: pull k-batch
        rounds off the host pipeline and pre-transfer FULL rounds to device.

        Device placement (``put_superbatch`` -> ``make_array_from_process_
        local_data``) is process-local — each process only places its own
        shard on its own devices, no cross-host communication — so it is
        safe on a background thread. The collectives (the per-round count
        allgather and the step programs) are issued by the CALLER in
        deterministic order; this generator never touches them.

        Yields ``(staged, group)``: ``staged`` is the [k,B,...] device
        superbatch for full rounds (None for short ones), ``group`` the
        host batches — retained so a rank that turns out globally short
        can transfer the agreed prefix (a staged rank slices its device
        superbatch instead). One short round ends the
        stream (source exhausted). The np.stack in ``put_superbatch`` (vs
        the single-process zero-copy ``iter_superbatches`` feed) is the
        price of the lockstep protocol — the min-truncate exchange needs
        discrete batches, and ``iter_superbatches`` may emit short groups
        at pool boundaries, which would end the protocol early on one rank
        — but the copy runs on this staging thread, off the critical path."""
        import itertools  # noqa: PLC0415

        def gen():
            it = self._pull(batches)
            try:
                while True:
                    group = list(itertools.islice(it, k))
                    staged = None
                    if len(group) == k:
                        staged = (self._staged_put(self.put_superbatch, group)
                                  if k > 1
                                  else self._staged_put(
                                      self.put_batch, group[0]))
                    yield staged, group
                    if len(group) < k:
                        return
            finally:
                close = getattr(it, "close", None)
                if close is not None:
                    close()

        if depth <= 0:
            return gen()
        from ..data.pipeline import _prefetch  # noqa: PLC0415
        return _prefetch(gen(), depth)

    def _stage_multiprocess(self, batches: Iterable[Dict[str, np.ndarray]],
                            k: int, depth: int):
        """Multi-process staging with transfer/compute overlap (VERDICT r3
        #1): same yield contract as ``_stage`` and the same lockstep
        min-truncate protocol as rounds-of-k ragged-shard handling — every
        train dispatch is a global-mesh collective, so all ranks must run
        the same number of steps even when file-level shards hold different
        record counts. Each round, ranks exchange how many local batches
        they pulled; everyone dispatches the global minimum and stops at
        the first short round (longer ranks' leftovers are dropped — the
        cross-rank generalization of drop_remainder; the records return
        next epoch under the reshuffle).

        The host->device transfer of full rounds runs on a background
        thread ``depth`` rounds ahead (see ``_stage_rounds``); ALL
        collectives — count allgathers and step programs — are enqueued
        from the caller's thread, so their order is identical on every
        rank."""
        from jax.experimental import multihost_utils  # noqa: PLC0415

        rounds = self._stage_rounds(batches, k, depth)
        try:
            for staged, group in rounds:
                counts = np.asarray(multihost_utils.process_allgather(
                    np.asarray([len(group)])))
                m = int(counts.min())
                if m == k and staged is not None:
                    n_ex = sum(g["label"].shape[0] for g in group)
                    yield staged, k, n_ex
                elif m > 0:
                    # Globally-short final round. Every rank must dispatch
                    # the SAME program sequence (the step programs are
                    # global collectives), so all ranks emit ONE m-step
                    # group: ranks that already transferred a full [k,B]
                    # superbatch slice its prefix ON DEVICE (advisor r5 —
                    # previously the staged transfer was discarded and the
                    # prefix re-transferred batch-by-batch), short ranks
                    # transfer just their m batches. m == 1 lands on the
                    # single-step program every rank has already compiled;
                    # m > 1 costs one tail-of-training compile of the
                    # [m,B] scan. The slice is collective-free, so only
                    # staged ranks running it cannot desync the mesh.
                    n_ex = sum(g["label"].shape[0] for g in group[:m])
                    if staged is not None and k > 1:
                        if m == 1:
                            dev = jax.jit(
                                lambda d: {key: v[0] for key, v in d.items()}
                            )(staged)
                        else:
                            dev = jax.jit(
                                lambda d, _m=m: {key: v[:_m]
                                                 for key, v in d.items()}
                            )(staged)
                        yield dev, m, n_ex
                    elif m == 1:
                        yield self.put_batch(group[0]), 1, n_ex
                    else:
                        yield self.put_superbatch(group[:m]), m, n_ex
                if m < k:
                    if len(group) > m:
                        ulog.warning(
                            f"ragged shards: dropped >= {len(group) - m} "
                            f"local batches to keep ranks in lockstep (min "
                            f"of {counts.reshape(-1).tolist()} per round)")
                    return
        finally:
            # Early exit abandons the staging thread mid-stream on longer
            # ranks; close it so prefetch threads and file handles release.
            close = getattr(rounds, "close", None)
            if close is not None:
                close()

    def _guard_verdict(self, guard: "guard_lib.NonFiniteGuard",
                       state: TrainState, m: Dict[str, Any]) -> str:
        """Per-dispatch guard check for the skip/rollback policies: sync the
        dispatch's loss (the one extra device read those policies pay), run
        the on-device all-isfinite param reduce, classify. Shared by the
        staged and device-resident fit loops."""
        loss = float(m["loss"])
        params_bad = (guard.params_nonfinite(state)
                      if math.isfinite(loss) else False)
        return guard.observe(loss, int(state.step), params_bad=params_bad)

    def _make_watchdog(self, guard, data_health
                       ) -> Optional["guard_lib.StallWatchdog"]:
        if self.cfg.dispatch_timeout_s <= 0:
            return None
        return guard_lib.StallWatchdog(
            self.cfg.dispatch_timeout_s,
            health=guard.health if guard is not None else None,
            data_health=data_health, abort=self.watchdog_abort).start()

    def fit(
        self,
        state: TrainState,
        batches: Iterable[Dict[str, np.ndarray]],
        *,
        hooks: Optional[list] = None,
        max_steps: Optional[int] = None,
        on_log: Optional[Callable[[int, float, float], None]] = None,
        guard: Optional["guard_lib.NonFiniteGuard"] = None,
    ) -> Tuple[TrainState, Dict[str, float]]:
        """Run the train loop over an iterable of host batches.

        Dispatches ``cfg.steps_per_loop`` optimizer steps per host round trip
        (one stacked transfer + one lax.scan program); hooks fire once per
        dispatch with ``metrics["steps_done"]`` = number of steps taken.

        ``guard`` (a :class:`guard_lib.NonFiniteGuard`) enables the
        non-finite policy: under ``abort`` it piggybacks on the log-cadence
        loss sync; under ``skip``/``rollback`` every dispatch is checked
        before its update is accepted — a skip restores the pre-dispatch
        state and fires no hooks (the dropped dispatch never happened), a
        rollback raises :class:`guard_lib.RollbackSignal` for the task
        driver to restore the last checkpoint.
        """
        # Start-up's last two phases, in the fit that makes the process's
        # first dispatch (None in every later one): the first superbatch in
        # hand, then that dispatch enqueued.
        boot = startup.first_fit()
        cfg = self.cfg
        k = max(cfg.steps_per_loop, 1)
        world = jax.process_count() if self.mesh_info.mesh is not None else 1
        src_health = getattr(batches, "health", None)
        if max_steps is not None:
            import itertools  # noqa: PLC0415
            batches = itertools.islice(iter(batches), max_steps)
        depth = cfg.transfer_ahead
        # Device staging ring: every staging-thread transfer below routes
        # through it (via _staged_put), fencing on slot reuse — 2 slots =
        # transfer/compute overlap, 1 slot = serialized A/B baseline.
        ring = _StagingRing(cfg.staging_buffers)
        self._ring = ring
        if self._tier is not None:
            # Hot/cold tiering: plan + prefetch + slot remap on the staging
            # thread (single-process single-device by construction).
            staged_iter = self._stage_tiered(batches, k, depth)
        elif world > 1:
            # Lockstep min-truncate protocol + background transfer: all
            # collectives (the count allgathers AND the step programs) are
            # enqueued on THIS thread in the same order on every rank; only
            # the process-local host->device transfers run ahead on the
            # staging thread (VERDICT r3 #1: previously depth was forced to
            # 0 here, serializing transfer with dispatch).
            staged_iter = self._stage_multiprocess(batches, k, depth)
        else:
            staged_iter = self._stage(batches, k, depth)
        guard_active = guard is not None and guard.per_dispatch
        watchdog = self._make_watchdog(guard, src_health)
        last_loss = float("nan")
        t0 = time.time()
        examples_since_log = 0
        n_steps = 0
        m: Dict[str, Any] = {}
        prev_state: Optional[TrainState] = None
        meter = prof_lib.ThroughputMeter()
        comm_applies = 0
        try:
            for dev_batch, steps_done, local_ex in staged_iter:
                if boot is not None:
                    boot.batch_in_hand()
                if self._tier is not None:
                    # Install this dispatch's fetched cold rows BEFORE the
                    # guard's prev_state snapshot: a skipped dispatch then
                    # still retains its installs, keeping the directory and
                    # the device cache consistent.
                    state = self._tier.apply_next(state)
                if guard_active:
                    # Donation is off under skip (see __init__), so the
                    # pre-dispatch state stays valid for a dropped update.
                    prev_state, prev_m = state, m
                with trace_lib.span("train.dispatch",
                                    seq=ring.dispatched + 1,
                                    steps=steps_done, examples=local_ex):
                    if steps_done == 1:
                        state, m = self.train_step(state, dev_batch)
                    else:
                        state, m = self.multi_step(state, dev_batch)
                if boot is not None:
                    # where set-up went, tracing on or off (TUNING §17)
                    ulog.info(boot.dispatched(steps_done))
                    boot = None
                # Slot fence + comms accounting BEFORE the guard verdict: a
                # skipped dispatch still occupied its staging slot and its
                # collectives still crossed the fabric.
                ring.retire(m["loss"])
                comm_applies += (steps_done // self._accum
                                 + steps_done % self._accum)
                if guard_active:
                    verdict = self._guard_verdict(guard, state, m)
                    if verdict == "skip":
                        # The poisoned batch is consumed; its update is not.
                        # No hooks, no step count: the dispatch never
                        # happened as far as checkpoints/logs are concerned.
                        state, m = prev_state, prev_m
                        if watchdog is not None:
                            watchdog.beat(n_steps)
                        continue
                    if verdict == "rollback":
                        raise guard_lib.RollbackSignal(int(state.step))
                prev_steps = n_steps
                n_steps += steps_done
                examples_since_log += local_ex * world
                meter.update(local_ex * world, steps_done)
                if watchdog is not None:
                    watchdog.beat(n_steps)
                if cfg.log_steps and (n_steps // cfg.log_steps
                                      > prev_steps // cfg.log_steps):
                    with trace_lib.span("train.log_sync",
                                        step=n_steps) as sync:
                        # device sync, bounded by the log cadence
                        loss = float(m["loss"])
                        gstep = int(state.step)
                        if trace_lib.enabled():
                            # the step's counts (the last scanned step's,
                            # like the loss): ready with it
                            # (whole numbers, but for a model's count
                            # that is a float: kda_chunk_log_decay_min)
                            counts = {key: (int(v) if jnp.issubdtype(
                                jnp.result_type(v), jnp.integer)
                                else float(v)) for key, v in m.items()
                                      if key not in ("loss", "xent",
                                                     "steps_done")}
                            # a model whose loss has parts says each, the
                            # main one (``xent``) too
                            counts.update({
                                key: float(m[key]) for key in getattr(
                                    self.model, "loss_parts", ())})
                            # how the compiled step made its table
                            # gradient, or wrote its rows back
                            for key, how in ((EMBED_GRAD, self.embed_grad),
                                             (EMBED_GRAD_BY_TABLE,
                                              self.embed_grad_by_table),
                                             (EMBED_LOOKUP,
                                              self.embed_lookup),
                                             (ROW_WRITEBACK,
                                              self.row_writeback)):
                                if how is not None:
                                    counts[key] = how
                            # what the model says its traced step is made
                            # of (a note may name a count in braces)
                            counts.update({
                                key: note.format(**counts) for key, note
                                in getattr(self.model, "step_notes",
                                           {}).items()})
                            sync.add(**counts)
                    last_loss = loss
                    if guard is not None and not guard_active:
                        # abort policy: reuse the loss scalar this log line
                        # already synced — zero extra dispatch cost.
                        guard.observe(
                            loss, gstep,
                            params_bad=(guard.params_nonfinite(state)
                                        if math.isfinite(loss) else False))
                    dt = time.time() - t0
                    eps = examples_since_log / max(dt, 1e-9)
                    ulog.info(
                        f"step={gstep} loss={loss:.5f} examples/sec={eps:,.0f}")
                    health = getattr(batches, "health", None)
                    if health is not None and health.consume_dirty():
                        # Fault events (healed retries / skipped records) since
                        # the last log line — same cadence as the loss log.
                        ulog.info(f"data health: {health.summary()}")
                    if on_log is not None:
                        # Same cadence as the log line: loss/step were already
                        # synced above, so the callback adds no device reads.
                        on_log(gstep, loss, eps)
                    t0 = time.time()
                    examples_since_log = 0
                if hooks:
                    m = dict(m)
                    m["steps_done"] = steps_done
                    for hook in hooks:
                        hook(state, m)
        finally:
            if watchdog is not None:
                watchdog.stop()
            # Unblock a staging thread parked on a slot fence before closing
            # the generator (close joins the prefetch thread).
            ring.close()
            self._ring = None
            # A mid-loop exception (rollback, preemption, abort) abandons the
            # staging generator; close it so prefetch threads, input-service
            # workers and file handles release before any retry attempt.
            close = getattr(staged_iter, "close", None)
            if close is not None:
                close()
        if n_steps:
            # Fold the async-dispatch drain into the measurement window so
            # the meter reports completed-on-device throughput, not host
            # dispatch rate.
            jax.block_until_ready(m["loss"])
            meter.record_drain()
        if np.isnan(last_loss) and n_steps:
            last_loss = float(m["loss"])
        out = {"loss": last_loss, "steps": float(n_steps)}
        out.update({k_: v for k_, v in meter.summary().items() if k_ != "steps"})
        if self.mesh_info.data_size > 1 and comm_applies:
            # Analytic comms volume of the gradient sync: applies x
            # per-apply payload.
            out["collective_applies"] = float(comm_applies)
            out["collective_bytes"] = float(
                comm_applies * self._grad_payload_bytes())
            out["collective_strategy"] = (
                "hierarchical" if self._hier_groups is not None else "flat")
        return state, out

    # ------------------------------------------------------------------
    # Device-resident dataset mode
    # ------------------------------------------------------------------
    # The decoded epoch lives in device memory; each dispatch gathers its
    # batches by row index ON DEVICE, so per-dispatch host->device traffic
    # is one int32 scalar (the cursor) instead of k*B records. The epoch's
    # emission order is computed on host exactly as the staged pooled path
    # would emit it, so with mesh=None the trajectory is bit-identical to
    # ``fit`` over the same pipeline (the CPU parity test pins this).

    @staticmethod
    def _device_memory_bytes() -> int:
        """Per-device memory limit as the backend reports it. The CPU
        backend reports none, and there the budget check runs against
        ``_CPU_TEST_MEMORY_BYTES`` so tests can exercise it through
        device_dataset_hbm_fraction; an accelerator that reports no limit
        is an error, not a guess."""
        dev = jax.devices()[0]
        limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
        if limit > 0:
            return limit
        if dev.platform == "cpu":
            return _CPU_TEST_MEMORY_BYTES
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            "bytes_limit in memory_stats(); cannot size the device-resident "
            "dataset against its memory")

    def device_dataset_ineligible(self, pipe) -> Optional[str]:
        """None when ``fit_device_resident`` can reproduce the staged run
        for this pipeline, else a human-readable disqualifier (the caller
        warns and falls back to the staged path)."""
        cfg = self.cfg
        if self._multitask:
            return "multi-task run (the decoded-cache column set carries a "\
                   "single label column)"
        if jax.process_count() > 1:
            return "multi-process run (device columns would need per-host "\
                   "record sharding)"
        if self.mesh_info.model_size > 1:
            return "model-parallel mesh (row-sharded embedding lookups use "\
                   "the shard_map step path)"
        if getattr(pipe, "decoded_cache", "off") == "off":
            return "pipeline has no decoded cache (device upload reads the "\
                   "cached columns)"
        if getattr(pipe, "skip_batches", 0):
            return "resume skip_batches offset pending (staged path owns "\
                   "the trained-prefix drop)"
        try:
            cols = pipe.decoded_epoch_columns()
        except Exception as exc:  # cache build failed: surface via staged path
            return f"decoded cache unavailable ({exc})"
        n = cols.num_records
        if n == 0:
            return "empty dataset"
        k = max(cfg.steps_per_loop, 1)
        if pipe.shuffle and n >= max(pipe.shuffle_buffer, k * pipe.batch_size):
            return (f"shuffle pool smaller than the epoch ({n} records): "
                    "pool drain order depends on chunk arrival and cannot "
                    "be reproduced as a device gather")
        per_device = (cols.nbytes() // max(self.mesh_info.data_size, 1)
                      + n * 4)  # columns (row-sharded) + replicated index
        budget = int(self._device_memory_bytes()
                     * cfg.device_dataset_hbm_fraction)
        if per_device > budget:
            return (f"decoded epoch needs ~{per_device / 2**20:.1f} MiB "
                    f"per device, over the {budget / 2**20:.1f} MiB budget "
                    f"(device_dataset_hbm_fraction="
                    f"{cfg.device_dataset_hbm_fraction})")
        return None

    def _dd_upload(self, pipe) -> Dict[str, jax.Array]:
        """Upload the cached columns once per fingerprint; later epochs
        (and later fit calls over the same data) reuse the device copy."""
        fp = pipe.decoded_cache_fingerprint()
        if self._dd_cols is not None and self._dd_cols[0] == fp:
            return self._dd_cols[1]
        cols = pipe.decoded_epoch_columns()
        host = {"label": np.ascontiguousarray(cols.labels, np.float32),
                "feat_ids": np.ascontiguousarray(cols.ids, np.int32),
                "feat_vals": np.ascontiguousarray(cols.vals, np.float32)}
        mi = self.mesh_info
        if mi.mesh is None:
            dev = jax.device_put(host)
        else:
            # Single-process data mesh: rows sharded over 'data' (padding
            # rows are never indexed — every gather index is < n).
            pad = (-cols.num_records) % mi.data_size
            if pad:
                host = {key: np.concatenate(
                    [v, np.zeros((pad,) + v.shape[1:], v.dtype)])
                    for key, v in host.items()}
            dev = {key: jax.device_put(v, mi.sharding(
                P(mesh_lib.DATA_AXIS, *([None] * (v.ndim - 1)))))
                for key, v in host.items()}
        self._dd_cols = (fp, dev)
        return dev

    def _dd_put_indices(self, idx: np.ndarray) -> jax.Array:
        mi = self.mesh_info
        if mi.mesh is None:
            return jax.device_put(idx)
        return jax.device_put(idx, mi.sharding(P(None)))

    def _dd_program(self, m_steps: int, bsz: int) -> Callable:
        """Compiled ``(state, cols, idx, start) -> (state, metrics)``: slice
        ``m_steps*bsz`` emission indices at the cursor, gather the rows on
        device, scan the train step over them (same rng folding and metric
        convention as ``multi_step``). ``start`` is a traced scalar, so one
        compile serves every cursor position of this shape."""
        key = (m_steps, bsz)
        prog = self._dd_programs.get(key)
        if prog is not None:
            return prog

        def run(state: TrainState, cols, idx, start):
            sel = jax.lax.dynamic_slice_in_dim(idx, start, m_steps * bsz)
            sel = sel.reshape(m_steps, bsz)

            def body(st, s):
                batch = {"label": cols["label"][s],
                         "feat_ids": cols["feat_ids"][s],
                         "feat_vals": cols["feat_vals"][s]}
                new_st, m = self._step_impl(
                    st, batch, data_axis=None, shard_axis=None)
                return new_st, jnp.stack((m["loss"], m["xent"]))

            state2, ms = jax.lax.scan(body, state, sel)
            return state2, {"loss": ms[-1, 0], "xent": ms[-1, 1]}

        # Plain jit even under a (pure-data) mesh: inputs carry their
        # shardings and GSPMD partitions the gather + step; the global-mean
        # gradient math is identical to the single-device formulation.
        prog = jax.jit(run, donate_argnums=(0,) if self._donate_state else ())
        self._dd_programs[key] = prog
        return prog

    def fit_device_resident(
        self,
        state: TrainState,
        pipe,
        *,
        hooks: Optional[list] = None,
        max_steps: Optional[int] = None,
        on_log: Optional[Callable[[int, float, float], None]] = None,
        guard: Optional["guard_lib.NonFiniteGuard"] = None,
    ) -> Tuple[TrainState, Dict[str, float]]:
        """Train with the whole decoded dataset resident on device.

        Callers must have cleared :meth:`device_dataset_ineligible` first.
        Mirrors ``fit``'s contract: same dispatch grouping as the staged
        pooled pipeline (k-step superbatches, then single batches, then the
        short remainder unless ``drop_remainder``), same hook/log/meter
        cadence, same guard semantics, same return dict.
        """
        cfg = self.cfg
        k = max(cfg.steps_per_loop, 1)
        bs = pipe.batch_size
        cols = pipe.decoded_epoch_columns()
        n = cols.num_records
        dev_cols = self._dd_upload(pipe)
        remaining = max_steps
        meter = prof_lib.ThroughputMeter()
        last_loss = float("nan")
        t0 = time.time()
        examples_since_log = 0
        n_steps = 0
        m: Dict[str, Any] = {}
        health = getattr(pipe, "health", None)
        guard_active = guard is not None and guard.per_dispatch
        watchdog = self._make_watchdog(guard, health)
        try:
            for e in range(pipe.num_epochs):
                if remaining is not None and remaining <= 0:
                    break
                epoch = e + getattr(pipe, "epoch_offset", 0)
                idx_dev = self._dd_put_indices(
                    pipe.device_epoch_indices(epoch, k))
                # The staged pool's emission plan for one epoch, as batch
                # sizes.
                n_batches = n // bs
                r = n - n_batches * bs
                sizes = [bs] * n_batches
                if r and not pipe.drop_remainder:
                    sizes.append(r)
                if remaining is not None:
                    sizes = sizes[:remaining]
                    remaining -= len(sizes)
                start = 0
                i = 0
                while i < len(sizes):
                    if (sizes[i] == bs and i + k <= len(sizes)
                            and sizes[i + k - 1] == bs):
                        mm, bsz = k, bs
                    else:
                        mm, bsz = 1, sizes[i]
                    prog = self._dd_program(mm, bsz)
                    if guard_active:
                        prev_state, prev_m = state, m
                    state, m = prog(state, dev_cols, idx_dev, np.int32(start))
                    # The dispatch's rows are consumed whether or not its
                    # update survives the guard.
                    start += mm * bsz
                    i += mm
                    if guard_active:
                        verdict = self._guard_verdict(guard, state, m)
                        if verdict == "skip":
                            state, m = prev_state, prev_m
                            if watchdog is not None:
                                watchdog.beat(n_steps)
                            continue
                        if verdict == "rollback":
                            raise guard_lib.RollbackSignal(int(state.step))
                    prev_steps = n_steps
                    n_steps += mm
                    examples_since_log += mm * bsz
                    meter.update(mm * bsz, mm)
                    if watchdog is not None:
                        watchdog.beat(n_steps)
                    if cfg.log_steps and (n_steps // cfg.log_steps
                                          > prev_steps // cfg.log_steps):
                        loss = float(m["loss"])
                        gstep = int(state.step)
                        last_loss = loss
                        if guard is not None and not guard_active:
                            guard.observe(
                                loss, gstep,
                                params_bad=(guard.params_nonfinite(state)
                                            if math.isfinite(loss) else False))
                        dt = time.time() - t0
                        eps = examples_since_log / max(dt, 1e-9)
                        ulog.info(f"step={gstep} loss={loss:.5f} "
                                  f"examples/sec={eps:,.0f}")
                        if health is not None and health.consume_dirty():
                            ulog.info(f"data health: {health.summary()}")
                        if on_log is not None:
                            on_log(gstep, loss, eps)
                        t0 = time.time()
                        examples_since_log = 0
                    if hooks:
                        m = dict(m)
                        m["steps_done"] = mm
                        for hook in hooks:
                            hook(state, m)
        finally:
            if watchdog is not None:
                watchdog.stop()
        if n_steps:
            jax.block_until_ready(m["loss"])
            meter.record_drain()
        if np.isnan(last_loss) and n_steps:
            last_loss = float(m["loss"])
        out = {"loss": last_loss, "steps": float(n_steps)}
        out.update({k_: v for k_, v in meter.summary().items() if k_ != "steps"})
        return state, out

    def lockstep_batches(
        self,
        batches: Iterable[Dict[str, np.ndarray]],
        make_dummy: Callable[[], Dict[str, np.ndarray]],
        *,
        rounds_of: Optional[int] = None,
    ) -> Iterator[Tuple[Dict[str, np.ndarray], bool]]:
        """Yield ``(batch, is_real)`` with IDENTICAL yield counts across
        ranks — the shared lockstep mechanism for collective step functions
        over ragged per-rank shards (used by ``evaluate`` and the infer
        task; ``fit`` uses min-truncation instead because dummy batches
        would corrupt optimizer state).

        Each round every rank pulls up to ``rounds_of`` local batches and
        allgathers its count once; ranks below the round maximum top up with
        ``make_dummy()`` batches (callers mask them via zero weight or by
        discarding the output). Terminates when every rank is exhausted.
        One cross-host exchange per round, not per batch; all collectives
        are issued from the caller's thread in deterministic order.
        """
        from jax.experimental import multihost_utils  # noqa: PLC0415
        import itertools  # noqa: PLC0415

        k = max(self.cfg.steps_per_loop, 1) if rounds_of is None else rounds_of
        it = iter(batches)
        try:
            while True:
                group = list(itertools.islice(it, k))
                counts = np.asarray(multihost_utils.process_allgather(
                    np.asarray([len(group)])))
                top = int(counts.max())
                if top == 0:
                    return  # every rank exhausted
                for b in group:
                    yield b, True
                for _ in range(top - len(group)):
                    yield make_dummy(), False
        finally:
            # A consumer exception mid-eval/infer abandons the source; close
            # it so prefetch threads and file handles release promptly.
            close = getattr(it, "close", None)
            if close is not None:
                close()

    def _dummy_eval_batch(self, local_bs: int) -> Dict[str, np.ndarray]:
        """All-zero-weight batch: contributes nothing to AUC/loss."""
        hist_len = (self.cfg.history_max_len
                    if getattr(self.model, "uses_history", False) else 0)
        return {**zero_batch(self.cfg.field_size, local_bs,
                             num_labels=len(self._task_names),
                             hist_len=hist_len),
                "weight": np.zeros((local_bs, 1), np.float32)}

    def evaluate(
        self,
        state: TrainState,
        batches: Iterable[Dict[str, np.ndarray]],
    ) -> Dict[str, float]:
        """Streaming eval: AUC (reference's sole metric, :249-251) + mean loss.

        Collective-safe on ragged shards: every batch is padded to the
        compiled shape with a zero-weight tail (so NO record is dropped and
        none double-counts), and under multi-process ``lockstep_batches``
        keeps the eval_step collectives aligned — a rank whose shard is
        exhausted feeds zero-weight dummy batches until every rank is done."""
        if self._tier is not None:
            # Offline eval runs the ordinary dense forward over the full
            # table (flushed hot rows + cold store).
            state = self._tier.densified(state)
        cfg = self.cfg
        world = jax.process_count() if self.mesh_info.mesh is not None else 1
        local_bs = cfg.batch_size // world
        if cfg.batch_size % world != 0:
            raise ValueError(
                f"global batch_size={cfg.batch_size} not divisible by "
                f"process_count={world}")
        if self._multitask:
            acc = ({name: metrics_lib.auc_init(cfg.auc_num_thresholds)
                    for name in self._task_names},
                   metrics_lib.mean_init())
        else:
            acc = (metrics_lib.auc_init(cfg.auc_num_thresholds),
                   metrics_lib.mean_init())
        acc = jax.device_put(acc)
        n = 0
        if world > 1:
            staged = ((b if not real else _with_weight(b, local_bs), real)
                      for b, real in self.lockstep_batches(
                          batches, lambda: self._dummy_eval_batch(local_bs)))
        else:
            staged = ((_with_weight(b, local_bs), True) for b in batches)
        # K batches per dispatch (one stacked transfer + one lax.scan
        # program, VERDICT r3 #2) with single-step fallback for the short
        # tail group and for non-uniform shapes (an oversize batch jit-
        # respecializes on the single-step path). Group boundaries are
        # rank-identical under multi-process: lockstep_batches dummy-fills
        # every round to the same count on every rank, so the k-grouping —
        # and therefore the dispatched program sequence — stays aligned.
        k = max(cfg.steps_per_loop, 1)
        dispatched = 0
        t_start = time.time()
        group: list = []

        def flush(acc, dispatched):
            if len(group) == k and k > 1 and len(
                    {g["label"].shape[0] for g in group}) == 1:
                acc = self.eval_multi_step(
                    state, self.put_superbatch(group), acc)
                dispatched += 1
            else:
                for g in group:
                    acc = self.eval_step(state, self.put_batch(g), acc)
                    dispatched += 1
            group.clear()
            return acc, dispatched

        t_first_done = None  # wall clock after the first dispatch returned
        n_first = 0          # real batches covered by that first dispatch
        for batch, real in staged:
            group.append(batch)
            n += int(real)  # real local batches only (dummies excluded)
            if len(group) == k:
                acc, dispatched = flush(acc, dispatched)
                if t_first_done is None:
                    t_first_done = time.time()
                    n_first = n
        if group:
            acc, dispatched = flush(acc, dispatched)
            if t_first_done is None:
                t_first_done = time.time()
                n_first = n
        if dispatched == 0:
            # Nothing ran anywhere (a rank that only fed dummies still has a
            # valid psum-merged global acc and must NOT zero it out).
            out = {"auc": 0.0, "loss": 0.0, "batches": 0.0,
                   "examples_per_sec": 0.0,
                   "examples_per_sec_steady": 0.0}
            if self._multitask:
                out.update({f"auc_{name}": 0.0 for name in self._task_names})
            return out
        auc_state, loss_state = acc
        if self._multitask:
            per_task_auc = {
                name: float(metrics_lib.auc_compute(auc_state[name]))
                for name in self._task_names}  # device sync
            auc = per_task_auc[self._task_names[0]]
        else:
            per_task_auc = None
            auc = float(metrics_lib.auc_compute(auc_state))  # device sync
        n_examples = float(loss_state.count)  # global weighted count
        # Wall includes the final device sync above, so the rate is
        # completed-on-device, not dispatch rate. First-call numbers include
        # compile; steady-state callers (e.g. per-epoch eval after epoch 1)
        # see the amortized scanned-dispatch rate (VERDICT r3 #2).
        elapsed = max(time.time() - t_start, 1e-9)
        raw_eps = n_examples / elapsed
        # Steady-state rate: exclude the first dispatch (whose return time
        # bounds the jit compile) from the window and its batches from the
        # numerator. On a single-dispatch eval there is no steady window —
        # report the raw rate so the key is always present and comparable.
        first_elapsed = (t_first_done - t_start) if t_first_done else 0.0
        if dispatched > 1 and n > n_first and elapsed - first_elapsed > 1e-9:
            steady_eps = (n_examples * (n - n_first) / n) / (
                elapsed - first_elapsed)
        else:
            steady_eps = raw_eps
        out = {
            "auc": auc,
            "loss": float(metrics_lib.mean_compute(loss_state)),
            "batches": float(n),
            "examples_per_sec": raw_eps,
            "examples_per_sec_steady": steady_eps,
        }
        if per_task_auc is not None:
            # Named per-task AUCs alongside the headline (= first task).
            out.update({f"auc_{name}": v for name, v in per_task_auc.items()})
        return out

    def _local_rows(self, arr: jax.Array) -> np.ndarray:
        """This process's rows of a data-sharded output. Fully-addressable
        arrays (single process) fetch whole; otherwise concatenate the
        addressable row-shards in index order, deduplicating replicas across
        the 'model' axis."""
        if arr.is_fully_addressable:
            return np.asarray(jax.device_get(arr))
        seen: Dict[int, np.ndarray] = {}
        for s in arr.addressable_shards:
            start = s.index[0].start or 0
            if start not in seen:
                seen[start] = np.asarray(s.data)
        return np.concatenate([seen[k] for k in sorted(seen)])

    def _local_rows_stacked(self, arr: jax.Array) -> np.ndarray:
        """This process's rows of a [K, B]-stacked data-sharded output as a
        [K, local_B] array (axis 1 carries the 'data' sharding; axis 0 is
        the scan/stack dimension, replicated)."""
        if arr.is_fully_addressable:
            return np.asarray(jax.device_get(arr))
        seen: Dict[int, np.ndarray] = {}
        for s in arr.addressable_shards:
            start = s.index[1].start or 0
            if start not in seen:
                seen[start] = np.asarray(s.data)
        return np.concatenate([seen[k] for k in sorted(seen)], axis=1)

    def predict(
        self,
        state: TrainState,
        batches: Iterable[Dict[str, np.ndarray]],
    ) -> Iterator[np.ndarray]:
        """Yield per-batch probability vectors for this process's rows
        (reference infer task :445-449).

        Uniform-shaped batches are grouped ``steps_per_loop`` at a time into
        ONE stacked transfer + one scanned program (``predict_multi_step``,
        VERDICT r3 #2); short or ragged groups fall back to per-batch
        dispatch. A caller feeding a constant-shape padded stream (the infer
        task) gets the amortized path automatically, and per-batch yield
        order is preserved either way."""
        if self._tier is not None:
            state = self._tier.densified(state)
        k = max(self.cfg.steps_per_loop, 1)
        group: list = []
        for batch in batches:
            group.append(batch)
            if len(group) == k:
                yield from self._predict_group(state, group)
                group = []
        if group:
            yield from self._predict_group(state, group)

    def _predict_group(self, state: TrainState, group: list
                       ) -> Iterator[np.ndarray]:
        if len(group) > 1 and len({g["label"].shape[0] for g in group}) == 1:
            probs = self.predict_multi_step(state, self.put_superbatch(group))
            rows = self._local_rows_stacked(probs)
            for i in range(rows.shape[0]):
                yield rows[i]
        else:
            for g in group:
                yield self._local_rows(
                    self.predict_step(state, self.put_batch(g)))
