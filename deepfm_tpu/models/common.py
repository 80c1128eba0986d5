"""Shared model building blocks: initializers, DNN tower with BN/dropout.

Behavioral parity notes (vs reference ``model_fn``, ``1-ps-cpu/...py:149-292``):
  * Hidden layers: dense -> ReLU -> [BatchNorm] -> [dropout] (BN applied
    *after* the activation, reference ``:219-221``).
  * ``dropout`` values are KEEP probabilities (``tf.nn.dropout(keep_prob=...)``
    reference ``:222``), applied in TRAIN mode only.
  * Final output layer: dense to 1 with identity activation (``:226``).
  * Weight init: glorot/Xavier (``glorot_normal_initializer`` for embeddings
    ``:167-168``; ``fully_connected`` default glorot_uniform for the tower).
  * Only FM_W / FM_V carry an effective l2 penalty — the tower's regularizer
    losses were never added to the loss in the reference (TF1 collection not
    collected), so the tower here has none.

TPU-first: tower matmuls run in ``compute_dtype`` (bfloat16 by default) with
float32 params and float32 loss; BN statistics are float32. Under data
parallelism (``data_axis`` set, inside shard_map) BatchNorm uses
*cross-replica* statistics via pmean — a deliberate improvement over the
reference's per-worker BN stats (deterministic w.r.t. world size).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import embedding as emb_ops
from ..ops import pallas_embedding as pemb

Params = Dict[str, Any]
State = Dict[str, Any]


def glorot_normal(rng: jax.Array, shape: Sequence[int],
                  dtype: jnp.dtype = jnp.float32) -> jax.Array:
    fan_in, fan_out = _fans(shape)
    std = (2.0 / (fan_in + fan_out)) ** 0.5
    return std * jax.random.normal(rng, shape, dtype)


def glorot_uniform(rng: jax.Array, shape: Sequence[int],
                   dtype: jnp.dtype = jnp.float32) -> jax.Array:
    fan_in, fan_out = _fans(shape)
    limit = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


def _fans(shape: Sequence[int]) -> Tuple[int, int]:
    if len(shape) < 1:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    recep = 1
    for s in shape[:-2]:
        recep *= s
    return shape[-2] * recep, shape[-1] * recep


# ---------------------------------------------------------------------------
# BatchNorm (running-stats state; reference batch_norm_layer :286-291)
# ---------------------------------------------------------------------------


def batch_norm(
    h32: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    bn_state: State,
    *,
    train: bool,
    decay: float,
    data_axis: Optional[str] = None,
    eps: float = 1e-3,
) -> Tuple[jnp.ndarray, State]:
    """Normalize h32 [B, D] (float32). Returns (normalized, new_bn_state)."""
    if train:
        mean = jnp.mean(h32, axis=0)
        mean_sq = jnp.mean(jnp.square(h32), axis=0)
        if data_axis is not None:
            mean = jax.lax.pmean(mean, data_axis)
            mean_sq = jax.lax.pmean(mean_sq, data_axis)
        var = mean_sq - jnp.square(mean)
        new_state = {
            "mean": decay * bn_state["mean"] + (1 - decay) * mean,
            "var": decay * bn_state["var"] + (1 - decay) * var,
        }
    else:
        mean, var = bn_state["mean"], bn_state["var"]
        new_state = bn_state
    out = (h32 - mean) * jax.lax.rsqrt(var + eps) * scale + bias
    return out, new_state


# ---------------------------------------------------------------------------
# DNN tower
# ---------------------------------------------------------------------------


def init_hidden_stack(rng: jax.Array, in_dim: int, layer_sizes: Sequence[int],
                      use_bn: bool) -> Tuple[Params, State]:
    params: Params = {"layers": []}
    state: State = {"bn": []}
    dims = [in_dim] + list(layer_sizes)
    keys = jax.random.split(rng, max(len(layer_sizes), 1))
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        layer = {
            "w": glorot_uniform(keys[i], (d_in, d_out)),
            "b": jnp.zeros((d_out,), jnp.float32),
        }
        if use_bn:
            layer["bn_scale"] = jnp.ones((d_out,), jnp.float32)
            layer["bn_bias"] = jnp.zeros((d_out,), jnp.float32)
            state["bn"].append({
                "mean": jnp.zeros((d_out,), jnp.float32),
                "var": jnp.ones((d_out,), jnp.float32),
            })
        params["layers"].append(layer)
    return params, state


def _hidden_stack(
    params: Params,
    state: State,
    x: jnp.ndarray,
    *,
    train: bool,
    dropout_keep: Sequence[float],
    use_bn: bool,
    bn_decay: float,
    rng: Optional[jax.Array],
    compute_dtype: jnp.dtype = jnp.bfloat16,
    data_axis: Optional[str] = None,
) -> Tuple[jnp.ndarray, State]:
    """dense->relu->[BN]->[dropout] stack. x: [B, D_in] -> ([B, D_last], state)."""
    new_state: State = {"bn": []}
    h = x.astype(compute_dtype)
    n_layers = len(params["layers"])
    if train and rng is not None and n_layers:
        drop_keys = list(jax.random.split(rng, n_layers))
    else:
        drop_keys = [None] * n_layers
    for i, layer in enumerate(params["layers"]):
        h = h @ layer["w"].astype(compute_dtype) + layer["b"].astype(compute_dtype)
        h = jax.nn.relu(h)
        if use_bn:
            h32, bn_new = batch_norm(
                h.astype(jnp.float32), layer["bn_scale"], layer["bn_bias"],
                state["bn"][i], train=train, decay=bn_decay, data_axis=data_axis)
            new_state["bn"].append(bn_new)
            h = h32.astype(compute_dtype)
        keep = dropout_keep[i] if i < len(dropout_keep) else 1.0
        if train and keep < 1.0 and drop_keys[i] is not None:
            mask = jax.random.bernoulli(drop_keys[i], keep, h.shape)
            h = jnp.where(mask, h / keep, jnp.zeros((), h.dtype))
    return h, new_state


#: One dense->relu stack, named for where it stands: the top tower of every
#: model, and the bottom MLP under dlrm_dcnv2's numeric features.
apply_hidden_stack = jax.named_scope("tower")(_hidden_stack)
apply_bottom_stack = jax.named_scope("bottom")(_hidden_stack)


def init_tower(rng: jax.Array, in_dim: int, layer_sizes: Sequence[int],
               use_bn: bool) -> Tuple[Params, State]:
    """Hidden stack + final dense->1. Returns (params, bn_state)."""
    k_stack, k_out = jax.random.split(rng)
    params, state = init_hidden_stack(k_stack, in_dim, layer_sizes, use_bn)
    last = layer_sizes[-1] if layer_sizes else in_dim
    params["out"] = {
        "w": glorot_uniform(k_out, (last, 1)),
        "b": jnp.zeros((1,), jnp.float32),
    }
    return params, state


@jax.named_scope("tower")
def apply_tower(
    params: Params,
    state: State,
    x: jnp.ndarray,
    *,
    train: bool,
    dropout_keep: Sequence[float],
    use_bn: bool,
    bn_decay: float,
    rng: Optional[jax.Array],
    compute_dtype: jnp.dtype = jnp.bfloat16,
    data_axis: Optional[str] = None,
) -> Tuple[jnp.ndarray, State]:
    """Run hidden stack + output head. x: [B, D] -> ([B], new_bn_state)."""
    h, new_state = apply_hidden_stack(
        params, state, x, train=train, dropout_keep=dropout_keep, use_bn=use_bn,
        bn_decay=bn_decay, rng=rng, compute_dtype=compute_dtype,
        data_axis=data_axis)
    out = h @ params["out"]["w"].astype(h.dtype) + params["out"]["b"].astype(h.dtype)
    return out.astype(jnp.float32)[:, 0], new_state


def l2_half_sum(x: jnp.ndarray) -> jnp.ndarray:
    """tf.nn.l2_loss semantics: 0.5 * sum(x^2) (reference loss ``:244-246``)."""
    return 0.5 * jnp.sum(jnp.square(x.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# Embedding schema: monolithic vs hash-bucketed multi-table layout
# ---------------------------------------------------------------------------


class EmbeddingSchema:
    """Resolves cfg into the embedding-table layout and owns every operation
    the models and trainer perform on it.

    Two layouts behind one interface:

    * **monolithic** (``embedding_buckets`` empty): one ``[padded_vocab,...]``
      array per embedding param — the original layout, entry pytree and init
      numerics unchanged (checkpoints stay compatible).
    * **hashed** (``embedding_buckets`` set): a dict of N tables
      ``{"t0": [B0,...], ...}``; ids map to a table (by id-hash or by field)
      and to a per-table bucket via stateless uint32 mixing
      (ops.embedding.hash_bucket), so the *logical* ``feature_size`` can
      exceed any single physical allocation.

    The sparse-update path speaks :class:`ops.embedding.PlanEntry` per
    table: the trainer builds one plan per batch, gathers the touched rows
    as the gradient leaf, and the models consume the gathered view through
    ``lookup_rows`` — the cotangent scatter-add (the segment-sum) therefore
    sizes with the batch's unique ids, never with the vocab.
    """

    #: plan/rows dict key for the monolithic table
    MONO = "table"

    def __init__(self, cfg: Any):
        self.feature_size = int(cfg.feature_size)
        self.field_size = int(cfg.field_size)
        self.buckets: List[int] = list(cfg.embedding_bucket_sizes)
        self.hashed = bool(self.buckets)
        self.assign = cfg.embedding_assign
        self.lookup_strategy = cfg.embedding_lookup
        self.kernels = getattr(cfg, "embedding_kernels", "auto")
        self.padded_vocab = emb_ops.padded_vocab(
            cfg.feature_size, cfg.mesh_model)
        # Row-sharding metadata (--embedding_shard rows): num_shards is the
        # model-axis size the tables are partitioned over; 1 means every
        # device holds full tables (the replicated layout). Table SHAPES
        # never depend on this (padded_vocab is mesh-independent), only
        # the placement and the step program do.
        self.shard_rows = getattr(cfg, "embedding_shard", "off") == "rows"
        self.num_shards = max(int(cfg.mesh_model), 1) if self.shard_rows else 1

    def table_rows(self, key: str) -> int:
        """Global row count of one physical table."""
        if not self.hashed:
            return self.padded_vocab
        return self.buckets[int(key[1:])]

    def rows_local(self, key: str) -> int:
        """Rows per shard of one table (== table_rows when unsharded)."""
        return self.table_rows(key) // self.num_shards

    # -- layout ---------------------------------------------------------
    def table_keys(self) -> List[str]:
        if not self.hashed:
            return [self.MONO]
        return [f"t{i}" for i in range(len(self.buckets))]

    def num_physical_rows(self) -> int:
        """Rows actually allocated (vs the logical feature_size)."""
        return sum(self.buckets) if self.hashed else self.padded_vocab

    def init_entry(self, rng: jax.Array, trailing: Tuple[int, ...]) -> Any:
        """Glorot-normal tables (reference embedding init). Monolithic
        reproduces the original init bit-for-bit: glorot over the REAL
        vocab, zero pad rows concatenated after."""
        if not self.hashed:
            t = glorot_normal(rng, (self.feature_size, *trailing))
            if self.padded_vocab != self.feature_size:
                pad = self.padded_vocab - self.feature_size
                t = jnp.concatenate(
                    [t, jnp.zeros((pad, *trailing), t.dtype)])
            return t
        keys = jax.random.split(rng, len(self.buckets))
        return {f"t{i}": glorot_normal(keys[i], (b, *trailing))
                for i, b in enumerate(self.buckets)}

    # -- id -> (table, bucket) mapping ---------------------------------
    def _table_of(self, feat_ids: jnp.ndarray) -> jnp.ndarray:
        n = len(self.buckets)
        if self.assign == "field":
            f = jnp.arange(feat_ids.shape[-1], dtype=jnp.int32) % n
            return jnp.broadcast_to(f, feat_ids.shape)
        return emb_ops.hash_table_assign(feat_ids, n)

    # -- dense forward --------------------------------------------------
    def lookup(self, entry: Any, feat_ids: jnp.ndarray, *,
               axis_name: Optional[str] = None) -> jnp.ndarray:
        """[B,F,*trailing] gather for the dense path (and eval/predict)."""
        if not self.hashed:
            return emb_ops.lookup(entry, feat_ids, axis_name=axis_name,
                                  strategy=self.lookup_strategy)
        table_of = self._table_of(feat_ids)
        shard = (jax.lax.axis_index(axis_name)
                 if axis_name is not None else None)
        out = None
        for i, b in enumerate(self.buckets):
            bucket = emb_ops.hash_bucket(feat_ids, b, salt=i + 1)
            tab = entry[f"t{i}"]
            if shard is None:
                part = jnp.take(tab, bucket, axis=0)
            else:
                # Row-sharded bucket (--embedding_shard rows): local
                # masked take; ONE psum below reassembles every bucket's
                # shard contributions at once.
                local = bucket - shard * tab.shape[0]
                ok = (local >= 0) & (local < tab.shape[0])
                part = jnp.take(tab, jnp.clip(local, 0, tab.shape[0] - 1),
                                axis=0)
                okx = ok.reshape(ok.shape + (1,) * (part.ndim - ok.ndim))
                part = jnp.where(okx, part, jnp.zeros((), part.dtype))
            sel = (table_of == i).astype(part.dtype)
            sel = sel.reshape(sel.shape + (1,) * (part.ndim - sel.ndim))
            part = part * sel
            out = part if out is None else out + part
        if axis_name is not None:
            out = jax.lax.psum(out, axis_name)
        return out

    # -- sparse-update plan ---------------------------------------------
    def sparse_plan(self, feat_ids: jnp.ndarray,
                    num_rows: Optional[int] = None
                    ) -> Dict[str, emb_ops.PlanEntry]:
        """One batch's dedup plan per table. ``num_rows`` overrides the
        monolithic OOB fill id (the tiered runtime feeds SLOT ids, whose
        table is embedding_hot_rows tall — padded_vocab still works as the
        fill because slots < hot_rows < padded_vocab, but an explicit
        override keeps intent readable)."""
        if not self.hashed:
            rows = self.padded_vocab if num_rows is None else int(num_rows)
            return {self.MONO: pemb.plan_build(feat_ids, rows,
                                               mode=self.kernels)}
        table_of = self._table_of(feat_ids)
        plan = {}
        for i, b in enumerate(self.buckets):
            bucket = emb_ops.hash_bucket(feat_ids, b, salt=i + 1)
            sel = table_of == i
            per_table = jnp.where(sel, bucket, jnp.int32(b))  # OOB when not ours
            plan[f"t{i}"] = pemb.plan_build(
                per_table, b, mask=sel.astype(jnp.float32),
                mode=self.kernels)
        return plan

    def tables(self, entry: Any) -> Dict[str, jax.Array]:
        """Uniform dict view of an entry: {key: [rows, ...] table}."""
        return entry if self.hashed else {self.MONO: entry}

    def from_tables(self, tables: Dict[str, jax.Array]) -> Any:
        return tables if self.hashed else tables[self.MONO]

    def gather_rows(self, entry: Any, plan: Dict[str, emb_ops.PlanEntry]
                    ) -> Dict[str, jax.Array]:
        """Touched rows per table — the sparse path's gradient leaf."""
        tabs = self.tables(entry)
        return {k: emb_ops.gather_rows(tabs[k], plan[k]) for k in plan}

    def lookup_rows(self, rows: Dict[str, jax.Array],
                    plan: Optional[Dict[str, emb_ops.PlanEntry]]
                    ) -> jnp.ndarray:
        """[B,F,*trailing] forward view over pre-gathered rows. When
        ``plan`` is None the rows are already the [B,F,...] batch view
        (the fused-backward path remaps once for all params up front)."""
        if plan is None:
            assert len(rows) == 1
            return next(iter(rows.values()))
        out = None
        for k in plan:
            part = emb_ops.lookup_rows(rows[k], plan[k])
            out = part if out is None else out + part
        return out

    # -- regularization -------------------------------------------------
    def l2(self, entry: Any, *, axis_name: Optional[str] = None
           ) -> jnp.ndarray:
        """0.5*sum(x^2) over REAL rows only — padded_vocab pad rows are
        structurally excluded (they are zero, so the value is unchanged;
        the exclusion guarantees their gradient is exactly zero by
        construction, not by reachability argument)."""
        if self.hashed:
            return sum(l2_half_sum(t) for t in entry.values())
        keep = emb_ops.pad_row_mask(entry.shape[0], self.feature_size,
                                    axis_name)
        keep = keep.reshape((-1,) + (1,) * (entry.ndim - 1))
        sq = jnp.square(entry.astype(jnp.float32))
        return 0.5 * jnp.sum(jnp.where(keep, sq, jnp.zeros((), sq.dtype)))

    def l2_rows(self, rows: Dict[str, jax.Array],
                plan: Dict[str, emb_ops.PlanEntry]) -> jnp.ndarray:
        """Sparse-mode L2 over the batch's TOUCHED rows only (OOB fill
        slots excluded). Deliberate deviation from dense L2 — idle rows do
        not decay between touches; TUNING §2.11 quantifies the drift."""
        total = None
        for k, entry in plan.items():
            valid = emb_ops.valid_rows(entry).astype(jnp.float32)
            valid = valid.reshape((-1,) + (1,) * (rows[k].ndim - 1))
            sq = jnp.square(rows[k].astype(jnp.float32)) * valid
            s = 0.5 * jnp.sum(sq)
            total = s if total is None else total + s
        return total

    def mask_pad_grads(self, grad_entry: Any, *,
                       axis_name: Optional[str] = None) -> Any:
        """Zero pad-row gradients on the dense path (hashed tables have no
        pad rows — every bucket is reachable)."""
        if self.hashed:
            return grad_entry
        return emb_ops.mask_pad_rows(grad_entry, self.feature_size,
                                     axis_name)
