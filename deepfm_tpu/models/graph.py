"""Explicit feature→tower graph: the decomposition every ranking model rides.

Every model in the zoo factors into the same three stages:

  1. **Embedding lookup** — named `EmbeddingSchema` entries (``fm_w`` [V],
     ``fm_v`` [V,K]) gathered per batch; row-shardable over the ``model``
     mesh axis, or fed pre-gathered touched rows on the sparse-update path.
  2. **Shared interaction blocks** — pure functions over the embedded
     features: first-order sum, FM second-order, DCN-v2 cross network,
     DLRM dot-interaction, the DNN hidden stack (``models.common``), and
     the MMoE expert mixture (``models.multitask``).
  3. **Task heads** — each named task reduces the block outputs to one
     logit. Single-task graphs emit ``[B]``; multi-task graphs
     (``models.multitask``) emit ``[B, T]`` with per-task losses combined
     by configurable weights.

``deepfm_tpu.models`` exports ``GraphDeepFM``, ``GraphWideDeep`` and
``GraphDCNv2`` under their public names ``DeepFM``, ``WideDeep``, ``DCNv2``;
the NumPy oracles in tests/test_models.py hold their forward and loss.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import export as jax_export

from ..config import Config
from ..ops import fm as fm_ops
from ..ops import pallas_fm
from . import common


# ----------------------------------------------------------------------
# Interaction blocks: pure functions over embedded features.
# ----------------------------------------------------------------------

@jax.named_scope("fm")
def first_order(w: jnp.ndarray, feat_vals: jnp.ndarray) -> jnp.ndarray:
    """Linear term sum_f W[ids]*vals — the "wide" part. [B,F] -> [B]."""
    return jnp.sum(w * feat_vals, axis=1)


@jax.named_scope("fm")
def fm_block(cfg: Config, w: jnp.ndarray, feat_vals: jnp.ndarray,
             xv: jnp.ndarray) -> jnp.ndarray:
    """First-order + FM second-order in one block (fused on TPU).

    Matches DeepFM's reference graph: ``sum_f(W*vals) + FM(xv)``. Takes the
    Pallas fused kernel when supported — both reductions in one VMEM pass —
    else the factored identity from ``ops.fm``.

    The kernel's grid is sized from the batch, so it needs a static one: the
    exported serving function (``utils.export``) traces with a symbolic
    batch and is lowered for CPU and TPU at once, and therefore takes the
    portable formulation on every platform.
    """
    if (cfg.use_pallas and not jax_export.is_symbolic_dim(xv.shape[0])
            and pallas_fm.supported(cfg.field_size, cfg.embedding_size)):
        # Fused Pallas path: both FM reductions in one VMEM pass over the
        # same xv the tower consumes; d(xv)->d(v),d(vals) via JAX's
        # product rule outside the kernel.
        return pallas_fm.fused_fm(w, feat_vals, xv)
    return jnp.sum(w * feat_vals, axis=1) + fm_ops.fm_interaction(xv)


def init_cross_layer(key: jax.Array, d: int, cross_rank: int
                     ) -> Dict[str, jnp.ndarray]:
    """One DCN-v2 cross layer: full-rank W [D,D] or low-rank U/V factors."""
    if cross_rank > 0:
        return {
            "u": common.glorot_uniform(key, (cross_rank, d)),
            "v": common.glorot_uniform(
                jax.random.fold_in(key, 1), (d, cross_rank)),
            "b": jnp.zeros((d,), jnp.float32),
        }
    return {
        "w": common.glorot_uniform(key, (d, d)),
        "b": jnp.zeros((d,), jnp.float32),
    }


@jax.named_scope("cross")
def cross_network(cross_params, x0c: jnp.ndarray,
                  compute_dtype: jnp.dtype) -> jnp.ndarray:
    """DCN-v2 cross tower: x_{l+1} = x0 * (W_l x_l + b_l) + x_l.

    ``x0c`` must already be cast to ``compute_dtype``; per-layer weights are
    cast inside the loop (the MXU-friendly recipe the legacy class used).
    """
    cdt = compute_dtype
    x = x0c
    for layer in cross_params:
        if "u" in layer:
            inner = (x @ layer["v"].astype(cdt)) @ layer["u"].astype(cdt)
        else:
            inner = x @ layer["w"].astype(cdt)
        x = x0c * (inner + layer["b"].astype(cdt)) + x
    return x


@jax.named_scope("cross")
def dot_interaction(xv: jnp.ndarray) -> jnp.ndarray:
    """DLRM-style pairwise dot-interaction (Naumov et al., 2019).

    All F·(F-1)/2 distinct pairwise dots of the per-field embedding vectors:
    [B,F,K] -> [B, F*(F-1)/2]. The Gram matmul is MXU work; the triangular
    gather indices are static.
    """
    f = xv.shape[1]
    iu, ju = np.triu_indices(f, k=1)
    gram = jnp.matmul(xv, jnp.swapaxes(xv, 1, 2))  # [B,F,F]
    return gram[:, iu, ju]


# ----------------------------------------------------------------------
# Graph model skeleton: embedding stage + generic regularization.
# ----------------------------------------------------------------------

class GraphModel:
    """Shared skeleton of every feature→tower graph.

    Owns the embedding stage (schema, dense/sparse lookup, pad-aware L2)
    so concrete graphs only wire interaction blocks and heads. Subclasses
    define ``init`` and ``apply``; ``task_names``/``num_tasks`` default to
    the single-task contract (logits ``[B]``).
    """

    name = "graph"
    task_names: Tuple[str, ...] = ("ctr",)

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.emb = common.EmbeddingSchema(cfg)
        self.padded_vocab = self.emb.padded_vocab

    @property
    def num_tasks(self) -> int:
        return len(self.task_names)

    @jax.named_scope("embed")
    def _emb_lookup(self, params: common.Params, name: str,
                    feat_ids: jnp.ndarray, shard_axis: Optional[str],
                    emb_rows: Optional[Dict[str, Any]],
                    emb_plan: Optional[Dict[str, Any]]) -> jnp.ndarray:
        """Dense gather from the full table, or (sparse-update path) the
        batch's pre-gathered touched rows — ``emb_rows[name]`` is the
        gradient leaf there, so AD of this inverse-index gather lowers to
        the batch-sized segment-sum scatter instead of a full-table one."""
        if emb_rows is not None:
            return self.emb.lookup_rows(emb_rows[name], emb_plan)
        return self.emb.lookup(params[name], feat_ids, axis_name=shard_axis)

    def lookup_ids(self, feat_ids: jnp.ndarray) -> jnp.ndarray:
        """The ids ``apply`` looks up in every embedding table (what it
        hands ``_emb_lookup``): all fields' here. A trainer that passes
        pre-gathered views as ``emb_rows`` gathers them at these."""
        return feat_ids

    @jax.named_scope("l2")
    def l2_loss(self, params: common.Params, *,
                shard_axis: Optional[str] = None,
                emb_rows: Optional[Dict[str, Any]] = None,
                emb_plan: Optional[Dict[str, Any]] = None) -> jnp.ndarray:
        """l2_reg * sum of pad-aware L2 over every embedding entry
        (reference :244-246). The sparse path penalizes only the batch's
        touched rows (TUNING §2.11)."""
        names = self.embedding_param_names()
        if emb_rows is not None:
            total = self.emb.l2_rows(emb_rows[names[0]], emb_plan)
            for n in names[1:]:
                total = total + self.emb.l2_rows(emb_rows[n], emb_plan)
        else:
            total = self.emb.l2(params[names[0]], axis_name=shard_axis)
            for n in names[1:]:
                total = total + self.emb.l2(params[n], axis_name=shard_axis)
        return self.cfg.l2_reg * total

    def embedding_param_names(self) -> Tuple[str, ...]:
        """Top-level param keys that are row-sharded over the model axis."""
        return ("fm_w", "fm_v")


class GraphDeepFM(GraphModel):
    """DeepFM as a graph: (fm_w, fm_v) → [fm_block, tower] → ctr head.

    The reference ``model_fn`` graph
    (``1-ps-cpu/DeepFM-dist-ps-for-multipleCPU-multiInstance.py:149-292``):

        y = FM_B + sum_f(W[ids]*vals) + FM(xv) + DNN(flatten(xv))
        pred = sigmoid(y)

    with FM_W: [V], FM_V: [V, K] glorot-normal (reference ``:166-168``), the
    FM identity from ``ops.fm`` and the tower from ``models.common``.
    """

    name = "deepfm"

    def init(self, rng: jax.Array) -> Tuple[common.Params, common.State]:
        cfg = self.cfg
        k_w, k_v, k_mlp = jax.random.split(rng, 3)
        fm_w = self.emb.init_entry(k_w, ())
        fm_v = self.emb.init_entry(k_v, (cfg.embedding_size,))
        tower, bn_state = common.init_tower(
            k_mlp, cfg.field_size * cfg.embedding_size, cfg.deep_layer_sizes,
            cfg.batch_norm)
        params = {"fm_b": jnp.zeros((1,), jnp.float32),
                  "fm_w": fm_w, "fm_v": fm_v, "tower": tower}
        return params, bn_state

    def apply(
        self,
        params: common.Params,
        state: common.State,
        feat_ids: jnp.ndarray,   # int32 [B, F]
        feat_vals: jnp.ndarray,  # f32 [B, F]
        *,
        train: bool,
        rng: Optional[jax.Array] = None,
        shard_axis: Optional[str] = None,
        data_axis: Optional[str] = None,
        emb_rows: Optional[Dict[str, Any]] = None,
        emb_plan: Optional[Dict[str, Any]] = None,
    ) -> Tuple[jnp.ndarray, common.State]:
        cfg = self.cfg
        feat_vals = feat_vals.astype(jnp.float32)

        # Embedding stage (reference :177-187).
        w = self._emb_lookup(params, "fm_w", feat_ids, shard_axis,
                             emb_rows, emb_plan)  # [B,F]
        v = self._emb_lookup(params, "fm_v", feat_ids, shard_axis,
                             emb_rows, emb_plan)  # [B,F,K]
        xv = v * feat_vals[..., None]

        # Interaction blocks: fused first+second order FM, deep tower over
        # flattened xv (reference :203-226).
        y_wv = fm_block(cfg, w, feat_vals, xv)
        deep_in = xv.reshape(xv.shape[0], cfg.field_size * cfg.embedding_size)
        tower_fn = lambda p, x: common.apply_tower(
            p, state, x, train=train, dropout_keep=cfg.dropout_rates,
            use_bn=cfg.batch_norm, bn_decay=cfg.batch_norm_decay, rng=rng,
            compute_dtype=jnp.dtype(cfg.compute_dtype), data_axis=data_axis)
        if cfg.remat:
            y_d, new_state = jax.checkpoint(tower_fn)(params["tower"], deep_in)
        else:
            y_d, new_state = tower_fn(params["tower"], deep_in)

        logits = params["fm_b"][0] + y_wv + y_d  # [B] (reference :229-231)
        return logits, new_state


class GraphWideDeep(GraphDeepFM):
    """Wide&Deep as a graph: first_order block + tower, no FM term.

    Same input contract and embedding tables as DeepFM; the model drops the
    second-order FM term, keeping y = b + wide(ids, vals) + DNN(xv).
    """

    name = "widedeep"

    def apply(
        self,
        params: common.Params,
        state: common.State,
        feat_ids: jnp.ndarray,
        feat_vals: jnp.ndarray,
        *,
        train: bool,
        rng: Optional[jax.Array] = None,
        shard_axis: Optional[str] = None,
        data_axis: Optional[str] = None,
        emb_rows: Optional[Dict[str, Any]] = None,
        emb_plan: Optional[Dict[str, Any]] = None,
    ) -> Tuple[jnp.ndarray, common.State]:
        cfg = self.cfg
        feat_vals = feat_vals.astype(jnp.float32)

        # Wide: linear over sparse features (first-order block).
        w = self._emb_lookup(params, "fm_w", feat_ids, shard_axis,
                             emb_rows, emb_plan)
        y_wide = first_order(w, feat_vals)

        # Deep: tower over embedded features.
        v = self._emb_lookup(params, "fm_v", feat_ids, shard_axis,
                             emb_rows, emb_plan)
        xv = v * feat_vals[..., None]
        deep_in = xv.reshape(xv.shape[0], cfg.field_size * cfg.embedding_size)
        y_d, new_state = common.apply_tower(
            params["tower"], state, deep_in, train=train,
            dropout_keep=cfg.dropout_rates, use_bn=cfg.batch_norm,
            bn_decay=cfg.batch_norm_decay, rng=rng,
            compute_dtype=jnp.dtype(cfg.compute_dtype), data_axis=data_axis)

        logits = params["fm_b"][0] + y_wide + y_d
        return logits, new_state


class GraphDCNv2(GraphDeepFM):
    """DCN-v2 as a graph: cross_network + hidden stack → combination head.

    Same sparse-CTR input contract as DeepFM. Cross layers follow DCN-v2
    (Wang et al., 2021):

        x_{l+1} = x_0 * (W_l x_l + b_l) + x_l          (full-rank)
        x_{l+1} = x_0 * (U_l (V_l x_l) + b_l) + x_l    (low-rank, cross_rank > 0)

    The [D, D] cross matmuls (D = F*K) are dense MXU work.
    """

    name = "dcnv2"

    def init(self, rng: jax.Array) -> Tuple[common.Params, common.State]:
        cfg = self.cfg
        params, bn_state = super().init(rng)
        d = cfg.field_size * cfg.embedding_size
        keys = jax.random.split(jax.random.fold_in(rng, 7), cfg.cross_layers)
        cross = []
        for i in range(cfg.cross_layers):
            cross.append(init_cross_layer(keys[i], d, cfg.cross_rank))
        params["cross"] = cross
        # Combination head over concat(cross_out[D], deep_out_hidden).
        deep_out_dim = cfg.deep_layer_sizes[-1] if cfg.deep_layer_sizes else d
        params["head"] = {
            "w": common.glorot_uniform(
                jax.random.fold_in(rng, 11), (d + deep_out_dim, 1)),
            "b": jnp.zeros((1,), jnp.float32),
        }
        return params, bn_state

    def apply(
        self,
        params: common.Params,
        state: common.State,
        feat_ids: jnp.ndarray,
        feat_vals: jnp.ndarray,
        *,
        train: bool,
        rng: Optional[jax.Array] = None,
        shard_axis: Optional[str] = None,
        data_axis: Optional[str] = None,
        emb_rows: Optional[Dict[str, Any]] = None,
        emb_plan: Optional[Dict[str, Any]] = None,
    ) -> Tuple[jnp.ndarray, common.State]:
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        feat_vals = feat_vals.astype(jnp.float32)

        v = self._emb_lookup(params, "fm_v", feat_ids, shard_axis,
                             emb_rows, emb_plan)
        xv = v * feat_vals[..., None]
        x0 = xv.reshape(xv.shape[0], cfg.field_size * cfg.embedding_size)

        # Cross tower.
        x0c = x0.astype(cdt)
        cross_out = cross_network(params["cross"], x0c, cdt)

        # Deep tower (hidden stack only; the head combines both towers).
        h, new_state = common.apply_hidden_stack(
            params["tower"], state, x0, train=train,
            dropout_keep=cfg.dropout_rates, use_bn=cfg.batch_norm,
            bn_decay=cfg.batch_norm_decay, rng=rng, compute_dtype=cdt,
            data_axis=data_axis)

        combined = jnp.concatenate([cross_out, h.astype(cdt)], axis=1)
        out = combined @ params["head"]["w"].astype(cdt) + params["head"]["b"].astype(cdt)
        logits = params["fm_b"][0] + out.astype(jnp.float32)[:, 0]
        return logits, new_state


class DLRM(GraphDeepFM):
    """DLRM-style model: first-order + tower over [xv, pairwise dots].

    DLRM-style, not Naumov et al.'s (2019) architecture: it borrows that
    paper's pairwise dot-interaction, but has no bottom MLP (numeric fields
    are embedded like every other field), adds a first-order term, and
    feeds the flattened embeddings to the tower beside the dots. The
    published recommender of this family is ``dlrm_dcnv2``
    (:class:`GraphDLRMDCNv2`). Same input contract and embedding tables as
    DeepFM.
    """

    name = "dlrm"

    def top_input_dim(self) -> int:
        cfg = self.cfg
        return (cfg.field_size * cfg.embedding_size
                + cfg.field_size * (cfg.field_size - 1) // 2)

    def init(self, rng: jax.Array) -> Tuple[common.Params, common.State]:
        cfg = self.cfg
        k_w, k_v, k_mlp = jax.random.split(rng, 3)
        fm_w = self.emb.init_entry(k_w, ())
        fm_v = self.emb.init_entry(k_v, (cfg.embedding_size,))
        tower, bn_state = common.init_tower(
            k_mlp, self.top_input_dim(), cfg.deep_layer_sizes, cfg.batch_norm)
        params = {"fm_b": jnp.zeros((1,), jnp.float32),
                  "fm_w": fm_w, "fm_v": fm_v, "tower": tower}
        return params, bn_state

    def apply(
        self,
        params: common.Params,
        state: common.State,
        feat_ids: jnp.ndarray,
        feat_vals: jnp.ndarray,
        *,
        train: bool,
        rng: Optional[jax.Array] = None,
        shard_axis: Optional[str] = None,
        data_axis: Optional[str] = None,
        emb_rows: Optional[Dict[str, Any]] = None,
        emb_plan: Optional[Dict[str, Any]] = None,
    ) -> Tuple[jnp.ndarray, common.State]:
        cfg = self.cfg
        feat_vals = feat_vals.astype(jnp.float32)

        w = self._emb_lookup(params, "fm_w", feat_ids, shard_axis,
                             emb_rows, emb_plan)
        v = self._emb_lookup(params, "fm_v", feat_ids, shard_axis,
                             emb_rows, emb_plan)
        xv = v * feat_vals[..., None]

        y_first = first_order(w, feat_vals)
        flat = xv.reshape(xv.shape[0], cfg.field_size * cfg.embedding_size)
        top_in = jnp.concatenate([flat, dot_interaction(xv)], axis=1)
        tower_fn = lambda p, x: common.apply_tower(
            p, state, x, train=train, dropout_keep=cfg.dropout_rates,
            use_bn=cfg.batch_norm, bn_decay=cfg.batch_norm_decay, rng=rng,
            compute_dtype=jnp.dtype(cfg.compute_dtype), data_axis=data_axis)
        if cfg.remat:
            y_d, new_state = jax.checkpoint(tower_fn)(params["tower"], top_in)
        else:
            y_d, new_state = tower_fn(params["tower"], top_in)

        logits = params["fm_b"][0] + y_first + y_d
        return logits, new_state


class GraphDLRMDCNv2(GraphModel):
    """MLPerf DLRM-DCNv2: bottom MLP + embeddings -> stacked low-rank cross
    network -> top MLP (mlcommons/training ``recommendation_v2/
    torchrec_dlrm``; cross layers are DCN-v2's, Wang et al. 2021, in
    torchrec's ``LowRankCrossNet`` form).

        b   = bottom MLP over the numeric values (every layer ReLU)
        x0  = concat(b, e_1, ..., e_C)        e_f = fm_v[id_f], one id a field
        x3  = cross_network(x0)               stacked: the MLP reads x3
        out = top MLP over x3 (hidden layers ReLU, then one linear -> logit)

    The first ``numeric_fields`` fields of the ``[B, F]`` contract carry the
    numeric values in ``feat_vals`` (already transformed; their ids are
    never looked up), the other ``C = F - numeric_fields`` an id each
    (``feat_vals`` is not read for them). One table leaf, ``fm_v``: there
    is no first-order term and no global bias.
    """

    name = "dlrm_dcnv2"

    def embedding_param_names(self) -> Tuple[str, ...]:
        return ("fm_v",)

    def lookup_ids(self, feat_ids: jnp.ndarray) -> jnp.ndarray:
        """The categorical fields' ids: the numeric fields' are not read."""
        return feat_ids[:, self.cfg.numeric_fields:]

    def init(self, rng: jax.Array) -> Tuple[common.Params, common.State]:
        cfg = self.cfg
        k_v, k_bottom, k_cross, k_top = jax.random.split(rng, 4)
        bottom, _ = common.init_hidden_stack(
            k_bottom, cfg.numeric_fields, cfg.bottom_layer_sizes, False)
        # x0: the bottom MLP's output beside one embedding a looked-up field
        d = (1 + cfg.field_size - cfg.numeric_fields) * cfg.embedding_size
        cross = [init_cross_layer(k, d, cfg.cross_rank)
                 for k in jax.random.split(k_cross, cfg.cross_layers)]
        tower, bn_state = common.init_tower(
            k_top, d, cfg.deep_layer_sizes, False)
        params = {"fm_v": self.emb.init_entry(k_v, (cfg.embedding_size,)),
                  "bottom": bottom, "cross": cross, "tower": tower}
        return params, bn_state

    def apply(
        self,
        params: common.Params,
        state: common.State,
        feat_ids: jnp.ndarray,   # int32 [B, F]
        feat_vals: jnp.ndarray,  # f32 [B, F]
        *,
        train: bool,
        rng: Optional[jax.Array] = None,
        shard_axis: Optional[str] = None,
        data_axis: Optional[str] = None,
        emb_rows: Optional[Dict[str, Any]] = None,
        emb_plan: Optional[Dict[str, Any]] = None,
    ) -> Tuple[jnp.ndarray, common.State]:
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        n_num = cfg.numeric_fields
        stack = dict(train=train, use_bn=False, bn_decay=cfg.batch_norm_decay,
                     compute_dtype=cdt, data_axis=data_axis)

        dense = feat_vals[:, :n_num].astype(jnp.float32)
        b, _ = common.apply_bottom_stack(
            params["bottom"], state, dense, dropout_keep=(), rng=None,
            **stack)
        e = self._emb_lookup(params, "fm_v", self.lookup_ids(feat_ids),
                             shard_axis, emb_rows, emb_plan)  # [B,C,K]
        x0c = jnp.concatenate(
            [b, e.astype(cdt).reshape(e.shape[0], -1)], axis=1)
        x = cross_network(params["cross"], x0c, cdt)
        logits, new_state = common.apply_tower(
            params["tower"], state, x, dropout_keep=cfg.dropout_rates,
            rng=rng, **stack)
        return logits, new_state
