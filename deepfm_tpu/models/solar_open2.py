"""Hybrid gated-attention / delta-rule mixture-of-experts decoder (``--model
solar_open2``).

``models.kimi_linear``'s stack (pre-norm residual blocks of RMSNorm, a mixer
and a feed-forward; next-token cross-entropy the model owns; ``hist_ids``
[B, L] the tokens, ``tok_emb`` the table) with what Solar-Open2-250B
(``model_type: solar_open2``) changes, and nothing written a second time:
the delta-rule scan and the KDA mixer, the expert layer, the router, the
shared expert, the head's loss, the layers' loop and the counts are
``kimi_linear``'s and ``sdar_moe``'s, by inheritance and import.
``benchmark/reference_solar_open2.py`` holds the equations.

* **The period is led by its full layer.** Layer i (from 0) mixes by softmax
  attention where ``--attn_every`` divides i and by KDA elsewhere (the
  config's ``gqa_layers`` 0, 4, 8, ...); Kimi-Linear's full layer closes its
  period. Every layer's feed-forward is the expert layer beside the shared
  expert: there is no dense layer.
* **The full layer is gated grouped-query attention without positions**
  (``gqa_mixer``, which is ``sdar_moe.attention`` over this layer's
  leaves): ``--attn_q_heads`` query heads on ``--attn_kv_heads``
  key/value heads of ``--attn_head_dim`` (query head j reads key/value head
  j // group), no rotary and no QK-norm, causal softmax, and the heads'
  outputs times ``sigmoid(xn gqa_w_gate)`` elementwise ahead of ``gqa_wo``
  (``use_gqa_gate``). Keys and values are equally wide, so the masked scores
  are ``sdar_moe.masked_scores`` under ``kimi_linear.causal``: on a TPU the
  block kernel over the causal half (136 of 256 blocks of 512 at 8,192
  positions), elsewhere XLA's chunked path, picked by
  ``sdar_moe.attn_scores_by`` and said in ``step_notes``. The score call has
  a scope of its own, ``attn_scores``, inside ``attn``.
* **The delta rule's write strength reaches 2**: ``beta = 2 sigmoid(xn
  kda_w_b)`` (``kda_allow_neg_eigval``), so the transition ``I - beta k k^T``
  has eigenvalue ``1 - beta`` in (-1, 1) along k. ``kda_beta_over_one``, in
  the model state and the step's metrics, counts the positions x heads of
  the step whose strength passed 1.
* The router scores by sigmoid, takes ``--moe_top_k``, renormalises and
  scales by ``--moe_route_scale`` (1 as published), as Kimi-Linear's.

A share of a layer is told as there: ``--kda_heads`` KDA heads,
``--attn_q_heads`` / ``--attn_kv_heads`` attention heads,
``--moe_experts_held`` experts from ``--moe_first_expert`` on; norms, the
gates' bottlenecks, the router and the shared expert whole; ``wo``'s and the
experts' partial sums unreduced.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp

from . import common
from .kimi_linear import (BETA_OVER_ONE, COUNT_NAMES, DECAY_MIN, KimiLinear,
                          causal, kda_mixer)
from .sdar_moe import attention, attn_notes, attn_scores_by

#: The write strength's factor: ``beta = BETA_SCALE * sigmoid(...)``.
BETA_SCALE = 2.0


def layer_kinds(cfg: Any) -> Tuple[Tuple[str, str], ...]:
    """((mixer, feed-forward) of each layer): layer i (from 0) mixes by gated
    grouped-query attention where ``attn_every`` divides i and by KDA
    elsewhere; every layer feeds forward through the expert layer."""
    return tuple(("gqa" if i % cfg.attn_every == 0 else "kda", "moe")
                 for i in range(cfg.decoder_layers))


def gqa_mixer(lp: Dict[str, jnp.ndarray], x: jnp.ndarray, *, head_dim: int,
              eps: float, cdt: jnp.dtype, scores_by: str = "xla"
              ) -> jnp.ndarray:
    """The held heads' part of ``GQA(RMSNorm(x))``, gated and without
    positional encoding: x [B, S, d] -> [B, S, d] (``gqa_wo``'s sum over the
    held heads, unreduced): ``sdar_moe.attention`` over this layer's leaves
    under its names, with no gains for q and k and nothing rotated.
    ``scores_by`` is ``sdar_moe.attn_scores_by``'s word for what makes the
    causal scores."""
    leaves = {"norm1": lp["norm1"], "wg": lp["gqa_w_gate"],
              **{w: lp["gqa_" + w] for w in ("wq", "wk", "wv", "wo")}}
    return attention(leaves, x, None, mask=causal, head_dim=head_dim,
                     eps=eps, theta=None, cdt=cdt, scores_by=scores_by,
                     scores_scope="attn_scores")


class SolarOpen2(KimiLinear):
    """Gated-GQA / KDA mixture-of-experts decoder over ``hist_ids``; see the
    module's docstring."""

    name = "solar_open2"
    _kinds = staticmethod(layer_kinds)
    score_mixers = ("gqa",)

    def init_counts(self) -> common.State:
        return {**{n: jnp.zeros((), jnp.int32)
                   for n in (*COUNT_NAMES, BETA_OVER_ONE)},
                DECAY_MIN: jnp.zeros((), jnp.float32)}

    def _init_mixer(self, mixer: str, glorot, keys) -> Dict[str, jnp.ndarray]:
        if mixer == "kda":
            return super()._init_mixer(mixer, glorot, keys)
        cfg = self.cfg
        d = cfg.embedding_size
        q, kv = (n * cfg.attn_head_dim
                 for n in (cfg.attn_q_heads, cfg.attn_kv_heads))
        return {"gqa_wq": glorot(d, q), "gqa_wk": glorot(d, kv),
                "gqa_wv": glorot(d, kv), "gqa_w_gate": glorot(d, q),
                "gqa_wo": glorot(q, d)}

    def _paths(self, ids: jnp.ndarray, one_device: bool) -> Dict[str, str]:
        cfg = self.cfg
        seq = ids.shape[1]
        scores_by = attn_scores_by(seq, cfg.attn_head_dim,
                                   one_device=one_device)
        self.step_notes.update(attn_notes(
            scores_by, causal, seq, cfg.attn_q_heads // cfg.attn_kv_heads))
        return {**self._moe_paths(ids, one_device),
                "scores_by": scores_by,
                "scan_by": self._scan_by(ids, one_device)}

    def _mixer(self, mixer: str, lp: Dict[str, jnp.ndarray], x: jnp.ndarray,
               scores_by: str = "xla", scan_by: str = "xla"
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        if mixer == "kda":
            return kda_mixer(lp, x, head_dim=cfg.kda_head_dim,
                             eps=cfg.rms_norm_eps, cdt=self.cdt,
                             beta_scale=BETA_SCALE, scan_by=scan_by)
        return gqa_mixer(lp, x, head_dim=cfg.attn_head_dim,
                         eps=cfg.rms_norm_eps, cdt=self.cdt,
                         scores_by=scores_by), {}
