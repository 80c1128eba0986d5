"""Windowed / global gated-attention mixture-of-experts decoder (``--model
afmoe``).

``models.kimi_linear``'s stack (residual blocks of RMSNorm, a mixer and a
feed-forward; next-token cross-entropy the model owns; ``hist_ids`` [B, L]
the tokens, ``tok_emb`` the table) with what Trinity-Mini (``model_type:
afmoe``) changes, and nothing written a second time: the layers' loop, the
dense SwiGLU, the expert layer beside a shared expert, the router, the
head's loss and the counts are ``kimi_linear``'s and ``sdar_moe``'s, the
selection bias in the model state ``lfm2_moe.SelectionBias``'s, by
inheritance and import; both kinds of layer mix by ``sdar_moe.attention``
itself. ``benchmark/reference_afmoe.py`` holds the equations. What the
class states is stated here and by no flag:

* **The layers' kinds are a list.** ``--layer_types`` names each held
  layer's mixer (``window_attention`` / ``full_attention``: the config's
  ``sliding_attention`` / ``full_attention`` entries of the layers held
  here); the first ``--dense_layers`` of them feed forward through a dense
  SwiGLU of ``--dense_mlp_width``, the others through the expert layer
  beside a shared expert of ``--moe_shared_width``.
* **Both kinds are gated grouped-query attention with a per-head RMS norm
  of q and k** (gains ``q_norm``, ``k_norm`` [D], shared over heads): the
  heads' outputs times ``sigmoid(xn wg)`` ahead of ``wo``. **A windowed
  layer rotates** q and k (``--rope_theta``, every column) and a query
  reads its own position and the ``--attn_window - 1`` before it
  (``kimi_linear.window``); **a full layer rotates nothing** and reads
  every position up to its own (``kimi_linear.causal``). Each mask's score
  call has a scope of its own inside ``attn``: ``attn_scores`` the full
  layers', ``attn_scores_window`` the windowed ones'; on a TPU both are the
  block kernel under their own tables (``sdar_moe.attn_scores_by``;
  ``step_notes``: ``attn_score_blocks``, ``attn_window_blocks``).
* **Sandwich norms.** ``a = x + RMSNorm(Attn(RMSNorm(x; norm1));
  norm1_post)``, ``y = a + RMSNorm(FF(RMSNorm(a; norm2)); norm2_post)``:
  the two further gains are leaves of every layer, and
  ``KimiLinear._layer`` applies them where a layer has them. ``FF`` of an
  expert layer is the routed and the shared sum together.
* **A scaled embedding** (``mup_enabled``): the looked-up rows times
  ``sqrt(d)`` on their way into the stream (``embed_scale``).
* **The router** scores by sigmoid, picks the ``--moe_top_k`` largest of
  score + bias, and weighs by the unbiased scores over their sum + 1e-20,
  times ``--moe_route_scale`` (``route_norm``, ``route_scale``). The bias
  is model state that nothing moves (``SelectionBias``; ROADMAP B7(d)).
* **The head is untied.**

A share of a layer is told as there: ``--attn_q_heads`` /
``--attn_kv_heads`` attention heads, ``--moe_experts_held`` experts from
``--moe_first_expert`` on; the norms, the router, the shared expert and the
dense MLP whole; ``wo``'s and the experts' partial sums unreduced.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .kimi_linear import KimiLinear, causal, window
from .lfm2_moe import SelectionBias, layer_kinds
from .sdar_moe import attention, attn_scores_by, masks_notes, route

#: epsilon beside the chosen scores' sum where they are renormalised
RENORM_EPS = 1e-20
#: mixer -> (the scope of its score call, the note of its visited blocks)
SCORES = {"window_attention": ("attn_scores_window", "attn_window_blocks"),
          "full_attention": ("attn_scores", "attn_score_blocks")}


class Afmoe(SelectionBias, KimiLinear):
    """Windowed / global gated-attention mixture-of-experts decoder over
    ``hist_ids``; see the module's docstring."""

    name = "afmoe"
    _kinds = staticmethod(layer_kinds)
    score_mixers = tuple(SCORES)

    def __init__(self, cfg: Any):
        super().__init__(cfg)
        self.embed_scale = math.sqrt(cfg.embedding_size)
        self.masks = {"window_attention": window(cfg.attn_window),
                      "full_attention": causal}
        self.route_by = functools.partial(
            route, score=jax.nn.sigmoid, scale=cfg.moe_route_scale,
            renorm_eps=RENORM_EPS)

    def _init_mixer(self, mixer: str, glorot, keys) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        d, hd = cfg.embedding_size, cfg.attn_head_dim
        q, kv = cfg.attn_q_heads * hd, cfg.attn_kv_heads * hd
        return {"wq": glorot(d, q), "wk": glorot(d, kv), "wv": glorot(d, kv),
                "wg": glorot(d, q), "wo": glorot(q, d),
                "q_norm": jnp.ones((hd,), jnp.float32),
                "k_norm": jnp.ones((hd,), jnp.float32)}

    def _init_layer(self, rng: jax.Array, mixer: str, ffn: str
                    ) -> Dict[str, jnp.ndarray]:
        lp = super()._init_layer(rng, mixer, ffn)
        d = self.cfg.embedding_size
        return {**lp, "norm1_post": jnp.ones((d,), jnp.float32),
                "norm2_post": jnp.ones((d,), jnp.float32)}

    def _paths(self, ids: jnp.ndarray, one_device: bool) -> Dict[str, str]:
        cfg = self.cfg
        seq = ids.shape[1]
        scores_by = attn_scores_by(seq, cfg.attn_head_dim,
                                   one_device=one_device)
        self.step_notes.update(masks_notes(
            scores_by, {SCORES[mixer][1]: self.masks[mixer]
                        for mixer in sorted({m for m, _ in self.kinds})},
            seq, cfg.attn_q_heads // cfg.attn_kv_heads))
        return {**self._moe_paths(ids, one_device), "scores_by": scores_by}

    def _mixer(self, mixer: str, lp: Dict[str, jnp.ndarray], x: jnp.ndarray,
               scores_by: str = "xla"
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        return attention(
            lp, x, jnp.arange(x.shape[1]), mask=self.masks[mixer],
            head_dim=cfg.attn_head_dim, eps=cfg.rms_norm_eps,
            theta=cfg.rope_theta if mixer == "window_attention" else None,
            cdt=self.cdt, scores_by=scores_by,
            scores_scope=SCORES[mixer][0]), {}
