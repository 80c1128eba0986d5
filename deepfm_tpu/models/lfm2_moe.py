"""Hybrid short-convolution / grouped-query-attention mixture-of-experts
decoder (``--model lfm2_moe``).

``models.kimi_linear``'s stack (pre-norm residual blocks of RMSNorm, a mixer
and a feed-forward; next-token cross-entropy the model owns; ``hist_ids``
[B, L] the tokens, ``tok_emb`` the table) with what LFM2-8B-A1B
(``model_type: lfm2_moe``) changes, and nothing written a second time: the
layers' loop, the dense SwiGLU, the expert layer, the router, the head's
loss and the counts are ``kimi_linear``'s and ``sdar_moe``'s, by inheritance
and import; the full layer is ``sdar_moe.attention`` itself, under
``kimi_linear.causal``. ``benchmark/reference_lfm2_moe.py`` holds the
equations.

* **The layers' kinds are a list.** ``--layer_types`` names each held
  layer's mixer (``conv`` / ``full_attention``: the config's own
  ``layer_types``, the entries of the layers held here); the first
  ``--dense_layers`` of them feed forward through a dense SwiGLU of
  ``--dense_mlp_width``, the others through the expert layer.
* **The gated short convolution** (``conv_mixer``): ``[B | C | u] = xn
  conv_w_in`` (``[d, 3d]``, split in that order), ``z = B * u``, a causal
  depthwise convolution of ``--conv_taps`` taps over positions
  (``kimi_linear.causal_conv``: positions before the first read zero, no
  bias), the output ``(C * conv(z)) conv_w_out``. No activation, no
  recurrence, no softmax: two matrix products around three elementwise
  passes over ``[T, d]`` (scope ``conv``, the passes ``conv_taps`` inside).
* **The full layer** is grouped-query attention with a per-head RMS norm of
  q and k (gains shared over heads) and rotary positions
  (``sdar_moe.attention``), causal. Heads of 64 take the block kernel on a
  TPU as they are (``ops/block_attention.supported``); the score call has a
  scope of its own, ``attn_scores``, inside ``attn``.
* **The router has a selection bias and no shared expert beside it**:
  sigmoid scores, the ``--moe_top_k`` largest of score + bias, weights the
  chosen scores over their sum + 1e-6 (``sdar_moe.route``). The bias
  (``SELECT_BIAS`` [expert layers, experts], float32) lives in the model
  state, not among the parameters: no gradient reaches it and no optimizer
  state follows it. The published training moves it by a load rule outside
  the gradient; nothing here does (ROADMAP B7(d)): ``init`` makes it zeros
  and a step hands it on bit for bit. ``moe_bias_moved_picks``
  (``sdar_moe.BIAS_MOVED``) in the state and the step's metrics counts the
  (position, layer) selections the bias changed.
* **The head is the token table** (``tied_head``): ``logits = RMSNorm(h)
  tok_emb^T`` over the table's real rows; the leaf's gradient is the sum of
  its two uses', by AD.

A share of a layer is told as there: ``--attn_q_heads`` /
``--attn_kv_heads`` attention heads, ``--moe_experts_held`` experts from
``--moe_first_expert`` on; the convolution mixer, the dense MLP, the norms
and the router whole; ``wo``'s and the experts' partial sums unreduced.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import common
from .kimi_linear import COUNT_NAMES, KimiLinear, causal, causal_conv
from .sdar_moe import (BIAS_MOVED, _dot, attention, attn_notes,
                       attn_scores_by, rms_norm, route)

#: The routers' selection bias in the model state: [expert layers, experts].
SELECT_BIAS = "moe_select_bias"
#: epsilon beside the chosen scores' sum where they are renormalised
RENORM_EPS = 1e-6


def layer_kinds(cfg: Any) -> Tuple[Tuple[str, str], ...]:
    """((mixer, feed-forward) of each layer): the mixers are
    ``layer_types``' words; the first ``dense_layers`` feed forward through
    a dense MLP, the rest through the expert layer."""
    return tuple((mixer, "mlp" if i < cfg.dense_layers else "moe")
                 for i, mixer in enumerate(cfg.layer_type_list))


@jax.named_scope("conv")
def conv_mixer(lp: Dict[str, jnp.ndarray], x: jnp.ndarray, *, eps: float,
               cdt: jnp.dtype) -> jnp.ndarray:
    """``Conv(RMSNorm(x))``, whole on every chip: x [B, S, d] -> [B, S, d]
    (the module's docstring)."""
    xn = rms_norm(x, lp["norm1"], eps)
    bcu = _dot(xn, lp["conv_w_in"], cdt)
    with jax.named_scope("conv_taps"):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        y = c * causal_conv(b * u, lp["conv_w"])
    return _dot(y, lp["conv_w_out"], cdt)


class SelectionBias:
    """For a ``KimiLinear`` stack whose routers pick by score + bias (mixed
    in ahead of it): the bias ``SELECT_BIAS`` [expert blocks, experts] in
    the model state, a row an expert layer in the layers' order, read by
    each beside its own leaves and handed on as it came; ``BIAS_MOVED``
    among the counts."""

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        """The expert layers' numbers among the layers, in order."""
        return tuple(i for i, (_, ffn) in enumerate(self.kinds)
                     if ffn == "moe")

    def init_counts(self) -> common.State:
        return {n: jnp.zeros((), jnp.int32)
                for n in (*COUNT_NAMES, BIAS_MOVED)}

    def init_bias(self) -> jnp.ndarray:
        return jnp.zeros((sum(ffn == "moe" for _, ffn in self.block_kinds),
                          self.cfg.moe_experts), jnp.float32)

    def init(self, rng: jax.Array) -> Tuple[common.Params, common.State]:
        params, counts = super().init(rng)
        return params, {**counts, SELECT_BIAS: self.init_bias()}

    def step_counts(self, model_state: common.State
                    ) -> Dict[str, jnp.ndarray]:
        """The counts a step's metrics carry beside its loss (the state
        without the bias, which is no count)."""
        return {k: v for k, v in model_state.items() if k != SELECT_BIAS}

    def _with_bias(self, params: common.Params, bias: jnp.ndarray
                   ) -> common.Params:
        """``params`` with each expert layer reading its row of ``bias``
        beside its own leaves, as ``select_bias``."""
        layers = dict(params["layers"])
        for row, i in enumerate(self.moe_layers):
            layers[str(i)] = {**layers[str(i)], "select_bias": bias[row]}
        return {**params, "layers": layers}

    def _run(self, params, state, tokens, shard_axis, data_axis, emb):
        bias = state[SELECT_BIAS]
        h, tokens, counts = super()._run(
            self._with_bias(params, bias), state, tokens, shard_axis,
            data_axis, emb)
        return h, tokens, {**counts, SELECT_BIAS: bias}


class Lfm2Moe(SelectionBias, KimiLinear):
    """Short-convolution / GQA mixture-of-experts decoder over ``hist_ids``;
    see the module's docstring."""

    name = "lfm2_moe"
    tied_head = True
    _kinds = staticmethod(layer_kinds)
    score_mixers = ("full_attention",)

    def __init__(self, cfg: Any):
        super().__init__(cfg)
        self.step_notes = {"conv_taps_by": "xla"}
        self.route_by = functools.partial(
            route, score=jax.nn.sigmoid, scale=cfg.moe_route_scale,
            renorm_eps=RENORM_EPS)

    def _init_mixer(self, mixer: str, glorot, keys) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        d, hd = cfg.embedding_size, cfg.attn_head_dim
        if mixer == "conv":
            return {"conv_w_in": glorot(d, 3 * d),
                    "conv_w": glorot(cfg.conv_taps, d),
                    "conv_w_out": glorot(d, d)}
        q, kv = cfg.attn_q_heads * hd, cfg.attn_kv_heads * hd
        return {"wq": glorot(d, q), "wk": glorot(d, kv), "wv": glorot(d, kv),
                "q_norm": jnp.ones((hd,), jnp.float32),
                "k_norm": jnp.ones((hd,), jnp.float32), "wo": glorot(q, d)}

    def _paths(self, ids: jnp.ndarray, one_device: bool) -> Dict[str, str]:
        cfg = self.cfg
        paths = self._moe_paths(ids, one_device)
        if any(mixer == "full_attention" for mixer, _ in self.kinds):
            seq = ids.shape[1]
            paths["scores_by"] = attn_scores_by(seq, cfg.attn_head_dim,
                                                one_device=one_device)
            self.step_notes.update(attn_notes(
                paths["scores_by"], causal, seq,
                cfg.attn_q_heads // cfg.attn_kv_heads))
        return paths

    def _mixer(self, mixer: str, lp: Dict[str, jnp.ndarray], x: jnp.ndarray,
               scores_by: str = "xla"
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        if mixer == "conv":
            return conv_mixer(lp, x, eps=cfg.rms_norm_eps, cdt=self.cdt), {}
        return attention(lp, x, jnp.arange(x.shape[1]), mask=causal,
                         head_dim=cfg.attn_head_dim, eps=cfg.rms_norm_eps,
                         theta=cfg.rope_theta, cdt=self.cdt,
                         scores_by=scores_by, scores_scope="attn_scores"), {}
