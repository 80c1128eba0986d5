"""Plain reference of Solar-Open2-250B's training step: one chip's share of a
hybrid gated-attention / delta-rule mixture-of-experts decoder, forward,
loss, gradients and Adam's step from their equations, in ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. It imports
nothing of ``deepfm_tpu``; it is handed arrays by name, the tokens and the
share (which heads and experts this chip holds are in the arrays' shapes and
``first_expert``). What its equations share with Kimi-Linear's to the letter
(RMSNorm, the short convolution, the delta-rule recurrence a position at a
time, the sigmoid router, SwiGLU, the expert layer beside its shared expert,
the head's loss, Adam) is ``reference_kimi_linear``'s, by import: one plain
statement of each; what this model changes is written here.

The model (``model_type: solar_open2``; sizes in ``sizes``). x is the
residual stream [S, d]; ``x_n = RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) g``
with eps 1e-5; every block is ``h = x + Mixer_i(RMSNorm(x; norm1))``,
``y = h + Shared(h_n) + sum_{e in top8(h_n)} w_e Expert_e(h_n)`` with
``h_n = RMSNorm(h; norm2)``; then a final RMSNorm and an untied head. Layer i
(from 0) mixes by GQA where ``attn_every`` (4) divides i (the config's
``gqa_layers`` 0, 4, ..., 44), by KDA elsewhere; every layer has the expert
layer (``first_k_dense_replace`` 0). **[departure]** marks what differs from
the published description, **[memory]** what is the family's published
implementation as the issue's writer remembers it and the config has no key
for.

* **GQA**, gated, without positional encoding (``use_rope: false``,
  ``use_gqa_gate: true``), per held query head j of 128 on key/value head
  j // group: ``q = x_n gqa_wq``, ``k = x_n gqa_wk``, ``v = x_n gqa_wv``; no
  rotary, no QK-norm and no biases (the config has a key for none);
  ``p_{t,s} = softmax_{s <= t}(q_t . k_s / sqrt(128))``;
  ``o_t = sum_s p_{t,s} v_s``; ``y = o * sigmoid(x_n gqa_w_gate)``, the gate
  elementwise over the heads' 128 channels, from the normed input
  **[memory]**; output ``concat_j(y) gqa_wo``.
* **KDA**, per held head, d_k = d_v = 128: Kimi-Linear's
  (``reference_kimi_linear``: q, k, v through a causal depthwise convolution
  of 4 taps and SiLU; q and k L2-normalised, q scaled by 128^-1/2; log-decay
  a channel ``g_t = -exp(kda_a_log) softplus((x_n kda_w_fa) kda_w_fb +
  kda_dt_bias)`` through a 128-wide bottleneck, ``kda_use_full_proj: false``)
  with one change: **``beta_t = 2 sigmoid(x_n kda_w_b)``**
  (``kda_allow_neg_eigval: true``), so the transition ``I - beta_t k_t
  k_t^T`` has eigenvalue ``1 - beta_t`` in (-1, 1) along k_t;
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``,
  ``S_0 = 0``; ``o_t = S_t^T q_t``;
  ``y_t = RMSNorm_128(o_t; kda_out_norm) * sigmoid((x_n kda_w_ga)
  kda_w_gb)``; output ``concat_h(y) kda_wo``. The recurrence runs a position
  at a time (``reference_kimi_linear.kda_recurrence``). **[departure]** a
  packed sequence's documents are not told apart.
* **Expert layer**: ``s = sigmoid(x_n router)`` in R^320; the 8 largest
  (equal ones to the lower index), ``w_i = s_i / sum_selected s_j``
  (``norm_topk_prob``, ``routed_scaling_factor`` 1);
  ``y = sum_{i selected and held} w_i E_i(x_n) + E_shared(x_n)``,
  ``E(x) = (SiLU(x w_gate) * x w_up) w_down``, 4096 -> 1280 -> 4096.
  **[departure]** the selection bias is zero and constant and there is no
  balance loss.
* The held heads' and held experts' parts of the sums are the result: what
  the absent chips add is left out; the shared expert, the gates'
  bottlenecks and the router are whole.

Loss: ``(1 / (B (L-1))) sum_{i < L-1} -log softmax(logits_i)[token_{i+1}]``
over this chip's rows of the vocabulary. Adam as ``reference_kimi_linear``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark import reference_kimi_linear as base
from benchmark.reference_kimi_linear import (feed_forward, head_loss,
                                             layer_names, rms_norm)

#: The write strength's factor (``kda_allow_neg_eigval``).
BETA_SCALE = 2.0
#: Queries a block of the score matrix holds (so that it fits).
QUERY_BLOCK = 1024


def gqa(x, lp, sizes, gate=True, causal=True):
    """x [B, S, d] (already normed) -> the held heads' part of gated GQA.
    ``gate=False`` leaves the gate out and ``causal=False`` lets a query read
    every key (tests' broken models)."""
    hd = sizes["head_dim"]
    b, s, _ = x.shape
    q = (x @ lp["gqa_wq"]).reshape(b, s, -1, hd)
    k = (x @ lp["gqa_wk"]).reshape(b, s, -1, hd)
    v = (x @ lp["gqa_wv"]).reshape(b, s, -1, hd)
    group = q.shape[2] // k.shape[2]
    # query head j reads key/value head j // group
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint         # (a block's scores are made again, not kept)
    def rows(start):        # the full causal rows of a block of queries
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) / math.sqrt(hd)
        if causal:
            seen = jnp.arange(s)[None, :] <= (
                start + jnp.arange(block))[:, None]
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                          v)

    out = jax.lax.map(rows, jnp.arange(0, s, block))    # [blocks, B, Q, H, D]
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, -1)
    if gate:
        out = out * jax.nn.sigmoid(x @ lp["gqa_w_gate"])
    return out @ lp["gqa_wo"]


def kda(x, lp, sizes, beta_scale=BETA_SCALE):
    """x [B, S, d] (already normed) -> the held heads' part of KDA with the
    write strength ``beta_scale * sigmoid(...)`` (1: Kimi-Linear's, a test's
    broken model here)."""
    b, s, _ = x.shape
    q, k, v, g, beta = base.kda_inputs(x, lp, sizes)
    per_head = jax.vmap(base.kda_recurrence, in_axes=(1, 1, 1, 1, 1),
                        out_axes=1)
    o = jax.vmap(per_head)(q, k, v, g, beta_scale * beta)  # [B, S, H, 128]
    gate = jax.nn.sigmoid(((x @ lp["kda_w_ga"]) @ lp["kda_w_gb"]).reshape(
        o.shape))
    y = rms_norm(o, lp["kda_out_norm"], sizes["eps"]) * gate
    return y.reshape(b, s, -1) @ lp["kda_wo"]


def mixer(x, lp, sizes):
    """``x + Mixer(RMSNorm(x; norm1))``; which mixer is read from the
    leaves' names."""
    xn = rms_norm(x, lp["norm1"], sizes["eps"])
    return x + (kda(xn, lp, sizes) if "kda_wq" in lp else gqa(xn, lp, sizes))


def layer(x, lp, sizes):
    """One block (the feed-forward half is ``reference_kimi_linear``'s: the
    expert layer beside its shared expert)."""
    return feed_forward(mixer(x, lp, sizes), lp, sizes)


def forward_loss(params, tokens, sizes):
    """(loss, logits [B, L, V]) of the whole model, for sizes a machine holds
    at once; ``Follower`` does the same a layer at a time."""
    x = jnp.take(params["tok_emb"], tokens, axis=0)
    for names in layer_names(params):
        x = layer(x, {leaf: params[n] for leaf, n in names.items()}, sizes)
    return head_loss(x, params["final_norm"], params["head"], tokens, sizes)


class Follower(base.Follower):
    """``reference_kimi_linear.Follower`` (the host's copy of the parameters
    and Adam's moments, one layer on the device at a time, the block's two
    halves taken back one after the other) over this model's block."""

    def __init__(self, params, sizes, learning_rate):
        super().__init__(params, sizes, learning_rate)
        sz = self.sizes
        self._layer = jax.jit(lambda x, lp: layer(x, lp, sz))
        self._mixer = jax.jit(lambda x, lp: mixer(x, lp, sz))
        self._mixer_back = jax.jit(lambda x, lp, dy: jax.vjp(
            lambda x_, lp_: mixer(x_, lp_, sz), x, lp)[1](dy))
