"""Plain reference of LFM2-8B-A1B's training step: one chip's share of a
hybrid short-convolution / grouped-query-attention mixture-of-experts
decoder, forward, loss, gradients and Adam's step from their equations, in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
It imports nothing of ``deepfm_tpu``; it is handed arrays by name, the
tokens, the routers' selection bias and the share (which heads and experts
this chip holds are in the arrays' shapes and ``first_expert``). What its
equations share with the other references to the letter is theirs, by
import: RMSNorm, the depthwise causal convolution, SwiGLU, Adam and the
leaves' names (``reference_kimi_linear``), rotate-half rotary
(``reference_sdar_moe``); what this model has of its own is written here.

The model (``model_type: lfm2_moe``; sizes in ``sizes``). x is the residual
stream [S, d], d = 2048; ``x_n = RMSNorm(x; g) = x / sqrt(mean(x^2) + eps)
g`` with eps 1e-5 (``norm_eps``); every block is ``h = x + Op(RMSNorm(x;
norm1))``, ``y = h + FFN(RMSNorm(h; norm2))``; ``Op`` by the config's
``layer_types``, ``FFN`` the dense SwiGLU for the first
``num_dense_layers`` layers and the expert layer after; then a final
RMSNorm and the token table as the head. **[departure]** marks what differs
from the published description, **[memory]** what is the published
implementation's as the issue's writer remembers it and the config has no
key for.

* **conv** (``conv_bias: false``, ``conv_L_cache: 3``): ``[B | C | u] = x_n
  conv_w_in``, ``conv_w_in`` [d, 3d] split in that order **[memory]**;
  ``z = B * u``; ``c_t = sum_{j=0..2} w_j * z_{t-2+j}``, depthwise over the d
  channels, ``conv_w`` [3, d], positions before the first reading zero;
  ``Op = (C * c) conv_w_out``, ``conv_w_out`` [d, d]. No activation.
  **[departure]** a packed sequence's documents are not told apart: the
  convolution and the attention run on through a boundary.
* **full_attention**: ``q = x_n wq`` -> the held query heads of 64,
  ``k = x_n wk``, ``v = x_n wv`` -> the held key/value heads of 64, no
  biases; ``q <- rotary(RMSNorm_64(q; q_norm))``, ``k <-
  rotary(RMSNorm_64(k; k_norm))``, the gains shared over heads and the norm
  ahead of the rotation **[memory]**, ``rope_theta`` 1e6, rotate-half
  pairing; query head j reads key/value head ``j // group``; causal softmax
  of ``q . k / 8``; ``Op = concat(heads) wo``.
* **dense FFN**: ``(SiLU(x_n w_gate) * x_n w_up) w_down``, 7168 wide.
* **expert layer**: ``s = sigmoid(x_n router)`` in R^32; the 4 largest of
  ``s + b`` are the token's experts (equal ones to the lower index), b the
  layer's selection bias (``use_expert_bias``); their weights
  ``s_e / (sum_chosen s + 1e-6)`` (``norm_topk_prob``; the 1e-6
  **[memory]**) times ``routed_scaling_factor`` 1;
  ``FFN = sum_{e chosen and held} weight_e E_e(x_n)``, 2048 -> 1792 -> 2048.
  No shared expert. **[departure]** b is seeded and constant (the published
  training moves it by a load rule outside the gradient) and there is no
  balance loss.
* **head**: ``logits = RMSNorm(h_L; final_norm) E^T``, E the token table
  (tied: the config has no key; the family's convention, and the count that
  gives the published 8.3B). E's gradient is the sum of its two uses'.
* The held heads' and held experts' parts of the sums are the result: what
  the absent chips add is left out; the convolution mixer, the dense MLP and
  the router are whole.

Loss: ``(1 / (B (L-1))) sum_{i < L-1} -log softmax(logits_i)[token_{i+1}]``
over this chip's rows of the vocabulary. Adam as ``reference_kimi_linear``.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import reference_kimi_linear as base
from benchmark.reference_kimi_linear import (layer_names, rms_norm,
                                             short_conv, swiglu)
from benchmark.reference_sdar_moe import rotary

#: epsilon beside the chosen scores' sum where they are renormalised
RENORM_EPS = 1e-6
#: Queries a block of the score matrix holds (so that it fits: 32 heads of
#: 512 queries against 8,192 keys of 2 sequences are 1.07 GB of scores).
QUERY_BLOCK = 512
TABLE = "tok_emb"


def conv(x, lp, taps_ahead=0, gate=True):
    """x [B, S, d] (already normed) -> the gated short convolution.
    ``taps_ahead=1`` reads one position ahead (a non-causal shift) and
    ``gate=False`` leaves the output gate C out (tests' broken models)."""
    b, c, u = jnp.split(x @ lp["conv_w_in"], 3, axis=-1)
    z = b * u
    if taps_ahead:
        z = jnp.concatenate(
            [z[:, taps_ahead:], jnp.zeros_like(z[:, :taps_ahead])], axis=1)
    y = short_conv(z, lp["conv_w"])
    return (c * y if gate else y) @ lp["conv_w_out"]


def attention(x, lp, sizes, rotate_k=True):
    """x [B, S, d] (already normed) -> the held heads' part of the full
    layer. ``rotate_k=False`` leaves rotary out of k (a test's broken
    model)."""
    hd, eps, theta = sizes["head_dim"], sizes["eps"], sizes["theta"]
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q = (x @ lp["wq"]).reshape(b, s, -1, hd)
    k = (x @ lp["wk"]).reshape(b, s, -1, hd)
    v = (x @ lp["wv"]).reshape(b, s, -1, hd)
    q = rotary(rms_norm(q, lp["q_norm"], eps), positions, theta)
    k = rms_norm(k, lp["k_norm"], eps)
    if rotate_k:
        k = rotary(k, positions, theta)
    group = q.shape[2] // k.shape[2]
    # query head j reads key/value head j // group
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint         # (a block's scores are made again, not kept)
    def rows(start):        # the full causal rows of a block of queries
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) / math.sqrt(hd)
        seen = jnp.arange(s)[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(rows, jnp.arange(0, s, block))    # [blocks, B, Q, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, -1) @ lp["wo"]


def router_weights(x, router, bias, sizes, weigh_by_pick=False):
    """x [T, d] -> [T, E]: each token's weight on every expert, zero off its
    k selected: the k largest of ``sigmoid(x router) + bias``, equal ones to
    the lower index; weights ``scale s_i / (sum_selected s_j + 1e-6)`` of
    the unbiased scores. ``weigh_by_pick=True`` weighs by score + bias (a
    test's broken model)."""
    s = jax.nn.sigmoid(x @ router)
    order = jnp.argsort(-(s + bias), axis=-1, stable=True)[:, : sizes["top_k"]]
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], order].set(1.0) \
        * (s + bias if weigh_by_pick else s)
    return sizes["route_scale"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + RENORM_EPS)


def moe(x, lp, sizes):
    """x [B, S, d] (already normed) -> the held experts' part of the routed
    sum. ``lp['select_bias']`` [E] is the layer's selection bias."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    w = router_weights(x, lp["router"], lp["select_bias"], sizes)
    held = lp["w_gate"].shape[0]
    first = sizes["first_expert"]

    def add_one(out, expert):       # a held expert on every position
        w_e, w_gate, w_up, w_down = expert
        return out + w_e[:, None] * swiglu(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(add_one, jnp.zeros_like(x), (
        w[:, first:first + held].T, lp["w_gate"], lp["w_up"], lp["w_down"]))
    return out.reshape(shape)


def mixer(x, lp, sizes):
    """``x + Op(RMSNorm(x; norm1))``; which mixer is read from the leaves'
    names."""
    xn = rms_norm(x, lp["norm1"], sizes["eps"])
    return x + (conv(xn, lp) if "conv_w_in" in lp
                else attention(xn, lp, sizes))


def feed_forward(h, lp, sizes):
    """``h + FFN(RMSNorm(h; norm2))``: the dense MLP or the expert layer."""
    hn = rms_norm(h, lp["norm2"], sizes["eps"])
    if "mlp_w_gate" in lp:
        return h + swiglu(hn, lp["mlp_w_gate"], lp["mlp_w_up"],
                          lp["mlp_w_down"])
    return h + moe(hn, lp, sizes)


def layer(x, lp, sizes):
    """One block."""
    return feed_forward(mixer(x, lp, sizes), lp, sizes)


def head_loss(h, final_norm, table, tokens, sizes):
    """h [B, L, d]: the last residual stream; ``table`` [V, d] the token
    table, which is the head. -> (loss, logits)"""
    logits = rms_norm(h, final_norm, sizes["eps"]) @ table.T
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll), logits


def with_bias(params: Dict[str, np.ndarray], names, bias) -> list:
    """[{leaf: array}] a layer: its parameters and, for an expert layer, its
    row of the selection bias ``bias`` [expert layers, E] as
    ``select_bias``."""
    out, row = [], 0
    for layer_leaves in names:
        lp = {leaf: params[n] for leaf, n in layer_leaves.items()}
        if "router" in lp:
            lp["select_bias"] = bias[row]
            row += 1
        out.append(lp)
    return out


def forward_loss(params, tokens, bias, sizes):
    """(loss, logits [B, L, V]) of the whole model, for sizes a machine holds
    at once; ``Follower`` does the same a layer at a time."""
    x = jnp.take(params[TABLE], tokens, axis=0)
    for lp in with_bias(params, layer_names(params), bias):
        x = layer(x, lp, sizes)
    return head_loss(x, params["final_norm"], params[TABLE], tokens, sizes)


class Follower(base.Follower):
    """``reference_kimi_linear.Follower`` (the host's copy of the parameters
    and Adam's moments, one layer on the device at a time, the block's two
    halves taken back one after the other) over this model's block, with
    the selection bias ``bias`` [expert layers, E] beside the parameters (no
    gradient, no moment: it stays as handed in) and the tied table's
    gradient the sum of the head's and the lookup's."""

    def __init__(self, params, bias, sizes, learning_rate):
        super().__init__(params, sizes, learning_rate)
        self.bias = np.array(bias, np.float32)
        sz = self.sizes
        self._layer = jax.jit(lambda x, lp: layer(x, lp, sz))
        self._mixer = jax.jit(lambda x, lp: mixer(x, lp, sz))

        def back(half):
            def run(x, lp, dy):
                return jax.vjp(lambda x_, lp_: half(x_, lp_, sz), x, lp)[1](dy)
            return jax.jit(run)
        self._mixer_back, self._ffn_back = back(mixer), back(feed_forward)

        def top(h, final_norm, table, tokens):
            (loss, _), grads = jax.value_and_grad(
                lambda h_, n_, w_: head_loss(h_, n_, w_, tokens, sz),
                argnums=(0, 1, 2), has_aux=True)(h, final_norm, table)
            return loss, grads
        self._top = jax.jit(top)

    def _step(self, tokens) -> float:
        self.count += 1
        table = jnp.asarray(self.params[TABLE])
        x = jnp.take(table, tokens, axis=0)
        layers = with_bias(self.params, self.layers, self.bias)
        kept = []
        for lp in layers:
            kept.append(x)
            x = self._layer(x, lp)
        loss, (dx, d_norm, d_table) = self._top(
            x, self.params["final_norm"], table, tokens)
        del x
        self._apply("final_norm", d_norm)
        for names, lp in zip(reversed(self.layers), reversed(layers)):
            x = kept.pop()
            dh, d_ffn = self._ffn_back(self._mixer(x, lp), lp, dx)
            dx, d_mixer = self._mixer_back(x, lp, dh)
            del dh
            for leaf, n in names.items():   # (a leaf is read by one half)
                self._apply(n, d_ffn[leaf] + d_mixer[leaf])
        self._apply(TABLE, d_table.at[tokens.reshape(-1)].add(
            dx.reshape(-1, dx.shape[-1])))
        return float(loss)
