"""Plain reference of Trinity-Mini's training step: one chip's share of a
windowed / global gated-attention mixture-of-experts decoder, forward, loss,
gradients and Adam's step from their equations, in ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``. It imports nothing of
``deepfm_tpu``; it is handed arrays by name, the tokens, the routers'
selection bias and the share (which experts and which rows of the vocabulary
this chip holds are in the arrays' shapes and ``first_expert``). What its
equations share with the other references to the letter is theirs, by
import: RMSNorm, SwiGLU, the head's loss, Adam, the follower's frame and the
leaves' names (``reference_kimi_linear``), rotate-half rotary
(``reference_sdar_moe``); what this model has of its own is written here.

The model (``model_type: afmoe``; sizes in ``sizes``). d = 2048, 32 query
heads on 4 key/value heads of 128, eps 1e-5; ``RMSNorm(x; g) = x /
sqrt(mean(x^2) + eps) g``. **[departure]** marks what differs from the
published description, **[memory]** what is the published implementation's
as the issue's writer remembers it and the config has no key for.

* **Stream in:** ``h_0 = sqrt(d) Emb[t]`` (``mup_enabled``) **[memory]**.
* **Layer:** ``a = x + RMSNorm(Attn(RMSNorm(x; norm1)); norm1_post)``,
  ``y = a + RMSNorm(FF(RMSNorm(a; norm2)); norm2_post)``: four norms a
  layer, the two post-norms on the sublayers' outputs inside the residual
  sum **[memory]**.
* **Attention**, ``xn`` the normed input: ``q = RMSNorm_128(xn wq; q_norm)``,
  ``k = RMSNorm_128(xn wk; k_norm)`` a head (gains shared over heads), ``v =
  xn wv``, ``g = sigmoid(xn wg)`` with ``wg`` [d, 32 * 128] **[memory]**.
  A ``window_attention`` layer (the config's ``sliding_attention``) rotates
  q and k (``rope_theta`` 10000, all 128 columns, ``rope_scaling`` null;
  rotate-half pairing **[memory]**) and query i reads the keys j with ``0 <=
  i - j < sliding_window``; a ``full_attention`` layer rotates nothing and
  reads every j <= i **[memory]**. Both: ``o = softmax(q k^T / sqrt(128) +
  mask) v``, query head j on key/value head ``j // 8``; ``Attn = (o * g)
  wo``. **[departure]** a packed sequence's documents are not told apart.
* **FF of the leading dense layers:** SwiGLU, 6144 wide. **Elsewhere:** ``s =
  sigmoid(xn router)`` over the 128 experts; the 8 picked are the 8 largest
  of ``s + b`` (equal ones to the lower index; ``n_group`` 1 and
  ``topk_group`` 1: no group limit); ``w = route_scale s[picked] / (sum
  s[picked] + 1e-20)`` (``route_norm``; the 1e-20 **[memory]**); ``FF =
  sum_{e picked and held} w_e SwiGLU_e(xn) + SwiGLU_shared(xn)``, 1024 wide.
  **[departure]** b is seeded and constant (the published training moves it
  by a load rule outside the gradient, ``load_balance_coeff``) and there is
  no balance loss.
* **Out:** ``logits = RMSNorm(h_L; final_norm) head`` (untied), next-token
  cross-entropy, mean over positions 0 .. L-2, over this chip's rows of the
  vocabulary. **[departure]** the head is fed the last *held* layer's
  output.
* The held experts' part of the routed sum is the result: what the absent
  chips add is left out; the mixers, the dense MLP, the shared expert, the
  router and the norms are whole.

So that it fits at the timed sizes the scores are made a block of
``QUERY_BLOCK`` queries at a time (a ragged last block padded, its rows
dropped), each block made again in the backward pass (**[departure]** from
"nothing recomputed": 32 blocks' scores of 32 heads are 34 GB); the follower
holds one layer on the device at a time. Adam as ``reference_kimi_linear``.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import reference_kimi_linear as base
from benchmark.reference_kimi_linear import (head_loss, layer_names,
                                             rms_norm, swiglu)
from benchmark.reference_sdar_moe import rotary

#: epsilon beside the chosen scores' sum where they are renormalised
RENORM_EPS = 1e-20
#: Queries a block of the score matrix holds (so that it fits: 32 heads of
#: 512 queries against 16,384 keys are 1.07 GB of scores).
QUERY_BLOCK = 512
TABLE = "tok_emb"
WINDOWED = "window_attention"


def attention(x, lp, sizes, windowed: bool):
    """x [B, S, d] (already normed) -> the held heads' part of a layer's
    attention: ``windowed`` the rotated layer under the window, else the
    unrotated one under the causal mask."""
    hd, eps = sizes["head_dim"], sizes["eps"]
    b, s, _ = x.shape
    q = rms_norm((x @ lp["wq"]).reshape(b, s, -1, hd), lp["q_norm"], eps)
    k = rms_norm((x @ lp["wk"]).reshape(b, s, -1, hd), lp["k_norm"], eps)
    v = (x @ lp["wv"]).reshape(b, s, -1, hd)
    if windowed:
        positions = jnp.arange(s)
        q = rotary(q, positions, sizes["theta"])
        k = rotary(k, positions, sizes["theta"])
    group = q.shape[2] // k.shape[2]
    # query head j reads key/value head j // group
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint         # (a block's scores are made again, not kept)
    def rows(start):        # the whole rows of a block of queries
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) / math.sqrt(hd)
        queries = (start + jnp.arange(block))[:, None]
        seen = keys <= queries
        if windowed:
            seen = seen & (queries - keys < sizes["window"])
        p = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    o = jax.lax.map(rows, jnp.arange(0, s + pad, block))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s + pad, -1)[:, :s]
    return (o * jax.nn.sigmoid(x @ lp["wg"])) @ lp["wo"]


def router_weights(x, router, bias, sizes):
    """x [T, d] -> [T, E]: each token's weight on every expert, zero off its
    k picked: the k largest of ``sigmoid(x router) + bias``, equal ones to
    the lower index; weights ``route_scale s_i / (sum_picked s_j + 1e-20)``
    of the unbiased scores."""
    s = jax.nn.sigmoid(x @ router)
    order = jnp.argsort(-(s + bias), axis=-1, stable=True)[:, : sizes["top_k"]]
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], order].set(1.0) * s
    return sizes["route_scale"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + RENORM_EPS)


def moe(x, lp, sizes):
    """x [B, S, d] (already normed) -> the held experts' part of the routed
    sum and the shared expert. ``lp['select_bias']`` [E] is the layer's
    selection bias."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    w = router_weights(x, lp["router"], lp["select_bias"], sizes)
    shared = swiglu(x, lp["shared_w_gate"], lp["shared_w_up"],
                    lp["shared_w_down"])
    held = lp["w_gate"].shape[0]
    first = sizes["first_expert"]

    def add_one(out, expert):       # a held expert on every position
        w_e, w_gate, w_up, w_down = expert
        return out + w_e[:, None] * swiglu(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(add_one, shared, (
        w[:, first:first + held].T, lp["w_gate"], lp["w_up"], lp["w_down"]))
    return out.reshape(shape)


def mixer(x, lp, sizes, kind):
    """``x + RMSNorm(Attn(RMSNorm(x; norm1)); norm1_post)`` of a layer of
    kind ``kind`` (``window_attention`` / ``full_attention``)."""
    eps = sizes["eps"]
    y = attention(rms_norm(x, lp["norm1"], eps), lp, sizes, kind == WINDOWED)
    return x + rms_norm(y, lp["norm1_post"], eps)


def feed_forward(a, lp, sizes):
    """``a + RMSNorm(FF(RMSNorm(a; norm2)); norm2_post)``: the dense MLP or
    the expert layer."""
    eps = sizes["eps"]
    an = rms_norm(a, lp["norm2"], eps)
    y = swiglu(an, lp["mlp_w_gate"], lp["mlp_w_up"], lp["mlp_w_down"]) \
        if "mlp_w_gate" in lp else moe(an, lp, sizes)
    return a + rms_norm(y, lp["norm2_post"], eps)


def layer(x, lp, sizes, kind=None):
    """One block of kind ``kind`` (``sizes['kind']`` where it is not
    given)."""
    kind = sizes["kind"] if kind is None else kind
    return feed_forward(mixer(x, lp, sizes, kind), lp, sizes)


def embed(table, tokens):
    """``sqrt(d) Emb[t]``"""
    return math.sqrt(table.shape[-1]) * jnp.take(table, tokens, axis=0)


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
    at once; ``Follower`` does the same a layer at a time.
    ``sizes['layer_types']`` names each layer's kind."""
    x = embed(params[TABLE], tokens)
    layers = with_bias(params, layer_names(params), bias)
    for lp, kind in zip(layers, sizes["layer_types"], strict=True):
        x = layer(x, lp, sizes, kind)
    return head_loss(x, params["final_norm"], params["head"], tokens, sizes)


class Follower(base.Follower):
    """``reference_kimi_linear.Follower`` (the host's copy of the parameters
    and Adam's moments, one layer on the device at a time, the block's two
    halves taken back one after the other) over this model's two kinds of
    block, with the selection bias ``bias`` [expert layers, E] beside the
    parameters (no gradient, no moment: it stays as handed in) and the
    embedding's constant on the way in and in the table's gradient."""

    def __init__(self, params, bias, sizes, learning_rate):
        super().__init__(params, sizes, learning_rate)
        self.bias = np.array(bias, np.float32)
        sz = self.sizes
        self.kinds = tuple(sz["layer_types"])
        if len(self.kinds) != len(self.layers):
            raise ValueError("layer_types names another number of layers "
                             "than the parameters hold")

        def back(half):
            def run(x, lp, dy):
                return jax.vjp(half, x, lp)[1](dy)
            return jax.jit(run)

        def mixer_of(kind):
            return lambda x, lp: mixer(x, lp, sz, kind)

        # (one set of compiled mixers a kind; the feed-forward's half is
        # every kind's)
        self._by_kind = {kind: {
            "layer": jax.jit(lambda x, lp, k=kind: layer(x, lp, sz, k)),
            "mixer": jax.jit(mixer_of(kind)),
            "mixer_back": back(mixer_of(kind))} for kind in set(self.kinds)}
        self._ffn_back = back(lambda a, lp: feed_forward(a, lp, sz))
        self._embed = jax.jit(embed)

    def _step(self, tokens) -> float:
        self.count += 1
        table = jnp.asarray(self.params[TABLE])
        x = self._embed(table, tokens)
        layers = with_bias(self.params, self.layers, self.bias)
        kept = []
        for lp, kind in zip(layers, self.kinds):
            kept.append(x)
            x = self._by_kind[kind]["layer"](x, lp)
        loss, (dx, d_norm, d_head) = self._top(
            x, self.params["final_norm"], self.params["head"], tokens)
        del x
        self._apply("final_norm", d_norm)
        self._apply("head", d_head)
        for names, lp, kind in zip(reversed(self.layers), reversed(layers),
                                   reversed(self.kinds)):
            fns = self._by_kind[kind]
            x = kept.pop()
            da, d_ffn = self._ffn_back(fns["mixer"](x, lp), lp, dx)
            dx, d_mixer = fns["mixer_back"](x, lp, da)
            del da
            for leaf, n in names.items():   # (a leaf is read by one half)
                self._apply(n, d_ffn[leaf] + d_mixer[leaf])
        scale = math.sqrt(table.shape[-1])
        self._apply(TABLE, jnp.zeros_like(table).at[tokens.reshape(-1)].add(
            scale * dx.reshape(-1, dx.shape[-1])))
        return float(loss)
