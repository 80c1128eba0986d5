"""Plain reference of Kimi-Linear-48B-A3B's training step: one chip's share
of a hybrid linear-attention mixture-of-experts decoder, forward, loss,
gradients and Adam's step from their equations, in ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``. It imports nothing of
``deepfm_tpu``; it is handed arrays by name, the tokens and the share (which
heads and experts this chip holds are in the arrays' shapes and
``first_expert``).

The model (``model_type: kimi_linear``; sizes in ``sizes``). x is the
residual stream [S, d]; ``x_n = RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) g``
with eps 1e-5; every block is ``h = x + Mixer(RMSNorm(x; norm1))``,
``y = h + FFN(RMSNorm(h; norm2))``; then a final RMSNorm and an untied head.
Layer i (from 1) mixes by MLA where ``attn_every`` (4) divides i, by KDA
elsewhere; layer 1's FFN is a dense MLP, every other an expert layer.
**[departure]** marks what differs from the published description,
**[memory]** what is the published implementation's as the issue's writer
remembers it and the config has no key for.

* **KDA** (Kimi Delta Attention), per held head h, d_k = d_v = 128:
  ``q = SiLU(conv(x_n kda_wq))``, k and v likewise, ``conv`` a causal
  depthwise convolution over the last 4 positions (``y_t = sum_j w_j
  x_{t-3+j}``, zero before the first position, no bias **[memory]**); q and k
  L2-normalised over the 128 (``x / sqrt(sum x^2 + 1e-6)``) and q scaled by
  128^-1/2 **[memory]**; log-decay
  ``g_t = -exp(kda_a_log_h) softplus((x_n kda_w_fa) kda_w_fb + kda_dt_bias)``
  in R^128, a decay a channel through a 128-wide bottleneck **[memory: the
  bottleneck's width is the head's]**; ``beta_t = sigmoid(x_n kda_w_b)_h``;
  state ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
  + beta_t k_t v_t^T``, ``S_0 = 0``, S in R^{128 x 128};
  ``o_t = S_t^T q_t``;
  ``y_t = RMSNorm_128(o_t; kda_out_norm)
  * sigmoid((x_n kda_w_ga) kda_w_gb)_h``;
  output ``concat_h(y) kda_wo``. The recurrence runs a position at a time
  (``kda_recurrence``: a ``lax.scan`` over positions). **[departure]** a
  packed sequence's documents are not told apart: the state and the
  convolution run on through a boundary.
* **MLA** without positional encoding (``mla_use_nope``), per held head h:
  ``q_h = x_n mla_wq^h`` in R^192; ``[c, k_r] = x_n mla_w_kva`` with c in
  R^512 and k_r in R^64; ``c~ = RMSNorm(c; mla_kv_norm)``;
  ``[k_nope^h, v^h] = c~ mla_w_kvb^h`` (128 + 128); ``k^h = [k_nope^h, k_r]``,
  k_r the same for every head and not rotated; scores
  ``q_h k^h / sqrt(192)``, causal softmax, values; output
  ``concat_h(o^h) mla_wo``. No ``q_lora_rank`` (the config's is null).
* **Expert layer**: ``s = sigmoid(x_n router)`` in R^256; the 8 largest of
  ``s + b`` (equal ones to the lower index; ``num_expert_group`` 1 and
  ``topk_group`` 1: a plain top-8), ``w_i = 2.446 s_i / sum_selected s_j``;
  ``y = sum_{i selected and held} w_i E_i(x_n) + E_shared(x_n)``,
  ``E(x) = (SiLU(x w_gate) * x w_up) w_down``. **[departure]** the selection
  bias b is zero and constant (the published training moves it by a load
  rule outside the gradient) and there is no balance loss.
* **Dense MLP** (layer 1): one such E, 9216 wide.
* The held heads' and held experts' parts of the sums are the result: what
  the absent chips add is left out; the shared expert, the dense MLP, the
  gates' bottlenecks, ``mla_w_kva`` and the router are whole.

Loss: ``(1 / (B (L-1))) sum_{i < L-1} -log softmax(logits_i)[token_{i+1}]``
over this chip's rows of the vocabulary.

Adam: ``m = b1 m + (1-b1) g``, ``v = b2 v + (1-b2) g^2``,
``p -= lr (m / (1-b1^n)) / (sqrt(v / (1-b2^n)) + eps)``.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
L2_EPS = 1e-6
#: Queries a block of MLA's score matrix holds (so that it fits).
QUERY_BLOCK = 1024


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def short_conv(x, w):
    """x [B, S, C], w [K, C]: ``y_t = sum_j w_j x_{t-K+1+j}``."""
    taps = w.shape[0]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = x if back == 0 else jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :-back]], axis=1)
        out = out + shifted * w[j]
    return out


def kda_recurrence(q, k, v, g, beta):
    """One head, a position at a time: q, k, g [S, Dk], v [S, Dv], beta [S]
    -> o [S, Dv]."""
    @jax.checkpoint     # (the backward pass keeps a position's state only)
    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, None] * state
        state = state - b_t * jnp.outer(k_t, k_t @ state) \
            + b_t * jnp.outer(k_t, v_t)
        return state, state.T @ q_t

    state0 = jnp.zeros((q.shape[-1], v.shape[-1]), jnp.float32)
    return jax.lax.scan(step, state0, (q, k, v, g, beta))[1]


def kda_inputs(x, lp, sizes):
    """x [B, S, d] (already normed) -> (q, k, v, g [B, S, H, 128], beta
    [B, S, H]) of the held heads."""
    hd = sizes["kda_head_dim"]
    b, s, _ = x.shape

    def heads(y):
        return y.reshape(b, s, -1, hd)

    def unit(y):
        return y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)

    q = unit(heads(jax.nn.silu(short_conv(x @ lp["kda_wq"],
                                          lp["kda_conv_q"])))) / math.sqrt(hd)
    k = unit(heads(jax.nn.silu(short_conv(x @ lp["kda_wk"],
                                          lp["kda_conv_k"]))))
    v = heads(jax.nn.silu(short_conv(x @ lp["kda_wv"], lp["kda_conv_v"])))
    g = -jnp.exp(lp["kda_a_log"])[:, None] * heads(jax.nn.softplus(
        (x @ lp["kda_w_fa"]) @ lp["kda_w_fb"] + lp["kda_dt_bias"]))
    return q, k, v, g, jax.nn.sigmoid(x @ lp["kda_w_b"])


def kda(x, lp, sizes, decay=True):
    """x [B, S, d] (already normed) -> the held heads' part of KDA.
    ``decay=False`` leaves the decay out (a test's broken model)."""
    b, s, _ = x.shape
    q, k, v, g, beta = kda_inputs(x, lp, sizes)
    if not decay:
        g = jnp.zeros_like(g)
    per_head = jax.vmap(kda_recurrence, in_axes=(1, 1, 1, 1, 1), out_axes=1)
    o = jax.vmap(per_head)(q, k, v, g, beta)            # [B, S, H, 128]
    gate = jax.nn.sigmoid(((x @ lp["kda_w_ga"]) @ lp["kda_w_gb"]).reshape(
        o.shape))
    y = rms_norm(o, lp["kda_out_norm"], sizes["eps"]) * gate
    return y.reshape(b, s, -1) @ lp["kda_wo"]


def mla(x, lp, sizes):
    """x [B, S, d] (already normed) -> the held heads' part of MLA."""
    hd, rope = sizes["head_dim"], sizes["rope_dim"]
    b, s, _ = x.shape
    q = (x @ lp["mla_wq"]).reshape(b, s, -1, hd + rope)
    h = q.shape[2]
    kva = x @ lp["mla_w_kva"]
    latent = rms_norm(kva[..., :-rope], lp["mla_kv_norm"], sizes["eps"])
    kv = (latent @ lp["mla_w_kvb"]).reshape(b, s, h, 2 * hd)
    k = jnp.concatenate([kv[..., :hd], jnp.broadcast_to(
        kva[:, :, None, -rope:], (b, s, h, rope))], axis=-1)
    v = kv[..., hd:]
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def rows(start):        # the full causal rows of a block of queries
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) / math.sqrt(hd + rope)
        seen = jnp.arange(s)[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(rows, jnp.arange(0, s, block))    # [blocks, B, Q, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, -1) @ lp["mla_wo"]


def router_weights(x, router, sizes, bias=None):
    """x [T, d] -> [T, E]: each token's weight on every expert, zero off its
    k selected: the k largest of ``sigmoid(x router) + bias``, equal ones to
    the lower index; weights ``scale s_i / sum_selected s_j``."""
    s = jax.nn.sigmoid(x @ router)
    pick = s if bias is None else s + bias
    order = jnp.argsort(-pick, axis=-1, stable=True)[:, : sizes["top_k"]]
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], order].set(1.0) * s
    return sizes["route_scale"] * chosen / jnp.sum(chosen, axis=-1,
                                                   keepdims=True)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(x, lp, sizes):
    """x [B, S, d] (already normed) -> the held experts' part of the routed
    sum and the shared expert."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    w = router_weights(x, lp["router"], sizes)
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


def mixer(x, lp, sizes):
    """``x + Mixer(RMSNorm(x; norm1))``; which mixer is read from the
    leaves' names."""
    xn = rms_norm(x, lp["norm1"], sizes["eps"])
    return x + (kda(xn, lp, sizes) if "kda_wq" in lp else mla(xn, lp, sizes))


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


def head_loss(h, final_norm, head, tokens, sizes):
    """h [B, L, d]: the last residual stream. -> (loss, logits)"""
    logits = rms_norm(h, final_norm, sizes["eps"]) @ head
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll), logits


def layer_names(params: Dict[str, np.ndarray]) -> List[Dict[str, str]]:
    """[{leaf's name in the layer: its name among ``params``}] a layer, from
    the names ``layers.<i>.<leaf>``."""
    found: Dict[int, Dict[str, str]] = {}
    for name in params:
        if name.startswith("layers."):
            _, i, leaf = name.split(".", 2)
            found.setdefault(int(i), {})[leaf] = name
    return [found[i] for i in sorted(found)]


def forward_loss(params, tokens, sizes):
    """(loss, logits [B, L, V]) of the whole model, for sizes a machine holds
    at once; ``Follower`` does the same a layer at a time."""
    x = jnp.take(params["tok_emb"], tokens, axis=0)
    for names in layer_names(params):
        x = layer(x, {leaf: params[n] for leaf, n in names.items()}, sizes)
    return head_loss(x, params["final_norm"], params["head"], tokens, sizes)


def adam(p, g, m, v, n, lr):
    """One Adam step of one leaf, n counting from 1."""
    m = ADAM_B1 * m + (1 - ADAM_B1) * g
    v = ADAM_B2 * v + (1 - ADAM_B2) * g * g
    m_hat = m / (1 - ADAM_B1 ** n)
    v_hat = v / (1 - ADAM_B2 ** n)
    return p - lr * m_hat / (jnp.sqrt(v_hat) + ADAM_EPS), m, v


class Follower:
    """Follows the program's steps on the host's copy of the parameters,
    holding one layer on the device at a time: the forward pass keeps each
    layer's input, the backward pass takes each layer's gradient by
    ``jax.vjp`` (computing the layer again) and applies Adam to its leaves
    at once. ``params`` and ``mu`` (Adam's first moment) are the host's."""

    def __init__(self, params: Dict[str, np.ndarray], sizes: dict,
                 learning_rate: float):
        self.params = {k: np.array(v, np.float32) for k, v in params.items()}
        self.mu = {k: np.zeros_like(v) for k, v in self.params.items()}
        self.nu = {k: np.zeros_like(v) for k, v in self.params.items()}
        self.sizes = dict(sizes)
        self.lr = float(learning_rate)
        self.count = 0
        self.layers = layer_names(self.params)
        sz = self.sizes
        # (one compilation a set of leaves: a layer kind)
        self._layer = jax.jit(lambda x, lp: layer(x, lp, sz))
        self._mixer = jax.jit(lambda x, lp: mixer(x, lp, sz))

        # The block's two halves are taken back one after the other (the
        # mixer's output made again first): a layer's memory at the timed
        # sizes is then the larger half's, not their sum.
        def back(half):
            def run(x, lp, dy):
                return jax.vjp(lambda x_, lp_: half(x_, lp_, sz), x, lp)[1](dy)
            return jax.jit(run)
        self._mixer_back, self._ffn_back = back(mixer), back(feed_forward)

        def top(h, final_norm, head, tokens):
            (loss, _), grads = jax.value_and_grad(
                lambda h_, n_, w_: head_loss(h_, n_, w_, tokens, sz),
                argnums=(0, 1, 2), has_aux=True)(h, final_norm, head)
            return loss, grads
        self._top = jax.jit(top)
        self._adam = jax.jit(adam)

    def _apply(self, name, grad):
        """Adam on leaf ``name``, on the host's."""
        p, m, v = self._adam(self.params[name], grad, self.mu[name],
                             self.nu[name], self.count, self.lr)
        self.params[name], self.mu[name], self.nu[name] = (
            np.asarray(p), np.asarray(m), np.asarray(v))

    def step(self, tokens: np.ndarray) -> float:
        """One optimizer step on one batch; returns its loss."""
        with jax.default_matmul_precision("highest"):
            return self._step(jnp.asarray(tokens, jnp.int32))

    def _step(self, tokens) -> float:
        self.count += 1
        x = jnp.take(jnp.asarray(self.params["tok_emb"]), tokens, axis=0)
        kept = []
        for names in self.layers:
            kept.append(x)
            x = self._layer(x, {leaf: self.params[n]
                                for leaf, n in names.items()})
        loss, (dx, d_norm, d_head) = self._top(
            x, self.params["final_norm"], self.params["head"], tokens)
        del x
        self._apply("final_norm", d_norm)
        self._apply("head", d_head)
        for names in reversed(self.layers):
            x, lp = kept.pop(), {leaf: self.params[n]
                                 for leaf, n in names.items()}
            dh, d_ffn = self._ffn_back(self._mixer(x, lp), lp, dx)
            dx, d_mixer = self._mixer_back(x, lp, dh)
            del dh
            for leaf, n in names.items():   # (a leaf is read by one half)
                self._apply(n, d_ffn[leaf] + d_mixer[leaf])
        d_emb = jnp.zeros(self.params["tok_emb"].shape, jnp.float32).at[
            tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))
        self._apply("tok_emb", d_emb)
        return float(loss)
