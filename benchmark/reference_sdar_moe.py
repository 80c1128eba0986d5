"""Plain reference of SDAR-30B-A3B's training step: one chip's share of a
block-diffusion mixture-of-experts decoder, forward, loss, gradients and
Adam's step from their equations, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. It imports nothing of
``deepfm_tpu``; it is handed arrays by name, the noisy tokens ``x_t``, the
clean ones ``x_0``, each block's ``t`` and the share (which heads and which
experts this chip holds are in the arrays' shapes and ``first_expert``).

The model (``model_type: sdar_moe``, a Qwen3-MoE decoder; sizes in ``sizes``).
With d the width, S = 2L positions, Hq query heads and Hkv key/value heads
held here, each 128 wide:

* Block l on the residual stream x [S, d]:
  ``h = x + Attn(RMSNorm(x; norm1))``, ``y = h + MoE(RMSNorm(h; norm2))``,
  ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``.
* Attn: ``q = x wq`` -> [S, Hq, 128], ``k = x wk``, ``v = x wv`` ->
  [S, Hkv, 128]; ``q <- RMSNorm(q; q_norm)``, ``k <- RMSNorm(k; k_norm)`` over
  the 128; rotate-half rotary at each position's index p with base theta
  (``x cos + rot(x) sin``, ``rot([a, b]) = [-b, a]``, angle
  ``p * theta^(-2i/128)``); scores ``q k / sqrt(128) + M``, softmax in
  float32, query head j reads key/value head ``j // (Hq / Hkv)``; output
  ``concat_heads(P v) wo``. The held heads' part of ``wo``'s sum is the
  result: what the absent heads add is left out.
* MoE: ``p = softmax(x router)`` over all E experts; the k largest, weights
  ``p_e / sum of those k`` (``norm_topk_prob``);
  ``MoE(x) = sum over held e among the k of
  w_e (silu(x w_gate_e) * (x w_up_e)) w_down_e``. A chosen expert that is not
  held (``first_expert <= e < first_expert + held``) adds nothing. No
  auxiliary balance loss: the published config carries no coefficient (a
  stated departure from Qwen3-MoE's training).
* Final ``RMSNorm(.; final_norm)``, logits ``h head`` over this chip's rows of
  the vocabulary.

Block-diffusion training (SDAR, arXiv:2510.06303; mask and objective of
BD3-LM, arXiv:2503.09573). A sequence x0 of L tokens is cut into blocks of b.
For each block t ~ U[t_min, 1]; each of its tokens becomes ``[MASK]`` with
probability t, giving xt. The model reads ``[xt ; x0]``, 2L positions, with
position indices ``(0..L-1, 0..L-1)``. With beta(i) the block of position i:
a noisy query i reads noisy keys j with beta(j) = beta(i) and clean keys with
beta(j) < beta(i); a clean query reads clean keys with beta(j) <= beta(i);
nothing else (``block_diffusion_mask``). Loss, over the noisy half only:
``(1 / (B L)) sum_{i masked} (1 / t_beta(i)) (-log softmax(logits_i)[x0_i])``.

Adam: ``m = b1 m + (1-b1) g``, ``v = b2 v + (1-b2) g^2``,
``p -= lr (m / (1-b1^n)) / (sqrt(v / (1-b2^n)) + eps)``.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
#: The stacked leaves of one layer, by name under ``layers.``.
LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "norm2",
                "router", "w_gate", "w_up", "w_down")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(x, positions, theta):
    """``x`` [..., S, H, D] rotated at ``positions`` [S] (rotate-half)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-b, a], axis=-1) * sin


def block_diffusion_mask(length: int, block: int) -> np.ndarray:
    """bool [2L, 2L]: may query i (rows) read key j (columns)? Positions
    0..L-1 are the noisy copy, L..2L-1 the clean one."""
    beta = np.arange(length) // block
    blk = np.concatenate([beta, beta])
    noisy = np.concatenate([np.ones(length, bool), np.zeros(length, bool)])
    qb, kb = blk[:, None], blk[None, :]
    qn, kn = noisy[:, None], noisy[None, :]
    return np.where(qn, (kn & (kb == qb)) | (~kn & (kb < qb)),
                    ~kn & (kb <= qb))


def attention(x, lp, sizes, mask, positions):
    """x [B, S, d] (already normed) -> the held heads' part of Attn."""
    hd = sizes["head_dim"]
    b, s, _ = x.shape
    q = (x @ lp["wq"]).reshape(b, s, -1, hd)
    k = (x @ lp["wk"]).reshape(b, s, -1, hd)
    v = (x @ lp["wv"]).reshape(b, s, -1, hd)
    q = rotary(rms_norm(q, lp["q_norm"], sizes["eps"]), positions,
               sizes["theta"])
    k = rotary(rms_norm(k, lp["k_norm"], sizes["eps"]), positions,
               sizes["theta"])
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, -1)
    return out @ lp["wo"]


def router_weights(x, router, sizes):
    """x [T, d] -> [T, E]: each token's weight on every expert, zero off its
    k largest; the k sum to one."""
    p = jax.nn.softmax(x @ router, axis=-1)
    kth = jnp.sort(p, axis=-1)[:, -sizes["top_k"]][:, None]
    chosen = jnp.where(p >= kth, p, 0.0)
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def moe(x, lp, sizes):
    """x [B, S, d] (already normed) -> the held experts' part of MoE."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    w = router_weights(x, lp["router"], sizes)
    out = jnp.zeros_like(x)
    for e in range(lp["w_gate"].shape[0]):      # the held ones
        h = jax.nn.silu(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])
        out = out + w[:, sizes["first_expert"] + e, None] * (
            h @ lp["w_down"][e])
    return out.reshape(shape)


def layer(x, lp, sizes, mask, positions):
    h = x + attention(rms_norm(x, lp["norm1"], sizes["eps"]), lp, sizes,
                      mask, positions)
    return h + moe(rms_norm(h, lp["norm2"], sizes["eps"]), lp, sizes)


def head_loss(h, final_norm, head, x0, masked, t_tok, sizes):
    """h [B, L, d]: the noisy half's last residual stream. -> (loss, logits)"""
    logits = rms_norm(h, final_norm, sizes["eps"]) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, x0[..., None], axis=-1)[..., 0]
    loss = jnp.sum(jnp.where(masked, nll / t_tok, 0.0)) / masked.size
    return loss, logits


def token_t(t_blocks, block: int):
    """[B, L / b] -> [B, L]: each token's t is its block's."""
    return jnp.repeat(t_blocks, block, axis=1)


def inputs(x_t, x_0):
    """(ids [B, 2L], positions [2L]) of ``[xt ; x0]``."""
    length = x_0.shape[1]
    pos = jnp.concatenate([jnp.arange(length), jnp.arange(length)])
    return jnp.concatenate([x_t, x_0], axis=1), pos


def layer_params(params: Dict[str, np.ndarray], i: int) -> dict:
    return {n: params["layers." + n][i] for n in LAYER_LEAVES}


def forward_loss(params, x_t, x_0, t_blocks, sizes):
    """(loss, logits [B, L, V]) of the whole model, for sizes a machine holds
    at once; ``Follower`` does the same a layer at a time."""
    length = x_0.shape[1]
    ids, pos = inputs(x_t, x_0)
    mask = jnp.asarray(block_diffusion_mask(length, sizes["block"]))
    x = jnp.take(params["tok_emb"], ids, axis=0)
    n_layers = params["layers.wq"].shape[0]
    for i in range(n_layers):
        x = layer(x, layer_params(params, i), sizes, mask, pos)
    masked = x_t != x_0
    return head_loss(x[:, :length], params["final_norm"], params["head"],
                     x_0, masked, token_t(t_blocks, sizes["block"]), sizes)


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
        sz = self.sizes
        self._layer = jax.jit(lambda x, lp, mask, pos: layer(
            x, lp, sz, mask, pos))

        def layer_back(x, lp, mask, pos, dy):
            _, vjp = jax.vjp(lambda x_, lp_: layer(x_, lp_, sz, mask, pos),
                             x, lp)
            return vjp(dy)
        self._layer_back = jax.jit(layer_back)

        def top(h, final_norm, head, x0, masked, t_tok):
            (loss, _), grads = jax.value_and_grad(
                lambda h_, n_, w_: head_loss(h_, n_, w_, x0, masked, t_tok,
                                             sz),
                argnums=(0, 1, 2), has_aux=True)(h, final_norm, head)
            return loss, grads
        self._top = jax.jit(top)
        self._adam = jax.jit(adam)

    def _apply(self, name, grad, index=None):
        """Adam on leaf ``name`` (or its layer ``index``), on the host's."""
        pick = (lambda a: a) if index is None else (lambda a: a[index])
        p, m, v = self._adam(pick(self.params[name]), grad,
                             pick(self.mu[name]), pick(self.nu[name]),
                             self.count, self.lr)
        for store, new in ((self.params, p), (self.mu, m), (self.nu, v)):
            if index is None:
                store[name] = np.asarray(new)
            else:
                store[name][index] = np.asarray(new)

    def step(self, x_t: np.ndarray, x_0: np.ndarray, t_blocks: np.ndarray
             ) -> float:
        """One optimizer step on one batch; returns its loss."""
        with jax.default_matmul_precision("highest"):
            return self._step(jnp.asarray(x_t), jnp.asarray(x_0),
                              jnp.asarray(t_blocks, jnp.float32))

    def _step(self, x_t, x_0, t_blocks) -> float:
        sz = self.sizes
        self.count += 1
        length = x_0.shape[1]
        ids, pos = inputs(x_t, x_0)
        mask = jnp.asarray(block_diffusion_mask(length, sz["block"]))
        n_layers = self.params["layers.wq"].shape[0]
        x = jnp.take(jnp.asarray(self.params["tok_emb"]), ids, axis=0)
        kept = []
        for i in range(n_layers):
            kept.append(x)
            x = self._layer(x, layer_params(self.params, i), mask, pos)
        loss, (dh, d_norm, d_head) = self._top(
            x[:, :length], self.params["final_norm"], self.params["head"],
            x_0, x_t != x_0, token_t(t_blocks, sz["block"]))
        dx = jnp.concatenate([dh, jnp.zeros_like(dh)], axis=1)
        del x, dh
        self._apply("final_norm", d_norm)
        self._apply("head", d_head)
        for i in reversed(range(n_layers)):
            dx, d_lp = self._layer_back(kept.pop(), layer_params(self.params,
                                                                 i),
                                        mask, pos, dx)
            for n in LAYER_LEAVES:
                self._apply("layers." + n, d_lp[n], index=i)
        d_emb = jnp.zeros(self.params["tok_emb"].shape, jnp.float32).at[
            ids.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))
        self._apply("tok_emb", d_emb)
        return float(loss)


def noise_z(x_t: np.ndarray, x_0: np.ndarray, t_blocks: np.ndarray,
            block: int, t_min: float, mask_id: int) -> float:
    """How far a draw of the noise (``x_t`` [..., L] from ``x_0``, with
    ``t_blocks`` [..., L / block]) lies from what the objective states, as
    the largest of three sums in their own standard deviations; infinite
    where a rule without chance in it is broken (a changed token that is not
    ``[MASK]``, a t outside [t_min, 1]). With m the masked tokens of a block
    and t its own t, m ~ Binomial(block, t):

    * ``sum(t - (1 + t_min) / 2)``: t is uniform on [t_min, 1];
    * ``sum(m - block t)``: a token is masked with probability t (t^2, or a
      constant, moves it);
    * ``sum((m - block t)(t - mean t))``: with its own block's t (1 - t, or
      another block's t, leaves the sum above where it was and moves this
      one by block / 12 a block).
    """
    x_t, x_0 = np.asarray(x_t), np.asarray(x_0)
    t = np.asarray(t_blocks, np.float64)
    changed = x_t != x_0
    if (x_t[changed] != mask_id).any() or t.min() < t_min or t.max() > 1.0:
        return float("inf")
    m = changed.reshape(t.shape + (block,)).sum(axis=-1)
    off, var, w = m - block * t, block * t * (1.0 - t), t - t.mean()
    sums = ((np.sum(t - (1.0 + t_min) / 2.0),
             t.size * (1.0 - t_min) ** 2 / 12.0),
            (np.sum(off), np.sum(var)),
            (np.sum(off * w), np.sum(var * w * w)))
    return float(max(abs(total) / math.sqrt(v) for total, v in sums))


def leaf_gap(got: np.ndarray, want: np.ndarray) -> float:
    """||got - want|| / ||want|| (2-norms, in float64): a few tokens routed
    another way by a rounding move a leaf's gradient by their share of it,
    and a worst element would read the one token."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = float(np.linalg.norm(want))
    return float(np.linalg.norm(got - want)) / scale if scale else float(
        np.linalg.norm(got))


def worst_leaf_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
                   ) -> Tuple[float, str]:
    gaps = {n: leaf_gap(got[n], want[n]) for n in got}
    name = max(gaps, key=gaps.get)
    return gaps[name], name
