"""Plain reference of Phi-4-mini-flash-reasoning's training step: a cut of
whole layers of a dense decoder-decoder (SambaY, arXiv:2507.06607;
``model_type: phi4flash``), forward, loss, gradients and Adam's step from
their equations, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. It imports nothing of
``deepfm_tpu``; it is handed arrays by name and the tokens. What its
equations share with the other references to the letter is theirs, by
import: the depthwise causal convolution, Adam and the leaves' names
(``reference_kimi_linear``); what this model has of its own is written here.

The model (sizes in ``sizes``). x is the residual stream [S, d], d = 2560;
``LN(x; g, b) = (x - mean x) / sqrt(var x + eps) g + b`` with eps 1e-5; l is
a layer's **published** index from 0 (of 32): held layer i is ``l =
sizes['first_layer'] + i``. Every block is ``h = x + Mix_l(LN(x; norm1,
norm1_b))``, ``out = h + MLP(LN(h; norm2, norm2_b))``, ``MLP(u) = (SiLU(u
W_g) * (u W_u)) W_2`` with ``[W_g | W_u] = mlp_w_gate_up`` one matrix, the
gate half first, no biases. ``kind(l)``: l < 16 even **mamba**, l < 16 odd
**window_attention**; l = 16 mamba, whose scan output is the memory M; l =
17 **full_attention**, whose keys and values are kept; l >= 18 even **gmu**,
odd **cross_attention**. No positional encoding. **[departure]** marks what
differs from the published description, **[memory]** what is the published
implementation's as the issue's writer remembers it and the config has no
key for.

* **mamba(u)** (Mamba-1, arXiv:2312.00752; sizes **[memory]**: state 16, 4
  taps with a bias, expansion 2, step rank d / 16 = 160, no projection
  biases): ``[x | z] = u mamba_w_in`` (5120 each); ``x <- SiLU(conv_4(x) +
  mamba_conv_b)``, depthwise, causal; ``[delta | B | C] = x mamba_w_x`` (160
  | 16 | 16); ``D = softplus(delta mamba_w_dt + mamba_dt_bias)`` [T, 5120];
  ``A = -exp(mamba_a_log)`` [5120, 16]; per channel c and state n, from a
  zero state, a position at a time: ``s_t = exp(D_tc A_cn) s_{t-1} + D_tc
  B_tn x_tc``, ``y_tc = sum_n C_tn s_tcn + mamba_d_c x_tc``; the output
  ``(y * SiLU(z)) mamba_w_out``. **M = y**, the skip term in, the gate not
  yet **[memory]**.
* **gmu(u, M)** = ``(SiLU(u gmu_w1) * M) gmu_w2``.
* **DiffAttn(u; K, V)** (arXiv:2410.05258): ``q = u wq + bq`` -> 40 heads
  of 64; own layers ``k, v = u wk + bk, u wv + bv`` -> 20 heads of 64, a
  cross layer takes layer 17's (arXiv:2405.05254). Adjacent heads pair
  **[memory]**: query pair j (of 20) is ``(q_2j, q_2j+1)``, key pair i (of
  10) ``(k_2i, k_2i+1)``, value pair ``V_i = [v_2i | v_2i+1]`` (128 wide);
  query pair j reads pair ``j // 2``. ``A1 = softmax_mask(q1 k1^T / 8)``,
  ``A2 = softmax_mask(q2 k2^T / 8)``; ``lambda = exp(lambda_q1 . lambda_k1)
  - exp(lambda_q2 . lambda_k2) + lambda_init(l)``, ``lambda_init(l) = 0.8 -
  0.6 exp(-0.3 l)``; ``o_j = RMSNorm_128((A1 - lambda A2) V_i; sub_norm,
  1e-5) (1 - lambda_init(l))``; the output ``[o_0 .. o_19] wo + bo``. Mask:
  windowed ``0 <= t - s < 512`` (the query's own position counts
  **[memory]**); full and cross ``s <= t``. **[departure]** a packed
  sequence's documents are not told apart: scans, windows and masks run on
  through a boundary.
* **head**: ``logits = LN(h_L; final_norm, final_norm_b) E^T``, E the token
  table (``tie_word_embeddings``). E's gradient is the sum of its two uses'.

Loss: ``(1 / (B (L-1))) sum_{i < L-1} -log softmax(logits_i)[token_{i+1}]``
over this chip's rows of the vocabulary. Adam as ``reference_kimi_linear``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from benchmark import reference_kimi_linear as base
from benchmark.reference_kimi_linear import layer_names, short_conv

#: Published indices: the first 16 layers alternate scan and window, the
#: 17th scans and leaves M, the 18th attends whole and leaves K and V.
MEMORY_LAYER, SHARED_LAYER = 16, 17
#: Queries a block of the score matrices holds (so that they fit: 40 heads of
#: 512 queries against 8,192 keys are 671 MB of scores), and positions a
#: block of the recurrence whose states the backward pass keeps.
QUERY_BLOCK = 512
SCAN_BLOCK = 128
TABLE = "tok_emb"


def kind(layer: int) -> str:
    """The mixer of published layer ``layer``."""
    if layer <= MEMORY_LAYER:
        return "window_attention" if layer % 2 else "mamba"
    if layer == SHARED_LAYER:
        return "full_attention"
    return "cross_attention" if layer % 2 else "gmu"


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def recurrence(x, delta, a, b, c):
    """One sequence, a position at a time: x, delta [T, C], a [C, N], b, c
    [T, N] -> ``sum_n C_tn s_tcn`` [T, C]. (The state is held [N, C] and the
    positions go in blocks whose inside is made again in the backward pass:
    what is kept is a state a block, not one a position.)"""
    length, width = x.shape
    block = SCAN_BLOCK if length % SCAN_BLOCK == 0 else length
    a_t = a.T

    def step(state, at):
        x_t, d_t, b_t, c_t = at
        state = jnp.exp(d_t[None, :] * a_t) * state \
            + (d_t * x_t)[None, :] * b_t[:, None]
        return state, jnp.sum(c_t[:, None] * state, axis=0)

    @jax.checkpoint
    def one_block(state, xs):
        return jax.lax.scan(step, state, xs)

    blocks = tuple(v.reshape(length // block, block, -1)
                   for v in (x, delta, b, c))
    _, y = jax.lax.scan(one_block, jnp.zeros((a.shape[1], width),
                                             jnp.float32), blocks)
    return y.reshape(length, width)


def mamba(u, lp):
    """u [B, S, d] (already normed) -> (the mixer's output, M = y)."""
    x, z = jnp.split(u @ lp["mamba_w_in"], 2, axis=-1)
    x = jax.nn.silu(short_conv(x, lp["mamba_conv"]) + lp["mamba_conv_b"])
    rank, n = lp["mamba_w_dt"].shape[0], lp["mamba_a_log"].shape[1]
    dbc = x @ lp["mamba_w_x"]
    delta = jax.nn.softplus(dbc[..., :rank] @ lp["mamba_w_dt"]
                            + lp["mamba_dt_bias"])
    a = -jnp.exp(lp["mamba_a_log"])
    y = jax.vmap(recurrence, in_axes=(0, 0, None, 0, 0))(
        x, delta, a, dbc[..., rank:rank + n], dbc[..., rank + n:]) \
        + lp["mamba_d"] * x
    return (y * jax.nn.silu(z)) @ lp["mamba_w_out"], y


def gmu(u, lp, memory):
    return (jax.nn.silu(u @ lp["gmu_w1"]) * memory) @ lp["gmu_w2"]


def diff_attention(u, lp, sizes, layer, shared=None, windowed=False):
    """u [B, S, d] (already normed) -> (DiffAttn at published layer
    ``layer``, its keys and values [B, S, 20, 64] each). ``shared``: (k, v)
    of another layer, read in place of its own."""
    hd, eps = sizes["head_dim"], sizes["eps"]
    b, s, _ = u.shape

    def heads(name):
        return (u @ lp["w" + name] + lp["b" + name]).reshape(b, s, -1, hd)

    k, v = shared if shared is not None else (heads("k"), heads("v"))
    q = heads("q").reshape(b, s, -1, 2, hd)             # [B, S, j, p, D]
    per = q.shape[2] // (k.shape[2] // 2)       # query pairs a key pair
    # query pair j reads key and value pair j // per
    k_j = jnp.repeat(k.reshape(b, s, -1, 2, hd), per, axis=2)
    v_j = jnp.repeat(v.reshape(b, s, -1, 2 * hd), per, axis=2)
    lam_init = lambda_init(layer)
    lam = jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + lam_init
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint         # (a block's scores are made again, not kept)
    def rows(start):        # the masked rows of a block of queries
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqjpd,bkjpd->bjpqk", q_b, k_j) / math.sqrt(hd)
        at = (start + jnp.arange(block))[:, None]
        seen = keys <= at
        if windowed:
            seen = seen & (at - keys < sizes["window"])
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bjqk,bkjd->bqjd",
                          maps[:, :, 0] - lam * maps[:, :, 1], v_j)

    out = jax.lax.map(rows, jnp.arange(0, s, block))    # [blocks,B,Q,j,2D]
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, -1, 2 * hd)
    o = rms_norm(out, lp["sub_norm"], eps) * (1.0 - lam_init)
    return o.reshape(b, s, -1) @ lp["wo"] + lp["bo"], (k, v)


def mixer(x, lp, sizes, layer, read=None):
    """``x + Mix_l(LN(x))`` at published layer ``layer`` -> (h, what the
    layer leaves: ``memory`` or ``shared``). ``read``: what earlier layers
    left, under those names."""
    u = layer_norm(x, lp["norm1"], lp["norm1_b"], sizes["eps"])
    mix, left = kind(layer), {}
    if mix == "mamba":
        y, left["memory"] = mamba(u, lp)
    elif mix == "gmu":
        y = gmu(u, lp, read["memory"])
    else:
        y, kv = diff_attention(
            u, lp, sizes, layer, windowed=mix == "window_attention",
            shared=read["shared"] if mix == "cross_attention" else None)
        if mix == "full_attention":
            left["shared"] = kv
    return x + y, left


def feed_forward(h, lp, sizes):
    """``h + MLP(LN(h))``."""
    u = layer_norm(h, lp["norm2"], lp["norm2_b"], sizes["eps"])
    gate, up = jnp.split(u @ lp["mlp_w_gate_up"], 2, axis=-1)
    return h + (jax.nn.silu(gate) * up) @ lp["mlp_w_down"]


def layer(x, lp, sizes, index, read=None):
    """One block, the ``index``-th held -> (the stream, what it leaves)."""
    h, left = mixer(x, lp, sizes, sizes["first_layer"] + index, read)
    return feed_forward(h, lp, sizes), left


def head_loss(h, final_norm, final_norm_b, table, tokens, sizes):
    """h [B, L, d]: the last residual stream; ``table`` [V, d] the token
    table, which is the head. -> (loss, logits)"""
    logits = layer_norm(h, final_norm, final_norm_b, sizes["eps"]) @ table.T
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll), logits


def stack(x, params, sizes, first: int = 0, last: Optional[int] = None):
    """The held layers ``first .. last`` on the stream x -> the stream
    after them (``sizes['first_layer']`` is held layer 0's published
    index)."""
    read: Dict[str, object] = {}
    for i, names in list(enumerate(layer_names(params)))[first:last]:
        x, left = layer(x, {leaf: params[n] for leaf, n in names.items()},
                        sizes, i, read)
        read.update(left)
    return x


def forward_loss(params, tokens, sizes):
    """(loss, logits [B, L, V]) of the whole model, for sizes a machine holds
    at once; ``Follower`` does the same a layer at a time."""
    x = stack(jnp.take(params[TABLE], tokens, axis=0), params, sizes)
    return head_loss(x, params["final_norm"], params["final_norm_b"],
                     params[TABLE], tokens, sizes)


class Follower(base.Follower):
    """``reference_kimi_linear.Follower`` (the host's copy of the parameters
    and Adam's moments, one layer on the device at a time, the block's two
    halves taken back one after the other) over this model's blocks: a
    layer's mixer is handed what earlier layers left and hands back their
    cotangents, which wait (summed) for the layer that left them; the tied
    table's gradient is the sum of the head's and the lookup's."""

    def __init__(self, params, sizes, learning_rate):
        super().__init__(params, sizes, learning_rate)
        first = self.sizes["first_layer"]
        (self._mixer, self._ffn, self._mixer_back, self._ffn_back,
         self._top) = _programs(tuple(sorted(self.sizes.items())))
        self._index = lambda i: first + i

    def _step(self, tokens) -> float:
        self.count += 1
        table = jnp.asarray(self.params[TABLE])
        x = jnp.take(table, tokens, axis=0)
        layers = [{leaf: self.params[n] for leaf, n in names.items()}
                  for names in self.layers]
        kept, read = [], {}
        for i, lp in enumerate(layers):
            mine = {k: read[k] for k in _reads(self._index(i))}
            kept.append((x, mine))
            h, left = self._mixer(x, lp, mine, self._index(i))
            x = self._ffn(h, lp)
            read.update(left)
        loss, (dx, d_g, d_b, d_table) = self._top(
            x, self.params["final_norm"], self.params["final_norm_b"], table,
            tokens)
        del x, h
        self._apply("final_norm", d_g)
        self._apply("final_norm_b", d_b)
        # cotangents of what layers left, waiting for the layer that left it
        owed = {k: jax.tree.map(jnp.zeros_like, v) for k, v in read.items()}
        for i in reversed(range(len(layers))):
            names, lp = self.layers[i], layers[i]
            x, mine = kept.pop()
            l = self._index(i)
            h, left = self._mixer(x, lp, mine, l)
            dh, d_ffn = self._ffn_back(h, lp, dx)
            del h
            dx, d_mixer, d_read = self._mixer_back(
                x, lp, mine, l, dh, {k: owed[k] for k in left})
            for k in left:      # paid: earlier readers read an earlier one
                owed[k] = jax.tree.map(jnp.zeros_like, owed[k])
            for k, g in d_read.items():
                owed[k] = jax.tree.map(jnp.add, owed[k], g)
            del dh
            for leaf, n in names.items():   # (a leaf is read by one half)
                self._apply(n, d_ffn[leaf] + d_mixer[leaf])
        self._apply(TABLE, d_table.at[tokens.reshape(-1)].add(
            dx.reshape(-1, dx.shape[-1])))
        return float(loss)


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple):
    """The follower's jitted halves for ``sizes`` (its items, sorted): made
    once a process, so that a second follower of the same sizes compiles
    nothing (one compilation a published index: lambda_init is the
    layer's)."""
    sz = dict(sizes)
    mix = jax.jit(lambda x, lp, read, l: mixer(x, lp, sz, l, read),
                  static_argnums=3)
    ffn = jax.jit(lambda h, lp: feed_forward(h, lp, sz))

    def mixer_back(x, lp, read, l, dh, d_left):
        _, back = jax.vjp(lambda x_, lp_, r_: mixer(x_, lp_, sz, l, r_),
                          x, lp, read)
        return back((dh, d_left))

    def top(h, g, b, table, tokens):
        (loss, _), grads = jax.value_and_grad(
            lambda h_, g_, b_, w_: head_loss(h_, g_, b_, w_, tokens, sz),
            argnums=(0, 1, 2, 3), has_aux=True)(h, g, b, table)
        return loss, grads
    return (mix, ffn, jax.jit(mixer_back, static_argnums=3),
            jax.jit(lambda h, lp, dy: jax.vjp(
                lambda h_, lp_: feed_forward(h_, lp_, sz), h, lp)[1](dy)),
            jax.jit(top))


def _reads(layer: int):
    return {"gmu": ("memory",), "cross_attention": ("shared",)}.get(
        kind(layer), ())
