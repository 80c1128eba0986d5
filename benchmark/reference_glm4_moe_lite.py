"""Plain reference of GLM-4.7-Flash's training step: one chip's share of a
latent-attention mixture-of-experts decoder with a multi-token-prediction
module, forward, both losses, gradients and Adam's step from their
equations, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. It imports nothing of
``deepfm_tpu``; it is handed arrays by name, the tokens, the routers'
selection bias and the share (which heads and experts this chip holds are in
the arrays' shapes and ``first_expert``). What its equations share with the
other references to the letter is theirs, by import: RMSNorm, SwiGLU, Adam,
the follower and the leaves' names (``reference_kimi_linear``), rotate-half
rotary (``reference_sdar_moe``); what this model has of its own is written
here.

The model (``model_type: glm4_moe_lite``; sizes in ``sizes``). x is the
residual stream [S, d], d = 2048; ``x_n = RMSNorm(x; g) = x / sqrt(mean(x^2)
+ eps) g`` with eps 1e-5; every block is ``h = x + MLA(RMSNorm(x; norm1))``,
``y = h + FFN(RMSNorm(h; norm2))``; l is a layer's published index of 47
(0 dense, 1-46 sparse), 47 the module's block. No biases anywhere
(``attention_bias`` false). **[departure]** marks what differs from the
published description, **[assumed]** what the catalog row has no key for
(the family's convention, from memory: no network here).

* **MLA** (every layer), per held head h of H, positions p: ``c_q =
  RMSNorm_768(x_n W_qa)`` (``q_lora_rank``); ``q = c_q W_qb`` -> H x (192 |
  64): ``q_h = [q_h^n | R_p(q_h^r)]``. ``[c | kappa] = x_n W_kva`` (512 | 64);
  ``c <- RMSNorm_512(c)``; ``k^r = R_p(kappa)``, one for all heads;
  ``[k_h^n | v_h] = c W_kvb`` -> H x (192 | 256); ``k_h = [k_h^n | k^r]``
  (256). ``o_h = softmax_{s <= t}(q_h k_h^T / sqrt(256)) v_h`` (256 wide);
  output ``[o_1 .. o_H] W_o``. ``R_p`` is the rotary embedding over all 64
  columns (``partial_rotary_factor`` 1), theta 1e6, no scaling
  (``rope_scaling`` null). **[assumed]** rotate-half pairing (the published
  interleaved pairing is a permutation of ``W_qb``'s and ``W_kva``'s rope
  columns); the softmax scale ``1 / sqrt(192 + 64)`` with no YaRN factor.
* **FFN, layer 0:** ``SwiGLU_10240``. **Layers 1-46 and the module's
  block:** ``s = sigmoid(x_n W_r)`` (64 scores); the 4 experts of largest
  ``s + b`` (``noaux_tc``; ``n_group`` 1 and ``topk_group`` 1: no group
  limit; equal ones to the lower index); weights ``1.8 s_e / (sum_taken s +
  1e-20)`` (``norm_topk_prob``, ``routed_scaling_factor``); ``FFN = sum_{e
  taken and held} w_e SwiGLU_1536^(e)(x_n) + SwiGLU_1536^shared(x_n)``.
  **[departure]** b is seeded and constant (the published training moves it
  by a load rule outside the gradient) and there is no balance loss.
* **Main loss:** logits ``RMSNorm(h^last; final_norm) W_head`` (untied);
  ``L1`` = next-token cross-entropy, mean over positions 0 .. T-2.
* **The multi-token-prediction module** (``num_nextn_predict_layers`` 1: the
  DeepSeek-V3 module **[assumed]**): ``m_i = [RMSNorm(Emb(t_{i+1}); enorm) ;
  RMSNorm(h_i^last; hnorm)] W_eh`` (4096 -> 2048; the order ``[embedding ;
  hidden]`` **[assumed]**), i = 0 .. T-2, ``h^last`` the last held layer's
  output ahead of ``final_norm`` **[assumed]** (an implementation that hands
  the module the normed state differs by one learned gain); ``m' =
  Block_47(m)`` (MLA causal over i at positions i + 1; an expert layer with
  its own router, experts and bias); logits ``RMSNorm(m'; mtp.final_norm)
  W_head`` with **the main model's** ``Emb`` and ``W_head``; ``L2`` =
  cross-entropy of ``t_{i+2}``, mean over i = 0 .. T-3. ``L = L1 + lambda
  L2``, lambda 0.1 **[assumed]**. **[departure]** the module is fed the
  last *held* layer's output (layer 4's in the cut, where the model feeds it
  layer 46's).
* The held heads' and held experts' parts of the sums are the result: what
  the absent chips add is left out; ``W_qa``, ``W_kva``, their norms, the
  router, the shared expert, the dense MLP and ``W_eh`` are whole.
  **[departure]** a packed sequence's documents are not told apart.

So that it fits at the timed sizes: the scores are made a block of
``QUERY_BLOCK`` queries at a time (a ragged last block padded, its rows
dropped) and both head passes a chunk of ``HEAD_BLOCK`` positions at a time,
each made again in the backward pass. Adam as ``reference_kimi_linear``.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import reference_kimi_linear as base
from benchmark.reference_kimi_linear import layer_names, rms_norm, swiglu
from benchmark.reference_sdar_moe import rotary

#: epsilon beside the chosen scores' sum where they are renormalised
RENORM_EPS = 1e-20
#: Queries a block of the score matrix holds, positions a chunk of a head
#: pass (so that they fit: 5 heads of 512 queries against 8,192 keys of 2
#: sequences are 168 MB of scores, 1,024 positions' logits over 38,720 rows
#: of 2 sequences 317 MB).
QUERY_BLOCK = 512
HEAD_BLOCK = 1024
TABLE = "tok_emb"
#: The module's leaves outside its block.
MTP_OWN = ("enorm", "hnorm", "w_eh")


def causal_attention(q, k, v):
    """q, k [B, S, H, Dk], v [B, S, H, Dv] -> [B, S, H * Dv]: causal softmax
    of ``q k^T / sqrt(Dk)`` against v, a block of queries at a time."""
    b, s, _, dk = q.shape
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    @jax.checkpoint         # (a block's scores are made again, not kept)
    def rows(start):        # the full causal rows of a block of queries
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) / math.sqrt(dk)
        seen = jnp.arange(s)[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(rows, jnp.arange(0, s + pad, block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s + pad, -1)[:, :s]


def mla(x, lp, sizes, position0=0, rotate_k=True, norm_q=True):
    """x [B, S, d] (already normed) at positions ``position0 ..`` -> the
    held heads' part of MLA. ``rotate_k=False`` leaves the shared key
    unrotated and ``norm_q=False`` the query latent unnormed (tests' broken
    models)."""
    nope, rope = sizes["nope_dim"], sizes["rope_dim"]
    eps, theta = sizes["eps"], sizes["theta"]
    b, s, _ = x.shape
    positions = position0 + jnp.arange(s)
    c_q = x @ lp["mla_w_qa"]
    if norm_q:
        c_q = rms_norm(c_q, lp["mla_q_norm"], eps)
    q = (c_q @ lp["mla_w_qb"]).reshape(b, s, -1, nope + rope)
    h = q.shape[2]
    q = jnp.concatenate([q[..., :nope],
                         rotary(q[..., nope:], positions, theta)], axis=-1)
    kva = x @ lp["mla_w_kva"]
    latent = rms_norm(kva[..., :-rope], lp["mla_kv_norm"], eps)
    shared = kva[:, :, None, -rope:]
    if rotate_k:
        shared = rotary(shared, positions, theta)
    kv = (latent @ lp["mla_w_kvb"]).reshape(b, s, h, -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        shared, (b, s, h, rope))], axis=-1)
    return causal_attention(q, k, kv[..., nope:]) @ lp["mla_wo"]


def router_weights(x, router, bias, sizes):
    """x [T, d] -> [T, E]: each token's weight on every expert, zero off its
    k selected: the k largest of ``sigmoid(x router) + bias``, equal ones to
    the lower index; weights ``scale s_i / (sum_selected s_j + 1e-20)`` of
    the unbiased scores."""
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


def mixer(x, lp, sizes, position0=0):
    """``x + MLA(RMSNorm(x; norm1))``."""
    return x + mla(rms_norm(x, lp["norm1"], sizes["eps"]), lp, sizes,
                   position0)


def feed_forward(h, lp, sizes):
    """``h + FFN(RMSNorm(h; norm2))``: the dense MLP or the expert layer."""
    hn = rms_norm(h, lp["norm2"], sizes["eps"])
    if "mlp_w_gate" in lp:
        return h + swiglu(hn, lp["mlp_w_gate"], lp["mlp_w_up"],
                          lp["mlp_w_down"])
    return h + moe(hn, lp, sizes)


def layer(x, lp, sizes, position0=0):
    """One block."""
    return feed_forward(mixer(x, lp, sizes, position0), lp, sizes)


def mtp_input(emb_next, h, mp, sizes):
    """``[RMSNorm(Emb(t_{i+1}); enorm) ; RMSNorm(h_i; hnorm)] W_eh``:
    emb_next, h [B, S, d] -> [B, S, d]."""
    return jnp.concatenate([rms_norm(emb_next, mp["enorm"], sizes["eps"]),
                            rms_norm(h, mp["hnorm"], sizes["eps"])],
                           axis=-1) @ mp["w_eh"]


def logits(h, norm, head, sizes):
    return rms_norm(h, norm, sizes["eps"]) @ head


def mean_xent(h, norm, head, labels, sizes):
    """The mean over h's [B, S, d] positions of the cross-entropy of
    ``labels`` [B, S] under ``RMSNorm(h; norm) head``, a chunk of positions
    at a time (a ragged last chunk padded, its positions weighing
    nothing)."""
    b, s, d = h.shape
    chunk = min(HEAD_BLOCK, s)
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(x):      # [B, S, ...] -> [n, B, chunk, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 1, 0)

    @jax.checkpoint         # (a chunk's logits are made again, not kept)
    def one(args):
        h_c, y_c, w_c = args
        logp = jax.nn.log_softmax(logits(h_c, norm, head, sizes), axis=-1)
        nll = -jnp.take_along_axis(logp, y_c[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * w_c)

    return jnp.sum(jax.lax.map(one, (
        chunks(h), chunks(labels),
        chunks(jnp.ones((b, s), jnp.float32))))) / (b * s)


def head_loss(h, final_norm, head, tokens, sizes):
    """``L1``: h [B, L, d] the last residual stream."""
    return mean_xent(h[:, :-1], final_norm, head, tokens[:, 1:], sizes)


def mtp_loss(m, norm, head, tokens, sizes):
    """``L2``: m [B, L-1, d] the module's block's output at i = 0 .. L-2, of
    which the first L-2 have a token after the next."""
    return mean_xent(m[:, :-1], norm, head, tokens[:, 2:], sizes)


def mtp_leaves(params: Dict[str, np.ndarray]):
    """(the module's own leaves, its block's, its final norm) from the
    names ``mtp.<leaf>`` and ``mtp.block.<leaf>``."""
    own = {leaf: params["mtp." + leaf] for leaf in MTP_OWN}
    block = {name[len("mtp.block."):]: v for name, v in params.items()
             if name.startswith("mtp.block.")}
    return own, block, params["mtp.final_norm"]


def with_bias(params: Dict[str, np.ndarray], names, bias) -> list:
    """[{leaf: array}] a layer: its parameters and, for an expert layer, its
    row of the selection bias ``bias`` [expert blocks, E] as
    ``select_bias`` (the module's block's is the last row)."""
    out, row = [], 0
    for layer_leaves in names:
        lp = {leaf: params[n] for leaf, n in layer_leaves.items()}
        if "router" in lp:
            lp["select_bias"] = bias[row]
            row += 1
        out.append(lp)
    return out


def forward_losses(params, tokens, bias, sizes):
    """(L1, L2, the main model's logits [B, L, V]) of the whole model, for
    sizes a machine holds at once; ``Follower`` does the same a layer at a
    time. Without ``mtp.*`` leaves L2 is 0."""
    emb = jnp.take(params[TABLE], tokens, axis=0)
    x = emb
    for lp in with_bias(params, layer_names(params), bias):
        x = layer(x, lp, sizes)
    l1 = head_loss(x, params["final_norm"], params["head"], tokens, sizes)
    l2 = jnp.zeros((), jnp.float32)
    if "mtp.w_eh" in params:
        own, block, norm = mtp_leaves(params)
        m = mtp_input(emb[:, 1:], x[:, :-1], own, sizes)
        m = layer(m, {**block, "select_bias": bias[-1]}, sizes, position0=1)
        l2 = mtp_loss(m, norm, params["head"], tokens, sizes)
    return l1, l2, logits(x, params["final_norm"], params["head"], sizes)


def forward_loss(params, tokens, bias, sizes):
    """(``L1 + lambda L2``, the main model's logits)."""
    l1, l2, out = forward_losses(params, tokens, bias, sizes)
    return l1 + sizes["mtp_weight"] * l2, out


class Follower(base.Follower):
    """``reference_kimi_linear.Follower`` (the host's copy of the parameters
    and Adam's moments, one layer on the device at a time, the block's two
    halves taken back one after the other) over this model's block, with
    the selection bias ``bias`` [expert blocks, E] beside the parameters (no
    gradient, no moment: it stays as handed in) and the module after the
    last layer: ``step`` returns ``L1``, ``mtp_xent`` is the step's ``L2``;
    the gradient is of ``L1 + lambda L2``, the token table's and the head's
    the sum of both uses'; ``mtp_part`` keeps the first step's gradient of
    ``lambda L2`` alone for the head and the last layer."""

    def __init__(self, params, bias, sizes, learning_rate):
        super().__init__(params, sizes, learning_rate)
        self.bias = np.array(bias, np.float32)
        self.mtp_xent = 0.0
        #: the first step's gradient of ``lambda L2`` alone, by leaf
        #: (``_mtp_part``): what a check of the module's two ways back reads
        self.mtp_part: Dict[str, np.ndarray] = {}
        sz = self.sizes
        # (the stack's layers at positions 0 .., the module's block at 1 ..)
        self._layer = jax.jit(lambda x, lp, p0: layer(x, lp, sz, p0),
                              static_argnums=2)
        self._mixer = jax.jit(lambda x, lp, p0: mixer(x, lp, sz, p0),
                              static_argnums=2)

        def mixer_back(x, lp, dy, p0):
            return jax.vjp(lambda x_, lp_: mixer(x_, lp_, sz, p0), x,
                           lp)[1](dy)

        def ffn_back(x, lp, dy):
            return jax.vjp(lambda x_, lp_: feed_forward(x_, lp_, sz), x,
                           lp)[1](dy)
        self._mixer_back = jax.jit(mixer_back, static_argnums=3)
        self._ffn_back = jax.jit(ffn_back)

        def top(loss_of, weight):
            def run(h, norm, head, tokens):
                return jax.value_and_grad(
                    lambda h_, n_, w_: weight * loss_of(h_, n_, w_, tokens,
                                                        sz),
                    argnums=(0, 1, 2))(h, norm, head)
            return jax.jit(run)
        self._top = top(head_loss, 1.0)
        self._mtp_top = top(mtp_loss, sz.get("mtp_weight", 0.0))
        self._mtp_in = jax.jit(lambda e, h, mp: mtp_input(e, h, mp, sz))
        self._mtp_in_back = jax.jit(lambda e, h, mp, dm: jax.vjp(
            lambda e_, h_, mp_: mtp_input(e_, h_, mp_, sz), e, h, mp)[1](dm))

    def _block_back(self, names, lp, x, dx, p0=0):
        """One block's gradients applied (Adam on its leaves), its input's
        cotangent returned."""
        dh, d_ffn = self._ffn_back(self._mixer(x, lp, p0), lp, dx)
        dx, d_mixer = self._mixer_back(x, lp, dh, p0)
        for leaf, n in names.items():   # (a leaf is read by one half)
            self._apply(n, d_ffn[leaf] + d_mixer[leaf])
        return dx

    def _mtp_part(self, d_head, d_h, lp, x) -> Dict[str, np.ndarray]:
        """What ``lambda L2`` alone adds to the step's gradient where the
        module's two ways back into the main model arrive: the head's (its
        second use) and the last layer's leaves' (through ``h^last``'s
        cotangent ``d_h`` [B, L-1, d]); ``lp`` and ``x`` are that layer's
        leaves and input."""
        dy = jnp.zeros_like(x).at[:, :-1].set(d_h)
        dh, d_ffn = self._ffn_back(self._mixer(x, lp, 0), lp, dy)
        _, d_mixer = self._mixer_back(x, lp, dh, 0)
        return {"head": np.asarray(d_head), **{
            n: np.asarray(d_ffn[leaf] + d_mixer[leaf])
            for leaf, n in self.layers[-1].items()}}

    def _step(self, tokens) -> float:
        self.count += 1
        table = jnp.asarray(self.params[TABLE])
        emb = jnp.take(table, tokens, axis=0)
        layers = with_bias(self.params, self.layers, self.bias)
        kept, x = [], emb
        for lp in layers:
            kept.append(x)
            x = self._layer(x, lp, 0)
        head = self.params["head"]
        loss, (dx, d_norm, d_head) = self._top(
            x, self.params["final_norm"], head, tokens)
        self._apply("final_norm", d_norm)
        d_emb = jnp.zeros_like(emb)
        if "mtp.w_eh" in self.params:
            own, block, norm = mtp_leaves(self.params)
            block = {**block, "select_bias": self.bias[-1]}
            m = self._mtp_in(emb[:, 1:], x[:, :-1], own)
            weighted, (dm, d_mnorm, d_head2) = self._mtp_top(
                self._layer(m, block, 1), norm, head, tokens)
            self.mtp_xent = float(weighted) / self.sizes["mtp_weight"]
            d_head = d_head + d_head2
            self._apply("mtp.final_norm", d_mnorm)
            dm = self._block_back(
                {leaf: "mtp.block." + leaf for leaf in block
                 if leaf != "select_bias"}, block, m, dm, 1)
            d_next, d_h, d_own = self._mtp_in_back(emb[:, 1:], x[:, :-1],
                                                   own, dm)
            for leaf in MTP_OWN:
                self._apply("mtp." + leaf, d_own[leaf])
            if self.count == 1:
                self.mtp_part = self._mtp_part(d_head2, d_h, layers[-1],
                                               kept[-1])
            dx = dx.at[:, :-1].add(d_h)
            d_emb = d_emb.at[:, 1:].add(d_next)
        del x
        self._apply("head", d_head)
        for names, lp in zip(reversed(self.layers), reversed(layers)):
            dx = self._block_back(names, lp, kept.pop(), dx)
        d_emb = d_emb + dx
        self._apply(TABLE, jnp.zeros(table.shape, jnp.float32).at[
            tokens.reshape(-1)].add(d_emb.reshape(-1, d_emb.shape[-1])))
        return float(loss)
