"""Dense hybrid decoder-decoder of selective scans and differential attention
(``--model phi4_flash``).

``models.kimi_linear``'s stack (pre-norm residual blocks of a mixer and a
feed-forward, every layer recomputed in the backward pass; next-token
cross-entropy the model owns; ``hist_ids`` [B, L] the tokens, ``tok_emb``
the table, which is also the head) with what Phi-4-mini-flash-reasoning
(``model_type: phi4flash``; SambaY, arXiv:2507.06607) changes.
``benchmark/reference_phi4_flash.py`` holds the equations; this is the
program's form of them.

* **The layers' kinds are a list.** ``--layer_types`` names each held
  layer's mixer (``mamba`` / ``window_attention`` / ``full_attention`` /
  ``gmu`` / ``cross_attention``); ``--first_layer`` is the published index of
  the first of them (lambda_init follows the published index). Every layer's
  feed-forward is a dense SwiGLU whose gate and up halves are one matrix;
  there are no experts and no ``moe_*`` counts. No positional encoding.
* **Layers read other layers' tensors** (``READS``, ``_run_layer``): a
  ``mamba`` layer leaves its scan's output (the skip term in, the gate not
  yet) as ``memory``, a ``full_attention`` layer its keys and values as
  ``shared_k`` / ``shared_v``; a ``gmu`` layer reads the newest ``memory``
  and a ``cross_attention`` layer the newest keys and values. What a layer
  leaves is an output of its ``jax.checkpoint``, so every layer is still
  made again in the backward pass and the producers' gradients are whole:
  the consumers' cotangents sum into them by AD.
* **The block's norm is LayerNorm** with a gain and a bias
  (``layer_norm``); the attention projections have biases.
* **The selective scan** (``selective_scan``, scope ``mamba_scan`` inside
  the mixer's ``mamba``; Mamba-1, arXiv:2312.00752). Per channel c and state
  n: ``s_t = exp(D_tc A_cn) s_{t-1} + D_tc B_tn x_tc``, ``y_tc = sum_n C_tn
  s_tcn + skip_c x_tc``, D the step size. The decay is per channel *and*
  state, so no pairwise [t, s] form is a matrix product (``kda_scan``'s
  chunking does not carry over), and the state of a whole sequence is 2.7 GB
  at 8,192 positions of 5,120 x 16. Computed a **segment** of
  ``MAMBA_SEGMENT`` positions at a time, the segments in sequence (a
  ``lax.scan`` whose body is made again in the backward pass: its live state
  is a segment's); inside a segment the chunks of ``MAMBA_CHUNK`` positions
  step **in lockstep**: one ``lax.scan`` over a chunk's positions updates
  every chunk's local state (from zero) at once, [chunks, N, C] a step,
  channels on the lanes; the chunks' end states are then passed along
  (``S_{k+1} = P_k S_k + E_k``, P_k the chunk's whole decay) and each
  position takes what its chunk's entering state adds,
  ``sum_n C_tn exp(A_cn G_tc) S_kcn``, G the step sizes' running sum inside
  the chunk. No decay is divided by. All of it float32. The backward pass
  is the forward's, differentiated. That is the form everywhere but on a
  TPU: there, where the channels are whole blocks of 1,024, the positions
  whole blocks of 64 and the step one device's program (``scan_by``: read
  from the backend, the shapes and the mesh; no flag), the recurrence is
  the two kernels of ``ops/pallas_selective_scan``, which keep a block's
  state in vector registers from position to position (the lockstep form's
  time is the state's way to HBM and back, 8 bytes a position, channel and
  state a pass). ``mamba_chunk_log_decay_min`` in the model state and the
  step's metrics is the most negative whole-chunk log-decay ``A_cn G_end``
  of the step (a chunk the kernel's time block where it runs).
* **The gated memory unit** (``gmu``, scope ``gmu``): ``(SiLU(xn W_1) *
  memory) W_2``.
* **Differential attention** (``diff_attention``, scopes ``attn`` /
  ``attn_scores``; arXiv:2410.05258): adjacent heads pair; two softmax maps a
  pair, ``A1 - lambda A2``, against the pair's two value heads side by side
  (128 wide), a 128-wide RMS norm and ``1 - lambda_init`` after. Both maps
  of every pair are one call of ``sdar_moe.masked_scores`` with values twice
  as wide as keys: key head ``2i + p`` (map p of key pair i) carries the
  pair's values ``[v_2i | v_2i+1]`` and is read by the queries ``q_{2j+p}``
  of its two query pairs j = 2i, 2i + 1. The mask is a ``ScoreMask``:
  ``window(w)`` (a query reads itself and the ``w - 1`` positions before)
  or ``kimi_linear.causal``; on a TPU the block kernel visits 31 of 256
  blocks of 512 at 8,192 positions under the window of 512 and 136 under
  the causal mask.

The layers are whole here: the flags' heads and widths are the model's.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import pallas_selective_scan
from . import common
from .kimi_linear import MLP_KEPT, KimiLinear, causal, causal_conv, window
from .sdar_moe import (ScoreMask, _dot, _operand, attn_scores_by,
                       layer_policy, masked_scores, masks_notes, rms_norm)

#: What a mixer reads of what earlier layers left (``LEAVES``), by name.
READS = {"gmu": ("memory",), "cross_attention": ("shared_k", "shared_v")}
LEAVES = {"mamba": ("memory",), "full_attention": ("shared_k", "shared_v")}
#: The step's count: the most negative whole-chunk log-decay of the scans.
DECAY_MIN = "mamba_chunk_log_decay_min"
#: Positions of a chunk of the selective scan (a lockstep scan's length) and
#: of a segment (what the backward pass holds the states of at once:
#: 512 x 5,120 x 16 float32 are 168 MB). On a v5e at [1, 8192, 5120] x 16,
#: forward / forward and backward a layer (PERF.md section 6, PR 44): 64 in
#: 1,024 8.3 / 56.8 ms; 32 in 1,024 8.2 / 53.8; 128 in 1,024 7.6 / 72.0; 64
#: in 2,048 9.3 / 57.0; 32 in 512 10.1 / 51.4; 16 in 512 10.3 / 51.0; a
#: ``lax.scan`` over positions 7.0 / 58.9.
MAMBA_CHUNK = 32
MAMBA_SEGMENT = 512
#: lambda_init(l) = LAMBDA_TOP - LAMBDA_SPAN exp(-LAMBDA_RATE l), l the
#: layer's published index from 0.
LAMBDA_TOP, LAMBDA_SPAN, LAMBDA_RATE = 0.8, 0.6, 0.3


def layer_kinds(cfg: Any) -> Tuple[Tuple[str, str], ...]:
    """((mixer, feed-forward) of each layer): the mixers are
    ``layer_types``' words, every feed-forward the dense MLP."""
    return tuple((mixer, "mlp") for mixer in cfg.layer_type_list)


def lambda_init(layer: int) -> float:
    """Differential attention's lambda_init at published layer ``layer``."""
    return LAMBDA_TOP - LAMBDA_SPAN * math.exp(-LAMBDA_RATE * layer)


def layer_norm(x: jnp.ndarray, gain: jnp.ndarray, bias: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centred = x - mean
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * gain + bias


def scan_by(width: int, length: int, *, one_device: bool = True,
            backend: Optional[str] = None) -> str:
    """``kernel`` where the scan's kernels apply
    (``ops/pallas_selective_scan.supported``: a TPU backend, channels in
    whole blocks of 1,024, positions in whole blocks of 64; and a step that
    is one device's program, as ``sdar_moe.attn_scores_by`` asks), else
    ``xla`` (segments in sequence, chunks in lockstep): read from the
    backend, the shapes and the mesh."""
    return "kernel" if one_device and pallas_selective_scan.supported(
        width, length, backend) else "xla"


def scan_note(by: str) -> str:
    """What ``step_notes`` says of the scan's form."""
    return (f"kernel steps{pallas_selective_scan.STEPS}" if by == "kernel"
            else f"lockstep chunk{MAMBA_CHUNK}/segment{MAMBA_SEGMENT}")


@jax.named_scope("mamba_scan")
def selective_scan(x: jnp.ndarray, delta: jnp.ndarray, a: jnp.ndarray,
                   b: jnp.ndarray, c: jnp.ndarray, skip: jnp.ndarray, *,
                   by: str = "xla", chunk: int = 0, segment: int = 0,
                   interpret: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The selective recurrence from a zero state (the module's docstring):
    x, delta [B, T, C], a [C, N] (negative), b, c [B, T, N], skip [C],
    float32 -> (y [B, T, C], the most negative whole-chunk log-decay).
    ``by`` is ``scan_by``'s word for what computes it. ``chunk`` /
    ``segment``: positions of a chunk and of a segment of the XLA form
    (``MAMBA_CHUNK`` / ``MAMBA_SEGMENT`` unless a test says otherwise); a
    sequence that fills no whole segment is padded with steps of size 0,
    which leave the state as it is."""
    if by == "kernel":
        steps = pallas_selective_scan.STEPS
        whole = jnp.sum(delta.reshape(x.shape[0], -1, steps, x.shape[2]),
                        axis=2)
        y = pallas_selective_scan.selective_scan(x, delta, a, b, c,
                                                 interpret=interpret)
        return y + skip * x, jnp.min(jax.lax.stop_gradient(
            whole[..., None] * a))
    chunk = chunk or MAMBA_CHUNK
    segment = segment or MAMBA_SEGMENT
    batch, length, width = x.shape
    n = a.shape[1]
    segment = min(segment, -(-length // chunk) * chunk)
    pad = -length % segment
    n_seg, per = (length + pad) // segment, segment // chunk
    a_t = a.T                                           # [N, C]

    def split(v):   # [B, T, W] -> [segments, chunk, B, chunks a segment, W]
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        v = v.reshape(batch, n_seg, per, chunk, v.shape[-1])
        return jnp.transpose(v, (1, 3, 0, 2, 4))

    @jax.checkpoint
    def one_segment(state, xs):         # state [B, N, C]: entering it
        x_s, d_s, b_s, c_s = xs         # [chunk, B, per, C or N]

        def one_position(local, at):    # local [B, per, N, C]
            x_t, d_t, b_t, c_t = at
            local = jnp.exp(d_t[..., None, :] * a_t) * local \
                + (d_t * x_t)[..., None, :] * b_t[..., :, None]
            return local, jnp.sum(c_t[..., :, None] * local, axis=-2)

        ends, y_local = jax.lax.scan(
            one_position, jnp.zeros((batch, per, n, width), jnp.float32)
            + 0.0 * state[:, None], (x_s, d_s, b_s, c_s))
        run = jnp.cumsum(d_s, axis=0)               # [chunk, B, per, C]
        whole = run[-1][..., None, :] * a_t         # [B, per, N, C]: <= 0
        decay = jnp.exp(whole)
        entering = []
        for k in range(per):            # the chunks' end states, passed on
            entering.append(state)
            state = decay[:, k] * state + ends[:, k]
        entering = jnp.stack(entering, axis=1)      # [B, per, N, C]
        carried = jnp.sum(
            c_s[..., :, None] * jnp.exp(run[..., None, :] * a_t)
            * entering, axis=-2)                    # [chunk, B, per, C]
        return state, (y_local + carried, jnp.min(whole))

    state0 = jnp.zeros((batch, n, width), jnp.float32) + 0.0 * x[:, :1]
    _, (y, lows) = jax.lax.scan(one_segment, state0,
                                tuple(split(v) for v in (x, delta, b, c)))
    # [segments, chunk, B, per, C] -> [B, T, C]
    y = jnp.transpose(y, (2, 0, 3, 1, 4)).reshape(batch, -1, width)
    return y[:, :length] + skip * x, jnp.min(jax.lax.stop_gradient(lows))


@jax.named_scope("mamba")
def mamba_mixer(lp: Dict[str, jnp.ndarray], x: jnp.ndarray, *, eps: float,
                cdt: jnp.dtype, by: str = "xla"
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``Mamba(LN1(x))``: x [B, S, d] -> ([B, S, d], the scan's output y
    [B, S, C] (the skip term in, the gate not yet: what a memory unit
    reads), the scan's most negative whole-chunk log-decay). The step size's
    rank and the state's size are the leaves' shapes; ``by`` is
    ``scan_by``'s word."""
    xn = layer_norm(x, lp["norm1"], lp["norm1_b"], eps)
    u, z = jnp.split(_dot(xn, lp["mamba_w_in"], cdt), 2, axis=-1)
    u = jax.nn.silu(causal_conv(u, lp["mamba_conv"]) + lp["mamba_conv_b"])
    rank, n = lp["mamba_w_dt"].shape[0], lp["mamba_a_log"].shape[1]
    dbc = _dot(u, lp["mamba_w_x"], cdt)
    delta = jax.nn.softplus(
        _dot(dbc[..., :rank], lp["mamba_w_dt"], cdt) + lp["mamba_dt_bias"])
    y, low = selective_scan(
        u, delta, -jnp.exp(lp["mamba_a_log"]), dbc[..., rank:rank + n],
        dbc[..., rank + n:], lp["mamba_d"], by=by)
    return _dot(y * jax.nn.silu(z), lp["mamba_w_out"], cdt), y, low


@jax.named_scope("gmu")
def gmu(lp: Dict[str, jnp.ndarray], x: jnp.ndarray, memory: jnp.ndarray, *,
        eps: float, cdt: jnp.dtype) -> jnp.ndarray:
    """``(SiLU(LN1(x) W_1) * memory) W_2``: x [B, S, d], memory [B, S, C]
    -> [B, S, d]."""
    xn = layer_norm(x, lp["norm1"], lp["norm1_b"], eps)
    return _dot(jax.nn.silu(_dot(xn, lp["gmu_w1"], cdt)) * memory,
                lp["gmu_w2"], cdt)


@jax.named_scope("attn")
def diff_attention(lp: Dict[str, jnp.ndarray], x: jnp.ndarray, *,
                   mask: ScoreMask, layer: int, head_dim: int, eps: float,
                   cdt: jnp.dtype, scores_by: str = "xla",
                   shared: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
                   ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """``DiffAttn(LN1(x))`` at published layer ``layer`` under ``mask``:
    x [B, S, d] -> ([B, S, d], the keys and values [B, S, Hkv, D] it read,
    operands). ``shared``: another layer's keys and values, read in place of
    its own (a cross layer, which has no ``wk`` / ``wv``)."""
    b, s, _ = x.shape
    xn = layer_norm(x, lp["norm1"], lp["norm1_b"], eps)

    def heads(name):
        return (_dot(xn, lp["w" + name], cdt) + lp["b" + name]).reshape(
            b, s, -1, head_dim)

    k, v = shared if shared is not None else (
        _operand(heads("k"), cdt), _operand(heads("v"), cdt))
    pairs = k.shape[2] // 2                     # key pairs
    # query pair j = 2i + jj reads key pair i; its map p reads key 2i + p:
    # [B, S, (i, jj, p), D] -> [B, S, (i, p, jj), D], key head (i, p)'s two
    q = jnp.swapaxes(heads("q").reshape(b, s, pairs, -1, 2, head_dim), 3, 4)
    per_key = q.shape[4]
    # the pair's two value heads side by side, under both of its keys
    wide = jnp.repeat(v.reshape(b, s, pairs, 2 * head_dim), 2, axis=2)
    with jax.named_scope("attn_scores"):
        out = masked_scores(q.reshape(b, s, -1, head_dim), k, wide,
                            mask=mask, cdt=cdt, scores_by=scores_by)
    out = out.astype(jnp.float32).reshape(b, s, pairs, 2, per_key,
                                          2 * head_dim)
    lam_init = lambda_init(layer)
    lam = jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + lam_init
    o = rms_norm(out[:, :, :, 0] - lam * out[:, :, :, 1], lp["sub_norm"],
                 eps) * (1.0 - lam_init)
    return _dot(o.reshape(b, s, -1), lp["wo"], cdt) + lp["bo"], (k, v)


@jax.named_scope("mlp")
def mlp(lp: Dict[str, jnp.ndarray], h: jnp.ndarray, *, eps: float,
        cdt: jnp.dtype) -> jnp.ndarray:
    """``(SiLU(u W_g) * u W_u) W_2`` of ``u = LN2(h)``, ``[W_g | W_u]`` one
    matrix, the gate half first. The first product carries
    ``kimi_linear.MLP_KEPT``."""
    hn = layer_norm(h, lp["norm2"], lp["norm2_b"], eps)
    gate, up = jnp.split(checkpoint_name(
        _dot(hn, lp["mlp_w_gate_up"], cdt), MLP_KEPT), 2, axis=-1)
    return _dot(jax.nn.silu(gate) * up, lp["mlp_w_down"], cdt)


class Phi4Flash(KimiLinear):
    """Selective-scan / differential-attention decoder-decoder over
    ``hist_ids``; see the module's docstring."""

    name = "phi4_flash"
    tied_head = True
    #: no grouped product: the model has no experts
    kernel_scopes = ()
    _kinds = staticmethod(layer_kinds)
    score_mixers = ("window_attention", "full_attention", "cross_attention")

    def __init__(self, cfg: Any):
        super().__init__(cfg)
        self.step_notes = {}
        self.window = window(cfg.attn_window)

    def init_counts(self) -> common.State:
        return {DECAY_MIN: jnp.zeros((), jnp.float32)}

    def _score_heads(self) -> Tuple[int, int]:
        """... a key's pair of values side by side."""
        return self.cfg.attn_q_heads, 2 * self.cfg.attn_head_dim

    def _init_layer(self, rng: jax.Array, mixer: str, ffn: str
                    ) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        d, f, hd = cfg.embedding_size, cfg.dense_mlp_width, cfg.attn_head_dim
        keys = iter(jax.random.split(rng, 16))

        def glorot(*shape):
            return common.glorot_uniform(next(keys), shape)

        def ones(*shape):
            return jnp.ones(shape, jnp.float32)

        def zeros(*shape):
            return jnp.zeros(shape, jnp.float32)

        lp = {"norm1": ones(d), "norm1_b": zeros(d), "norm2": ones(d),
              "norm2_b": zeros(d), "mlp_w_gate_up": glorot(d, 2 * f),
              "mlp_w_down": glorot(f, d)}
        if mixer == "mamba":
            c, n, rank = (cfg.mamba_expand * d, cfg.mamba_state,
                          cfg.mamba_dt_rank)
            step = jnp.exp(jax.random.uniform(
                next(keys), (c,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            lp.update({
                "mamba_w_in": glorot(d, 2 * c),
                "mamba_conv": glorot(cfg.mamba_conv, c),
                "mamba_conv_b": zeros(c),
                "mamba_w_x": glorot(c, rank + 2 * n),
                "mamba_w_dt": glorot(rank, c),
                # a step size log-uniform in [0.001, 0.1], through the
                # inverse of softplus; a decay rate 1 .. N over the states
                "mamba_dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "mamba_a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=jnp.float32)), (c, n)),
                "mamba_d": ones(c), "mamba_w_out": glorot(c, d)})
        elif mixer == "gmu":
            c = cfg.mamba_expand * d
            lp.update({"gmu_w1": glorot(d, c), "gmu_w2": glorot(c, d)})
        else:
            q, kv = cfg.attn_q_heads * hd, cfg.attn_kv_heads * hd
            lp.update({"wq": glorot(d, q), "bq": zeros(q),
                       "wo": glorot(q, d), "bo": zeros(d),
                       "sub_norm": ones(2 * hd)})
            lp.update({name: 0.1 * jax.random.normal(next(keys), (hd,))
                       for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                                    "lambda_k2")})
            if mixer != "cross_attention":
                lp.update({"wk": glorot(d, kv), "bk": zeros(kv),
                           "wv": glorot(d, kv), "bv": zeros(kv)})
        return lp

    def init(self, rng: jax.Array) -> Tuple[common.Params, common.State]:
        params, counts = super().init(rng)
        params["final_norm_b"] = jnp.zeros_like(params["final_norm"])
        return params, counts

    def _paths(self, ids: jnp.ndarray, one_device: bool) -> Dict[str, str]:
        cfg = self.cfg
        seq = ids.shape[1]
        scores_by = attn_scores_by(seq, cfg.attn_head_dim,
                                   one_device=one_device)
        by = scan_by(cfg.mamba_expand * cfg.embedding_size, seq,
                     one_device=one_device)
        mixers = {mixer for mixer, _ in self.kinds}
        if "mamba" in mixers:
            self.step_notes["mamba_scan"] = scan_note(by)
        # the blocks the kernel visits under each mask the stack has
        masks = {"attn_window_blocks": (self.window, {"window_attention"}),
                 "attn_score_blocks": (causal, {"full_attention",
                                                "cross_attention"})}
        self.step_notes.update(masks_notes(
            scores_by, {key: mask for key, (mask, of) in masks.items()
                        if of & mixers},
            seq, cfg.attn_q_heads // cfg.attn_kv_heads))
        return {"scores_by": scores_by, "scan_by": by}

    def _layer(self, mixer: str, ffn: str, x: jnp.ndarray,
               lp: Dict[str, jnp.ndarray],
               read: Optional[Dict[str, jnp.ndarray]] = None, *,
               layer: int = 0, scores_by: str = "xla", scan_by: str = "xla"
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray],
                          Dict[str, jnp.ndarray]]:
        """One block at published layer ``layer``: ``h = x +
        Mixer(LN1(x))``, ``h + MLP(LN2(h))`` -> (the stream, the layer's
        counts, what it leaves for later layers by ``LEAVES``' names).
        ``read`` is what it reads of earlier layers', by ``READS``'."""
        cfg = self.cfg
        # (the barrier: ``models.sdar_moe.SdarMoE.hidden``)
        lp = jax.lax.optimization_barrier(lp)
        eps = cfg.rms_norm_eps
        counts, left = {}, {}
        if mixer == "mamba":
            y, left["memory"], counts[DECAY_MIN] = mamba_mixer(
                lp, x, eps=eps, cdt=self.cdt, by=scan_by)
        elif mixer == "gmu":
            y = gmu(lp, x, read["memory"], eps=eps, cdt=self.cdt)
        else:
            y, (k, v) = diff_attention(
                lp, x, layer=layer, head_dim=cfg.attn_head_dim, eps=eps,
                cdt=self.cdt, scores_by=scores_by,
                mask=self.window if mixer == "window_attention" else causal,
                shared=(read["shared_k"], read["shared_v"])
                if mixer == "cross_attention" else None)
            if mixer == "full_attention":
                left = {"shared_k": k, "shared_v": v}
        h = x + y
        return h + mlp(lp, h, eps=eps, cdt=self.cdt), counts, left

    def _run_layer(self, i: int, kind: Tuple[str, str], x: jnp.ndarray,
                   lp: Dict[str, jnp.ndarray], left: Dict[str, jnp.ndarray],
                   paths: Dict[str, str],
                   keeps: Optional[Dict[str, bool]] = None):
        read = {name: left[name] for name in READS.get(kind[0], ())}
        x, counts, leaves = jax.checkpoint(
            functools.partial(self._layer, *kind,
                              layer=self.cfg.first_layer + i, **paths),
            policy=layer_policy(keeps or {}))(x, lp, read)
        return x, counts, {**left, **leaves}

    @jax.named_scope("head")
    def logits(self, params: common.Params, h: jnp.ndarray) -> jnp.ndarray:
        """[..., d] of the last residual stream -> [..., V]: the final
        LayerNorm and the token table's real rows, transposed."""
        hn = layer_norm(h, params["final_norm"], params["final_norm_b"],
                        self.cfg.rms_norm_eps)
        return _dot(hn, params["tok_emb"][: self.cfg.feature_size].T,
                    self.cdt)
