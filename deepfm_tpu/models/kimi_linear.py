"""Hybrid linear-attention mixture-of-experts decoder (``--model
kimi_linear``).

A pre-norm residual decoder (RMSNorm) trained on next-token cross-entropy,
whose mixers alternate: ``--attn_every - 1`` layers of *Kimi Delta
Attention* (KDA: a per-channel-gated delta-rule recurrence with a matrix
state a head; Kimi Linear, arXiv:2510.26692) to one of multi-head latent
attention without positional encoding (MLA, NoPE); the first
``--dense_layers`` layers' feed-forward is a dense SwiGLU MLP, every later
layer's an expert layer (sigmoid-scored router, ``--moe_top_k`` a token,
renormalised and scaled) beside a shared expert every token passes.
``benchmark/reference_kimi_linear.py`` holds the equations; this is the
program's form of them. The expert layer, the router, the chunked attention,
the head's loss and the rounding of operands are ``models.sdar_moe``'s, by
import; ``models.solar_open2`` is this stack with another full layer and a
write strength to 2 (``kda_mixer``'s ``beta_scale``), by inheritance.

What it reads of a batch: ``hist_ids`` [B, L], the sequence's tokens, which
ride the record's history list (``--history_max_len L``); the token table is
the ``EmbeddingSchema`` entry ``tok_emb`` (``--feature_size`` rows of
``--embedding_size``, the model's width). The loss of a sequence is the mean
over positions 0 .. L-2 of the cross-entropy of the next token; the model
owns it (``owns_loss``).

**A share of a layer.** The layer is told what it holds: ``--kda_heads`` KDA
heads and ``--attn_q_heads`` MLA heads (their projections are here),
``--moe_experts_held`` experts from ``--moe_first_expert`` on. What every
chip of a layer computes alike is whole: the norms, the KDA gates'
bottlenecks (``kda_w_fa``, ``kda_w_ga``), MLA's latent projection and its
norm, the router, the shared expert, the dense MLP. The partial sums of the
mixers' ``wo`` and of the routed experts go on unreduced; no code stands in
for the absent chips.

**The delta-rule scan** (``kda_scan``). Per head, with g_t <= 0 the
per-channel log-decay and beta_t the write strength:
``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``,
``o_t = S_t^T q_t``. Computed a chunk of ``KDA_CHUNK`` positions at a time:
with G the cumulative log-decay inside a chunk, ``A[t,s] = sum_c k_tc k_sc
exp(G_tc - G_sc)`` (s < t) and ``B[t,s]`` likewise with q_t (s <= t), the
chunk's pseudo-values solve the unit lower-triangular system
``(I + Diag(beta) A) U = Diag(beta) (V - (exp(G) * K) S_0)`` and
``O = (exp(G) * Q) S_0 + B U``,
``S_end = Diag(exp G_end) S_0 + (K * exp(G_end - G))^T U``; only the passing
of S from chunk to chunk is sequential (a ``lax.scan``). No decay is ever
divided by: every exponent is a difference ``G_t - G_s`` with s <= t, taken
pairwise inside sub-chunks of ``KDA_SUB`` positions and through the
sub-chunk's own first cumulative sum between them, so each factor is at most
one whatever the decay (a chunk-wide ``exp(-G_s)`` overflows float32 from a
log-decay of -1.4 a position on). The backward pass is the forward's,
differentiated. ``kda_chunk_log_decay_min`` in the model state and the
step's metrics is the most negative ``G_end`` of the step.

**Which form runs where** (``kda_scan_by``: read from the backend, the
shapes and the mesh; no flag). On a TPU, with the sequence in whole chunks
of 64, heads of whole 128-lane lines and a step that is one device's
program, the same chunked mathematics at the same precisions is the two
Pallas kernels of ``ops/pallas_kda_scan`` (the state, the score matrices
and the inverse never leave VMEM; the backward kernel is ``jax.vjp`` of the
chunk, the chunks last to first; ``step_notes``: ``kernel chunk64``). Off a
TPU, across data replicas, at ragged lengths and narrower heads it is the
XLA form above (``chunk64/sub16``), which is also what the tests hold the
kernels to.

Memory: every layer is recomputed in the backward pass (``jax.checkpoint``);
a KDA layer whose scan is the kernels' keeps what the forward kernel hands
the backward one (the scan's output, the chunks' entering states and
inverses: 117 MB a layer at 8 heads x 8,192 positions) and is made again
around the scan, not through it (``_run_layer``). Two more things a layer
keeps where the device's memory holds them (``_keeps``). A layer whose
masked scores are the block kernel's (``score_mixers``; a stack derived from
this one, whose own latent attention is XLA's) keeps what that forward
kernel hands its backward kernels, the output in the operands' type and a
float32 log-sum-exp a query (``ops/block_attention.KEPT``: 134 + 2 MB a
layer at 32 heads of 128 over 16,384 positions), and is made again around
the kernel, which so runs once a layer and step. A layer also keeps the
float32 first products of its dense SwiGLU (``swiglu``'s gate and up
products, named ``MLP_KEPT``: 8 bytes a position and unit of width): the
backward pass reads them where it would make them again, and makes SiLU,
the gate's product with ``up`` and the down product's operand again from
them. How many layers keep each is ``sdar_moe.kept_by``'s, from the last
layer back, read from the device's memory limit, the parameters' bytes and
the step's positions (no flag), **the kernel's tensors first and the
products in what they leave**: a byte of the first buys about eight times
the time of a byte of the second (PERF.md section 6, PR 54). ``step_notes``
says both: ``attn_kept`` (``5/5 layers 0.68 GB``) and ``mlp_kept`` (``6/6
layers 4.03 GB``); off a TPU, or where the device says nothing of its
memory, no layer keeps anything.
The stack is a Python loop over layers of three shapes, not a scan.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import block_attention, pallas_kda_scan
from . import common, sdar_moe
from .graph import GraphModel
from .sdar_moe import (ScoreMask, _dot, _float32_bytes, _operand,
                       _operand_bytes, _scores_xla, expert_layer,
                       head_grad_by, head_grad_note, held_bytes, kept_by,
                       kept_note, layer_policy, moe_notes, moe_products_by,
                       moe_rows_by, rms_norm, route, weighted_nll)

#: The step's counts, in the model state and (by ``step_counts``) the metrics.
COUNT_NAMES = ("moe_pairs_held", "moe_pairs_over_buffer",
               "moe_expert_load_max", "moe_layer_pairs_max")
DECAY_MIN = "kda_chunk_log_decay_min"
#: Positions x heads of a step whose write strength passed 1 (the transition's
#: eigenvalue along k negative): counted where the model's strength can.
BETA_OVER_ONE = "kda_beta_over_one"
#: Positions a chunk of the delta-rule scan holds, and a sub-chunk inside
#: which decays are taken pair by pair (the kernels': their one statement).
KDA_CHUNK, KDA_SUB = pallas_kda_scan.CHUNK, pallas_kda_scan.SUB
#: epsilon of the L2 normalisation of q and k
L2_EPS = 1e-6
#: ``checkpoint_name`` of a dense SwiGLU's first products (``swiglu``'s gate
#: and up products, ``phi4_flash.mlp``'s one ``[gate | up]`` product; the
#: float32 results as SiLU reads them): a layer whose ``jax.checkpoint``
#: saves the name reads them in the backward pass and does not make them
#: again.
MLP_KEPT = "mlp_first_products"
_HIGHEST = jax.lax.Precision.HIGHEST


def _merged(name: str, least, largest, total):
    """How a count is merged over layers or replicas: a ``_min`` count's
    least (a scan's most negative chunk log-decay), a ``_max`` count's
    largest, any other's sum."""
    return least if name.endswith("_min") else (
        largest if name.endswith("_max") else total)


def layer_kinds(cfg: Any) -> Tuple[Tuple[str, str], ...]:
    """((mixer, feed-forward) of each layer): layer i (from 1) mixes by MLA
    where ``attn_every`` divides i and by KDA elsewhere; the first
    ``dense_layers`` feed forward through a dense MLP, the rest through the
    expert layer."""
    return tuple(("mla" if (i + 1) % cfg.attn_every == 0 else "kda",
                  "mlp" if i < cfg.dense_layers else "moe")
                 for i in range(cfg.decoder_layers))


def causal_conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution over the last ``w.shape[0]`` positions:
    x [B, S, C], w [K, C] -> ``y_t = sum_j w_j x_{t-K+1+j}``, positions before
    the first reading zero."""
    taps = w.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[j] for j in range(taps))


def _decayed_scores(x: jnp.ndarray, k: jnp.ndarray, gc: jnp.ndarray,
                    sub: int) -> jnp.ndarray:
    """``M[t, s] = sum_c x_tc k_sc exp(gc_tc - gc_sc)`` for s <= t inside a
    chunk (x, k, gc [..., C, D], gc the chunk's cumulative log-decay;
    entries with s > t are not meant to be read) -> [..., C, C], float32.
    Inside a sub-chunk the exponent is taken pair by pair; between
    sub-chunks it goes through the later one's reference (the cumulative
    sum just ahead of it), both factors at most one."""
    *lead, chunk, d = x.shape
    m = chunk // sub
    xs, ks, gs = (a.reshape(*lead, m, sub, d) for a in (x, k, gc))
    ref = jnp.concatenate([jnp.zeros_like(gs[..., :1, -1, :]),
                           gs[..., :-1, -1, :]], axis=-2)     # [..., m, D]
    x_dec = xs * jnp.exp(gs - ref[..., None, :])
    k_dec = k[..., None, :, :] * jnp.exp(jnp.minimum(
        ref[..., :, None, :] - gc[..., None, :, :], 0.0))     # [..., m, C, D]
    off = jnp.einsum("...itc,...isc->...its", x_dec, k_dec,
                     precision=_HIGHEST).reshape(*lead, chunk, chunk)
    pair = jnp.exp(jnp.minimum(
        gs[..., :, None, :] - gs[..., None, :, :], 0.0))  # [..., m, t, s, D]
    diag = jnp.sum(xs[..., :, None, :] * ks[..., None, :, :] * pair, axis=-1)
    diag = jnp.einsum("...its,ij->...itjs", diag,
                      jnp.eye(m, dtype=diag.dtype)).reshape(
                          *lead, chunk, chunk)
    sub_of = jnp.arange(chunk) // sub
    return jnp.where(sub_of[:, None] == sub_of[None, :], diag, off)


def kda_scan_by(length: int, head_dim: int, *, one_device: bool = True,
                backend: Optional[str] = None) -> str:
    """``kernel`` where the scan's kernels apply
    (``ops/pallas_kda_scan.supported``: a TPU backend, positions in whole
    chunks of 64, heads of whole 128-lane lines; and a step that is one
    device's program, as ``sdar_moe.attn_scores_by`` asks), else ``xla``:
    read from the backend, the shapes and the mesh."""
    return "kernel" if one_device and pallas_kda_scan.supported(
        length, head_dim, backend) else "xla"


def kda_scan_note(by: str) -> str:
    """What ``step_notes`` says of the scan's form."""
    return (f"kernel chunk{KDA_CHUNK}" if by == "kernel"
            else f"chunk{KDA_CHUNK}/sub{KDA_SUB}")


@jax.named_scope("kda_scan")
def kda_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
             beta: jnp.ndarray, *, cdt: jnp.dtype, chunk: int = KDA_CHUNK,
             sub: int = KDA_SUB, by: str = "xla", interpret: bool = False
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The gated delta-rule recurrence from a zero state, chunk by chunk (the
    module's docstring): q, k, g [B, L, H, Dk], v [B, L, H, Dv], beta
    [B, L, H], float32 -> (o [B, L, H, Dv] float32, the most negative
    cumulative log-decay a chunk held). Decays, the pairwise scores and the
    triangular solve are float32; the products with the state take operands
    of the compute precision. ``by`` is ``kda_scan_by``'s word for what
    computes it; the kernels take no other ``chunk`` and ``sub`` than these."""
    b, length, h, _ = q.shape
    if by == "kernel":
        assert (chunk, sub) == (KDA_CHUNK, KDA_SUB), (chunk, sub)
        whole = jnp.sum(g.reshape(b, -1, chunk, *g.shape[2:]), axis=2)
        o = pallas_kda_scan.kda_scan(q, k, v, g, beta, cdt=cdt,
                                     interpret=interpret)
        return o, jnp.min(jax.lax.stop_gradient(whole))
    pad = -length % chunk
    n = (length + pad) // chunk

    def chunks(x):      # [B, L, H, D] -> [B, H, N, C, D], zeros past L
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return jnp.moveaxis(x.reshape(b, n, chunk, h, x.shape[-1]), 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta[..., None]))
    gc = jnp.cumsum(g, axis=-2)
    g_end = gc[..., -1:, :]
    t_index = jnp.arange(chunk)
    before = t_index[:, None] > t_index[None, :]
    a = jnp.where(before, _decayed_scores(k, k, gc, sub), 0.0) * beta
    reads = jnp.where(t_index[:, None] >= t_index[None, :],
                      _decayed_scores(q, k, gc, sub), 0.0)
    decay = jnp.exp(gc)
    # (I + Diag(beta) A)^-1 applied to Diag(beta) [exp(G) * K, V]
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(chunk, dtype=a.dtype),
        beta * jnp.concatenate([k * decay, v], axis=-1),
        lower=True, unit_diagonal=True)
    w, u0 = solved[..., : k.shape[-1]], solved[..., k.shape[-1]:]
    k_end = k * jnp.exp(g_end - gc)

    def mm(spec, x, y):
        return jnp.einsum(spec, _operand(x, cdt), _operand(y, cdt),
                          preferred_element_type=jnp.float32)

    def one_chunk(state, xs):       # state [B, H, Dk, Dv]
        w_n, u0_n, q_n, reads_n, k_end_n, end_n = xs
        u = u0_n - mm("bhck,bhkv->bhcv", w_n, state)
        o = mm("bhck,bhkv->bhcv", q_n, state) + mm("bhcs,bhsv->bhcv",
                                                   reads_n, u)
        return end_n[..., None] * state + mm("bhck,bhcv->bhkv", k_end_n,
                                             u), o

    over_chunks = [jnp.moveaxis(x, 2, 0) for x in (
        w, u0, q * decay, reads, k_end, jnp.exp(g_end[..., 0, :]))]
    # (zeros made of the inputs: across data replicas the carry varies as
    # they do)
    state0 = jnp.einsum("bhk,bhv->bhkv", k[:, :, 0, 0] * 0.0,
                        v[:, :, 0, 0] * 0.0)
    _, o = jax.lax.scan(one_chunk, state0, over_chunks)
    o = jnp.transpose(o, (1, 0, 3, 2, 4))               # [B, N, C, H, Dv]
    o = o.reshape(b, n * chunk, h, -1)[:, :length]
    return o, jnp.min(jax.lax.stop_gradient(g_end))


@jax.named_scope("kda")
def kda_mixer(lp: Dict[str, jnp.ndarray], x: jnp.ndarray, *, head_dim: int,
              eps: float, cdt: jnp.dtype, beta_scale: float = 1.0,
              scan_by: str = "xla"
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The held heads' part of ``KDA(RMSNorm(x))``: x [B, S, d] ->
    ([B, S, d] (``kda_wo``'s sum over the held heads, unreduced), the
    mixer's counts: the scan's most negative chunk log-decay and, where the
    write strength can pass 1, how often it did). ``lp['kda_a_log']`` [H]
    says how many heads are held. The write strength is ``beta_scale *
    sigmoid(xn kda_w_b)``: 1 keeps the transition ``I - beta k k^T``'s
    eigenvalue along k in (0, 1) (Kimi-Linear), 2 lets it reach -1
    (``models.solar_open2``: ``kda_allow_neg_eigval``). ``scan_by`` is
    ``kda_scan_by``'s word."""
    b, s, _ = x.shape
    xn = rms_norm(x, lp["norm1"], eps)

    def heads(y):
        return y.reshape(b, s, -1, head_dim)

    def conv_in(name):
        return heads(jax.nn.silu(causal_conv(
            _dot(xn, lp["kda_w" + name], cdt), lp["kda_conv_" + name])))

    def unit(y):
        return y * jax.lax.rsqrt(
            jnp.sum(jnp.square(y), axis=-1, keepdims=True) + L2_EPS)

    q = unit(conv_in("q")) * head_dim ** -0.5
    k, v = unit(conv_in("k")), conv_in("v")
    g = -jnp.exp(lp["kda_a_log"])[:, None] * heads(jax.nn.softplus(
        _dot(_dot(xn, lp["kda_w_fa"], cdt), lp["kda_w_fb"], cdt)
        + lp["kda_dt_bias"]))
    beta = jax.nn.sigmoid(_dot(xn, lp["kda_w_b"], cdt))
    counts = {}
    if beta_scale != 1.0:
        beta = beta_scale * beta
        counts[BETA_OVER_ONE] = jnp.sum(
            jax.lax.stop_gradient(beta) > 1.0, dtype=jnp.int32)
    o, counts[DECAY_MIN] = kda_scan(q, k, v, g, beta, cdt=cdt, by=scan_by)
    gate = jax.nn.sigmoid(heads(
        _dot(_dot(xn, lp["kda_w_ga"], cdt), lp["kda_w_gb"], cdt)))
    y = rms_norm(o, lp["kda_out_norm"], eps) * gate
    return _dot(y.reshape(b, s, -1), lp["kda_wo"], cdt), counts


#: A query reads the keys at and before its own position.
causal = ScoreMask(("causal",), lambda q, k: k <= q)


def window(width: int) -> ScoreMask:
    """A query reads its own position and the ``width - 1`` before it."""
    return ScoreMask(("window", width),
                     lambda q, k: (k <= q) & (q - k < width))


@jax.named_scope("attn")
def mla_mixer(lp: Dict[str, jnp.ndarray], x: jnp.ndarray, *, head_dim: int,
              rope_dim: int, eps: float, cdt: jnp.dtype) -> jnp.ndarray:
    """The held heads' part of ``MLA(RMSNorm(x))`` without positional
    encoding: x [B, S, d] -> [B, S, d]. A head's key is its own
    ``head_dim`` columns of the expanded latent beside ``rope_dim`` columns
    every head shares (not rotated); its value ``head_dim`` columns of the
    expanded latent. The scores are ``models.sdar_moe``'s chunked XLA path
    under the causal mask."""
    b, s, _ = x.shape
    xn = rms_norm(x, lp["norm1"], eps)
    q = _dot(xn, lp["mla_wq"], cdt).reshape(b, s, -1, head_dim + rope_dim)
    h = q.shape[2]
    kva = _dot(xn, lp["mla_w_kva"], cdt)
    latent = rms_norm(kva[..., :-rope_dim], lp["mla_kv_norm"], eps)
    kv = _dot(latent, lp["mla_w_kvb"], cdt).reshape(b, s, h, 2 * head_dim)
    shared = jnp.broadcast_to(kva[..., None, -rope_dim:],
                              (b, s, h, rope_dim))
    k = jnp.concatenate([kv[..., :head_dim], shared], axis=-1)
    out = _scores_xla(_operand(q, cdt)[:, :, :, None], _operand(k, cdt),
                      _operand(kv[..., head_dim:], cdt), cdt=cdt,
                      mask=causal)
    return _dot(out, lp["mla_wo"], cdt)


@jax.named_scope("mlp")
def swiglu(lp: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, *,
           eps: float, cdt: jnp.dtype) -> jnp.ndarray:
    """``E(RMSNorm(x; norm2))``, ``E(x) = (SiLU(x W_g) * x W_u) W_d``, whole
    on every chip: the dense MLP (``mlp_``) or the shared expert
    (``shared_``). The two first products carry ``MLP_KEPT``."""
    xn = rms_norm(x, lp["norm2"], eps)
    gate, up = (checkpoint_name(_dot(xn, lp[prefix + w], cdt), MLP_KEPT)
                for w in ("w_gate", "w_up"))
    return _dot(jax.nn.silu(gate) * up, lp[prefix + "w_down"], cdt)


class KimiLinear(GraphModel):
    """Hybrid KDA / MLA mixture-of-experts decoder over ``hist_ids``; see the
    module's docstring."""

    name = "kimi_linear"
    uses_history = True
    owns_loss = True
    #: (``models.sdar_moe.SdarMoE.kernel_scopes``)
    kernel_scopes = (("ragged-dot", "moe"),)
    #: Whether the head's matrix is the token table (no ``head`` leaf).
    tied_head = False
    #: What the looked-up rows are multiplied by on their way into the
    #: stream (``models.afmoe``: sqrt(d)); 1, nothing.
    embed_scale = 1.0
    #: cfg -> ((mixer, feed-forward) of each layer); a model with another
    #: pattern names its own
    _kinds = staticmethod(layer_kinds)
    #: The mixers that mix by attention scores (a model with others names
    #: its own): where ``_paths`` says the scores are the block kernel's
    #: (``scores_by``; this model's are XLA's and it says nothing), their
    #: layers may keep the kernel's output and log-sum-exp (``_keeps``).
    score_mixers: Tuple[str, ...] = ("mla",)

    def __init__(self, cfg: Any):
        super().__init__(cfg)
        self.cdt = jnp.dtype(cfg.compute_dtype)
        self.kinds = self._kinds(cfg)
        #: the kinds of every block a step runs: the stack's layers and, in
        #: a model that has one, what follows them (``models.glm4_moe_lite``:
        #: the multi-token-prediction module's block)
        self.block_kinds = self.kinds
        #: What the traced step is made of, said beside its counts on
        #: ``train.log_sync`` while tracing is on (``hidden`` adds the expert
        #: layers' ``sdar_moe.moe_notes``).
        self.step_notes: Dict[str, str] = {}
        self.route_by = functools.partial(
            route, score=jax.nn.sigmoid, scale=cfg.moe_route_scale)

    def embedding_param_names(self) -> Tuple[str, ...]:
        return ("tok_emb",)

    def init_counts(self) -> common.State:
        return {**{n: jnp.zeros((), jnp.int32) for n in COUNT_NAMES},
                DECAY_MIN: jnp.zeros((), jnp.float32)}

    def step_counts(self, model_state: common.State
                    ) -> Dict[str, jnp.ndarray]:
        """The counts a step's metrics carry beside its loss."""
        return dict(model_state)

    def _init_mixer(self, mixer: str, glorot, keys) -> Dict[str, jnp.ndarray]:
        """The leaves of a mixer of kind ``mixer``: ``glorot(*shape)`` draws
        a matrix, ``keys`` yields further keys."""
        cfg = self.cfg
        d, hd = cfg.embedding_size, cfg.attn_head_dim
        if mixer == "kda":
            h, kd = cfg.kda_heads, cfg.kda_head_dim
            step = jnp.exp(jax.random.uniform(
                next(keys), (h * kd,), jnp.float32,
                jnp.log(1e-3), jnp.log(1e-1)))
            return {
                "kda_wq": glorot(d, h * kd), "kda_wk": glorot(d, h * kd),
                "kda_wv": glorot(d, h * kd),
                "kda_conv_q": glorot(cfg.kda_conv, h * kd),
                "kda_conv_k": glorot(cfg.kda_conv, h * kd),
                "kda_conv_v": glorot(cfg.kda_conv, h * kd),
                "kda_w_fa": glorot(d, kd), "kda_w_fb": glorot(kd, h * kd),
                # a step size log-uniform in [0.001, 0.1], through the
                # inverse of softplus; a decay rate uniform in [1, 16]
                "kda_dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "kda_a_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), jnp.float32, 1.0, 16.0)),
                "kda_w_b": glorot(d, h),
                "kda_w_ga": glorot(d, kd), "kda_w_gb": glorot(kd, h * kd),
                "kda_out_norm": jnp.ones((kd,), jnp.float32),
                "kda_wo": glorot(h * kd, d)}
        h, rope, latent = (cfg.attn_q_heads, cfg.mla_rope_dim,
                           cfg.mla_latent_dim)
        return {
            "mla_wq": glorot(d, h * (hd + rope)),
            "mla_w_kva": glorot(d, latent + rope),
            "mla_kv_norm": jnp.ones((latent,), jnp.float32),
            "mla_w_kvb": glorot(latent, h * 2 * hd),
            "mla_wo": glorot(h * hd, d)}

    def _init_layer(self, rng: jax.Array, mixer: str, ffn: str
                    ) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        d = cfg.embedding_size
        keys = iter(jax.random.split(rng, 24))

        def glorot(*shape):
            return common.glorot_uniform(next(keys), shape)

        lp = {"norm1": jnp.ones((d,), jnp.float32),
              "norm2": jnp.ones((d,), jnp.float32),
              **self._init_mixer(mixer, glorot, keys)}
        if ffn == "mlp":
            f = cfg.dense_mlp_width
            lp.update({"mlp_w_gate": glorot(d, f), "mlp_w_up": glorot(d, f),
                       "mlp_w_down": glorot(f, d)})
        else:
            f, held, fs = (cfg.moe_expert_width, cfg.moe_experts_held,
                           cfg.moe_shared_width)
            lp.update({
                "router": glorot(d, cfg.moe_experts),
                "w_gate": glorot(held, d, f), "w_up": glorot(held, d, f),
                "w_down": glorot(held, f, d)})
            if fs:      # (a model without a shared expert: lfm2_moe)
                lp.update({"shared_w_gate": glorot(d, fs),
                           "shared_w_up": glorot(d, fs),
                           "shared_w_down": glorot(fs, d)})
        return lp

    def init(self, rng: jax.Array) -> Tuple[common.Params, common.State]:
        cfg = self.cfg
        d = cfg.embedding_size
        k_emb, k_head, *k_layers = jax.random.split(rng, 2 + len(self.kinds))
        params = {
            "tok_emb": self.emb.init_entry(k_emb, (d,)),
            "layers": {str(i): self._init_layer(k, *kind) for i, (k, kind)
                       in enumerate(zip(k_layers, self.kinds))},
            "final_norm": jnp.ones((d,), jnp.float32),
        }
        if not self.tied_head:
            params["head"] = common.glorot_uniform(
                k_head, (d, cfg.feature_size))
        return params, self.init_counts()

    def _moe_paths(self, ids: jnp.ndarray, one_device: bool
                   ) -> Dict[str, str]:
        """``moe_rows_by``'s and ``moe_products_by``'s words for the step of
        ``ids`` [B, L], as ``expert_layer``'s keywords; ``step_notes`` is
        told."""
        cfg = self.cfg
        width, hidden = cfg.embedding_size, cfg.moe_expert_width
        rows_by = moe_rows_by(width, ids.size, cfg.moe_pair_capacity,
                              one_device=one_device)
        products_by = moe_products_by(width, hidden, cfg.moe_pair_capacity,
                                      one_device=one_device)
        self.step_notes.update(moe_notes(
            rows_by, cfg.moe_pair_capacity,
            sum(ffn == "moe" for _, ffn in self.block_kinds), products_by,
            width, hidden))
        return {"rows_by": rows_by, "products_by": products_by}

    def _paths(self, ids: jnp.ndarray, one_device: bool) -> Dict[str, str]:
        """What the step of ``ids`` [B, L] is made of where the code picks
        from what it can see, as ``_layer``'s keywords (``_moe_paths``'s; a
        model's further ones go to its ``_mixer``); ``step_notes`` is told."""
        self.step_notes["mla_scores"] = "xla"
        return {**self._moe_paths(ids, one_device),
                "scan_by": self._scan_by(ids, one_device)}

    def _scan_by(self, ids: jnp.ndarray, one_device: bool) -> str:
        """``kda_scan_by``'s word for the step of ``ids`` [B, L];
        ``step_notes`` is told."""
        by = kda_scan_by(ids.shape[1], self.cfg.kda_head_dim,
                         one_device=one_device)
        self.step_notes["kda_scan"] = kda_scan_note(by)
        return by

    def _head_params(self, params: common.Params) -> List[Any]:
        """What each head pass of a step reads (``weighted_nll``'s
        ``logits_of``: final norm and head matrix), a pass an entry."""
        return [(params["final_norm"],
                 params["tok_emb" if self.tied_head else "head"])]

    def _head_notes(self, params: common.Params, ids: jnp.ndarray
                    ) -> Dict[str, str]:
        """What ``step_notes`` says of the head passes of the step of
        ``ids`` [B, L] (``head_grad``; a second pass's under its scope's
        name)."""
        return {name: head_grad_note(by, ids.shape[0], size)
                for name, (by, size) in zip(
                    ("head_grad", "mtp_head_grad"),
                    self._head_grads(params, ids))}

    def _head_grads(self, params: common.Params, ids: jnp.ndarray
                    ) -> List[Tuple[str, int]]:
        """(``sdar_moe.head_grad_by``'s word, the float32 bytes of what the
        pass reads) of each head pass of the step of ``ids`` [B, L]."""
        sizes = [_float32_bytes(read) for read in self._head_params(params)]
        return [(head_grad_by(ids.shape[0], size,
                              sdar_moe.device_memory_bytes(),
                              one_device=not jax.typeof(ids).vma), size)
                for size in sizes]

    def _held_bytes(self, params: common.Params, ids: jnp.ndarray) -> int:
        """``sdar_moe.held_bytes`` of the step of ``ids`` [B, L]."""
        return held_bytes(params, self.cfg.optimizer,
                          self._head_grads(params, ids), ids.shape[0])

    def _score_heads(self) -> Tuple[int, int]:
        """(query heads, a value's width) of a layer's masked scores."""
        return self.cfg.attn_q_heads, self.cfg.attn_head_dim

    def _keeps(self, params: common.Params, ids: jnp.ndarray,
               paths: Dict[str, str]) -> Tuple[Dict[str, bool], ...]:
        """What each block (``block_kinds``) of the step of ``ids`` [B, L]
        keeps for the backward pass, as ``layer_policy``'s argument:
        ``block_attention.KEPT``, the attention kernel's output and
        log-sum-exp, where its scores are the kernel's (``paths``'
        ``scores_by``); ``MLP_KEPT``, its SwiGLU's first products, where it
        has a dense SwiGLU. The kernel's tensors are placed first, the
        products in the room they leave (``kept_by``, each from the last
        block back); ``step_notes`` is told both (``attn_kept``,
        ``mlp_kept``)."""
        cfg = self.cfg
        heads, value = self._score_heads()
        scores = block_attention.kept_bytes(ids.size * heads, value,
                                            _operand_bytes(self.cdt))
        limit = sdar_moe.device_memory_bytes()
        keeps = [{} for _ in self.block_kinds]

        def place(name, note, sizes, limit, held):
            """``name`` kept in as many of the blocks that have ``sizes``
            of it as find room beside ``held`` -> the bytes placed."""
            have = [i for i, size in enumerate(sizes) if size]
            kept = kept_by([sizes[i] for i in have], positions=ids.size,
                           limit=limit, held=held)
            keeping = have[len(have) - kept:]
            placed = sum(sizes[i] for i in keeping)
            self.step_notes[note] = kept_note(kept, len(have), placed)
            for i, keep in enumerate(keeps):
                keep[name] = i in keeping
            return placed

        held = self._held_bytes(params, ids)
        held += place(
            block_attention.KEPT, "attn_kept",
            [scores * (mixer in self.score_mixers)
             for mixer, _ in self.block_kinds],
            limit if paths.get("scores_by") == "kernel" else 0, held)
        # float32 gate and up products a position
        place(MLP_KEPT, "mlp_kept",
              [8 * ids.size * (cfg.dense_mlp_width if ffn == "mlp"
                               else cfg.moe_shared_width)
               for _, ffn in self.block_kinds], limit, held)
        return tuple(keeps)

    def _mixer(self, mixer: str, lp: Dict[str, jnp.ndarray], x: jnp.ndarray,
               scan_by: str = "xla"
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """``Mixer(RMSNorm(x))`` of a layer of kind ``mixer`` -> (the held
        heads' part, the mixer's counts). ``scan_by``: ``_paths``' word."""
        cfg = self.cfg
        if mixer == "kda":
            return kda_mixer(lp, x, head_dim=cfg.kda_head_dim,
                             eps=cfg.rms_norm_eps, cdt=self.cdt,
                             scan_by=scan_by)
        return mla_mixer(lp, x, head_dim=cfg.attn_head_dim,
                         rope_dim=cfg.mla_rope_dim, eps=cfg.rms_norm_eps,
                         cdt=self.cdt), {}

    def _layer(self, mixer: str, ffn: str, x: jnp.ndarray,
               lp: Dict[str, jnp.ndarray], rows_by: str = "xla",
               products_by: str = "xla", **paths
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """One block: ``h = x + Mixer(RMSNorm(x))``,
        ``h + FFN(RMSNorm(h))`` -> (the stream, the layer's counts). Where
        the layer has the leaves, a sublayer's output passes a norm of its
        own before it is added (``norm1_post`` the mixer's, ``norm2_post``
        the feed-forward's whole sum: ``models.afmoe``). ``paths`` is what
        ``_paths`` says beyond the expert layer's ``rows_by`` and
        ``products_by``, the mixer's."""
        cfg = self.cfg
        # (the barrier: ``models.sdar_moe.SdarMoE.hidden``)
        lp = jax.lax.optimization_barrier(lp)
        eps = cfg.rms_norm_eps
        y, counts = self._mixer(mixer, lp, x, **paths)
        if "norm1_post" in lp:
            with jax.named_scope("attn"):
                y = rms_norm(y, lp["norm1_post"], eps)
        h = x + y
        if ffn == "mlp":
            y = swiglu(lp, "mlp_", h, eps=eps, cdt=self.cdt)
            if "norm2_post" in lp:
                with jax.named_scope("mlp"):
                    y = rms_norm(y, lp["norm2_post"], eps)
            return h + y, counts
        y, moe_counts = expert_layer(
            lp, h, top_k=cfg.moe_top_k, first_expert=cfg.moe_first_expert,
            capacity=cfg.moe_pair_capacity, eps=eps, cdt=self.cdt,
            route_by=self.route_by, rows_by=rows_by,
            products_by=products_by)
        counts = {**counts, **moe_counts}
        if "norm2_post" in lp:      # the norm of the routed and shared sum
            if "shared_w_gate" in lp:
                y = y + swiglu(lp, "shared_", h, eps=eps, cdt=self.cdt)
            with jax.named_scope("moe"):
                return h + rms_norm(y, lp["norm2_post"], eps), counts
        out = h + y
        if "shared_w_gate" in lp:
            out = out + swiglu(lp, "shared_", h, eps=eps, cdt=self.cdt)
        return out, counts

    def _run_layer(self, i: int, kind: Tuple[str, str], x: jnp.ndarray,
                   lp: Dict[str, jnp.ndarray], left: Dict[str, jnp.ndarray],
                   paths: Dict[str, str],
                   keeps: Optional[Dict[str, bool]] = None):
        """Layer ``i`` (of kind ``kind``, leaves ``lp``), made again in the
        backward pass -> (the stream, the layer's counts, what the layers so
        far leave for later ones to read, by name). ``left`` is what the
        earlier layers left; here no layer reads or leaves anything (a model
        whose layers read other layers' tensors hands them through its own:
        ``models.phi4_flash``). ``keeps``: ``_keeps``' word for this
        layer."""
        # (a KDA layer by the kernels keeps its scan's output, entering
        # states and inverses: the layer is made again around them, the scan
        # is not; a layer that keeps its attention kernel's output and
        # log-sum-exp or its SwiGLU's first products is made again around
        # those too)
        policy = layer_policy({
            pallas_kda_scan.KEPT: paths.get("scan_by") == "kernel",
            **(keeps or {})})
        x, counts = jax.checkpoint(functools.partial(
            self._layer, *kind, **paths), policy=policy)(x, lp)
        return x, counts, left

    def hidden(self, params: common.Params, ids: jnp.ndarray, *,
               shard_axis: Optional[str] = None,
               emb_rows: Optional[Dict[str, Any]] = None,
               emb_plan: Optional[Dict[str, Any]] = None,
               data_axis: Optional[str] = None,
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """ids [B, L] -> (the last residual stream [B, L, d], the layers'
        counts: sums, the ``_max`` ones' largest, the decay's least).
        ``data_axis`` names the mesh axis of a step across data replicas."""
        paths = self._paths(ids, one_device=data_axis is None)
        keeps = self._keeps(params, ids, paths)
        x = self._emb_lookup(params, "tok_emb", ids, shard_axis, emb_rows,
                             emb_plan).astype(jnp.float32)
        if self.embed_scale != 1.0:
            with jax.named_scope("embed"):
                x = x * jnp.float32(self.embed_scale)
        x, seen = self._run_layers(params, x, paths, keeps)
        return x, self._merged_counts(seen)

    def _run_layers(self, params: common.Params, x: jnp.ndarray,
                    paths: Dict[str, str], keeps: Sequence[Dict[str, bool]]
                    ) -> Tuple[jnp.ndarray, Dict[str, list]]:
        """The stack's layers over the looked-up rows x [B, L, d] -> (the
        last residual stream, the layers' counts: {count: [a value a
        layer]})."""
        seen: Dict[str, list] = {}
        left: Dict[str, jnp.ndarray] = {}
        for i, kind in enumerate(self.kinds):
            x, counts, left = self._run_layer(
                i, kind, x, params["layers"][str(i)], left, paths, keeps[i])
            for name, value in counts.items():
                seen.setdefault(name, []).append(value)
        return x, seen

    @staticmethod
    def _merged_counts(seen: Dict[str, list]) -> Dict[str, jnp.ndarray]:
        """The layers' counts as the step's: sums, the ``_max`` ones'
        largest, the ``_min`` ones' least."""
        return {name: _merged(name, jnp.min, jnp.max, jnp.sum)(
            jnp.stack(values)) for name, values in seen.items()}

    @jax.named_scope("head")
    def logits(self, params: common.Params, h: jnp.ndarray) -> jnp.ndarray:
        """[..., d] of the last residual stream -> [..., V]: final norm and
        head product; a tied head's matrix is the token table's real rows,
        transposed (the leaf's two uses sum in its gradient)."""
        hn = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        head = (params["tok_emb"][: self.cfg.feature_size].T
                if self.tied_head else params["head"])
        return _dot(hn, head, self.cdt)

    def _run(self, params, state, tokens, shard_axis, data_axis, emb):
        tokens = tokens.astype(jnp.int32)
        h, counts = self.hidden(params, tokens, shard_axis=shard_axis,
                                data_axis=data_axis, **emb)
        counts = {**self.init_counts(), **counts}
        if data_axis is not None:       # the replicas' counts, as one
            counts = {k: _merged(k, jax.lax.pmin, jax.lax.pmax, jax.lax.psum)(
                v, data_axis) for k, v in counts.items()}
        if "moe_pairs_over_buffer" in counts:   # (a model with experts)
            counts["moe_pairs_over_buffer"] = (
                state["moe_pairs_over_buffer"]
                + counts["moe_pairs_over_buffer"])
        return h, tokens, counts

    def apply(self, params: common.Params, state: common.State,
              feat_ids: jnp.ndarray, feat_vals: jnp.ndarray, *,
              train: bool, rng: Optional[jax.Array] = None,
              shard_axis: Optional[str] = None,
              data_axis: Optional[str] = None,
              emb_rows: Optional[Dict[str, Any]] = None,
              emb_plan: Optional[Dict[str, Any]] = None,
              hist_ids: Optional[jnp.ndarray] = None,
              hist_mask: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray, common.State]:
        """Logits [B, L, V]: position i's are of token i + 1."""
        h, _, counts = self._run(
            params, state, hist_ids, shard_axis, data_axis,
            {"emb_rows": emb_rows, "emb_plan": emb_plan})
        return self.logits(params, h), counts

    def per_example_loss(self, params: common.Params, state: common.State,
                         batch: Dict[str, jnp.ndarray], *, train: bool,
                         rng: Optional[jax.Array],
                         shard_axis: Optional[str] = None,
                         data_axis: Optional[str] = None, **emb
                         ) -> Tuple[jnp.ndarray, common.State]:
        """(loss a sequence [B], new state): the mean over positions
        0 .. L-2 of the next token's cross-entropy. The head runs over all L
        positions in whole chunks; the last one's weight is zero."""
        h, tokens, counts = self._run(
            params, state, batch["hist_ids"], shard_axis, data_axis, emb)
        length = tokens.shape[1]
        labels = jnp.roll(tokens, -1, axis=1)
        weight = jnp.broadcast_to(
            (jnp.arange(length) < length - 1).astype(jnp.float32),
            tokens.shape)
        self.step_notes.update(self._head_notes(params, tokens))
        per_seq = weighted_nll(functools.partial(self.logits, params), h,
                               labels, weight) / (length - 1)
        return per_seq, counts
