"""The gated delta-rule scan (``models/kimi_linear.py`` states it) on a TPU
with a chunk's whole work in VMEM: forward and backward as two Pallas
kernels behind a ``custom_vjp``.

Layout. q, k, g, v stay as the mixer lays them out, ``[B, L, H, D]`` viewed
``[B, L, H * D]``: a grid step's block is ``CHUNK`` positions of ``heads``
adjacent heads, a head a 128-lane column group, so no array is transposed on
its way in or out (XLA fuses the change of view into the ops that make q, k,
g and v). beta rides as ``[B, H / heads, L, heads]`` (one word a position
and head). The grid is (sequence, head group, chunk), the chunks in
sequence; the float32 state of every head of the group is a VMEM scratch the
chunk axis carries, held transposed, ``[Dv, Dk]``, so that a channel's decay
runs along the lanes.

A chunk (``_chunk``), every intermediate in VMEM:

* every log-decay the chunk needs is a sum of g's own terms (all <= 0), so
  every exponential is a factor of at most one, whatever the decay: no
  quotient by a decay, no exponent through a reference, no cap. Inside
  blocks of ``SUB`` = 16 and of 8, 4 and 2 positions (``FINE``) the sums
  from a block's start to a position and from a position to its block's end
  are one product of g with a stack of 0/1 matrices (no ``cumsum`` in a
  kernel) at full float32 precision; the decays inside a half of 32 and the
  whole chunk follow from the sub-chunks' as products with the exponentials
  of the sub-chunks' totals;
* both pairwise-decay score matrices ``sum_c x_tc k_sc exp(G_tc - G_sc)``
  (x = k, strictly below the diagonal: A; x = q, at and below: the reads),
  k's rows stacked over q's, are six masked products, one a size of block b
  = 32, 16, 8, 4, 2, 1: the pairs whose later position lies in the second
  block of b of a block of 2b and whose earlier one lies in its first. x is
  decayed from the second block's start to its position, k from its
  position to the first block's end: ``G_t - G_s`` split at the boundary
  between them, the two sums above. The reads' diagonal is ``q_t . k_t``.
  The XLA form takes the pairs inside a sub-chunk one by one (a
  ``[16, 16, 128]`` tensor of exponentials a sub-chunk) to the same end;
* ``(I + Diag(beta) A)^-1`` by blocks of 16 (``_unit_lower_inverse``; no
  ``solve_triangular`` in a kernel): the diagonal blocks' inverse P is the
  nilpotent product ``(I + N)(I + N^2)(I + N^4)(I + N^8)``, N = -D, and
  the blocks below them come back through ``M = P E`` (strictly
  block-lower, ``M^4 = 0``): ``(I - M)(I + M^2) P``; ten 64 x 64 products
  at full float32 precision, the forward kernel's largest part;
* the four products with the state and the state's update, operands of the
  compute precision, float32 accumulation.

Forward: a grid step is ``_chunk`` on each head of the group; the variant
that feeds the backward pass also writes the state entering every chunk
(``[B, H, L / CHUNK, Dv, Dk]`` float32: 67 MB at 8 heads x 8,192 positions)
and the chunk's inverse (``[..., 64, 64]``: 17 MB). Backward, the chunks
last to first with the state's cotangent in the VMEM scratch: ``jax.vjp`` of
``_chunk`` traced inside the kernel, from the kept entering state, so the
chunk's forward is made again in VMEM but for the inverse, which is read
(``_kept_inverse``: its cotangent reaches A as ``-T^T G T^T``, two products
where the ten's transposes would be twenty); q, k, v, g and beta all get
their cotangents (g's through the cumulative sum and every exponential).
What the forward hands the backward is named ``KEPT``
(``checkpoint_name``): a ``jax.checkpoint`` around the layer that saves the
name makes the layer again around the scan and not the scan.

``supported`` says where the compiled kernels apply; ``interpret=True`` runs
them through the Pallas interpreter (the CPU tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: Positions of a chunk, of its half, and of a sub-chunk (a block of the
#: inverse; the XLA form's pair-by-pair block: ``kimi_linear.KDA_SUB``).
CHUNK = 64
HALF = 32
SUB = 16
#: The sizes of block under ``SUB`` that the scores' pairs are split by.
FINE = (8, 4, 2)
#: ``checkpoint_name`` of what the forward kernel hands the backward one (the
#: output, the entering states, the inverses).
KEPT = "kda_scan_kept"
_VMEM_LIMIT = 64 * 2 ** 20
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def supported(length: int, head_dim: int, backend: Optional[str] = None
              ) -> bool:
    """True where the compiled kernels apply: a TPU backend, positions in
    whole chunks and heads of whole 128-lane lines."""
    backend = jax.default_backend() if backend is None else backend
    return backend == "tpu" and length % CHUNK == 0 \
        and head_dim % LANES == 0


def heads_a_step(heads: int) -> int:
    """Heads a grid step works on: their chains are independent and a
    step's overhead is shared (4 a step take 7% less than 1; 8 no less)."""
    return next(n for n in (4, 2, 1) if heads % n == 0)


def _full(a, b, dims=_NN):
    """A product at full float32 precision."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=_F32)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular a [C, C], by blocks of
    ``SUB`` (the module's docstring)."""
    c = a.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (t == s).astype(_F32)
    diag = jnp.where(t // SUB == s // SUB, a, 0.0)
    n1 = -diag
    n2 = _full(n1, n1)
    n4 = _full(n2, n2)
    n8 = _full(n4, n4)
    p = _full(_full(_full(eye + n1, eye + n2), eye + n4), eye + n8)
    m = _full(p, a - diag)
    return _full(_full(eye - m, eye + _full(m, m)), p)


@jax.custom_vjp
def _kept_inverse(a, inverse):
    """``(I + a)^-1`` where the forward pass kept it: ``inverse`` itself,
    with the cotangent ``-T^T G T^T`` for a."""
    return inverse


def _kept_inverse_fwd(a, inverse):
    return inverse, inverse


def _kept_inverse_bwd(inverse, d):
    return (-_full(_full(inverse, d, _TN), inverse, _NT),
            jnp.zeros_like(inverse))


_kept_inverse.defvjp(_kept_inverse_fwd, _kept_inverse_bwd)


@functools.partial(jax.jit, static_argnames=("cdt",))
def _chunk(q, k, v, g, beta, state, kept=None, *, cdt):
    """One chunk of one head: q, k, g [C, Dk], v [C, Dv], beta [C, 1],
    ``state`` [Dv, Dk] (transposed: a channel's decay runs along its lanes)
    entering it -> (o [C, Dv], the state leaving it, ``(I + A)^-1``
    [C, C]); the module's docstring says how. ``kept``: that inverse from
    an earlier pass over the chunk, not made again. Jitted, so that every
    head, kernel and call site shares one trace of it (and of its
    transpose): traced a head and a site, the kernels added 5 s to a
    step's tracing."""
    cdt = jnp.dtype(cdt)
    c = q.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    sub_of = row // SUB

    # (under b where t and s lie in one block of b positions, b a power of 2)
    apart = t ^ s

    # log-decays (all <= 0) from the start of t's block to t and from t to
    # its block's end, for blocks of SUB and of every size in FINE: one
    # product with a stack of 0/1 matrices, so each is a sum of g's terms
    # and none a difference of sums
    sizes = (SUB, *FINE)
    sums = _full(jnp.concatenate(
        [((s <= t) & (apart < b)).astype(_F32) for b in sizes]
        + [((s > t) & (apart < b)).astype(_F32) for b in sizes], axis=0), g)
    sums = [sums[j * c:(j + 1) * c] for j in range(2 * len(sizes))]
    (g_sub, *to_t), (past_sub, *past_t) = (sums[:len(sizes)],
                                           sums[len(sizes):])

    # a half's and the chunk's follow from the sub-chunks' totals (rows), as
    # factors at most one
    total = [g_sub[j * SUB + SUB - 1: (j + 1) * SUB] for j in range(c // SUB)]
    whole = [jnp.exp(x) for x in total]

    def where_sub(*of, other=0.0):  # {sub-chunk: its rows' value}
        out = other
        for j, x in of:
            out = jnp.where(sub_of == j, x, out)
        return out

    in_sub = jnp.exp(g_sub)
    left_sub = jnp.exp(past_sub)
    in_half = in_sub * where_sub((1, whole[0]), (3, whole[2]), other=1.0)
    left_half = left_sub * where_sub((0, whole[1]), (2, whole[3]), other=1.0)
    decay = in_half * jnp.where(row >= HALF, whole[0] * whole[1], 1.0)
    left_chunk = left_half * jnp.where(row < HALF, whole[2] * whole[3], 1.0)

    # k's rows over q's: both score matrices by the same products, one a
    # size of block: the pairs whose later position lies in the second
    # block of b of a block of 2b and whose earlier one in its first, x
    # decayed from the second's start and k to the first's end
    x = jnp.concatenate([k, q], axis=0)

    def twice(f):
        return jnp.concatenate([f, f], axis=0)

    levels = [(HALF, x * twice(in_half), k * left_half),
              (SUB, x * twice(in_sub), k * left_sub)]
    levels += [(b, x * twice(jnp.exp(to_b)), k * jnp.exp(past_b))
               for b, to_b, past_b in zip(FINE, to_t, past_t)]
    levels += [(1, x * twice(jnp.exp(g)), k)]
    later = jnp.where(s < t, apart, 0)
    below, reads = 0.0, 0.0
    for b, x_b, k_b in levels:
        pairs = (later >= b) & (later < 2 * b)
        scores = _full(x_b, k_b, _NT)
        below = jnp.where(pairs, scores[:c], below)
        reads = jnp.where(pairs, scores[c:], reads)
    a = below * beta
    reads = jnp.where(t == s, jnp.sum(q * k, axis=1, keepdims=True), reads)

    inverse = (_unit_lower_inverse(a) if kept is None
               else _kept_inverse(a, kept))
    w = _full(inverse, beta * (k * decay))
    u0 = _full(inverse, beta * v)

    def mm(x, y, dims):
        return jax.lax.dot_general(x.astype(cdt), y.astype(cdt), dims,
                                   preferred_element_type=_F32)

    u = u0 - mm(w, state, _NT)
    o = mm(q * decay, state, _NT) + mm(reads, u, _NN)
    end = whole[0] * whole[1] * whole[2] * whole[3]            # [1, Dk]
    return o, end * state + mm(u, k * left_chunk, _TN), inverse


def _column(x, h):
    """Column h of x [C, n] -> [C, 1]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == h, x, 0.0), axis=1, keepdims=True)


def _heads_blocks(refs, beta, h, dk, dv):
    """Head h's q, k, v, g blocks and beta column off a step's refs."""
    q_ref, k_ref, v_ref, g_ref = refs
    ks, vs = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
    return (q_ref[:, ks], k_ref[:, ks], v_ref[:, vs], g_ref[:, ks],
            _column(beta, h))


def _forward(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, heads: int,
             dk: int, dv: int, cdt: str):
    *kept_refs, s_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    beta = beta_ref[...]
    # (every head's reads, then their chains, then the writes: nothing of
    # one head waits on another's)
    entering = [s_ref[h] for h in range(heads)]
    blocks = [_heads_blocks((q_ref, k_ref, v_ref, g_ref), beta, h, dk, dv)
              for h in range(heads)]
    out = [_chunk(*blocks[h], entering[h], cdt=cdt) for h in range(heads)]
    for h, (o, leaving, inverse) in enumerate(out):
        o_ref[:, h * dv:(h + 1) * dv] = o
        s_ref[h] = leaving
        if kept_refs:
            kept_refs[0][h] = entering[h]
            kept_refs[1][h] = inverse


def _backward(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, enter_ref,
              inverse_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref,
              *, heads: int, dk: int, dv: int, cdt: str):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    beta = beta_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, beta.shape, 1)
    blocks = [_heads_blocks((q_ref, k_ref, v_ref, g_ref), beta, h, dk, dv)
              for h in range(heads)]
    cotangents = []
    for h in range(heads):
        kept = inverse_ref[h]

        def chunk(*a, kept=kept):
            return _chunk(*a, kept, cdt=cdt)[:2]
        _, back = jax.vjp(chunk, *blocks[h], enter_ref[h])
        cotangents.append(back((do_ref[:, h * dv:(h + 1) * dv], ds_ref[h])))
    dbeta = jnp.zeros_like(beta)
    for h, (dq, dkk, dvv, dg, db, ds) in enumerate(cotangents):
        ks, vs = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        dq_ref[:, ks], dk_ref[:, ks], dv_ref[:, vs], dg_ref[:, ks] = (
            dq, dkk, dvv, dg)
        ds_ref[h] = ds
        dbeta = jnp.where(lane == h, db, dbeta)
    dbeta_ref[...] = dbeta


def _specs(heads: int, dk: int, dv: int, chunk_of):
    """(q, k, g's block spec, v's, beta's, the entering states', the kept
    inverses'): ``chunk_of(ni)`` is the chunk grid step ni works on."""
    def wide(d):
        return pl.BlockSpec((None, CHUNK, heads * d),
                            lambda b, hg, ni: (b, chunk_of(ni), hg))
    beta = pl.BlockSpec((None, None, CHUNK, heads),
                        lambda b, hg, ni: (b, hg, chunk_of(ni), 0))
    def kept(rows, columns):
        return pl.BlockSpec((None, heads, None, rows, columns),
                            lambda b, hg, ni: (b, hg, chunk_of(ni), 0, 0))
    return wide(dk), wide(dv), beta, kept(dv, dk), kept(CHUNK, CHUNK)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _sizes(q, v, beta):
    batch, length, _ = q.shape
    groups, heads = beta.shape[1], beta.shape[3]
    dk, dv = q.shape[2] // (groups * heads), v.shape[2] // (groups * heads)
    return batch, length, groups, heads, dk, dv


def _call_forward(q, k, v, g, beta, cdt, keep, interpret):
    """-> [o] or, with ``keep``, [o, the state entering every chunk, every
    chunk's ``(I + A)^-1``]."""
    batch, length, groups, heads, dk, dv = _sizes(q, v, beta)
    n = length // CHUNK
    key, value, word, enter, inverse = _specs(heads, dk, dv, lambda ni: ni)
    out = jax.ShapeDtypeStruct(v.shape, _F32)
    kept = [jax.ShapeDtypeStruct((batch, groups * heads, n, *tail), _F32)
            for tail in ((dv, dk), (CHUNK, CHUNK))]
    return pl.pallas_call(
        functools.partial(_forward, heads=heads, dk=dk, dv=dv, cdt=cdt),
        grid=(batch, groups, n),
        in_specs=[key, key, value, key, word],
        out_specs=[value, enter, inverse] if keep else [value],
        out_shape=[out, *kept] if keep else [out],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="kda_scan_fwd_keep" if keep else "kda_scan_fwd")(
            q, k, v, g, beta)


def _call_backward(q, k, v, g, beta, do, entering, inverses, cdt, interpret):
    batch, length, groups, heads, dk, dv = _sizes(q, v, beta)
    n = length // CHUNK
    key, value, word, enter, inverse = _specs(heads, dk, dv,
                                              lambda ni: n - 1 - ni)
    like = [jax.ShapeDtypeStruct(x.shape, _F32) for x in (q, k, v, g, beta)]
    return pl.pallas_call(
        functools.partial(_backward, heads=heads, dk=dk, dv=dv, cdt=cdt),
        grid=(batch, groups, n),
        in_specs=[key, key, value, key, word, value, enter, inverse],
        out_specs=[key, key, value, key, word], out_shape=like,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="kda_scan_bwd")(q, k, v, g, beta, do, entering, inverses)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(q, k, v, g, beta, cdt, interpret):
    return _call_forward(q, k, v, g, beta, cdt, False, interpret)[0]


def _scan_fwd(q, k, v, g, beta, cdt, interpret):
    # (named: a ``jax.checkpoint`` whose policy saves ``KEPT`` does not run
    # this kernel again in its backward pass)
    o, *kept = (checkpoint_name(x, KEPT) for x in _call_forward(
        q, k, v, g, beta, cdt, True, interpret))
    return o, (q, k, v, g, beta, *kept)


def _scan_bwd(cdt, interpret, kept, do):
    return tuple(_call_backward(*kept[:5], do, *kept[5:], cdt, interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
             beta: jnp.ndarray, *, cdt: jnp.dtype, interpret: bool = False
             ) -> jnp.ndarray:
    """q, k, g [B, L, H, Dk], v [B, L, H, Dv], beta [B, L, H], float32, L in
    whole chunks -> o [B, L, H, Dv] float32 from a zero state, by the
    kernels; differentiable in all five."""
    b, length, h, _ = q.shape
    heads = heads_a_step(h)

    def flat(x):
        return x.reshape(b, length, -1)

    # [B, L, H] -> [B, H / heads, L, heads]: a word a position and head
    words = jnp.moveaxis(beta.reshape(b, length, h // heads, heads), 2, 1)
    o = _scan(flat(q), flat(k), flat(v), flat(g), words,
              jnp.dtype(cdt).name, interpret)
    return o.reshape(v.shape)
