"""Pallas TPU kernel: fused FM first-order + second-order interaction.

The reference computes the first-order term and the FM identity as separate
graph ops (``1-ps-cpu/...py:177-187``). Here both reductions run in one
VMEM pass over ``xv``: the kernel consumes the already-materialized
``xv = V[ids] * vals`` (which the DeepFM tower reuses as its input, so it
costs no extra HBM), produces ``y_w + y_v`` directly, and the hand-written
backward emits the compact ``dxv = (S - xv) * g`` form in a single pass —
avoiding the chain of separate square/reduce/broadcast kernels XLA schedules
for the naive formulation.

    y[b] = sum_f w[b,f]*vals[b,f]
         + 0.5 * sum_k [ (sum_f xv[b,f,k])^2 - sum_f xv[b,f,k]^2 ]

Exposed as ``fused_fm(w, vals, xv)`` with a custom VJP; gradients w.r.t. the
embedding ``v`` and ``vals``-through-``xv`` flow via JAX's product rule on
the caller side (xv is an ordinary traced value there). Both passes are
Pallas kernels gridded over batch tiles sized to VMEM. ``interpret=True``
runs the same kernels through the Pallas interpreter (used by the CPU test
suite to check numerics against the plain-jnp formulation in ``ops.fm``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

# VMEM budget for picking the batch-tile height. The backward kernel keeps
# ~4 [Bt, F, K] f32 buffers effectively live (inputs/outputs stream per grid
# step with double buffering); F pads to the 8-sublane, K to the 128-lane
# tile. 14MB of the ~16MB/core leaves headroom for scalars and control.
_VMEM_BUDGET = 14 * 1024 * 1024
_LIVE_BUFFERS = 4


def _pick_block_b(f: int, k: int) -> int:
    """Largest batch tile whose kernel fits VMEM; 0 if none does."""
    fpad = max(-(-f // 8) * 8, 8)
    kpad = max(-(-k // 128) * 128, 128)
    per_row = fpad * kpad * 4
    for bt in (128, 64, 32, 16, 8):
        if _LIVE_BUFFERS * bt * per_row <= _VMEM_BUDGET:
            return bt
    return 0


# Interpret mode has no VMEM constraint; used when _pick_block_b returns 0
# (callers should have gated the compiled path off via supported()).
_BLOCK_FALLBACK = 128


def supported(field_size: int = 39, embedding_size: int = 32) -> bool:
    """True when the compiled kernels can run at this (F, K) shape: a TPU
    backend and a batch tile that fits VMEM. Where it is False the caller
    takes the XLA formulation (``ops.fm``) — a choice made from the
    platform and the shape, both of which the step's program records."""
    return (jax.default_backend() == "tpu"
            and _pick_block_b(field_size, embedding_size) > 0)


def _block_specs(bt: int, f: int, k: int, memory_space):
    kw = {} if memory_space is None else {"memory_space": memory_space}
    return [
        pl.BlockSpec((bt, f), lambda i: (i, 0), **kw),          # w
        pl.BlockSpec((bt, f), lambda i: (i, 0), **kw),          # vals
        pl.BlockSpec((bt, f, k), lambda i: (i, 0, 0), **kw),    # xv
    ]


def _fwd_kernel(w_ref, vals_ref, xv_ref, out_ref):
    # All intermediates stay >= 2-D (rank-1 vectors break Mosaic layout
    # inference on TPU). Inputs may be bf16 in HBM/VMEM; accumulate in f32
    # (cast after load — keeps HBM traffic and residuals at bf16 width).
    xv = xv_ref[:].astype(jnp.float32)                     # [Bt, F, K]
    s = jnp.sum(xv, axis=1)                                # [Bt, K]
    sum_sq = jnp.sum(s * s, axis=1, keepdims=True)         # [Bt, 1]
    sq_sum = jnp.sum(jnp.sum(xv * xv, axis=1), axis=1, keepdims=True)
    y_w = jnp.sum(w_ref[:].astype(jnp.float32)
                  * vals_ref[:].astype(jnp.float32), axis=1, keepdims=True)
    out_ref[:] = y_w + 0.5 * (sum_sq - sq_sum)


def _bwd_kernel(g_ref, w_ref, vals_ref, xv_ref, dw_ref, dvals_ref, dxv_ref):
    g = g_ref[:]                                           # [Bt, 1] f32
    xv = xv_ref[:].astype(jnp.float32)
    s = jnp.sum(xv, axis=1)                                # [Bt, K]
    dw_ref[:] = (vals_ref[:].astype(jnp.float32) * g).astype(dw_ref.dtype)
    dvals_ref[:] = (w_ref[:].astype(jnp.float32) * g).astype(dvals_ref.dtype)
    # d(y_v)/d(xv) * g
    dxv_ref[:] = ((s[:, None, :] - xv) * g[:, :, None]).astype(dxv_ref.dtype)


def _vma(*arrays) -> frozenset:
    """Mesh axes the kernel's outputs vary over inside ``shard_map``: the
    union of its inputs' (empty outside one). ``pallas_call`` cannot infer
    it, and ``check_vma`` refuses an output that does not say."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def _pad_b(x: jnp.ndarray, b_pad: int) -> jnp.ndarray:
    if b_pad == 0:
        return x
    pad = [(0, b_pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def _run_fwd(w, vals, xv, interpret: bool) -> jnp.ndarray:
    b, f = w.shape
    k = xv.shape[-1]
    bt = _pick_block_b(f, k) or _BLOCK_FALLBACK
    b_pad = (-b) % bt
    w, vals, xv = _pad_b(w, b_pad), _pad_b(vals, b_pad), _pad_b(xv, b_pad)
    bp = b + b_pad
    ms = None if interpret else pltpu.VMEM
    kw = {} if ms is None else {"memory_space": ms}
    out = pl.pallas_call(
        _fwd_kernel,
        grid=(bp // bt,),
        in_specs=_block_specs(bt, f, k, ms),
        out_specs=pl.BlockSpec((bt, 1), lambda i: (i, 0), **kw),
        out_shape=jax.ShapeDtypeStruct((bp, 1), jnp.float32,
                                       vma=_vma(w, vals, xv)),
        interpret=interpret,
        name="fm_fwd",
    )(w, vals, xv)
    return out[:b, 0]


def _run_bwd(g, w, vals, xv, interpret: bool):
    b, f = w.shape
    k = xv.shape[-1]
    bt = _pick_block_b(f, k) or _BLOCK_FALLBACK
    b_pad = (-b) % bt
    g2 = _pad_b(g.reshape(b, 1), b_pad)
    w, vals, xv = _pad_b(w, b_pad), _pad_b(vals, b_pad), _pad_b(xv, b_pad)
    bp = b + b_pad
    ms = None if interpret else pltpu.VMEM
    kw = {} if ms is None else {"memory_space": ms}
    g_spec = pl.BlockSpec((bt, 1), lambda i: (i, 0), **kw)
    vma = _vma(g2, w, vals, xv)
    dw, dvals, dxv = pl.pallas_call(
        _bwd_kernel,
        grid=(bp // bt,),
        in_specs=[g_spec] + _block_specs(bt, f, k, ms),
        out_specs=[
            pl.BlockSpec((bt, f), lambda i: (i, 0), **kw),
            pl.BlockSpec((bt, f), lambda i: (i, 0), **kw),
            pl.BlockSpec((bt, f, k), lambda i: (i, 0, 0), **kw),
        ],
        out_shape=[
            # Cotangent dtypes mirror the primals (bf16 in -> bf16 grads),
            # written directly by the kernel — no f32 round trip in HBM.
            jax.ShapeDtypeStruct((bp, f), w.dtype, vma=vma),
            jax.ShapeDtypeStruct((bp, f), vals.dtype, vma=vma),
            jax.ShapeDtypeStruct((bp, f, k), xv.dtype, vma=vma),
        ],
        interpret=interpret,
        name="fm_bwd",
    )(g2, w, vals, xv)
    return dw[:b], dvals[:b], dxv[:b]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_fm(w: jnp.ndarray, vals: jnp.ndarray, xv: jnp.ndarray,
             interpret: bool = False) -> jnp.ndarray:
    """Fused y_w + y_v.  w: [B,F], vals: [B,F], xv: [B,F,K] -> [B] (f32).

    Inputs may be bf16: the kernels cast to f32 AFTER the VMEM load, so
    residuals saved for the backward pass stay at bf16 width in HBM (the
    r1 version saved f32 copies — 2x the residual memory)."""
    return _run_fwd(w, vals, xv, interpret)


def _fused_fm_fwd(w, vals, xv, interpret):
    return _run_fwd(w, vals, xv, interpret), (w, vals, xv)


def _fused_fm_bwd(interpret, res, g):
    w, vals, xv = res
    dw, dvals, dxv = _run_bwd(g.astype(jnp.float32), w, vals, xv, interpret)
    return dw, dvals, dxv


fused_fm.defvjp(_fused_fm_fwd, _fused_fm_bwd)


def reference_fm(w: jnp.ndarray, vals: jnp.ndarray, xv: jnp.ndarray) -> jnp.ndarray:
    """Plain-jnp oracle for the fused kernel (same math as ``ops.fm``)."""
    y_w = jnp.sum(w.astype(jnp.float32) * vals.astype(jnp.float32), axis=1)
    xv = xv.astype(jnp.float32)
    s = jnp.sum(xv, axis=1)
    y_v = 0.5 * jnp.sum(s * s, axis=1) - 0.5 * jnp.sum(xv * xv, axis=(1, 2))
    return y_w + y_v
