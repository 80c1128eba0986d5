"""The selective (Mamba-1) recurrence on a TPU with its state in VMEM:
``s_t = exp(D_t A) s_{t-1} + (D_t x_t) B_t``, ``y_t = sum_n C_tn s_tn``, per
channel c and state n from a zero state (``models/phi4_flash.py`` states
it), forward and backward as two Pallas kernels behind a ``custom_vjp``.

Layout. A block of ``BLOCK_CHANNELS`` = 8 x 128 channels is one vector
register a state: the state of a block is ``N`` registers, which never
leave the core between positions. The grid is (channel blocks, time blocks
of ``STEPS`` positions), the time blocks in sequence; a position's
per-state scalars ``B_tn`` and ``C_tn`` are read from SMEM and splat. x, the
step size and y are viewed ``[T, C / 128, 128]`` (a free reshape), so a
position's block is one ``[8, 128]`` tile.

Forward: the loop over a block's positions, and the state entering every
time block written out (``[T / STEPS, N, C]``: what the backward pass starts
each block from; 42 MB at 8,192 x 5,120 x 16). Backward, the time blocks
last to first: the block's entering states made again into a VMEM scratch
(``STEPS x N`` registers, 4 MB), then its positions last to first with the
state's cotangent carried in registers. ``dB_tn`` and ``dC_tn`` are sums
over channels: the kernel sums a register's 8 sublanes and writes the 128
lanes' partial sums a (position, state, channel block); the caller adds the
lanes and the blocks. Everything float32.

``supported`` says where the compiled kernels apply; ``interpret=True`` runs
them through the Pallas interpreter (the CPU tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, ROWS = 128, 8
BLOCK_CHANNELS = ROWS * LANES
#: Positions of a time block: what the backward pass holds the states of.
STEPS = 64
_VMEM_LIMIT = 48 * 2 ** 20


def supported(width: int, length: int, backend: Optional[str] = None) -> bool:
    """True where the compiled kernels apply: a TPU backend, channels in
    whole blocks of 1,024 and positions in whole time blocks."""
    backend = jax.default_backend() if backend is None else backend
    return backend == "tpu" and width % BLOCK_CHANNELS == 0 \
        and length % STEPS == 0


def _forward(x_ref, d_ref, a_ref, b_ref, c_ref, y_ref, enter_ref, s_ref, *,
             states: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    enter_ref[...] = s_ref[...]

    def one(t, carry):
        d = d_ref[t]
        u = d * x_ref[t]
        y = jnp.zeros_like(d)
        for n in range(states):
            s = jnp.exp(d * a_ref[n]) * s_ref[n] + u * b_ref[t, n]
            s_ref[n] = s
            y = y + c_ref[t, n] * s
        y_ref[t] = y
        return carry
    jax.lax.fori_loop(0, STEPS, one, 0)


def _backward(x_ref, d_ref, a_ref, b_ref, c_ref, dy_ref, enter_ref,
              dx_ref, dd_ref, da_ref, db_ref, dc_ref, hist_ref, g_ref, *,
              states: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)

    def again(t, s):        # the state entering each position, made again
        d = d_ref[t]
        u = d * x_ref[t]
        new = []
        for n in range(states):
            hist_ref[t, n] = s[n]
            new.append(jnp.exp(d * a_ref[n]) * s[n] + u * b_ref[t, n])
        return tuple(new)
    jax.lax.fori_loop(0, STEPS, again,
                      tuple(enter_ref[n] for n in range(states)))

    def back(i, g):         # g: the cotangent of the state after position t
        t = STEPS - 1 - i
        d, x, dy = d_ref[t], x_ref[t], dy_ref[t]
        u = d * x
        du = jnp.zeros_like(d)
        dd = jnp.zeros_like(d)
        new = []
        for n in range(states):
            a = jnp.exp(d * a_ref[n])
            before = hist_ref[t, n]
            s = a * before + u * b_ref[t, n]
            g_n = g[n] + c_ref[t, n] * dy
            dc_ref[t, pl.ds(n, 1), :] = jnp.sum(dy * s, axis=0, keepdims=True)
            db_ref[t, pl.ds(n, 1), :] = jnp.sum(g_n * u, axis=0,
                                                keepdims=True)
            du = du + g_n * b_ref[t, n]
            through = g_n * before * a      # d loss / d (D_t A_n)
            dd = dd + through * a_ref[n]
            da_ref[n] = da_ref[n] + through * d
            new.append(a * g_n)
        dd_ref[t] = dd + du * x
        dx_ref[t] = du * d
        return tuple(new)
    g = jax.lax.fori_loop(0, STEPS, back,
                          tuple(g_ref[n] for n in range(states)))
    for n in range(states):
        g_ref[n] = g[n]


def _specs(n: int, time_of):
    """(a [T, C/128, 128] operand's, the rates', a [T, N] operand's in SMEM)
    block specs; ``time_of(ti)`` is the time block grid step ti works on."""
    big = pl.BlockSpec((STEPS, ROWS, LANES),
                       lambda ci, ti: (time_of(ti), ci, 0))
    rates = pl.BlockSpec((n, ROWS, LANES), lambda ci, ti: (0, ci, 0))
    small = pl.BlockSpec((STEPS, n), lambda ci, ti: (time_of(ti), 0),
                         memory_space=pltpu.SMEM)
    return big, rates, small


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _call_forward(x, delta, a_t, b, c, interpret):
    length, tiles, _ = x.shape
    n = a_t.shape[0]
    blocks, times = tiles // ROWS, length // STEPS
    big, rates, small = _specs(n, lambda ti: ti)
    enter = pl.BlockSpec((None, n, ROWS, LANES),
                         lambda ci, ti: (ti, 0, ci, 0))
    return pl.pallas_call(
        functools.partial(_forward, states=n), grid=(blocks, times),
        in_specs=[big, big, rates, small, small], out_specs=[big, enter],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((times, n, tiles, LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, ROWS, LANES), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="mamba_scan_fwd")(x, delta, a_t, b, c)


def _call_backward(x, delta, a_t, b, c, dy, entering, interpret):
    length, tiles, _ = x.shape
    n = a_t.shape[0]
    blocks, times = tiles // ROWS, length // STEPS
    big, rates, small = _specs(n, lambda ti: times - 1 - ti)
    enter = pl.BlockSpec((None, n, ROWS, LANES),
                         lambda ci, ti: (times - 1 - ti, 0, ci, 0))
    sums = pl.BlockSpec((None, STEPS, n, LANES),
                        lambda ci, ti: (ci, times - 1 - ti, 0, 0))
    lane_sums = jax.ShapeDtypeStruct((blocks, length, n, LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_backward, states=n), grid=(blocks, times),
        in_specs=[big, big, rates, small, small, big, enter],
        out_specs=[big, big, rates, sums, sums],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(a_t.shape, jnp.float32),
                   lane_sums, lane_sums],
        scratch_shapes=[pltpu.VMEM((STEPS, n, ROWS, LANES), jnp.float32),
                        pltpu.VMEM((n, ROWS, LANES), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="mamba_scan_bwd")(x, delta, a_t, b, c, dy, entering)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, delta, a_t, b, c, interpret):
    return _call_forward(x, delta, a_t, b, c, interpret)[0]


def _scan_fwd(x, delta, a_t, b, c, interpret):
    y, entering = _call_forward(x, delta, a_t, b, c, interpret)
    return y, (x, delta, a_t, b, c, entering)


def _scan_bwd(interpret, kept, dy):
    x, delta, a_t, b, c, entering = kept
    dx, dd, da_t, db, dc = _call_backward(x, delta, a_t, b, c, dy, entering,
                                          interpret)
    return dx, dd, da_t, jnp.sum(db, axis=(0, 3)), jnp.sum(dc, axis=(0, 3))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x: jnp.ndarray, delta: jnp.ndarray, a: jnp.ndarray,
                   b: jnp.ndarray, c: jnp.ndarray, *,
                   interpret: bool = False) -> jnp.ndarray:
    """x, delta [B, T, C], a [C, N] (negative), b, c [B, T, N], float32 ->
    ``sum_n C_tn s_tcn`` [B, T, C] (the skip term is the caller's), by the
    kernels; differentiable in all five."""
    _, length, width = x.shape
    n = a.shape[1]
    tiles = width // LANES
    a_t = a.T.reshape(n, tiles, LANES)

    def one(x_b, d_b, b_b, c_b):
        return _scan(x_b.reshape(length, tiles, LANES),
                     d_b.reshape(length, tiles, LANES), a_t, b_b, c_b,
                     interpret).reshape(length, width)
    # (a Python loop over the batch: a sequence or two a step)
    return jnp.stack([one(x[i], delta[i], b[i], c[i])
                      for i in range(x.shape[0])])
