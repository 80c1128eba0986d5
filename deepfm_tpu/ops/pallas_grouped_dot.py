"""Pallas TPU kernels: an expert layer's grouped products over the valid
prefix of the pair buffer, and nothing past it.

A pass of ``models/sdar_moe.expert_layer`` holds ``C`` rows sorted by held
expert; ``ends[g]`` is the row at which expert ``g``'s rows end, so rows
``[ends[g-1], ends[g])`` multiply matrix ``g`` and the rows from
``n = ends[-1]`` on name no pair (the buffer is twice the mean load).
``grouped_dot(a, w, ends)`` is ``a[i] @ w[group of i]`` for ``i < n``, under
``jax.custom_vjp``: three kernels whose grids are bounded by the row tiles the
groups really reach, read from tables in SMEM (scalar prefetch), as the
grouped-matmul kernels JAX ships do (``jax.experimental.pallas.ops.tpu
.megablox``, which is also what XLA:TPU compiles ``jax.lax.ragged_dot`` to, at
tiles of 512 rows by 512 by 256: PERF.md section 6, PR 52):

``moe_grouped_dot`` (forward): a grid step takes one tile of ``tile_rows``
rows and one group, multiplies the tile by the group's whole matrix (every
column of it, so a tile's rows are read once and a group's matrix once a
group: the matrix stays in VMEM while the grid walks the group's tiles) and
keeps the rows that are the group's; a tile two groups share is visited once
for each.

``moe_grouped_dot_da`` (the rows' gradient): the same kernel on ``dy`` with
the matrices read transposed, ``dy[i] @ w[group of i]^T``.

``moe_grouped_dot_dw`` (the matrices' gradient): ``a[rows of g]^T @ dy[rows of
g]`` a group, summed over the group's tiles into the result's block, which
stays in VMEM while the group lasts; rows of a tile that are not the group's
are replaced by zeros in **both** operands before the product (a select, not
a product with a mask); a group of no rows is visited once and written as
zeros.

**Rows past the prefix are never read and never written.** The tiles past
``n`` are in no grid, so those rows of a result hold whatever lay in its
buffer, and a visited tile's rows that are not its group's keep what they
held. Nothing that sums may read them: ``expert_layer`` reads a pass's rows
through ``pallas_moe_rows`` (prefix only) or under ``jnp.where(valid, ...)``,
the elementwise passes between the products may run over whatever lies
there, and the two gradients above select before they multiply, so a NaN in a
spare row of any operand reaches no sum (``tests/test_grouped_dot.py``
poisons them).

Operands are multiplied in the matrices' type (bfloat16 in the cells; a
float32 ``dy`` is rounded to it in VMEM, as the MXU rounds it for XLA's
kernel at its ``contract_precision<bf16>``), sums and results are float32.

``supported`` says where the compiled kernels apply (a TPU backend, widths of
whole 128-lane lines, a pass of whole row tiles); ``interpret=True`` runs the
same kernels through the Pallas interpreter for the CPU tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Lanes of one vector register line: both widths must be whole lines.
LANES = 128
#: Rows of a tile. A tile two groups share is computed once for each, so
#: small tiles waste less (16 groups in 16,384 rows: 79 visits of 256 rows
#: against 47 of 512) and large ones amortise a grid step.
TILE_ROWS = 256
#: Bytes of the float32 block of a matrix's gradient a grid holds (twice:
#: one block is written back while the next group's is summed); wider
#: matrices are made a block of columns at a time, their rows read again.
DW_BLOCK_BYTES = 16 * 1024 * 1024
#: What a kernel may hold in VMEM (a v5e's is 128 MiB): a group's matrix of
#: 4,096 by 1,280 twice, a tile of rows and its result twice.
VMEM_LIMIT = 100 * 1024 * 1024


def supported(rows: int, width: int, hidden: int,
              backend: Optional[str] = None) -> bool:
    """True where the compiled kernels multiply a pass of ``rows`` rows
    between ``width`` and ``hidden`` columns: a TPU backend, both widths in
    whole 128-lane lines, the pass in whole row tiles."""
    backend = jax.default_backend() if backend is None else backend
    return (backend == "tpu" and width % LANES == 0 and hidden % LANES == 0
            and rows % TILE_ROWS == 0)


def tiling(width: int, hidden: int) -> str:
    """What ``step_notes`` says of the tiles: rows a tile, and the columns
    of a block of the matrices' gradients (both products')."""
    blocks = sorted({_dw_columns(width, hidden), _dw_columns(hidden, width)})
    return "rows%d dw%s" % (TILE_ROWS, "/".join(str(b) for b in blocks))


def _dw_columns(k: int, n: int) -> int:
    """Columns of a block of a [k, n] float32 gradient: the most whole lines
    that divide ``n`` and keep the block within ``DW_BLOCK_BYTES`` (all of
    ``n`` where it is no whole lines: the interpreter's shapes)."""
    if n % LANES:
        return n
    lines = n // LANES
    for parts in range(1, lines + 1):
        if lines % parts == 0 and 4 * k * (n // parts) <= DW_BLOCK_BYTES:
            return n // parts
    return LANES


def visits(ends: jax.Array, rows: int, tile: int, *, empty_groups: bool
           ) -> Tuple[Tuple[jax.Array, jax.Array, jax.Array], jax.Array]:
    """The grid's tables: ((offsets [G + 1], group [V], row tile [V]), the
    number of visits), V = rows / tile + G - 1 the most there can be. Visit
    ``v`` multiplies row tile ``tile[v]`` for group ``group[v]``, whose rows
    are ``[offsets[g], offsets[g + 1])``; groups in order, a group's tiles in
    order, so a tile is revisited only by consecutive visits. A group of no
    rows has no visit, or one (``empty_groups``: its gradient is to be
    written as zeros)."""
    groups = ends.shape[0]
    ends = ends.astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    starts = offsets[:-1]
    first = starts // tile
    count = jnp.where(ends > starts, (ends - 1) // tile - first + 1,
                      1 if empty_groups else 0)
    upto = jnp.cumsum(count)
    most = rows // tile + groups - 1
    visit = jnp.arange(most, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(visit[:, None] >= upto[None, :], axis=1, dtype=jnp.int32),
        groups - 1)
    row_tile = first[group] + visit - (upto - count)[group]
    return ((offsets, group, jnp.clip(row_tile, 0, rows // tile - 1)),
            upto[-1])


def _own_rows(offsets_ref, group, base, tile: int):
    """bool [tile, 1]: which of the tile's rows are the group's."""
    rows = base + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    return (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])


def _dot_kernel(offsets_ref, group_ref, tile_ref, a_ref, w_ref, out_ref, *,
                tile, transposed):
    visit = pl.program_id(0)
    group = group_ref[visit]
    own = _own_rows(offsets_ref, group, tile_ref[visit] * tile, tile)
    w = w_ref[...]
    product = jax.lax.dot_general(
        a_ref[...].astype(w.dtype), w,
        (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # (the rows of another group keep what that group's visit wrote, or
    # will write; rows past the prefix keep whatever lay there)
    out_ref[...] = jnp.where(own, product, out_ref[...])


@functools.partial(jax.jit, static_argnames=("transposed", "tile",
                                             "interpret"))
def _dot(a, w, ends, *, transposed, tile, interpret):
    rows, k = a.shape
    n = w.shape[1] if transposed else w.shape[2]
    tables, n_visits = visits(ends, rows, tile, empty_groups=False)
    return pl.pallas_call(
        functools.partial(_dot_kernel, tile=tile, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_visits,),
            in_specs=[
                pl.BlockSpec((tile, k), lambda v, off, grp, til: (til[v], 0)),
                pl.BlockSpec((None,) + w.shape[1:],
                             lambda v, off, grp, til: (grp[v], 0, 0))],
            out_specs=pl.BlockSpec((tile, n),
                                   lambda v, off, grp, til: (til[v], 0))),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_grouped_dot_da" if transposed else "moe_grouped_dot",
    )(*tables, a, w)


def _dw_kernel(offsets_ref, group_ref, tile_ref, a_ref, dy_ref, out_ref, *,
               tile, dtype):
    visit = pl.program_id(1)
    group = group_ref[visit]

    @pl.when((visit == 0) | (group_ref[jnp.maximum(visit - 1, 0)] != group))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def add(a, dy):
        out_ref[...] += jax.lax.dot_general(
            a.astype(dtype), dy.astype(dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    base = tile_ref[visit] * tile
    start, end = offsets_ref[group], offsets_ref[group + 1]
    whole = (start <= base) & (end >= base + tile)

    @pl.when(whole)
    def _():
        add(a_ref[...], dy_ref[...])

    @pl.when(jnp.logical_not(whole) & (end > start))
    def _():
        own = _own_rows(offsets_ref, group, base, tile)
        # a select on each side: a spare row may hold anything on either
        add(jnp.where(own, a_ref[...].astype(jnp.float32), 0.0),
            jnp.where(own, dy_ref[...].astype(jnp.float32), 0.0))


@functools.partial(jax.jit, static_argnames=("dtype", "tile", "interpret"))
def _dw(a, dy, ends, *, dtype, tile, interpret):
    rows, k = a.shape
    n = dy.shape[1]
    groups = ends.shape[0]
    columns = _dw_columns(k, n)
    tables, n_visits = visits(ends, rows, tile, empty_groups=True)
    return pl.pallas_call(
        functools.partial(_dw_kernel, tile=tile, dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // columns, n_visits),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda c, v, off, grp, til: (til[v], 0)),
                pl.BlockSpec((tile, columns),
                             lambda c, v, off, grp, til: (til[v], c))],
            out_specs=pl.BlockSpec(
                (None, k, columns),
                lambda c, v, off, grp, til: (grp[v], 0, c))),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_grouped_dot_dw",
    )(*tables, a, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _grouped_dot(tile, interpret, a, w, ends):
    return _dot(a, w, ends, transposed=False, tile=tile, interpret=interpret)


def _grouped_dot_fwd(tile, interpret, a, w, ends):
    return _grouped_dot(tile, interpret, a, w, ends), (a, w, ends)


def _grouped_dot_bwd(tile, interpret, res, dy):
    a, w, ends = res
    da = _dot(dy, w, ends, transposed=True, tile=tile, interpret=interpret)
    dw = _dw(a, dy, ends, dtype=w.dtype, tile=tile, interpret=interpret)
    return (da.astype(a.dtype), dw.astype(w.dtype),
            jnp.zeros(ends.shape, jax.dtypes.float0))


_grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


def grouped_dot(a: jax.Array, w: jax.Array, ends: jax.Array, *,
                tile: int = TILE_ROWS, interpret: bool = False) -> jax.Array:
    """float32 [C, n] whose row ``i < ends[-1]`` is ``a[i] @ w[g]``, ``g``
    the group whose rows ``[ends[g - 1], ends[g])`` hold ``i``; the other
    rows hold anything. ``a`` [C, k] (rounded to ``w``'s type), ``w``
    [G, k, n], ``ends`` int32 [G] nondecreasing, at most C; C whole
    ``tile``s. Differentiable in ``a`` and ``w``: the gradients are made
    over the prefix only too, ``a``'s rows past it hold anything, and a
    group of no rows has a gradient of zeros."""
    return _grouped_dot(tile, interpret, a, w, ends)
