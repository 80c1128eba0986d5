"""Pallas TPU kernels: move an expert layer's rows between token order and
buffer order, one asynchronous copy a row, over the rows really held.

A pass of ``models/sdar_moe.expert_layer`` computes ``C`` rows of its pair
buffer: row ``i`` belongs to position ``tok[i]``, the rows are sorted by
held expert, and only a **prefix** of them names a pair that is there (the
buffer is twice the mean load). XLA's ``jnp.take(x, tok)`` and
``out.at[tok].add(y)`` have static shapes and pay for all ``C`` rows, the
scatter-add 120 ns a row as a serial loop (PERF.md §6, PRs 28, 30, 34). The
two kernels here leave the ``[T, W]`` array of the positions in HBM
(``pl.ANY``), read ``tok`` and the groups' ends from SMEM (scalar prefetch),
loop over the valid prefix only, ``block`` rows a grid step, and stage a
block's rows in VMEM:

``take_rows(x, tok, ends, dtype)``: row ``i < n`` of the result is
``x[tok[i]]``, fetched by its own DMA, a block's copies in flight on one
semaphore and waited for in bulk; the block leaves as one ``[block, W]``
tile of ``dtype`` (the products': the cast rides the kernel) while the next
block's rows arrive. Rows ``i >= n`` are **zeros**, written so and not left
as they were found: where the grouped products are ``jax.lax.ragged_dot``
the spare rows ride its last group, and a stray NaN times a zero cotangent
poisons a weight's gradient (the kernels of ``ops/pallas_grouped_dot`` stop
at the prefix and read none of them). ``tok`` of a row past ``n`` is never
read.

``add_rows(out, y, tok, ends, scale)``: ``out[tok[i]] += scale[i] * y[i]``
for ``i < n``, in float32, **in place** (``out`` is aliased to the result: a
scan's carry); the scaling meets the rows in VMEM, so the pair weights cost
no pass of XLA's over all C rows.
A position has at most one row in each expert's group, so the rows between
two of ``ends`` name distinct positions and can be read, added and written
back together; inside a block (whose ``y`` arrives by one copy) the kernel
goes a group's segment at a time: the positions' rows of ``out`` are
fetched by one DMA each, the tile gains the ``y`` tile, the rows go back,
and the next segment starts when they have landed. No rank of a pair among
its position's, no second sort, no index bookkeeping: ``ends`` is what the
pass already computes for its group sizes.

**What a row is.** Under the TPU's (8, 128) tiling of float32 ``[T, W]`` a
row is ``W / 128`` lines of 512 B, one sublane of ``W / 128`` tiles 4 KB
apart, and Mosaic takes no one-row slice of a tiled array wider than one
line. ``_by_row`` reshapes to ``[T/8, W/128, 8, 1, 128]``, whose tiles are
single lines: the same bytes (XLA compiles it to a bitcast, both ways), and
``[t // 8, :, t % 8]`` is a slice one strided copy moves. The VMEM tile has
the same shape, so its bytes are a ``[block, W]`` tile's, and column block
``c`` of every staged row is every ``W / 128``-th whole (8, 128) tile of it.

``n = ends[-1]``: ``ends`` is nondecreasing, ``ends[g]`` the row at which
group ``g`` ends.

``gather`` and ``combine`` are the two under ``jax.custom_vjp``, each the
other's transpose: the cotangent of ``gather``'s rows is added to their
positions by ``add_rows``; that of ``combine``'s rows and of their scales is
``take_rows_weighted`` of the result's cotangent (the taken rows times their
scale, and each one's dot product with its ``y``, both made in VMEM); ``tok``
and ``ends`` are integers and carry none.

``supported`` says where the compiled kernels apply (a TPU backend, a row of
whole 128-lane lines, positions and a buffer of whole sublane tiles);
everything else keeps XLA's ops. ``interpret=True`` runs the same kernels
through the Pallas interpreter, which is how the CPU tests hold them to
``jnp.take`` and ``.at[].add`` (it stores through no reshaped reference,
so there the staged tile's columns are indexed in its own shape).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Lanes of one vector register line: a row must be whole lines.
LANES = 128
#: Sublanes of a float32 tile: the rows that share a tile's 4 KB.
SUBLANES = 8
#: Rows a grid step stages in VMEM (a tile of float32 rows and the tile that
#: leaves or arrives).
BLOCK = 512
#: Rows a trip of a kernel's copy loop starts (Mosaic unrolls all or nothing);
#: like ``SUBLANES`` a power of two, divided by with a shift of 3.
UNROLL = 8
#: What a kernel may hold in VMEM: three float32 tiles of ``BLOCK`` rows of
#: 2,304 are 14 MB, the compiler's default limit less its own buffers.
VMEM_LIMIT = 48 * 1024 * 1024


def block_rows(rows: int, most: int = BLOCK) -> Optional[int]:
    """The largest block of whole 16-row sublane tiles (bfloat16's; float32's
    are 8) that divides ``rows`` and is at most ``most``; None where there is
    none."""
    for block in range(min(most, rows) // 16 * 16, 0, -16):
        if rows % block == 0:
            return block
    return None


def supported(width: int, positions: int, rows: int,
              backend: Optional[str] = None) -> bool:
    """True where the compiled kernels can move ``rows`` buffer rows of
    ``width`` from and to ``positions`` positions: a TPU backend, a row of
    whole 128-lane lines, positions in whole 8-row tiles and a buffer that
    splits into blocks of whole sublane tiles. Read from the backend and the
    shapes, which the step's program records."""
    backend = jax.default_backend() if backend is None else backend
    return (backend == "tpu" and width % LANES == 0
            and positions % SUBLANES == 0 and block_rows(rows) is not None)


def _by_row(x: jax.Array) -> jax.Array:
    """float32 ``x`` [T, W] as [T/8, W/128, 8, 1, 128]: the same bytes (under
    the TPU's (8, 128) tiling of [T, W] a row is W/128 lines, one sublane of
    W/128 tiles 4 KB apart; XLA compiles this to a bitcast), in a shape whose
    tiles are single lines, so that ``[t // 8, :, t % 8]`` is a slice Mosaic
    takes and one strided copy moves. (It refuses a one-row slice of a tiled
    array wider than a line.)"""
    rows, width = x.shape
    return x.reshape(rows // SUBLANES, SUBLANES, width // LANES, 1,
                     LANES).transpose(0, 2, 1, 3, 4)


def _from_by_row(x: jax.Array) -> jax.Array:
    tiles, per_row = x.shape[:2]
    return x.transpose(0, 2, 1, 3, 4).reshape(tiles * SUBLANES,
                                              per_row * LANES)


def _row(ref, i):
    """Row ``i`` (not negative) of a ``_by_row`` array. (A shift and a mask:
    ``//`` and ``%`` of a signed integer are a dozen scalar instructions
    each, in a loop the scalar core bounds.)"""
    return ref.at[pl.ds(jax.lax.shift_right_logical(i, 3), 1), :,
                  pl.ds(jax.lax.bitwise_and(i, SUBLANES - 1), 1)]


def _row_loop(lo, hi, start_row):
    """``start_row(i)`` for every i in [lo, hi), ``UNROLL`` a trip."""
    trips = jax.lax.shift_right_logical(hi - lo, 3)      # // UNROLL

    def trip(t, _):
        for k in range(UNROLL):
            start_row(lo + t * UNROLL + k)
        return 0

    jax.lax.fori_loop(0, trips, trip, 0)
    jax.lax.fori_loop(lo + trips * UNROLL, hi,
                      lambda i, _: (start_row(i), 0)[1], 0)


def _wait_rows(count, most: int, staged, sem):
    """Wait for ``count`` (at most ``most``) one-row copies on ``sem``: all
    are one size, and a wait takes its size from the descriptor, so at most
    ``log2(most) + 1`` waits, each for a power-of-two number of rows of
    ``staged`` (a ``_by_row`` tile)."""
    rows_a_wait = 1 << (most.bit_length() - 1)
    while rows_a_wait:
        @pl.when((count & rows_a_wait) != 0)
        def _(m=rows_a_wait):
            part = (staged.at[pl.ds(0, m // SUBLANES)] if m >= SUBLANES
                    else staged.at[pl.ds(0, 1), :, pl.ds(0, m)])
            pltpu.make_async_copy(part, part, sem).wait()
        rows_a_wait //= 2


def _columns(staged, block, per_row, by_index):
    """(load, store) of column block ``c`` of every staged row, as
    [block / 8, 8, 128]: whole tiles, ``per_row`` apart."""
    if by_index:
        def load(c):
            return staged[:, c, :, 0, :]

        def store(c, value):
            staged[:, c, :, 0, :] = value
    else:
        tiles = staged.reshape(block * per_row // SUBLANES, SUBLANES, LANES)

        def load(c):
            return tiles[pl.ds(c, block // SUBLANES, stride=per_row)]

        def store(c, value):
            tiles[pl.ds(c, block // SUBLANES, stride=per_row)] = value
    return load, store


def _take_kernel(ends_ref, tok_ref, x_ref, *refs, block, groups, per_row,
                 by_index, weighted):
    if weighted:
        (y_ref, w_ref, out_ref, dot_ref, staged, tile, y_tile, w_tile,
         dot_tile, sem, out_sem, in_sem) = refs
    else:
        out_ref, staged, tile, sem, out_sem = refs
    step = pl.program_id(0)
    base = step * block
    count = jnp.clip(ends_ref[groups - 1] - base, 0, block)
    arrive = [pltpu.make_async_copy(ref.at[pl.ds(base, block)], to, in_sem)
              for ref, to in ((y_ref, y_tile), (w_ref, w_tile))
              ] if weighted else []

    @pl.when(count > 0)
    def _():
        for copy in arrive:
            copy.start()

    def fetch_row(i):
        pltpu.make_async_copy(_row(x_ref, tok_ref[i]), _row(staged, i - base),
                              sem).start()

    _row_loop(base, base + count, fetch_row)
    _wait_rows(count, block, staged, sem)
    leave = [pltpu.make_async_copy(tile, out_ref.at[pl.ds(base, block)],
                                   out_sem)]
    if weighted:
        leave.append(pltpu.make_async_copy(
            dot_tile, dot_ref.at[pl.ds(base, block)], out_sem))

    @pl.when(count > 0)
    def _():
        for copy in arrive:
            copy.wait()

    @pl.when(step > 0)      # the last block's tiles, still on their way out
    def _():
        for copy in leave:
            copy.wait()
    # the rows as a [block, W] tile of the result's type, those past the
    # prefix as zeros (the blocks past the prefix's end leave as the first
    # of them did: the tiles are zeros already)
    @pl.when((step == 0) | (ends_ref[groups - 1] > base - block))
    def _():
        shape = (block // SUBLANES, SUBLANES, LANES)
        held = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * SUBLANES
                + jax.lax.broadcasted_iota(jnp.int32, shape, 1)) < count
        load, _ = _columns(staged, block, per_row, by_index)
        dot = jnp.zeros(shape, jnp.float32)
        for c in range(per_row):
            rows = load(c)
            if weighted:    # (masked after: a tile not filled holds anything)
                dot += jnp.where(held, rows * y_tile[
                    :, c * LANES:(c + 1) * LANES].reshape(shape), 0.0)
                rows = rows * w_tile[...].reshape(shape)
            tile[:, c * LANES:(c + 1) * LANES] = jnp.where(
                held, rows, 0.0).reshape(block, LANES).astype(tile.dtype)
        if weighted:
            dot_tile[...] = dot.reshape(block, LANES)
    for copy in leave:
        copy.start()

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        for copy in leave:
            copy.wait()


def _lanes(scale: jax.Array) -> jax.Array:
    """A number a row, [C], as a line a row, [C, 128]: the shape a kernel
    reads and writes a row's scalar in."""
    return jnp.broadcast_to(scale.astype(jnp.float32)[:, None],
                            (scale.shape[0], LANES))


@functools.partial(jax.jit, static_argnums=(3, 5, 6))
def _take(x, tok, ends, dtype, weights, block, interpret):
    # (jitted for its caches: a step traces and lowers a kernel once a shape,
    # not once a call; unrolled layers would pay 0.5 s a layer and kernel)
    assert x.dtype == jnp.float32, x.dtype
    rows, width = tok.shape[0], x.shape[1]
    per_row = width // LANES
    block = block_rows(rows, block)
    x = _by_row(x)
    if not interpret:   # (the interpreter does not know the primitive)
        x, *weights = (pltpu.with_memory_space_constraint(a, pltpu.HBM)
                       for a in (x, *weights))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    weighted = bool(weights)
    tile = jax.ShapeDtypeStruct((rows, width), dtype)
    dma = pltpu.SemaphoreType.DMA(())
    scratch = [pltpu.VMEM((block // SUBLANES, per_row, SUBLANES, 1, LANES),
                          jnp.float32),
               pltpu.VMEM((block, width), dtype)]
    if weighted:    # a block's y, its scales, its dot products
        scratch += [pltpu.VMEM((block, width), jnp.float32),
                    pltpu.VMEM((block, LANES), jnp.float32),
                    pltpu.VMEM((block, LANES), jnp.float32), dma]
    scratch += [dma, dma]
    return pl.pallas_call(
        functools.partial(_take_kernel, block=block, groups=ends.shape[0],
                          per_row=per_row, weighted=weighted,
                          by_index=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // block,),
            in_specs=[anywhere] * (1 + len(weights)),
            out_specs=[anywhere] * 2 if weighted else anywhere,
            scratch_shapes=scratch),
        out_shape=[tile, jax.ShapeDtypeStruct((rows, LANES), jnp.float32)]
        if weighted else tile,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_take_rows",
    )(ends.astype(jnp.int32), tok.astype(jnp.int32), x, *weights)


def take_rows(x: jax.Array, tok: jax.Array, ends: jax.Array,
              dtype=jnp.float32, *, block: int = BLOCK,
              interpret: bool = False) -> jax.Array:
    """[C, W] of ``dtype`` whose row ``i < ends[-1]`` is ``x[tok[i]]`` and
    whose other rows are zeros. ``x`` float32 [T, W], ``tok`` int32 [C]
    (read below ``ends[-1]`` only), ``ends`` int32 [G] nondecreasing, at
    most C."""
    return _take(x, tok, ends, dtype, (), block, interpret)


def take_rows_weighted(x: jax.Array, y: jax.Array, scale: jax.Array,
                       tok: jax.Array, ends: jax.Array, *,
                       block: int = BLOCK, interpret: bool = False):
    """``add_rows(out, y, ..., scale=scale)`` transposed, ``x`` the
    cotangent of its result: (``scale[i] * x[tok[i]]`` [C, W], the
    cotangent of ``y``; ``sum(x[tok[i]] * y[i])`` [C], that of ``scale``),
    both float32, for the rows ``i < ends[-1]`` and zeros for the others. A
    block's rows meet its ``y`` and ``scale`` in VMEM, so neither product is
    a pass of XLA's over all C rows."""
    rows, dots = _take(x, tok, ends, jnp.float32,
                       (y.astype(jnp.float32), _lanes(scale)), block,
                       interpret)
    # a row's sum arrives as 128 partial sums, one a lane
    return rows, jnp.sum(dots, axis=1)


def _add_kernel(ends_ref, tok_ref, y_ref, *refs, block, groups, per_row,
                by_index, scaled):
    if scaled:
        w_ref, _, out_ref, staged, y_tile, w_tile, sem, y_sem = refs
    else:
        _, out_ref, staged, y_tile, sem, y_sem = refs
    base = pl.program_id(0) * block
    stop = base + jnp.clip(ends_ref[groups - 1] - base, 0, block)
    arrive = [pltpu.make_async_copy(y_ref.at[pl.ds(base, block)], y_tile,
                                    y_sem)]
    if scaled:
        arrive.append(pltpu.make_async_copy(
            w_ref.at[pl.ds(base, block)], w_tile, y_sem))
    load, store = _columns(staged, block, per_row, by_index)
    shape = (block // SUBLANES, SUBLANES, LANES)

    def fetch_row(i):
        pltpu.make_async_copy(_row(out_ref, tok_ref[i]),
                              _row(staged, i - base), sem).start()

    def put_row(i):
        pltpu.make_async_copy(_row(staged, i - base),
                              _row(out_ref, tok_ref[i]), sem).start()

    def segment(g, lo):
        """The block's rows of group ``g``, [lo, hi): distinct positions."""
        hi = jnp.clip(ends_ref[g], lo, stop)

        @pl.when(hi > lo)
        def _():
            _row_loop(lo, hi, fetch_row)

            @pl.when(lo == base)    # the block's first segment
            def _():
                for copy in arrive:
                    copy.wait()
            _wait_rows(hi - lo, block, staged, sem)
            # every row of the tile: those outside the segment hold what is
            # never written back
            for c in range(per_row):
                rows = y_tile[:, c * LANES:(c + 1) * LANES].astype(
                    jnp.float32)
                if scaled:
                    rows = rows * w_tile[...]
                store(c, load(c) + rows.reshape(shape))
            _row_loop(lo, hi, put_row)
            _wait_rows(hi - lo, block, staged, sem)
        return hi

    @pl.when(stop > base)
    def _():
        for copy in arrive:
            copy.start()
        jax.lax.fori_loop(0, groups, segment, base)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def add_rows(out: jax.Array, y: jax.Array, tok: jax.Array, ends: jax.Array,
             scale: Optional[jax.Array] = None, *, block: int = BLOCK,
             interpret: bool = False) -> jax.Array:
    """``out`` float32 [T, W] with ``y[i]`` (times ``scale[i]`` where a
    ``scale`` [C] is given) added to row ``tok[i]`` for every
    ``i < ends[-1]``, in place where the caller donates it (a loop's carry).
    ``y`` [C, W] (float32 or bfloat16; added as float32), ``tok`` int32 [C],
    ``ends`` int32 [G] nondecreasing: the rows between two of them must name
    **distinct** positions (a position's rows lie in different groups)."""
    assert out.dtype == jnp.float32, out.dtype
    rows, width = y.shape
    per_row = width // LANES
    block = block_rows(rows, block)
    scaled = scale is not None
    buffers = (y, _lanes(scale)) if scaled else (y,)
    if not interpret:
        buffers = tuple(pltpu.with_memory_space_constraint(a, pltpu.HBM)
                        for a in buffers)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    out = _by_row(out)
    dma = pltpu.SemaphoreType.DMA(())
    scratch = [pltpu.VMEM((block // SUBLANES, per_row, SUBLANES, 1, LANES),
                          jnp.float32),
               pltpu.VMEM((block, width), y.dtype)]
    if scaled:
        scratch.append(pltpu.VMEM((block, LANES), jnp.float32))
    return _from_by_row(pl.pallas_call(
        functools.partial(_add_kernel, block=block, groups=ends.shape[0],
                          per_row=per_row, scaled=scaled,
                          by_index=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // block,),
            in_specs=[anywhere] * (len(buffers) + 1), out_specs=anywhere,
            scratch_shapes=[*scratch, dma, dma]),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        # inputs count the prefetched integers: (ends, tok, *buffers, out)
        input_output_aliases={2 + len(buffers): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_add_rows",
    )(ends.astype(jnp.int32), tok.astype(jnp.int32), *buffers, out))


def _no_cotangent(x):
    """The cotangent of an integer argument."""
    return jnp.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gather(dtype, interpret, x, through, tok, ends):
    return take_rows(x, tok, ends, dtype, interpret=interpret), through


def _gather_fwd(dtype, interpret, x, through, tok, ends):
    return _gather(dtype, interpret, x, through, tok, ends), (tok, ends)


def _gather_bwd(dtype, interpret, res, cotangents):
    tok, ends = res
    g, g_through = cotangents
    return (jnp.zeros_like(g_through),
            add_rows(g_through, g, tok, ends, interpret=interpret),
            _no_cotangent(tok), _no_cotangent(ends))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(interpret, out, y, scale, tok, ends):
    return add_rows(out, y, tok, ends, scale, interpret=interpret)


def _combine_fwd(interpret, out, y, scale, tok, ends):
    return (_combine(interpret, out, y, scale, tok, ends),
            (y, scale, tok, ends))


def _combine_bwd(interpret, res, g):
    y, scale, tok, ends = res
    dy, dscale = take_rows_weighted(g, y, scale, tok, ends,
                                    interpret=interpret)
    return (g, dy.astype(y.dtype), dscale.astype(scale.dtype),
            _no_cotangent(tok), _no_cotangent(ends))


_gather.defvjp(_gather_fwd, _gather_bwd)
_combine.defvjp(_combine_fwd, _combine_bwd)


def gather(x: jax.Array, through: jax.Array, tok: jax.Array,
           ends: jax.Array, dtype=jnp.float32, *, interpret: bool = False):
    """(``take_rows`` of ``x``, ``through`` as it came), differentiable: the
    rows' cotangent is summed into **``through``'s** by ``add_rows``, in
    float32 and in place, and ``x`` gets none. ``through`` is ``x`` itself
    on its way through the loop that calls this (a scan's carry): the loop's
    backward pass then carries one accumulator where a cotangent handed to
    ``x``, a constant of the loop, would be a ``[T, W]`` of zeros filled,
    added into and added on, every trip."""
    return _gather(jnp.dtype(dtype), interpret, x, through, tok, ends)


def combine(out: jax.Array, y: jax.Array, scale: jax.Array, tok: jax.Array,
            ends: jax.Array, *, interpret: bool = False) -> jax.Array:
    """``add_rows`` of ``y``'s rows, each times its ``scale``,
    differentiable: ``take_rows_weighted`` of the result's cotangent is
    ``y``'s and ``scale``'s, ``out``'s is the result's own."""
    return _combine(interpret, out, y, scale, tok, ends)
