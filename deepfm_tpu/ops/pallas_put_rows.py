"""Pallas TPU kernel: write distinct rows into an HBM-resident table in
place, one asynchronous copy a row.

``table.at[uids].set(new, mode="drop")`` on a table-tall operand costs the
TPU 72 ns a slot as XLA's scatter, whatever the flags, while the same
512-byte line moves in 8 ns when it is gathered (PERF.md §6, PR 28). The
row-local table update (``Trainer._update_rows``) hands its write-back ids
that are **distinct**, so no two copies touch one row and none has to wait
for another. The kernel leaves the table where it is (``pl.ANY``, aliased
input to output, never blocked into VMEM) and the new rows too, reads the
ids from SMEM (scalar prefetch) and starts one ``(1, W)`` copy per slot,
HBM to HBM, ``block`` slots' copies in flight on one semaphore. All copies
are one size, so it counts the slots it started and waits for them in at
most ``log2(block) + 1`` rounds of waits, each for a power-of-two number of
rows. What it costs is the scalar core's loop over the slots (7 ns a slot
on a v5e, started or skipped; PERF.md §6, PR 30), not the copies, which is
why the loop is unrolled, a slot is tested with one unsigned compare, and
one pass over the ids serves every array that shares them (a table and its
optimizer state).

A slot whose id lies outside the table is **skipped**, never copied: an
out-of-bounds DMA is a device fault, not a dropped write. The result is
bit for bit XLA's scatter for distinct ids (a negative id is skipped too,
where the scatter would count it from the end: no caller has one).

``supported`` says where the compiled kernel applies (a TPU backend, a
float32 ``[V, W]`` table whose row is whole 128-lane lines); everything
else keeps the scatter. ``interpret=True`` runs the same kernel through the
Pallas interpreter, which is how the CPU tests hold it to the scatter.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Lanes of one vector register line: a row must be whole lines.
LANES = 128
#: Slots whose copies are in flight together (one grid step).
BLOCK = 2048
#: Slots a trip of the kernel's loop handles (Mosaic unrolls all or nothing).
UNROLL = 16


def supported(table) -> bool:
    """True where the compiled kernel can write this table's rows: a TPU
    backend and one float32 ``[V, W]`` array whose row is a whole number of
    128-lane lines (K=128: one line; a ``[V, 32]`` or ``[V]`` table is laid
    out with ids along the lanes, and a row is not one line there). Read
    from the backend and the shape, both of which the step's program
    records."""
    return (jax.default_backend() == "tpu"
            and table.ndim == 2 and table.dtype == jnp.float32
            and table.shape[1] % LANES == 0)


def _kernel(uids_ref, *refs, block, rows):
    # refs: n of new rows, n tables (aliased to) n outputs, the semaphore
    n = len(refs) // 3
    news, outs, sem = refs[:n], refs[2 * n:3 * n], refs[3 * n]
    base = pl.program_id(0) * block

    def start(i, started):
        for k in range(UNROLL):
            at = base + i * UNROLL + k
            uid = uids_ref[at]
            inside = uid.astype(jnp.uint32) < jnp.uint32(rows)

            @pl.when(inside)
            def _(at=at, uid=uid):
                for new_ref, out_ref in zip(news, outs):
                    pltpu.make_async_copy(
                        new_ref.at[pl.ds(at, 1)], out_ref.at[pl.ds(uid, 1)],
                        sem).start()
            started = started + inside.astype(jnp.int32)
        return started

    started = jax.lax.fori_loop(0, block // UNROLL, start, jnp.int32(0))
    # wait them: a wait takes its size from the descriptor, not from a copy
    rows_a_wait = 1 << (min(block, news[0].shape[0]).bit_length() - 1)
    while rows_a_wait:
        @pl.when((started & rows_a_wait) != 0)
        def _(m=rows_a_wait):
            for new_ref in news:
                pltpu.make_async_copy(new_ref.at[pl.ds(0, m)],
                                      new_ref.at[pl.ds(0, m)], sem).wait()
        rows_a_wait //= 2


def put_rows_many(tables: Sequence[jax.Array], uids: jax.Array,
                  news: Sequence[jax.Array], *, block: int = BLOCK,
                  interpret: bool = False) -> List[jax.Array]:
    """Each of ``tables`` (one shape ``[V, W]``, one dtype) with row
    ``uids[i]`` set to its ``news[k][i]`` for every slot whose id lies in
    ``[0, V)``; other slots are skipped. One launch and one pass over the
    ids for all of them (a table and its optimizer state share their ids).
    ``uids`` int32 ``[C]`` must be distinct among the in-bounds ones (two
    copies to one row would race); each of ``news`` is ``[C, W]``. The
    tables are updated in place where the caller donates them (a loop carry,
    a donated argument)."""
    n, table = len(tables), tables[0]
    assert all(t.shape == table.shape and t.dtype == table.dtype
               for t in tables), [(t.shape, t.dtype) for t in tables]
    rows = table.shape[0]
    slots = uids.shape[0]
    block = -(-min(block, slots) // UNROLL) * UNROLL
    uids = uids.astype(jnp.int32)
    if slots % block:  # a ragged last block: spare slots, skipped like any
        uids = jnp.concatenate(
            [uids, jnp.full((-slots % block,), rows, jnp.int32)])
    news = [new.astype(table.dtype) for new in news]
    if not interpret:   # (the interpreter does not know the primitive)
        # XLA would keep a trip's few new rows in VMEM, and a row copied
        # from there costs three times one copied from HBM (PERF.md §6,
        # PR 30)
        news = [pltpu.with_memory_space_constraint(new, pltpu.HBM)
                for new in news]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(uids.shape[0] // block,),
            in_specs=[anywhere] * (2 * n), out_specs=[anywhere] * n,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tables],
        # inputs count the prefetched ids: (uids, *news, *tables) -> tables
        input_output_aliases={1 + n + k: k for k in range(n)},
        interpret=interpret,
        name="embed_put_rows",
    )(uids, *news, *tables)


def put_rows(table: jax.Array, uids: jax.Array, new: jax.Array,
             **kw) -> jax.Array:
    """``put_rows_many`` of one table."""
    return put_rows_many((table,), uids, (new,), **kw)[0]
