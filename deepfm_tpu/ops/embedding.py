"""Embedding lookup ops — plain and row-sharded.

The reference keeps embedding tables either wholly on the parameter server
(``1-ps-cpu/...py:166-168``; every lookup crosses the gRPC wire) or fully
replicated per GPU (Horovod). The TPU-native design row-shards the table
across the ``model`` mesh axis and turns each lookup into a *dense*
local-gather + mask + ``psum`` — one ICI collective, no host round-trips
(SURVEY.md Stage 3; the mask-and-psum keeps shapes static for XLA).

``sharded_lookup`` is written to run inside ``shard_map`` where ``table`` is
the local shard and ``ids`` are the (replicated-over-model) global indices.
"""

from __future__ import annotations

import math
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def lookup(table: jax.Array, ids: jax.Array, *,
           axis_name: Optional[str] = None,
           strategy: str = "masked_psum") -> jax.Array:
    """Gather rows of ``table`` at ``ids``.

    table: [V, ...] (or local shard [V/m, ...] inside shard_map)
    ids:   int32 [...]
    Returns [..., *table.shape[1:]] (f32), reassembled across ``axis_name``
    shards when given.

    ``strategy`` selects the collective pattern for the sharded case (see
    TUNING.md §"Sharded embedding lookup" for the measured/analytic
    comparison):

    * ``masked_psum`` (default): local masked gather + psum of the [B,F,K]
      activations — traffic ∝ batch, wins when B·F ≪ V (the CTR regime:
      activations ~1.3 MB vs a ~15 MB table at the reference shape).
    * ``allgather_table``: all_gather the shards into the full table, then
      plain gather — traffic ∝ V·K, wins only when B·F ≫ V (huge batches
      over small tables); backward reduce-scatters the table cotangent.
    """
    if axis_name is None:
        return jnp.take(table, ids, axis=0)
    if strategy == "allgather_table":
        return sharded_lookup_allgather(table, ids, axis_name)
    if strategy != "masked_psum":
        raise ValueError(f"unknown embedding lookup strategy {strategy!r}")
    return sharded_lookup(table, ids, axis_name)


def sharded_lookup(local_table: jax.Array, ids: jax.Array, axis_name: str) -> jax.Array:
    """Row-sharded gather: local masked take + psum over the shard axis.

    Each shard owns rows ``[idx*rows_local, (idx+1)*rows_local)``. Out-of-range
    ids contribute zeros; the psum reassembles the full gather. O(shards)
    redundant local gathers, but fully dense and XLA/ICI-friendly.
    """
    idx = jax.lax.axis_index(axis_name)
    rows_local = local_table.shape[0]
    local_ids = ids.astype(jnp.int32) - idx * rows_local
    in_range = (local_ids >= 0) & (local_ids < rows_local)
    safe = jnp.clip(local_ids, 0, rows_local - 1)
    emb = jnp.take(local_table, safe, axis=0)
    mask = in_range
    if local_table.ndim > 1:
        mask = jnp.expand_dims(in_range, tuple(range(ids.ndim, emb.ndim)))
    emb = jnp.where(mask, emb, jnp.zeros((), emb.dtype))
    return jax.lax.psum(emb, axis_name)


def sharded_lookup_allgather(local_table: jax.Array, ids: jax.Array,
                             axis_name: str) -> jax.Array:
    """Row-sharded gather via table reassembly: rebuild the full [V, ...]
    table on every shard, then a plain local gather.

    Reassembled by ``all_gather_invariant``, not ``lax.all_gather``: the
    result is identical and typed replicated over the axis. Communication
    is O(V·K) per step independent of batch (vs masked+psum's O(B·F·K));
    the table cotangent reduces back with the
    transposed collective. Only competitive when ids volume exceeds table
    volume — selected via cfg.embedding_lookup for large-batch/small-table
    regimes (``test_trainer.py::test_allgather_lookup_matches_masked_psum``
    holds both strategies to the same weights)."""
    full = all_gather_invariant(local_table, axis_name)
    return jnp.take(full, ids.astype(jnp.int32), axis=0)


def all_gather_invariant(x: jax.Array, axis_name: str) -> jax.Array:
    """The shards' ``x`` ``[n, ...]`` one after another in the axis's order,
    ``[shards * n, ...]``, the same array on every shard and typed so:
    each shard's ``x`` in its own slice of zeros, summed over the axis.
    Every element has one contributor that is not zero, so the sum is
    exact. ``lax.all_gather`` moves half the bytes but its result is typed
    varying over the axis, which ``shard_map(check_vma)`` does not let
    through a replicated out spec."""
    n = x.shape[0]
    full = jnp.zeros((n * jax.lax.axis_size(axis_name), *x.shape[1:]),
                     x.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(
        full, x, jax.lax.axis_index(axis_name) * n, axis=0)
    return jax.lax.psum(full, axis_name)


# Vocab rows are padded to a multiple of this REGARDLESS of the current
# mesh, so the table shape — and therefore every checkpoint — is identical
# across all power-of-two mesh_model layouts up to 64-way. Without a fixed
# multiple, a checkpoint trained row-sharded (padded to mesh_model) could
# not restore on a different mesh (eval single-chip, resume after resize).
_VOCAB_PAD_MULTIPLE = 64


def padded_vocab(feature_size: int, num_shards: int) -> int:
    """Round the vocabulary up so the table divides evenly across shards AND
    keeps a mesh-independent shape (see _VOCAB_PAD_MULTIPLE).

    Padding rows are zero-initialized and unreachable from real ids, so they
    stay exactly zero under training (zero data gradient; l2 gradient of a
    zero row is zero). Non-power-of-two shard counts (no TPU topology has
    them) fall back to lcm-style padding and are self-consistent only."""
    m = math.lcm(_VOCAB_PAD_MULTIPLE, max(num_shards, 1))
    if m != _VOCAB_PAD_MULTIPLE:
        _warn_mesh_dependent_padding(num_shards)
    return ((feature_size + m - 1) // m) * m


def _warn_mesh_dependent_padding(num_shards: int) -> None:
    """Once-per-process heads-up: shard counts that don't divide 64 make
    the padding mesh-dependent again, so checkpoints from this mesh won't
    restore on meshes with a different padding (surface it at save/train
    time, not as a confusing restore failure later)."""
    global _pad_warned
    if _pad_warned:
        return
    _pad_warned = True
    from ..utils import logging as ulog  # noqa: PLC0415 (avoid eager import)
    ulog.warning(
        f"mesh_model={num_shards} does not divide {_VOCAB_PAD_MULTIPLE}: "
        f"embedding padding becomes mesh-dependent and checkpoints from "
        f"this mesh are NOT portable to meshes with different padding")


_pad_warned = False


# ---------------------------------------------------------------------------
# Deterministic id hashing (multi-table bucketed embeddings)
# ---------------------------------------------------------------------------
# Stateless uint32 mixing (Knuth multiplicative + murmur3-style finalizer):
# determinism across processes, restarts and resume comes for free because
# the mapping is pure arithmetic — no dictionaries, no RNG, no host state.
# All math stays in uint32 (JAX_ENABLE_X64 off in tests and on TPU).

# NumPy scalars, not jnp: a jnp scalar is a device array, and creating one
# at import starts the XLA backend before the launcher can call
# jax.distributed.initialize() — which then refuses to run.
_KNUTH = np.uint32(2654435761)        # 2^32 / golden ratio
_MIX1 = np.uint32(0x85EBCA6B)         # murmur3 fmix32 constants
_MIX2 = np.uint32(0xC2B2AE35)
TABLE_ASSIGN_SALT = 0x9E3779B9        # distinct stream for table selection


def hash_mix(ids: jax.Array, salt: int) -> jax.Array:
    """Avalanche-mix ids (any int dtype) into uniform uint32, salted so each
    consumer (table assignment, each table's bucketing) draws an independent
    stream from the same id."""
    x = ids.astype(jnp.uint32) ^ jnp.uint32(salt)
    x = x * _KNUTH
    x = x ^ (x >> 16)
    x = x * _MIX1
    x = x ^ (x >> 13)
    x = x * _MIX2
    x = x ^ (x >> 16)
    return x


def hash_bucket(ids: jax.Array, num_buckets: int, salt: int) -> jax.Array:
    """Bucket index in [0, num_buckets) for each id — table ``salt`` gives
    every table an independent bucketing, so two ids colliding in one table
    almost surely separate in another."""
    return (hash_mix(ids, salt) % jnp.uint32(num_buckets)).astype(jnp.int32)


def hash_table_assign(ids: jax.Array, num_tables: int) -> jax.Array:
    """Table index in [0, num_tables) per id (embedding_assign="hash")."""
    return (hash_mix(ids, TABLE_ASSIGN_SALT)
            % jnp.uint32(num_tables)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Sparse-update plan: static-shape dedup of one batch's ids
# ---------------------------------------------------------------------------


class PlanEntry(NamedTuple):
    """Dedup of one batch's ids against ONE physical table.

    uids: int32 [U]    sorted unique row ids; U = ids.size (static). Slots
                       beyond the real uniques hold ``num_rows`` — OUT OF
                       BOUNDS by construction, so gathers read zero
                       (mode="fill") and scatters drop them: no sentinel
                       row and no dynamic shapes needed.
    inv:  int32 [...]  ids-shaped map position -> uid slot.
    mask: f32   [...]  1.0 where the position reads this table (hashed
                       multi-table assignment), else 0.0. None = all
                       positions (monolithic table).
    num_rows: int      static row count used as the OOB fill id.
    touched: bool [R]  (counting plans only) per-ROW touch marks over the
                       id space — enables the select-writeback in
                       ``scatter_rows``. None on ``make_plan`` plans.
    rank: int32 [R]    (counting plans only) row id -> uid slot for touched
                       rows (arbitrary elsewhere, masked by ``touched``).
    """
    uids: jax.Array
    inv: jax.Array
    mask: Optional[jax.Array]
    num_rows: int
    touched: Optional[jax.Array] = None
    rank: Optional[jax.Array] = None


@jax.named_scope("embed")
def make_plan(ids: jax.Array, num_rows: int,
              mask: Optional[jax.Array] = None) -> PlanEntry:
    """Build a PlanEntry. ``ids`` must already be per-table row ids; masked
    positions must carry the OOB value ``num_rows`` (they then share the
    unique fill value and vanish in the drop-scatter)."""
    flat = ids.reshape(-1).astype(jnp.int32)
    uids, inv = jnp.unique(
        flat, size=flat.shape[0], fill_value=num_rows, return_inverse=True)
    return PlanEntry(uids=uids, inv=inv.reshape(ids.shape).astype(jnp.int32),
                     mask=mask, num_rows=num_rows)


@jax.named_scope("embed")
def make_plan_counting(ids: jax.Array, num_rows: int,
                       mask: Optional[jax.Array] = None) -> PlanEntry:
    """``make_plan`` with bit-identical uids/inv, built by counting instead
    of sorting.

    ``jnp.unique(size=N)`` lowers to a sort-based program (~5x the cost of
    this formulation on XLA:CPU). A presence-mark pass
    over the [num_rows+1] id space recovers the same sorted dedup:

        mark[r]   = 1 iff r occurs in ids            (one scatter)
        csum      = inclusive prefix sum of mark
        rank[r]   = csum[r] - mark[r]                (# distinct values < r)
        inv       = rank[ids]                        (index in sorted uniques)
        uids[j]   = searchsorted(csum, j+1)          (j-th distinct value;
                    past the last unique this is num_rows+1 -> clamped to
                    the OOB fill id num_rows, same as unique's fill slots)

    Cost is O(ids + num_rows); only selected for tables small enough that
    the vocab-shaped prefix sum beats the sort (ops.pallas_embedding owns
    that choice). The touched/rank outputs additionally let
    ``scatter_rows`` write back via a select over the id space instead of
    a scatter — same result, one cheap vocab-shaped pass."""
    flat = ids.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    mark = jnp.zeros((num_rows + 1,), jnp.int32).at[flat].set(1)
    csum = jnp.cumsum(mark)
    rank = csum - mark                       # exclusive rank per row id
    inv = jnp.take(rank, flat)
    uids = jnp.minimum(
        jnp.searchsorted(csum, jnp.arange(1, n + 1, dtype=csum.dtype),
                         side="left"),
        num_rows).astype(jnp.int32)
    return PlanEntry(uids=uids, inv=inv.reshape(ids.shape).astype(jnp.int32),
                     mask=mask, num_rows=num_rows,
                     touched=mark[:num_rows].astype(jnp.bool_),
                     rank=rank[:num_rows].astype(jnp.int32))


def valid_rows(entry: PlanEntry) -> jax.Array:
    """Bool [U]: which uid slots name a real (in-bounds) touched row."""
    return entry.uids < entry.num_rows


@jax.named_scope("embed")
def gather_rows(table: jax.Array, entry: PlanEntry) -> jax.Array:
    """[U, ...] rows at ``entry.uids``. OOB fill slots read as ZERO
    (``mode="fill"`` — jnp.take's default fill is NaN, which would poison
    any masked-multiply downstream). Fill-slot values are never referenced
    by ``inv`` and their updates are dropped by the OOB scatter; zeros keep
    them inert in sums/l2 as well."""
    return jnp.take(table, entry.uids, axis=0, mode="fill", fill_value=0)


@jax.named_scope("embed")
def lookup_rows(rows: jax.Array, entry: PlanEntry) -> jax.Array:
    """Positionwise view of gathered rows: rows[inv] (masked in hashed
    mode). Differentiating this gather w.r.t. ``rows`` IS the segment-sum:
    the transpose is a scatter-add of the per-position cotangents into [U]
    row slots — cost ∝ batch, never ∝ vocab."""
    out = jnp.take(rows, entry.inv, axis=0)
    if entry.mask is not None:
        mask = entry.mask.reshape(
            entry.mask.shape + (1,) * (out.ndim - entry.mask.ndim))
        out = out * mask
    return out


@jax.named_scope("embed")
def scatter_rows(table: jax.Array, entry: PlanEntry,
                 new_rows: jax.Array) -> jax.Array:
    """Write back updated touched rows; the OOB fill slots are DROPPED by
    XLA's default scatter mode, so unique's padding can never alias a real
    row. Distinct in-bounds uids make the scatter duplicate-free and
    deterministic.

    Counting plans (touched/rank present) write back as a SELECT over the
    id space instead — ``where(touched, new_rows[rank], table)`` — which
    XLA:CPU executes as one fused vocab-shaped pass (~7x cheaper than its
    row scatter) and is element-for-element identical:
    rank[r] is exactly the uid slot of each touched row r, untouched rows
    keep their bits. A table shorter than the id space (the tiered hot
    cache gathers with slot ids < hot_rows < padded_vocab) truncates the
    marks — all touched ids are in-bounds for it by construction."""
    if entry.touched is None:
        return table.at[entry.uids].set(new_rows)
    keep = entry.touched[: table.shape[0]]
    sel = jnp.take(new_rows, entry.rank[: table.shape[0]], axis=0)
    keep = keep.reshape((-1,) + (1,) * (table.ndim - 1))
    return jnp.where(keep, sel.astype(table.dtype), table)


@jax.named_scope("embed")
def set_rows_scalar(table: jax.Array, entry: PlanEntry,
                    value: jax.Array) -> jax.Array:
    """Set every touched row of a rank-1 per-row array (the lazy-Adam
    ``tau`` last-touch stamps) to ``value``. Same select-vs-scatter split
    as ``scatter_rows``."""
    if entry.touched is None:
        return table.at[entry.uids].set(value)
    keep = entry.touched[: table.shape[0]]
    return jnp.where(keep, jnp.asarray(value, table.dtype), table)


# ---------------------------------------------------------------------------
# Row sums of a batch's view cotangents (the dense step's row-local update)
# ---------------------------------------------------------------------------
# ``Trainer._update_rows`` differentiates the ``[B, F, ...]`` views of the
# tables and needs, per distinct looked-up row, the float32 sum of its
# positions' cotangents: the rows of the table-shaped gradient that are not
# zero, without the table. One two-operand sort of (id, position) does the
# dedup (``jnp.unique`` is three times a sort and 6.2 ms where this is 0.44:
# PERF.md §6, PR 28); the cotangents, permuted into id order, are
# scatter-added into an array as short as the batch, where a row costs 9 ns
# against 74 in the table (the cost is the operand's height, not the
# scatter's). Plain sums, no prefix-sum-and-subtract, so nothing cancels.


class RowSums(NamedTuple):
    """The distinct rows one batch looked up, and what each receives.

    uids:  int32 [M]      the distinct in-bounds row ids, ascending, in the
                          first ``count`` slots; every later slot holds an
                          id past the table (``num_rows + slot``: distinct,
                          ascending, out of bounds), so a gather reads fill
                          there and a scatter drops it. M is static: the
                          positions rounded up to ``multiple``.
    sums:  f32 [M, D]     per slot, the sum of its positions' cotangents
                          (all tables' columns side by side); past count,
                          nothing a scatter keeps.
    count: int32 []       distinct in-bounds rows.
    """
    uids: jax.Array
    sums: jax.Array
    count: jax.Array


class RowPlan(NamedTuple):
    """One sort of a batch's (id, position) pairs: which distinct rows its
    N positions name, and where each position's row lies among them. What
    ``sum_rows`` sums by, and what a forward that reads each distinct row
    once expands by (``Trainer._value_and_view_grads``).

    The ids are sorted ascending as keys: negative ones counted from the
    end, every id outside ``[0, kept rows)`` the one key ``int32`` max.

    perm:  int32 [N]  the position of the i-th id in that order.
    slot:  int32 [N]  the row slot of the i-th id in that order: distinct
                      keys numbered ascending from 0.
    uids:  int32 [M]  the distinct keys ascending, then ``int32`` max. M is
                      static: the positions rounded up to ``multiple``.
    held:  int32 []   distinct kept rows: the first ``held`` of ``uids``.
    count: int32 []   those of them in ``[0, valid_rows)``, which lie
                      first: the rows that receive.
    """
    perm: jax.Array
    slot: jax.Array
    uids: jax.Array
    held: jax.Array
    count: jax.Array

    def ids_of(self, upto: jax.Array, num_rows: int) -> jax.Array:
        """``uids`` with every slot from ``upto`` on an id past the table
        (``num_rows + slot``: distinct, ascending, out of bounds), so a
        gather reads fill there and a scatter drops it."""
        at = jnp.arange(self.uids.shape[0], dtype=jnp.int32)
        return jnp.where(at < upto, self.uids, num_rows + at)

    def slot_of_position(self) -> jax.Array:
        """int32 [N]: the row slot of every position, in the batch's order
        (``slot`` through the inverse of ``perm``)."""
        return jax.lax.sort((self.perm, self.slot), num_keys=1)[1]


@jax.named_scope("embed")
def plan_rows(ids: jax.Array, num_rows: int, valid_rows: int,
              multiple: int = 1, *, keep_pad_rows: bool = False) -> RowPlan:
    """The ``RowPlan`` of ``ids`` (any shape, N positions; negative ids
    count from the end, as ``jnp.take`` reads them). Ids outside
    ``[0, valid_rows)`` share the one key that receives nothing (pad rows,
    ids past the table); with ``keep_pad_rows`` a pad row (``valid_rows <=
    id < num_rows``) keeps a key of its own, after every row that receives
    — a forward reads it like any other — and only the ids past the table,
    which all read fill, share one."""
    flat = ids.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    m = -(-n // multiple) * multiple
    flat = jnp.where(flat < 0, flat + num_rows, flat)
    last = jnp.iinfo(jnp.int32).max
    kept = num_rows if keep_pad_rows else valid_rows
    key = jnp.where((flat >= 0) & (flat < kept), flat, last)
    key, perm = jax.lax.sort((key, jnp.arange(n, dtype=jnp.int32)),
                             num_keys=1)
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), key[1:] != key[:-1]])
    slot = jnp.cumsum(first.astype(jnp.int32)) - 1      # row slot a position
    held = jnp.sum(first & (key != last), dtype=jnp.int32)
    count = (jnp.sum(first & (key < valid_rows), dtype=jnp.int32)
             if keep_pad_rows else held)
    # each distinct id once, ascending: a sort is the cheapest compaction
    uids = jnp.sort(jnp.where(first, key, last))
    uids = jnp.concatenate([uids, jnp.full((m - n,), last, jnp.int32)])
    return RowPlan(perm=perm, slot=slot, uids=uids, held=held, count=count)


@jax.named_scope("embed")
def sum_planned(plan: RowPlan, cots: jax.Array, num_rows: int) -> RowSums:
    """Per distinct row of ``plan`` the float32 sum of the rows of ``cots``
    ``[N, D]`` at its positions; rows outside ``[0, valid_rows)`` (slots
    from ``plan.count`` on) hold nothing a scatter keeps."""
    sums = jnp.zeros((plan.uids.shape[0], cots.shape[1]),
                     jnp.float32).at[plan.slot].add(
        jnp.take(cots.astype(jnp.float32), plan.perm, axis=0),
        indices_are_sorted=True)
    return RowSums(uids=plan.ids_of(plan.count, num_rows), sums=sums,
                   count=plan.count)


#: Words of one 128-lane line. A table row narrower than it is strided over
#: tiles (ids run along the lanes) and dear to gather: ``take_planned`` pays.
LANES = 128


def narrow_rows(table: jax.Array) -> bool:
    """Whether ``take_planned`` reads ``table``'s views cheaper than
    ``jnp.take`` does a position: a row narrower than one 128-lane line. It
    is one copy either way, and which is cheaper is a matter of what a table
    row costs. In a ``[V, 32]`` table a row costs 39.9 ns to gather, in a
    ``[V]`` one 7.6, a position copied from a batch-tall ``[N, 33]`` array
    4.2, so at 6.8 positions a distinct row the two position gathers read
    7.65 ms and the rows form 2.44 (PERF.md §6, PR 42: Step 0); a row of
    whole lines gathers at 8.2 ns straight from the table (K = 128), which
    is what the copy from the batch-tall array would cost again."""
    return math.prod(table.shape[1:]) < LANES


@jax.named_scope("embed")
def take_planned(tables: Sequence[jax.Array], plan: RowPlan, shape,
                 trip: int) -> List[jax.Array]:
    """``jnp.take(table, ids, axis=0)`` of every table of ``tables`` (one
    height, the ``num_rows`` of the plan) for the ``ids`` (of ``shape``)
    that ``plan`` was made of with ``keep_pad_rows``, bit for bit, but each
    distinct row read from its table once: the rows are gathered, ``trip``
    slots a trip and as many trips as hold them (a spare slot of a gather
    costs a filled one), into one array as tall as the plan, the tables'
    columns side by side, and every position copies its slot of that array
    (one copy for all the tables: a position of a ``[N]`` array costs what
    one of a ``[V]`` table does, 7.6 ns, a row of ``[N, 33]`` 4.2; PERF.md
    §6, PR 42). An id past the table names a slot past the table too, and
    reads ``jnp.take``'s fill. Exact for any batch at static shapes."""
    num_rows = tables[0].shape[0]
    widths = [math.prod(t.shape[1:]) for t in tables]
    cuts = np.cumsum([0] + widths)
    uids = plan.ids_of(plan.held, num_rows)
    trips = (plan.slot[-1] + trip) // trip      # slots read: slot[-1] + 1

    # The rows travel as raw words, the widest table's type bit-cast (values
    # of a narrower type pass through it and back: exact). Left as floats,
    # XLA's bfloat16 propagation sees that the views' one reader casts them
    # (bfloat16 compute), makes the loop's copy of the table bfloat16 and
    # converts the whole table on the way into the loop, a pass over it a
    # step (`convert bf16[V, 32]` in a step with one narrow table, as
    # compiled for a v5e; PERF.md §6, PR 42).
    wide = jnp.result_type(*tables)
    words = jnp.dtype(f"uint{8 * wide.itemsize}")

    def one(carry):
        i, rows = carry
        at = jax.lax.dynamic_slice_in_dim(uids, i * trip, trip)
        got = jnp.concatenate([
            jnp.take(t, at, axis=0).reshape(trip, w).astype(wide)
            for t, w in zip(tables, widths)], axis=1)
        return i + 1, jax.lax.dynamic_update_slice_in_dim(
            rows, jax.lax.bitcast_convert_type(got, words), i * trip, axis=0)

    rows = jnp.zeros((uids.shape[0], cuts[-1]), words)
    varying = tuple(jax.typeof(uids).vma)   # inside shard_map: the ids' axes
    if varying:     # the loop's carry is typed as what the trips write
        rows = jax.lax.pcast(rows, varying, to="varying")
    _, rows = jax.lax.while_loop(lambda carry: carry[0] < trips, one,
                                 (jnp.zeros((), jnp.int32), rows))
    views = jax.lax.bitcast_convert_type(
        jnp.take(rows, plan.slot_of_position(), axis=0), wide)
    return [views[:, cuts[j]:cuts[j + 1]].astype(t.dtype).reshape(
        tuple(shape) + t.shape[1:]) for j, t in enumerate(tables)]


def sum_rows(ids: jax.Array, cots: jax.Array, num_rows: int,
             valid_rows: int, multiple: int = 1) -> RowSums:
    """Per distinct id of ``ids`` (any shape, N positions; negative ids
    count from the end, as ``jnp.take`` reads them) the float32 sum of the
    rows of ``cots`` ``[N, D]`` at its positions. Ids outside
    ``[0, valid_rows)`` receive nothing (pad rows, ids past the table)."""
    return sum_planned(plan_rows(ids, num_rows, valid_rows, multiple), cots,
                       num_rows)


# ---------------------------------------------------------------------------
# Row-sharded all-to-all exchange (--embedding_shard rows)
# ---------------------------------------------------------------------------
# The sparse path's plan (PlanEntry.uids, sorted ascending with OOB fill)
# meets a row-sharded table here: each model peer takes an equal contiguous
# slice of the uid positions, buckets its slice by owner shard (sorted uids
# make owner runs contiguous — two searchsorted calls give the bucket
# bounds), ships static-shape padded request sets over ``lax.all_to_all``,
# the owners answer with a second all_to_all, and a zeros+psum reassembly
# replicates the gathered rows on every peer (psum output is provably
# replicated, which shard_map's check_vma needs downstream; all_gather's is
# not). Every element of the result has exactly ONE nonzero contributor in
# the psum, so the exchange is bit-identical to ``gather_rows`` on the
# unsharded table — no float reassociation anywhere.


class ExchangePlan(NamedTuple):
    """Static-shape routing for one table's row exchange, built per step
    from the (model-replicated) PlanEntry. All shapes are static: ``reqs``
    pads each owner bucket to the slice capacity C = ceil(U / D) with the
    OOB id ``num_rows``, which owners answer with zero rows and the
    reassembly never reads.

    reqs:     int32 [D, C]  row ids this peer requests from each owner.
    flat_idx: int32 [C]     position into the flattened [D*C] response
                            block for this peer's slice; D*C (OOB -> fill 0)
                            for pad slots.
    num_rows: int           global rows in the table (OOB fill id).
    rows_local: int         rows per shard (num_rows // num_shards).
    num_shards: int         model-axis size D.
    n_ids: int              U — uid slot count (static = batch ids.size).
    """
    reqs: jax.Array
    flat_idx: jax.Array
    num_rows: int
    rows_local: int
    num_shards: int
    n_ids: int


@jax.named_scope("embed")
def build_exchange(entry: PlanEntry, num_shards: int,
                   axis_name: str) -> ExchangePlan:
    """Bucket this peer's uid slice by owner shard. Must run inside
    shard_map over ``axis_name``; the batch (hence the plan) is replicated
    over the model axis, so slicing by ``axis_index`` splits the request
    work D ways without any prior communication."""
    if entry.num_rows % num_shards:
        raise ValueError(
            f"table rows {entry.num_rows} not divisible by {num_shards} "
            f"shards")
    uids = entry.uids
    n = uids.shape[0]
    d = num_shards
    rows_local = entry.num_rows // d
    cap = -(-n // d)
    r = jax.lax.axis_index(axis_name)
    pad = jnp.full((d * cap - n,), entry.num_rows, uids.dtype)
    u_pad = jnp.concatenate([uids, pad])          # sorted: fill is the max
    sl = jax.lax.dynamic_slice_in_dim(u_pad, r * cap, cap)
    bounds = jnp.searchsorted(
        sl, jnp.arange(d + 1, dtype=sl.dtype) * rows_local,
        side="left").astype(jnp.int32)            # [D+1] owner-run bounds
    starts, ends = bounds[:-1], bounds[1:]
    idx = starts[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    valid = idx < ends[:, None]
    reqs = jnp.where(valid, jnp.take(sl, jnp.clip(idx, 0, cap - 1)),
                     entry.num_rows).astype(jnp.int32)
    owner = (sl // rows_local).astype(jnp.int32)  # fill ids land on D
    rank = jnp.arange(cap, dtype=jnp.int32) - jnp.take(
        starts, jnp.clip(owner, 0, d - 1))
    flat_idx = jnp.where(owner < d, owner * cap + rank, d * cap)
    return ExchangePlan(reqs=reqs, flat_idx=flat_idx,
                        num_rows=entry.num_rows, rows_local=rows_local,
                        num_shards=d, n_ids=n)


@jax.named_scope("embed")
def exchange_rows(local_table: jax.Array, ex: ExchangePlan,
                  axis_name: str) -> jax.Array:
    """Gather ``ex``'s uid rows from a row-sharded table: all_to_all the
    request sets, owner-gather (OOB and other-shard ids read zero),
    all_to_all the responses back, reassemble + replicate via psum.
    Returns [U, ...rows] bit-identical to ``gather_rows`` on the full
    table. Runs inside shard_map over ``axis_name``."""
    d, cap = ex.num_shards, ex.reqs.shape[1]
    r = jax.lax.axis_index(axis_name)
    recv = jax.lax.all_to_all(ex.reqs, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)   # [D, C] asks
    local = recv - r * ex.rows_local
    ok = (local >= 0) & (local < ex.rows_local)
    safe = jnp.where(ok, local, ex.rows_local)
    resp = jnp.take(local_table, safe.reshape(-1), axis=0, mode="fill",
                    fill_value=0).reshape((d, cap) + local_table.shape[1:])
    got = jax.lax.all_to_all(resp, axis_name, split_axis=0,
                             concat_axis=0, tiled=True)    # [D, C, ...]
    flat = got.reshape((d * cap,) + got.shape[2:])
    mine = jnp.take(flat, ex.flat_idx, axis=0, mode="fill", fill_value=0)
    return all_gather_invariant(mine, axis_name)[:ex.n_ids]


@jax.named_scope("embed")
def owner_scatter_add(g_rows: jax.Array, entry: PlanEntry, num_shards: int,
                      axis_name: Optional[str]) -> tuple[jax.Array, jax.Array]:
    """Scatter per-uid cotangents into this shard's table space.

    Returns (grad [rows_local, ...], touched bool [rows_local]): the
    contribution of THIS replica's batch to the rows this shard owns.
    Ids owned elsewhere (and the plan's OOB fill slots) route to the
    ``rows_local`` sentinel and are dropped by XLA's default scatter mode —
    the sentinel is non-negative on purpose, negative indices would wrap.
    With ``axis_name=None`` (one shard) this degrades to the plain
    table-space segment scatter."""
    rows_local = entry.num_rows // num_shards
    off = 0
    if axis_name is not None:
        off = jax.lax.axis_index(axis_name) * rows_local
    local = entry.uids - off
    owned = (local >= 0) & (local < rows_local) & valid_rows(entry)
    safe = jnp.where(owned, local, rows_local)
    grad = jnp.zeros((rows_local,) + g_rows.shape[1:],
                     g_rows.dtype).at[safe].add(g_rows)
    touched = jnp.zeros((rows_local,), jnp.bool_).at[safe].set(True)
    return grad, touched


def exchange_payload_bytes(n_ids: int, row_elems: int, num_shards: int,
                           itemsize: int = 4) -> int:
    """Analytic per-device bytes for one table's forward exchange: the
    request all_to_all (D·C int32 ids), the response all_to_all (D·C rows),
    and the psum reassembly buffer (D·C rows; a ring all-reduce moves
    ~2(D-1)/D of it per device). C = ceil(n_ids / D). Zero when unsharded.
    TUNING §2.11 derives when this beats replicating the table."""
    if num_shards <= 1:
        return 0
    cap = -(-n_ids // num_shards)
    block = num_shards * cap
    return block * 4 + 2 * block * row_elems * itemsize


def pad_row_mask(num_rows_local: int, feature_size: int,
                 axis_name: Optional[str] = None) -> jax.Array:
    """Bool [num_rows_local]: True for real vocabulary rows, False for
    ``padded_vocab`` padding. Inside shard_map the table is a local shard;
    ``axis_name`` recovers the global row index."""
    row = jnp.arange(num_rows_local)
    if axis_name is not None:
        row = row + jax.lax.axis_index(axis_name) * num_rows_local
    return row < feature_size


def mask_pad_rows(x: jax.Array, feature_size: int,
                  axis_name: Optional[str] = None) -> jax.Array:
    """Zero the padded_vocab pad rows of a table-shaped array (used on
    dense embedding grads: pad rows are unreachable so their grads are
    already zero — this makes the exclusion a structural guarantee rather
    than an emergent property)."""
    if axis_name is None and x.shape[0] <= feature_size:
        return x
    keep = pad_row_mask(x.shape[0], feature_size, axis_name)
    keep = keep.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(keep, x, jnp.zeros((), x.dtype))
