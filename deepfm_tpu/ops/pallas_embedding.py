"""Kernel-leg selection for the sparse embedding plane.

The sparse update path has three hot seams:

  1. **plan build** — ``make_plan``'s ``jnp.unique(size=N)`` lowers to a
     sort-based program;
  2. **gather + segment-sum cotangent** — one forward gather per embedding
     name, and one batch-sized scatter-add per name in the backward;
  3. **cache install** — ``TieredEmbeddingRuntime`` launched one pow2-padded
     jit scatter per array (w/m/v/tau = 4 launches) per transaction.

Each seam has the legs :func:`resolve` can return for it:

  * ``pallas`` — seam 2 only: :func:`take_rows_pallas`, compiled on TPU where
    :func:`supported` says its working set fits VMEM. The Pallas plan-build
    and install kernels this module used to carry were deleted: the TPU
    lowering refuses their scalar stores into VMEM before Mosaic sees them,
    so ``auto`` could select a program that did not exist (PERF.md, PR 21).
    The kernel bodies also run through the Pallas interpreter on CPU
    (``interpret=True``) so the tier-1 suite checks them against NumPy
    oracles without hardware.
  * ``opt`` — a restructured XLA program with bit-identical outputs: the
    counting plan build (``ops.embedding.make_plan_counting``), the
    select-writeback (``scatter_rows`` on counting plans), and the fused
    multi-array install.
  * ``ref`` — the seed formulation, byte-for-byte (``--embedding_kernels
    off`` restores it everywhere: the kill switch).

The one shape-dependent rule: the counting plan build does a vocab-shaped
prefix sum, so it wins only while the physical table is small relative to the
sort cost — above ``PLAN_COUNT_MAX_ROWS`` rows ``auto`` keeps the sort-based
``make_plan`` (and with it the scatter writeback, whose cost does not scale
with the vocab).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import embedding as emb_ops

#: embedding_kernels values (config-validated).
MODES = ("auto", "xla", "off")

# The counting plan build costs one [rows+1] prefix sum + one presence
# scatter; the sort-based unique costs O(N log N) independent of rows.
# Measured crossover on XLA:CPU is far above 4x262144 hashed buckets and a
# 100k-row monolithic table; 2M rows keeps a safety margin before the
# vocab-shaped pass could dominate. Not measured on the chip (ROADMAP C4+).
PLAN_COUNT_MAX_ROWS = 2_000_000

# Scoped-VMEM limit the take kernels are compiled with. They keep the
# [U, D] row block and the [N, D] batch block whole in VMEM, each row
# padded to the 128-lane tile and each block double-buffered by the
# pipeline; at the reference shape (U = N = 39,936, D = 33) that is ~82 MB,
# which a v5e's 128 MiB VMEM takes and the 16 MiB default limit does not
# (compiled on the chip at this setting: PERF.md, PR 21).
_VMEM_LIMIT = 100 * 1024 * 1024
_LANES = 128


def supported(kernel: str, *, num_rows: int = 0, n_ids: int = 0,
              width: int = 1) -> bool:
    """True when seam ``kernel`` ("plan" | "take" | "install") has a Pallas
    kernel that can run COMPILED at this shape: only ``take``, on a TPU
    backend, with ``num_rows`` gathered rows + ``n_ids`` positions of
    ``width`` columns fitting the VMEM limit it is compiled with."""
    if kernel not in ("plan", "take", "install"):
        raise ValueError(f"unknown kernel {kernel!r}")
    if kernel != "take" or jax.default_backend() != "tpu":
        return False
    lanes = -(-width // _LANES) * _LANES
    return 2 * 4 * lanes * (num_rows + n_ids) <= _VMEM_LIMIT


def resolve(mode: str, kernel: str, *, num_rows: int = 0, n_ids: int = 0,
            width: int = 1) -> str:
    """Pick the leg ("pallas" | "opt" | "ref") for one seam.

    ``off`` is the kill switch: the seed path everywhere, bit-for-bit.
    ``xla`` forces the optimized XLA legs even on TPU. ``auto`` takes the
    compiled kernel where :func:`supported` allows and the optimized XLA
    leg elsewhere — except the plan seam, where tables above
    ``PLAN_COUNT_MAX_ROWS`` keep the sort-based reference build (the
    vocab-shaped counting pass would scale with rows; the sort does not)."""
    if mode not in MODES:
        raise ValueError(f"embedding_kernels must be one of {MODES}, "
                         f"got {mode!r}")
    if mode == "off":
        return "ref"
    if kernel == "plan" and num_rows > PLAN_COUNT_MAX_ROWS:
        return "ref"
    if mode == "auto" and supported(
            kernel, num_rows=num_rows, n_ids=n_ids, width=width):
        return "pallas"
    return "opt"


# ---------------------------------------------------------------------------
# Seam 1: device-side plan build (unique + remap, static shapes)
# ---------------------------------------------------------------------------


def plan_build(ids: jax.Array, num_rows: int,
               mask: Optional[jax.Array] = None, *,
               mode: str = "auto") -> emb_ops.PlanEntry:
    """Build a sparse-update plan through the selected leg. All legs emit
    bit-identical uids/inv; the counting legs additionally carry the
    touched/rank select-writeback companions."""
    leg = resolve(mode, "plan", num_rows=num_rows, n_ids=ids.size)
    if leg == "opt":
        return emb_ops.make_plan_counting(ids, num_rows, mask)
    return emb_ops.make_plan(ids, num_rows, mask)


# ---------------------------------------------------------------------------
# Seam 2: fused gather forward + segment-sum backward (custom VJP)
# ---------------------------------------------------------------------------
# Forward: out[p] = rows[inv[p]] for every batch position p. Backward: the
# batch-sized segment-sum d_rows[u] = sum_{p: inv[p]=u} g[p] — the exact
# transpose XLA's AD emits for the gather, as one accumulate kernel instead
# of a gather + scatter-add pair per embedding name. The XLA legs stay
# plain ``jnp.take`` (AD supplies the identical scatter-add); the fusion
# win there is structural: the trainer concatenates every embedding name's
# rows into ONE [U, D] leaf so a single take/scatter-add pair serves all
# names (train.loop).


# The index vector is scalar-prefetched into SMEM: Mosaic refuses a scalar
# read at a dynamic lane offset of a VMEM vector ("cannot statically prove
# that index in dimension 1 is a multiple of 128"), while an SMEM scalar
# indexing a one-row sublane slab of the VMEM block is the supported form.


def _take_fwd_kernel(inv_ref, rows_ref, out_ref):
    def body(i, _):
        out_ref[pl.ds(i, 1), :] = rows_ref[pl.ds(inv_ref[i], 1), :]
        return 0

    jax.lax.fori_loop(0, out_ref.shape[0], body, 0)


def _take_bwd_kernel(inv_ref, g_ref, out_ref):
    out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, _):
        out_ref[pl.ds(inv_ref[i], 1), :] += g_ref[pl.ds(i, 1), :]
        return 0

    jax.lax.fori_loop(0, g_ref.shape[0], body, 0)


def _take_call(kernel, inv: jax.Array, x: jax.Array, out_rows: int,
               interpret: bool) -> jax.Array:
    """One whole-block launch of a take kernel: ``inv`` int32 [N] rides in
    SMEM, ``x`` [*, D] and the [out_rows, D] result whole in VMEM."""
    d = x.shape[1]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(x.shape, lambda i, inv: (0, 0))],
            out_specs=pl.BlockSpec((out_rows, d), lambda i, inv: (0, 0))),
        # Inside shard_map the result varies over whatever mesh axes the
        # operands do; pallas_call cannot infer that and check_vma asks.
        out_shape=jax.ShapeDtypeStruct(
            (out_rows, d), x.dtype,
            vma=jax.typeof(inv).vma | jax.typeof(x).vma),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="embed_take",
    )(inv, x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def take_rows_pallas(rows: jax.Array, inv: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """rows[inv] with a hand-written segment-sum VJP, both as Pallas
    kernels. rows: [U, D]; inv: int32 [...] -> out [..., D]."""
    flat = inv.reshape(-1).astype(jnp.int32)
    out = _take_call(_take_fwd_kernel, flat, rows, flat.shape[0], interpret)
    return out.reshape(inv.shape + rows.shape[1:])


def _take_rows_fwd(rows, inv, interpret):
    return take_rows_pallas(rows, inv, interpret), (inv, rows.shape[0])


def _take_rows_bwd(interpret, res, g):
    inv, u = res
    g2 = g.reshape(-1, g.shape[-1])
    flat = inv.reshape(-1).astype(jnp.int32)
    return _take_call(_take_bwd_kernel, flat, g2, u, interpret), None


take_rows_pallas.defvjp(_take_rows_fwd, _take_rows_bwd)


def take_rows(rows: jax.Array, inv: jax.Array, *,
              mode: str = "auto") -> jax.Array:
    """Positionwise view of gathered rows, leg-selected. The XLA legs are
    ``jnp.take`` — its AD transpose IS the batch-sized segment-sum — so
    every leg produces bit-identical values and cotangents."""
    leg = "opt" if rows.ndim != 2 else resolve(
        mode, "take", num_rows=int(rows.shape[0]), n_ids=inv.size,
        width=int(rows.shape[1]))
    if leg == "pallas":
        return take_rows_pallas(rows, inv)
    return jnp.take(rows, inv, axis=0)


# ---------------------------------------------------------------------------
# Seam 3: fused install/evict scatter (tiered cache transaction)
# ---------------------------------------------------------------------------
# One dispatch installs a transaction's weight rows AND the three lazy-Adam
# companions (m, v, tau) at their hot-cache slots; OOB slot ids (the pow2
# padding) are dropped. The "opt" leg fuses the four scatters into one jit
# program (one dispatch instead of four); "ref" is the seed per-array
# ``_jit_install``.


@functools.partial(jax.jit, donate_argnums=())
def _install_fused_xla(w, m, v, tau, slots, wv, mv, vv, tv):
    """The XLA "opt" install leg: all four scatters in one jit program —
    one dispatch per transaction instead of four. Slot list is pow2-padded
    by the caller (data.hot_cold), so the compile cache stays
    O(log max_group) per table shape."""
    return (w.at[slots].set(wv), m.at[slots].set(mv),
            v.at[slots].set(vv), tau.at[slots].set(tv))


def install_rows(w, m, v, tau, slots, wv, mv, vv, tv, *, mode: str = "auto"):
    """Leg-selected cache install. All legs are element-identical: the same
    rows get the same values, OOB (padding) slots are dropped."""
    leg = resolve(mode, "install", n_ids=int(slots.shape[0]),
                  width=int(w.shape[-1]) if w.ndim > 1 else 1)
    if leg == "opt":
        return _install_fused_xla(w, m, v, tau, slots, wv, mv, vv, tv)
    return None  # ref: caller keeps its per-array scatter path


def install_cache_size() -> int:
    """Compiled-variant count of the fused install program (the compile-
    cache bound test asserts the pow2 ladder keeps this O(log max))."""
    return _install_fused_xla._cache_size()


def install_cache_clear() -> None:
    _install_fused_xla.clear_cache()


# ---------------------------------------------------------------------------
# NumPy oracle (tests)
# ---------------------------------------------------------------------------


def reference_plan_numpy(ids, num_rows):
    """np.unique-based oracle for the plan builders (tests)."""
    import numpy as np
    flat = np.asarray(ids).reshape(-1).astype(np.int64)
    uniq, inv = np.unique(flat, return_inverse=True)
    n = flat.size
    uids = np.full((n,), num_rows, np.int32)
    uids[: uniq.size] = uniq
    touched = np.zeros((num_rows,), bool)
    touched[uniq[uniq < num_rows]] = True
    rank = np.zeros((num_rows,), np.int32)
    rank[uniq[uniq < num_rows]] = np.arange(uniq.size)[uniq < num_rows]
    return (uids, inv.reshape(np.asarray(ids).shape).astype(np.int32),
            touched, rank)


def reference_exchange_numpy(uids, num_rows, num_shards, shard):
    """Sequential-scan oracle for ``ops.embedding.build_exchange`` (tests).

    Walks this shard's uid slice in order and assigns each valid id the
    next slot of its owner's request bucket — for a SORTED uid list that
    is exactly the searchsorted bucketing the jit builder computes.
    Returns (reqs [D, C] int32, flat_idx [C] int32)."""
    import numpy as np
    uids = np.asarray(uids, np.int64)
    cap = -(-uids.size // num_shards)
    rows_local = num_rows // num_shards
    pad = np.full((num_shards * cap,), num_rows, np.int64)
    pad[:uids.size] = uids
    sl = pad[shard * cap:(shard + 1) * cap]
    reqs = np.full((num_shards, cap), num_rows, np.int32)
    flat_idx = np.full((cap,), num_shards * cap, np.int32)
    counts = [0] * num_shards
    for j, uid in enumerate(sl):
        if uid >= num_rows:
            continue
        owner = int(uid // rows_local)
        reqs[owner, counts[owner]] = uid
        flat_idx[j] = owner * cap + counts[owner]
        counts[owner] += 1
    return reqs, flat_idx
