"""Optimizer zoo matching the reference's four choices + world-size LR scaling.

Reference (``1-ps-cpu/...py:260-269``):
  Adam(lr, beta1=0.9, beta2=0.999, eps=1e-8)
  Adagrad(lr, initial_accumulator_value=1e-8)
  Momentum(lr, momentum=0.95)
  Ftrl(lr)  — TF defaults: lr_power=-0.5, initial_accumulator=0.1, l1=l2=0

Horovod variant scales lr by world size (``2-hvd-gpu/...py:149``); here that
is ``scale_lr_by_world`` x the data-axis size of the mesh.

FTRL has no optax built-in; ``ftrl()`` below is a custom
``GradientTransformation`` implementing FTRL-Proximal (McMahan et al. 2013),
the same update ``tf.train.FtrlOptimizer`` applies densely.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import chex
import jax
import jax.numpy as jnp
import optax

from ..config import Config
from ..ops import embedding as emb_ops


class FtrlState(NamedTuple):
    z: optax.Updates   # per-weight z accumulator
    n: optax.Updates   # per-weight squared-gradient accumulator


def ftrl(
    learning_rate: float,
    *,
    learning_rate_power: float = -0.5,
    initial_accumulator_value: float = 0.1,
    l1_regularization_strength: float = 0.0,
    l2_regularization_strength: float = 0.0,
    beta: float = 0.0,
) -> optax.GradientTransformation:
    """FTRL-Proximal as an optax GradientTransformation (requires params).

    w_new = 0                                  if |z| <= l1
          = -(z - sign(z)*l1) / ((beta + n_new^(-lr_power))/lr + 2*l2)  else
    with n_new = n + g^2 and z += g - (n_new^p - n^p)/lr * w, p = -lr_power.
    """
    if learning_rate_power > 0:
        raise ValueError("learning_rate_power must be <= 0")
    p = -learning_rate_power  # 0.5 for the default sqrt schedule

    def init_fn(params: optax.Params) -> FtrlState:
        return FtrlState(
            z=jax.tree.map(jnp.zeros_like, params),
            n=jax.tree.map(
                lambda x: jnp.full_like(x, initial_accumulator_value), params),
        )

    def update_fn(updates, state: FtrlState, params=None):
        if params is None:
            raise ValueError("ftrl requires params in update()")

        def leaf(g, z, n, w):
            g = g.astype(jnp.float32)
            n_new = n + jnp.square(g)
            sigma = (jnp.power(n_new, p) - jnp.power(n, p)) / learning_rate
            z_new = z + g - sigma * w
            denom = (beta + jnp.power(n_new, p)) / learning_rate \
                + 2.0 * l2_regularization_strength
            w_new = jnp.where(
                jnp.abs(z_new) <= l1_regularization_strength,
                jnp.zeros_like(w),
                -(z_new - jnp.sign(z_new) * l1_regularization_strength) / denom)
            return w_new - w, z_new, n_new

        flat = jax.tree.map(leaf, updates, state.z, state.n, params)
        deltas = jax.tree.map(lambda t: t[0], flat,
                              is_leaf=lambda t: isinstance(t, tuple))
        z_new = jax.tree.map(lambda t: t[1], flat,
                             is_leaf=lambda t: isinstance(t, tuple))
        n_new = jax.tree.map(lambda t: t[2], flat,
                             is_leaf=lambda t: isinstance(t, tuple))
        return deltas, FtrlState(z=z_new, n=n_new)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Sparse (touched-rows-only) Adam with lazy, timestamped moment correction
# ---------------------------------------------------------------------------
#
# Dense Adam updates EVERY table row every step: touched rows get the full
# update, idle rows still move by their decaying momentum tail
# (-lr * b1^k*m_hat / (sqrt(b2^k*v_hat)+eps)). Applying Adam only to the
# batch's touched rows therefore cannot be bit-exact — but the idle-row
# tail is bounded (a geometric series, ≲ lr*(sum_k b1^k/sqrt(b2^k)) ≈ 9*lr
# per idle stretch, far less in practice because v decays slower than m),
# so the trajectories agree within a pinned tolerance (tests).
#
# The lazy correction makes a touched row's update IDENTICAL to what dense
# Adam would compute for it: per row we store (m, v) and the step count
# ``tau`` at which the row was last touched. On a touch at global step
# ``count`` (1-based, optax convention):
#
#     m_t = b1^(count-tau) * m_stored + (1 - b1) * g       # k idle steps
#     v_t = b2^(count-tau) * v_stored + (1 - b2) * g^2     # decayed in O(1)
#     update = -lr * (m_t / (1-b1^count)) / (sqrt(v_t / (1-b2^count)) + eps)
#
# which is exactly optax.scale_by_adam's m/v for that row had the zero
# gradients been applied one step at a time — the decay factors simply
# telescope. Cost per step ∝ unique touched rows, never ∝ vocab.


class EmbedAdamEntry(NamedTuple):
    """Per-table lazy-Adam slots. ``tau`` is int32 [rows]: the global step
    count at which the row's (m, v) were last brought current."""
    m: jax.Array
    v: jax.Array
    tau: jax.Array


def embed_adam_init(table: jax.Array) -> EmbedAdamEntry:
    return EmbedAdamEntry(
        m=jnp.zeros_like(table, jnp.float32),
        v=jnp.zeros_like(table, jnp.float32),
        tau=jnp.zeros((table.shape[0],), jnp.int32),
    )


@jax.named_scope("opt")
def sparse_adam_rows(
    rows0: jax.Array,      # f32 [U, ...] touched rows (pre-update values)
    g_rows: jax.Array,     # f32 [U, ...] summed per-row gradient
    m_rows: jax.Array,     # f32 [U, ...] stored first moment at uids
    v_rows: jax.Array,     # f32 [U, ...] stored second moment at uids
    tau_rows: jax.Array,   # int32 [U]    last-touch step count at uids
    count: jax.Array,      # int32 []     global step count AFTER this step
    *,
    lr: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """One lazy-Adam step on gathered rows; returns (new_rows, new_m,
    new_v). Pure row-space math — the caller owns gather/scatter, so the
    tiered runtime can reuse this on hot-cache slots unchanged."""
    g = g_rows.astype(jnp.float32)
    cnt = count.astype(jnp.float32)
    idle = (count - tau_rows).astype(jnp.float32)  # [U] steps since touch
    idle = idle.reshape(idle.shape + (1,) * (g.ndim - 1))
    m = jnp.power(b1, idle) * m_rows + (1.0 - b1) * g
    v = jnp.power(b2, idle) * v_rows + (1.0 - b2) * jnp.square(g)
    m_hat = m / (1.0 - jnp.power(b1, cnt))
    v_hat = v / (1.0 - jnp.power(b2, cnt))
    new_rows = rows0.astype(jnp.float32) - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    return new_rows.astype(rows0.dtype), m, v


@jax.named_scope("opt")
def sparse_adam_masked(
    table: jax.Array,      # f32 [R, ...] full table (pre-update values)
    g_rows: jax.Array,     # f32 [R, ...] summed per-row gradient (junk on
                           #              untouched rows — masked out below)
    touched: jax.Array,    # bool [R]     rows present in this batch
    oe: EmbedAdamEntry,
    count: jax.Array,      # int32 []     global step count AFTER this step
    *,
    lr: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    decay: Optional[tuple] = None,
):
    """Lazy Adam as a masked TABLE-SPACE sweep: per-row math identical to
    :func:`sparse_adam_rows`, applied under ``touched`` with untouched rows
    keeping their exact bits (a ``where``, not a blend). The sweep costs
    one elementwise pass over the table — the same shape of work a dense
    Adam step does — so it beats the gather/apply/scatter round-trip
    whenever the physical table is small enough to sweep (the monolithic
    CTR regime; ops.pallas_embedding.PLAN_COUNT_MAX_ROWS bounds it).

    Numerics contract: the MATH matches sparse_adam_rows exactly, but the
    compiled programs differ in shape ([rows] sweep vs [uids] gather), so
    XLA:CPU is free to fuse/contract the m_hat / (sqrt(v_hat)+eps) tail
    differently — in practice a 1–2 ULP divergence per apply from step 2
    on (step 1 is exact because m=v=0). The trainer's kill-switch parity
    test therefore pins this leg with a tight tolerance rather than bit
    equality; ``optimization_barrier`` placements were tried and do not
    close the gap (XLA duplicates barriered chains per consumer).

    ``decay``: optional precomputed ``(b1^idle, b2^idle)`` pair of [R]
    arrays. The pows are the sweep's hot spot — left inline, XLA fuses
    the [R]-shaped pow into the [R, D] elementwise loop and evaluates it
    D times per row — so the caller computes them ONCE behind an
    optimization_barrier and shares them across every table of the plane
    (tau is identical across tables: same touched set every step).
    Returns ``(new_table, new_EmbedAdamEntry)``."""
    g = g_rows.astype(jnp.float32)
    cnt = count.astype(jnp.float32)
    if decay is None:
        idle = (count - oe.tau).astype(jnp.float32)  # [R] steps since touch
        decay = jax.lax.optimization_barrier(
            (jnp.power(b1, idle), jnp.power(b2, idle)))
    pw1, pw2 = (d.reshape(d.shape + (1,) * (g.ndim - 1)) for d in decay)
    m = pw1 * oe.m + (1.0 - b1) * g
    v = pw2 * oe.v + (1.0 - b2) * jnp.square(g)
    m_hat = m / (1.0 - jnp.power(b1, cnt))
    v_hat = v / (1.0 - jnp.power(b2, cnt))
    new_rows = table.astype(jnp.float32) - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    keep = touched.reshape(touched.shape + (1,) * (g.ndim - 1))
    new_table = jnp.where(keep, new_rows.astype(table.dtype), table)
    new_oe = EmbedAdamEntry(
        m=jnp.where(keep, m, oe.m),
        v=jnp.where(keep, v, oe.v),
        tau=jnp.where(touched, count, oe.tau))
    return new_table, new_oe


@jax.named_scope("opt")
def sparse_apply_rows(
    rows0: jax.Array,            # f32 [U, ...] touched rows (pre-update)
    g_rows: jax.Array,           # f32 [U, ...] summed per-row gradient
    entry: emb_ops.PlanEntry,
    oe: EmbedAdamEntry,
    count: jax.Array,
    *,
    lr: float,
    table: jax.Array,
):
    """One table's full sparse-Adam transaction: gather the lazy slots at
    the plan's uids, run :func:`sparse_adam_rows`, and write the three
    updated row sets plus the ``tau`` touch stamps back. Returns
    ``(new_table, new_entry)``. Shared by both sparse step impls (per-batch
    and merged-accumulation) so the gather/apply/writeback sequence — and
    therefore the numerics — exists in exactly one place; the writebacks go
    through ``scatter_rows``/``set_rows_scalar``, which pick the
    select-over-ids formulation automatically on counting plans."""
    new_rows, new_m, new_v = sparse_adam_rows(
        rows0, g_rows,
        emb_ops.gather_rows(oe.m, entry),
        emb_ops.gather_rows(oe.v, entry),
        emb_ops.gather_rows(oe.tau, entry),
        count, lr=lr)
    new_table = emb_ops.scatter_rows(table, entry, new_rows)
    new_oe = EmbedAdamEntry(
        m=emb_ops.scatter_rows(oe.m, entry, new_m),
        v=emb_ops.scatter_rows(oe.v, entry, new_v),
        tau=emb_ops.set_rows_scalar(oe.tau, entry, count))
    return new_table, new_oe


def zero_grad_keeps_row(cfg: Config) -> bool:
    """Whether ``build_optimizer``'s update leaves a parameter *and* its
    state bit for bit where the gradient is zero, whatever the step: what
    lets the dense step update only the rows a batch touched and still be
    the dense update (``Trainer._row_local_eligible``). Adagrad does
    (``s + 0``, ``w - lr * 0 * rsqrt(s)``); Adam and momentum move every row
    by their decaying moments, FTRL recomputes ``w`` from ``z, n``. Plain
    ``sgd`` would qualify and is left out until something needs it. The
    tests hold this to ``optax`` on a zero gradient."""
    return cfg.optimizer.lower() == "adagrad"


def select_params(opt_state, names, keep):
    """``opt_state`` with every parameter-shaped dict in it (one entry a
    parameter in ``names``: Adagrad's ``sum_of_squares``, Adam's ``mu`` and
    ``nu``) cut down to ``keep``: the state an elementwise ``optax``
    transformation holds for those parameters alone."""
    names = set(names)
    return jax.tree.map(
        lambda d: {k: d[k] for k in keep}, opt_state,
        is_leaf=lambda x: isinstance(x, dict) and set(x) == names)


def join_params(state_a, state_b, names_a):
    """Inverse of two ``select_params`` cuts: ``state_a`` (over
    ``names_a``) with ``state_b``'s parameters beside its own."""
    names_a = set(names_a)
    return jax.tree.map(
        lambda a, b: {**a, **b}, state_a, state_b,
        is_leaf=lambda x: isinstance(x, dict) and set(x) == names_a)


def build_optimizer(cfg: Config, *, world_size: int = 1) -> optax.GradientTransformation:
    lr = cfg.learning_rate
    if cfg.scale_lr_by_world and world_size > 1:
        lr = lr * world_size
    name = cfg.optimizer.lower()
    if name == "adam":
        return optax.adam(lr, b1=0.9, b2=0.999, eps=1e-8)
    if name == "adagrad":
        return optax.adagrad(lr, initial_accumulator_value=1e-8)
    if name in ("momentum", "sgd"):
        return optax.sgd(lr, momentum=0.95 if name == "momentum" else None)
    if name == "ftrl":
        return ftrl(lr)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
