"""``ops.pallas_put_rows``: the row-local table update's write-back as one
asynchronous copy a row (PERF.md §6, PR 30), run here through the Pallas
interpreter and held **bit for bit** to what it replaces,
``table.at[uids].set(new, mode="drop")``.

A spare slot (an id past the table) must be skipped, never copied: on the
chip an out-of-bounds DMA is a device fault. The interpreter clamps such a
copy onto the table's last row instead, so every case with spare slots and
an unwritten last row also holds that no spare slot reached a copy.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepfm_tpu.config import Config
from deepfm_tpu.ops import pallas_put_rows as ppr
from deepfm_tpu.train import Trainer, loop

V = 1000
INT_MAX = np.iinfo(np.int32).max


def _table(width=128, rows=V, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(rows, width)).astype(np.float32))


def _new(slots, width=128, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(slots, width)).astype(np.float32))


def _ids(real, slots, rows=V, seed=2):
    """``real`` distinct ascending in-bounds ids (never the last row), then
    spare slots as ``sum_rows`` fills them (``rows + slot``), the last one
    int32's max."""
    rng = np.random.default_rng(seed)
    ids = np.sort(rng.choice(rows - 1, real, replace=False))
    spare = rows + np.arange(real, slots)
    if slots > real:
        spare[-1] = INT_MAX
    return jnp.asarray(np.concatenate([ids, spare]).astype(np.int32))


def _scatter(table, uids, new):
    return table.at[uids].set(new, mode="drop")


def _put(table, uids, new, **kw):
    return jax.jit(functools.partial(ppr.put_rows, interpret=True, **kw))(
        table, uids, new)


def _same(got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("width", [128, 256])
@pytest.mark.parametrize("real,slots", [(64, 64), (40, 64), (0, 64), (1, 64)],
                         ids=["all-real", "spare-tail", "all-spare",
                              "one-real"])
def test_kernel_equals_the_scatter_bit_for_bit(real, slots, width):
    table, new = _table(width), _new(slots, width)
    uids = _ids(real, slots)
    got = _put(table, uids, new)
    _same(got, _scatter(table, uids, new))
    if real == 0:   # nothing to write: the table comes back untouched
        _same(got, table)
    # the last row is in no id: a spare slot copied would have landed there
    _same(got[-1], table[-1])


def test_the_tables_first_and_last_row():
    table, new = _table(), _new(32)
    uids = jnp.asarray(np.concatenate(
        [[0], np.arange(5, 34), [V - 1], [V + 31]]).astype(np.int32))
    got = _put(table, uids, new)
    _same(got, _scatter(table, uids, new))
    _same(got[0], new[0])
    _same(got[V - 1], new[30])


@pytest.mark.parametrize("slots,block", [(64, 64), (128, 64), (192, 64),
                                         (150, 64), (100, 48), (17, 2048)],
                         ids=["1-block", "2-blocks", "3-blocks",
                              "3-blocks-ragged", "block-rounded-up",
                              "fewer-slots-than-a-block"])
def test_grid_blocks_and_a_ragged_last_one(slots, block):
    table, new = _table(), _new(slots)
    uids = _ids(slots - 7, slots)
    got = _put(table, uids, new, block=block)
    _same(got, _scatter(table, uids, new))
    _same(got[-1], table[-1])


def test_ids_need_not_be_sorted_and_a_negative_one_is_skipped():
    """The kernel asks for distinct ids, not for an order (it counts the
    copies it started). A negative id is outside ``[0, V)``: skipped, where
    the scatter would count it from the end — ``sum_rows`` hands none."""
    table, new = _table(), _new(48)
    uids = np.array(_ids(40, 48))
    np.random.default_rng(3).shuffle(uids)
    got = _put(table, jnp.asarray(uids), new)
    _same(got, _scatter(table, jnp.asarray(uids), new))
    uids[0] = -5
    want = _scatter(table, jnp.asarray(np.where(uids < 0, V, uids)), new)
    _same(_put(table, jnp.asarray(uids), new), want)


@pytest.mark.parametrize("trips", [1, 3])
def test_inside_a_while_loop_carry_as_update_rows_uses_it(trips):
    """Table and accumulator ride a ``lax.while_loop`` and each trip gathers,
    changes and writes back its slice of the ids."""
    cap = 32
    w, s = _table(seed=4), jnp.abs(_table(seed=5))
    uids = _ids(cap * trips - 9, cap * trips)
    g = _new(cap * trips, seed=6)

    def run(put):
        def trip(carry):
            i, w, s = carry
            u = jax.lax.dynamic_slice_in_dim(uids, i * cap, cap)
            gi = jax.lax.dynamic_slice_in_dim(g, i * cap, cap)
            s2 = jnp.take(s, u, axis=0, mode="fill", fill_value=0) + gi * gi
            w2 = jnp.take(w, u, axis=0, mode="fill", fill_value=0) - gi
            return i + 1, put(w, u, w2), put(s, u, s2)
        return jax.jit(lambda w, s: jax.lax.while_loop(
            lambda c: c[0] < trips, trip, (jnp.zeros((), jnp.int32), w, s)
        )[1:])(w, s)

    got = run(functools.partial(ppr.put_rows, interpret=True))
    want = run(_scatter)
    _same(got[0], want[0])
    _same(got[1], want[1])


@pytest.mark.parametrize("shape,dtype,backend,want", [
    ((V, 128), jnp.float32, "tpu", True),
    ((V, 256), jnp.float32, "tpu", True),
    ((V, 32), jnp.float32, "tpu", False),       # ids along the lanes
    ((V,), jnp.float32, "tpu", False),
    ((V, 128), jnp.bfloat16, "tpu", False),
    ((V, 2, 64), jnp.float32, "tpu", False),
    ((V, 128), jnp.float32, "cpu", False),
])
def test_where_the_kernel_applies(shape, dtype, backend, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ppr.supported(jax.ShapeDtypeStruct(shape, dtype)) is want


# ---------------------------------------------------------------------------
# The row-local trainer with the kernel forced on (interpreter) against the
# same trainer writing back by XLA's scatter
# ---------------------------------------------------------------------------

FLAGS = dict(
    model="dlrm_dcnv2", feature_size=600, field_size=8, numeric_fields=2,
    embedding_size=128, bottom_layers="16,128", cross_layers=1, cross_rank=8,
    deep_layers="16,8", dropout="1.0,1.0", batch_size=32,
    compute_dtype="float32", optimizer="Adagrad", l2_reg=0.0,
    learning_rate=0.01, log_steps=0, seed=11, scale_lr_by_world=False,
    mesh_data=1, mesh_model=1, steps_per_loop=3, transfer_ahead=0)


def _fit_three_steps(monkeypatch, dma):
    monkeypatch.setattr(loop, "ROW_UPDATE_CAPACITY", 128)
    if dma:
        monkeypatch.setattr(
            ppr, "supported", lambda t: t.ndim == 2 and t.shape[1] % 128 == 0)
        monkeypatch.setattr(ppr, "put_rows_many", functools.partial(
            ppr.put_rows_many, interpret=True))
    tr = Trainer(Config(**FLAGS))
    assert tr._row_local_eligible()
    state = tr.init_state()
    rng = np.random.default_rng(5)
    trips = []
    for _ in range(3):
        ids = rng.integers(2, 500, (32, 8)).astype(np.int32)
        ids[:, :2] = np.arange(2)
        state, m = tr.train_step(state, tr.put_batch({
            "feat_ids": ids,
            "feat_vals": rng.normal(size=(32, 8)).astype(np.float32),
            "label": rng.integers(0, 2, (32, 1)).astype(np.float32)}))
        trips.append(int(m["embed_row_trips"]))
    return tr, jax.tree.map(np.asarray, (state.params, state.opt_state)), trips


def test_row_local_trainer_by_dma_equals_the_scatter_trainer(monkeypatch):
    """K=128, two trips a step, three steps: tables, accumulators and dense
    leaves, bit for bit."""
    with monkeypatch.context() as m:
        by_dma, got, trips = _fit_three_steps(m, dma=True)
    assert by_dma.row_writeback == "dma" and trips == [2, 2, 2]
    with monkeypatch.context() as m:
        by_scatter, want, _ = _fit_three_steps(m, dma=False)
    assert by_scatter.row_writeback == "scatter"
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
