"""The expert layer's grouped products over the valid prefix
(``ops/pallas_grouped_dot``), through the Pallas interpreter, against
``jax.lax.ragged_dot`` over the whole buffer with the spare rows (zeros) in
the last group, which is what they replace."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.ops import pallas_grouped_dot as grouped

C, K, N, G, TILE = 64, 128, 256, 4, 16

#: where each of the four groups ends, by what the case is there for
ENDS = {
    "a full buffer": [16, 32, 48, 64],
    "a prefix of no rows": [0, 0, 0, 0],
    "a prefix of one row": [1, 1, 1, 1],
    "a prefix that ends inside a tile": [10, 20, 30, 41],
    "a group of no rows": [0, 24, 24, 50],
    "a group boundary inside a tile": [7, 23, 39, 48],
    "one group holds everything": [0, 0, 64, 64],
    "groups smaller than a tile": [3, 5, 6, 9]}


def _operands(dtype, seed=0, k=K, n=N, rows=C, groups=G):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.normal(keys[0], (rows, k)).astype(dtype)
    w = (0.1 * jax.random.normal(keys[1], (groups, k, n))).astype(dtype)
    # (a cotangent the operands' type holds: the kernels round a float32
    # one to it, as the MXU does for XLA's on a TPU; a CPU's product does not)
    dy = jax.random.normal(keys[2], (rows, n)).astype(dtype).astype(
        jnp.float32)
    return a, w, dy


def _by_xla(a, w, ends):
    """Today's formulation: every row, the spare ones in the last group."""
    sizes = jnp.diff(ends, prepend=0)
    sizes = sizes.at[-1].add(a.shape[0] - ends[-1])
    return jax.lax.ragged_dot(a, w, sizes,
                              preferred_element_type=jnp.float32)


def _by_kernel(a, w, ends, tile=TILE):
    return grouped.grouped_dot(a, w, ends, tile=tile, interpret=True)


def _held(ends, rows=C):
    return (jnp.arange(rows) < ends[-1])[:, None]


def _value_and_grads(product, a, w, dy, ends):
    """(rows of the prefix, the rows' gradient on the prefix, the matrices'
    gradient) of ``sum(product(a, w) * dy)`` over the prefix's rows."""
    held = _held(ends, a.shape[0])

    def loss(a, w):
        y = product(a, w, ends)
        return jnp.sum(jnp.where(held, y * dy, 0.0)), y

    (_, y), (da, dw) = jax.value_and_grad(loss, (0, 1), has_aux=True)(a, w)
    return (jnp.where(held, y, 0.0),
            jnp.where(held, da.astype(jnp.float32), 0.0),
            dw.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(ENDS))
def test_values_and_both_gradients_equal_ragged_dots_on_the_prefix(case,
                                                                   dtype):
    ends = jnp.asarray(ENDS[case], jnp.int32)
    a, w, dy = _operands(dtype)
    a = jnp.where(_held(ends), a, 0).astype(dtype)      # (as the layer does)
    want = _value_and_grads(_by_xla, a, w, dy, ends)
    got = _value_and_grads(_by_kernel, a, w, dy, ends)
    # bfloat16: the gradients leave in the operands' type, a rounding each
    tol = {"atol": 1e-4} if dtype == jnp.float32 else {
        "rtol": 2e-2, "atol": 2e-2}
    for name, g, x in zip(("rows", "rows' gradient", "matrices' gradient"),
                          got, want):
        assert g.dtype == jnp.float32 and g.shape == x.shape
        np.testing.assert_allclose(g, x, err_msg=name, **tol)
    # a group of no rows has a gradient of zeros, written and not left
    sizes = np.diff(ENDS[case], prepend=0)
    assert not np.asarray(got[2])[sizes == 0].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["a prefix that ends inside a tile",
                                  "a group of no rows",
                                  "a prefix of no rows"])
def test_nans_in_the_spare_rows_reach_no_row_and_no_gradient(case, dtype):
    """The rows past the prefix are never read: poisoned in both operands
    and in the cotangent, the prefix's rows and every gradient are what
    they are over zeros."""
    ends = jnp.asarray(ENDS[case], jnp.int32)
    a, w, dy = _operands(dtype, seed=1)
    held = _held(ends)
    clean = _value_and_grads(_by_kernel, jnp.where(held, a, 0).astype(dtype),
                             w, jnp.where(held, dy, 0.0), ends)
    poisoned = _value_and_grads(
        _by_kernel, jnp.where(held, a, jnp.nan).astype(dtype), w,
        jnp.where(held, dy, jnp.nan), ends)
    for got, want in zip(poisoned, clean):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(got, want)
    # the gradient products read a poisoned cotangent's prefix only, too
    da = grouped._dot(jnp.where(held, dy, jnp.nan), w, ends, transposed=True,
                      tile=TILE, interpret=True)
    assert np.isfinite(np.asarray(jnp.where(held, da, 0.0))).all()


def test_the_rows_past_the_prefix_are_left_as_they_were_found():
    """No tile past the prefix is in the grid: the interpreter hands out a
    result of NaNs, and that is what those rows still hold."""
    ends = jnp.asarray([3, 9, 9, 20], jnp.int32)
    a, w, _ = _operands(jnp.float32)
    y = np.asarray(_by_kernel(a, w, ends))
    assert np.isfinite(y[:20]).all()
    assert np.isnan(y[32:]).all()          # tiles of 16: rows 32.. unvisited


@pytest.mark.parametrize("tile", [8, 16, 32, 64])
@pytest.mark.parametrize("k,n", [(128, 256), (256, 128), (384, 128)])
def test_any_tile_and_widths_give_the_same_rows(tile, k, n):
    ends = jnp.asarray([5, 5, 37, 50], jnp.int32)
    a, w, dy = _operands(jnp.float32, seed=2, k=k, n=n)
    a = jnp.where(_held(ends), a, 0)
    want = _value_and_grads(_by_xla, a, w, dy, ends)
    got = _value_and_grads(functools.partial(_by_kernel, tile=tile), a, w,
                           dy, ends)
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, atol=1e-4)


def test_a_wide_gradient_is_made_a_block_of_columns_at_a_time(monkeypatch):
    """Past ``DW_BLOCK_BYTES`` the matrices' gradient is made in blocks of
    whole lines of columns, the rows read again for each."""
    monkeypatch.setattr(grouped, "DW_BLOCK_BYTES", 4 * K * 128)
    assert grouped._dw_columns(K, N) == 128
    ends = jnp.asarray(ENDS["a group boundary inside a tile"], jnp.int32)
    a, w, dy = _operands(jnp.float32, seed=3)
    want = _by_xla_dw(a, dy, w, ends)
    got = grouped._dw(a, dy, ends, dtype=jnp.dtype("float32"), tile=TILE,
                      interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-4)


def _by_xla_dw(a, dy, w, ends):
    held = _held(ends)
    return jax.vjp(lambda w_: _by_xla(jnp.where(held, a, 0), w_, ends),
                   w)[1](jnp.where(held, dy, 0.0))[0]


@pytest.mark.parametrize("k,n,columns", [
    (2048, 768, 768), (768, 2048, 2048),        # SDAR: whole
    (2048, 1792, 1792), (2304, 1024, 1024), (2048, 1536, 1536),
    (4096, 1280, 640), (1280, 4096, 2048),      # Solar-Open2: in two
    (128, 100, 100)])                           # (no whole lines: as it is)
def test_columns_of_a_gradients_block_at_the_cells_widths(k, n, columns):
    assert grouped._dw_columns(k, n) == columns
    assert 4 * k * columns <= grouped.DW_BLOCK_BYTES and n % columns == 0


@pytest.mark.parametrize("case", list(ENDS))
@pytest.mark.parametrize("empty_groups", [False, True])
def test_the_grids_tables_name_every_tile_a_group_reaches_once(case,
                                                               empty_groups):
    ends = np.asarray(ENDS[case], np.int32)
    (offsets, group, tile), visits = grouped.visits(
        jnp.asarray(ends), C, TILE, empty_groups=empty_groups)
    starts = np.concatenate([[0], ends[:-1]])
    want = []
    for g, (lo, hi) in enumerate(zip(starts, ends)):
        if hi > lo:
            want += [(g, t) for t in range(lo // TILE, (hi - 1) // TILE + 1)]
        elif empty_groups:
            want.append((g, None))
    assert int(visits) == len(want) <= group.shape[0] == C // TILE + G - 1
    got = list(zip(np.asarray(group)[:len(want)].tolist(),
                   np.asarray(tile)[:len(want)].tolist()))
    for (g, t), (want_g, want_t) in zip(got, want):
        assert g == want_g and 0 <= t < C // TILE
        assert want_t is None or t == want_t
    np.testing.assert_array_equal(offsets, np.concatenate([[0], ends]))


@pytest.mark.parametrize("rows,width,hidden,backend,want", [
    (16384, 2048, 768, "tpu", True),        # SDAR's pass
    (16384, 2048, 1792, "tpu", True),       # LFM2's
    (16384, 2304, 1024, "tpu", True),       # Kimi-Linear's
    (16384, 2048, 1536, "tpu", True),       # GLM-4.7-Flash's
    (3328, 4096, 1280, "tpu", True),        # Solar-Open2's
    (16384, 2048, 768, "cpu", False), (16384, 2048, 768, "gpu", False),
    (16384, 2048, 96, "tpu", False),        # experts of no whole lines
    (16384, 64, 768, "tpu", False),         # nor the model's width
    (3280, 4096, 1280, "tpu", False),       # a pass of no whole tiles
    (100, 2048, 768, "tpu", False)])
def test_where_the_kernels_apply(rows, width, hidden, backend, want):
    assert grouped.supported(rows, width, hidden, backend) is want


def test_supported_reads_the_backend_it_runs_on(monkeypatch):
    assert not grouped.supported(16384, 2048, 768)          # a CPU, here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert grouped.supported(16384, 2048, 768)


def test_the_tiling_a_step_says():
    assert grouped.tiling(2048, 768) == "rows%d dw768/2048" \
        % grouped.TILE_ROWS
    assert grouped.tiling(4096, 1280) == "rows%d dw640/2048" \
        % grouped.TILE_ROWS
