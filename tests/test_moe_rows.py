"""``ops.pallas_moe_rows``: the expert layer's rows taken from and added to
their positions by one asynchronous copy a row over the valid prefix
(PERF.md §6, PR 34), run here through the Pallas interpreter and held to
what they replace, ``jnp.take`` and ``out.at[tok].add``.

A pass's rows: ``C`` buffer rows sorted into ``G`` groups (``ends``), the
first ``n = ends[-1]`` of them real, a position at most once a group. The
rows past ``n`` must come out as zeros and their ``tok`` must never be read:
every case with spare rows puts an id far outside the array there (on the
chip a copy from it would be a device fault).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepfm_tpu.ops import pallas_moe_rows as pmr

T, C, G = 64, 48, 4
FAR = 2 ** 30


def _rows(n, width=128, groups=G, rows=C, positions=T, seed=0, top=None):
    """(x [T, W], y [C, W], tok [C] with ``FAR`` past ``n``, the same with
    the true ids, ends [G]): ``groups`` equal groups cut at ``n``, distinct
    ascending positions inside each; with ``top`` the first ``top`` groups
    all hold position 7 (a position with ``top`` rows)."""
    rng = np.random.default_rng(seed)
    size = rows // groups
    tok = np.concatenate([np.sort(rng.choice(
        np.setdiff1d(np.arange(positions), [7]), size, replace=False))
        for _ in range(groups)]).astype(np.int32)
    if top:
        tok[np.arange(top) * size] = 7
    ends = np.minimum((np.arange(groups) + 1) * size, n).astype(np.int32)
    spare = tok.copy()
    spare[n:] = FAR
    x = rng.normal(size=(positions, width)).astype(np.float32)
    y = rng.normal(size=(rows, width)).astype(np.float32)
    return tuple(map(jnp.asarray, (x, y, spare, tok, ends)))


def _take(x, tok, ends, dtype=jnp.float32, **kw):
    return jax.jit(functools.partial(pmr.take_rows, dtype=dtype,
                                     interpret=True, **kw))(x, tok, ends)


def _add(out, y, tok, ends, **kw):
    return jax.jit(functools.partial(pmr.add_rows, interpret=True, **kw))(
        out, y, tok, ends)


def _xla_take(x, tok, n):
    held = (jnp.arange(tok.shape[0]) < n)[:, None]
    return jnp.where(held, jnp.take(x, tok, axis=0), 0.0)


def _xla_add(out, y, tok, n):
    held = (jnp.arange(tok.shape[0]) < n)[:, None]
    return out.at[tok].add(jnp.where(held, y, 0.0))


def _same(got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("width", [128, 256, 384])
@pytest.mark.parametrize("n", [0, 1, 20, C], ids=["none", "one", "middle",
                                                   "all"])
def test_take_rows_equals_take_on_the_prefix_and_zeros_past_it(n, width):
    x, _, spare, tok, ends = _rows(n, width)
    got = _take(x, spare, ends, block=16)
    _same(got, _xla_take(x, tok, n))
    _same(got[n:], jnp.zeros((C - n, width)))


@pytest.mark.parametrize("width", [128, 256, 384])
@pytest.mark.parametrize("n", [0, 1, 20, C], ids=["none", "one", "middle",
                                                   "all"])
def test_add_rows_equals_the_scatter_add_on_a_carry_that_is_not_zeros(
        n, width):
    x, y, spare, tok, ends = _rows(n, width)
    got = _add(x, y, spare, ends, block=16)
    # distinct positions inside a group and at most 4 addends a position
    np.testing.assert_allclose(got, _xla_add(x, y, tok, n), rtol=1e-6,
                               atol=1e-6)
    if n == 0:      # nothing to add: the carry comes back untouched
        _same(got, x)


@pytest.mark.parametrize("top", [1, 2, G])
def test_a_position_with_one_two_and_every_groups_row(top):
    """Position 7 has a row in each of the first ``top`` groups: one or two
    addends are bit for bit XLA's sum, more agree to rounding (the order of
    a position's addends is the groups', XLA's is its own)."""
    x, y, spare, tok, ends = _rows(C, top=top)
    assert int(jnp.sum(tok == 7)) == top
    zeros = jnp.zeros_like(x)
    got, want = _add(zeros, y, spare, ends, block=16), _xla_add(zeros, y, tok,
                                                                C)
    if top <= 2:
        _same(got[7], want[7])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    _same(got[7], functools.reduce(
        jnp.add, [y[i] for i in np.flatnonzero(np.asarray(tok) == 7)],
        zeros[7]))


def test_rows_past_the_prefix_are_zeros_though_their_positions_hold_nans():
    """A spare row is written as zeros, not copied from anywhere: the
    positions' array full of NaNs but for the prefix's rows."""
    n = 20
    x, _, spare, tok, ends = _rows(n)
    poisoned = jnp.full_like(x, jnp.nan).at[tok[:n]].set(x[tok[:n]])
    got = _take(poisoned, spare, ends, block=16)
    _same(got, _xla_take(x, tok, n))
    assert not np.isnan(np.asarray(got)).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rows_leave_and_arrive_in_bfloat16_too(dtype):
    """``take_rows`` writes its rows in the type asked for (the products'),
    ``add_rows`` adds bfloat16 rows (a cotangent's) as float32."""
    x, y, spare, tok, ends = _rows(30, 256)
    got = _take(x, spare, ends, dtype, block=16)
    assert got.dtype == dtype
    _same(got.astype(jnp.float32),
          _xla_take(x, tok, 30).astype(dtype).astype(jnp.float32))
    got = _add(x, y.astype(dtype), spare, ends, block=16)
    np.testing.assert_allclose(
        got, _xla_add(x, y.astype(dtype).astype(jnp.float32), tok, 30),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("rows,block,says", [
    (48, 16, 16), (48, 48, 48), (96, 64, 48), (64, 512, 64),
    (16384, 512, 512), (40, 512, None), (8, 512, None)])
def test_blocks_are_whole_sublane_tiles_that_divide_the_buffer(rows, block,
                                                               says):
    assert pmr.block_rows(rows, block) == says


@pytest.mark.parametrize("block", [16, 48])
@pytest.mark.parametrize("groups", [1, 3, 12])
def test_group_ends_inside_across_and_on_block_edges(groups, block):
    x, y, spare, tok, ends = _rows(40, groups=groups, positions=128)
    np.testing.assert_allclose(_add(x, y, spare, ends, block=block),
                               _xla_add(x, y, tok, 40), rtol=1e-6, atol=1e-6)
    _same(_take(x, spare, ends, block=block), _xla_take(x, tok, 40))


@pytest.mark.parametrize("passes", [1, 2])
def test_inside_a_scan_carry_as_the_expert_layer_uses_them(passes):
    """Both ride a ``lax.scan`` over passes: ``add_rows`` on the carry."""
    n = [C, 13][:passes]
    cases = [_rows(k, seed=5 + i) for i, k in enumerate(n)]
    x = cases[0][0]
    ys, spares, toks, ends = (jnp.stack([c[i] for c in cases])
                              for i in (1, 2, 3, 4))

    def run(take, add):
        def one(out, args):
            y, tok, e = args
            return add(out, take(x, tok, e) * y, tok, e), None
        return jax.jit(lambda: jax.lax.scan(
            one, jnp.zeros_like(x), (ys, spares, ends))[0])()

    got = run(functools.partial(pmr.take_rows, interpret=True, block=16),
              functools.partial(pmr.add_rows, interpret=True, block=16))
    want = jnp.zeros_like(x)
    for y, tok, k in zip(ys, toks, n):
        want = _xla_add(want, _xla_take(x, tok, k) * y, tok, k)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n", [0, 20, C], ids=["none", "middle", "all"])
def test_rows_are_scaled_on_their_way_in_and_the_transpose_is_one_kernel(n):
    """``add_rows(..., scale)`` adds each row times its scale, and
    ``take_rows_weighted`` is its transpose: the scaled rows of a cotangent
    and each row's dot product with its ``y``; zeros past the prefix
    whatever ``y`` and ``scale`` hold there."""
    x, y, spare, tok, ends = _rows(n, 256)
    scale = jnp.asarray(np.random.default_rng(9).uniform(
        0.1, 1.0, C).astype(np.float32))
    got = jax.jit(functools.partial(pmr.add_rows, interpret=True, block=16))(
        x, y, spare, ends, scale)
    np.testing.assert_allclose(got, _xla_add(x, y * scale[:, None], tok, n),
                               rtol=1e-6, atol=1e-6)
    wild = jnp.where((jnp.arange(C) < n)[:, None], y, jnp.nan)
    rows, dots = jax.jit(functools.partial(
        pmr.take_rows_weighted, interpret=True, block=16))(
            x, wild, jnp.where(jnp.arange(C) < n, scale, jnp.nan), spare,
            ends)
    taken = _xla_take(x, tok, n)
    np.testing.assert_allclose(rows, taken * scale[:, None], rtol=1e-6)
    np.testing.assert_allclose(dots, jnp.sum(taken * y, axis=1), rtol=1e-5,
                               atol=1e-5)
    _same(rows[n:], jnp.zeros((C - n, 256)))
    _same(dots[n:], jnp.zeros((C - n,)))


@pytest.mark.parametrize("n", [0, 20, C], ids=["none", "middle", "all"])
def test_gather_and_combine_differentiate_as_take_and_scatter_add(n):
    """``gather``'s rows hand their cotangent to what rides through it, in
    place; ``combine``'s is ``take_rows_weighted`` of the result's: every
    gradient of a function of both against ``jax.grad`` of the XLA form."""
    x, y, spare, tok, ends = _rows(n, 256)
    carry = jnp.cos(x)
    scale = jnp.asarray(np.random.default_rng(9).uniform(
        0.1, 1.0, C).astype(np.float32))

    def by_kernels(x, y, carry, scale):
        xs, through = pmr.gather(jax.lax.stop_gradient(x), x, spare, ends,
                                 interpret=True)
        out = pmr.combine(carry, xs * y, scale, spare, ends, interpret=True)
        return jnp.sum(jnp.sin(out)) + jnp.sum(through * through)

    def by_xla(x, y, carry, scale):
        out = _xla_add(carry, _xla_take(x, tok, n) * y * scale[:, None], tok,
                       n)
        return jnp.sum(jnp.sin(out)) + jnp.sum(x * x)

    np.testing.assert_allclose(by_kernels(x, y, carry, scale),
                               by_xla(x, y, carry, scale), rtol=1e-6)
    got = jax.grad(by_kernels, (0, 1, 2, 3))(x, y, carry, scale)
    want = jax.grad(by_xla, (0, 1, 2, 3))(x, y, carry, scale)
    for g, w, name in zip(got, want, ("x", "y", "carry", "scale")):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5, err_msg=name)
    _same(got[1][n:], jnp.zeros((C - n, 256)))     # spare rows: no gradient
    _same(got[3][n:], jnp.zeros((C - n,)))


def test_a_bfloat16_cotangent_is_summed_in_float32():
    """The rows' type is the products'; their cotangent comes back in it and
    is added to the positions' float32 cotangent unrounded."""
    x, y, spare, tok, ends = _rows(C, top=G)
    y16 = y.astype(jnp.bfloat16)

    def loss(x):
        xs, _ = pmr.gather(jax.lax.stop_gradient(x), x, spare, ends,
                           jnp.bfloat16, interpret=True)
        return jnp.sum((xs * y16).astype(jnp.float32))

    got = jax.grad(loss)(x)
    assert got.dtype == jnp.float32
    want = _xla_add(jnp.zeros_like(x), y16.astype(jnp.float32), tok, C)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # position 7's four bfloat16 addends, summed as float32
    assert float(jnp.max(jnp.abs(got[7] - got[7].astype(jnp.bfloat16)))) > 0


@pytest.mark.parametrize("width,positions,rows,backend,want", [
    (2048, 16384, 16384, "tpu", True), (2304, 16384, 16384, "tpu", True),
    (128, 64, 48, "tpu", True),
    (64, 64, 48, "tpu", False),         # a row that is not whole lines
    (2000, 16384, 16384, "tpu", False),
    (128, 60, 48, "tpu", False),        # positions not in whole tiles
    (128, 64, 40, "tpu", False),        # a buffer no block divides
    (2048, 16384, 16384, "cpu", False), (2048, 16384, 16384, "gpu", False)])
def test_where_the_kernels_apply(width, positions, rows, backend, want):
    assert pmr.supported(width, positions, rows, backend) is want


def test_supported_reads_the_backend_it_runs_on(monkeypatch):
    assert not pmr.supported(2048, 16384, 16384)        # a CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pmr.supported(2048, 16384, 16384)
