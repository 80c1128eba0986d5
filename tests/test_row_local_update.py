"""The dense-semantics step's row-local table update
(``Trainer._row_local_eligible`` / ``_row_local_apply``).

Under Adagrad without L2 a row the batch did not look up has a zero gradient
and keeps its value and its accumulator bit for bit, so the dense update can
be computed on the batch's distinct rows alone: no table-shaped gradient, no
sweep (PERF.md §6, PR 28). Held here, over {deepfm, dcnv2, dlrm_dcnv2} at
small size in float32:

* the mathematics, against a float32 NumPy dense Adagrad on every row;
* the same trainer with the predicate patched off (the table-shaped form,
  its gradient AD's: ``tests/test_dense_rows_grad.py`` holds the
  table-shaped gradient built from rows to the same);
* the shapes that stress the row plan: one row taking every id of a field,
  more distinct rows than a (patched-small) capacity;
* the counters against NumPy's ``unique``;
* who is *not* eligible, and that those steps still sweep the table;
* the eligible step as XLA:CPU compiled it: nothing as tall as the table but
  the in-place row writes of ``w`` and ``sum_of_squares``.
"""

import functools
import math

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from deepfm_tpu.config import Config
from deepfm_tpu.ops import embedding as emb_ops
from deepfm_tpu.train import Trainer, loop
from deepfm_tpu.train import optimizers as opt_lib
from deepfm_tpu.utils import profiling

V, F, B, STEPS = 300, 8, 32, 3
NUMERIC = 2             # dlrm_dcnv2: fields whose ids are never looked up
ID_RANGE = 200          # rows >= ID_RANGE are real and never touched
LR, ACC0, EPS = 0.01, 1e-8, 1e-7    # build_optimizer's optax.adagrad

MODELS = {
    "deepfm": {},
    "dcnv2": {"model": "dcnv2"},
    "dlrm_dcnv2": {"model": "dlrm_dcnv2", "numeric_fields": NUMERIC,
                   "bottom_layers": "8,4", "cross_layers": 2,
                   "cross_rank": 4},
}
NAMES = sorted(MODELS)


def _cfg(model, **over):
    flags = dict(
        feature_size=V, field_size=F, embedding_size=4, deep_layers="8,4",
        dropout="1.0,1.0", batch_size=B, compute_dtype="float32",
        optimizer="Adagrad", l2_reg=0.0, learning_rate=LR, log_steps=0,
        seed=11, scale_lr_by_world=False, mesh_data=1, mesh_model=1,
        steps_per_loop=STEPS, transfer_ahead=0)
    flags.update(MODELS[model])
    flags.update(over)
    return Config(**flags)


def _batches(model, seed=5, one_row_field=None):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(STEPS):
        ids = rng.integers(NUMERIC, ID_RANGE, (B, F)).astype(np.int32)
        ids[:, :NUMERIC] = np.arange(NUMERIC)
        if one_row_field is not None:
            ids[:, one_row_field] = 77
        out.append({
            "feat_ids": ids,
            "feat_vals": rng.normal(size=(B, F)).astype(np.float32),
            "label": rng.integers(0, 2, size=(B, 1)).astype(np.float32)})
    return out


def _looked_up(model, batch):
    ids = batch["feat_ids"]
    return ids[:, NUMERIC:] if model == "dlrm_dcnv2" else ids


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _seeded(tr):
    """The initial state with every leaf drawn anew: tables at a trained
    model's scale, biases away from zero."""
    state = tr.init_state()
    rng = np.random.default_rng(3)
    params = jax.tree.map(
        lambda x: jnp.asarray(rng.uniform(-0.3, 0.3, x.shape), x.dtype),
        state.params)
    return state.replace(params=params, opt_state=tr.tx.init(params))


def _run(model, eligible=True, batches=None, **over):
    """(trainer, state before, state after, metrics of each step), by single
    ``train_step`` calls so that every step's counters come back."""
    tr = Trainer(_cfg(model, **over))
    assert tr._row_local_eligible()
    if not eligible:        # the tables left to AD: a scatter-add, a sweep
        tr._grad_by_rows = lambda: False
        assert not tr._row_local_eligible()
    state = _seeded(tr)
    before = _host(state)
    metrics = []
    for batch in batches or _batches(model):
        state, m = tr.train_step(state, tr.put_batch(batch))
        metrics.append({k: float(v) for k, v in m.items()})
    return tr, before, _host(state), metrics


@functools.lru_cache(maxsize=None)
def _plain(model):
    return _run(model)


def _accumulator(opt_state):
    return optax.tree_utils.tree_get(opt_state, "sum_of_squares")


def _assert_close(got, want, tol, what):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(flat, jax.tree.leaves(want)):
        gap = np.linalg.norm((a - b).ravel()) / max(
            np.linalg.norm(np.asarray(b).ravel()), 1e-30)
        assert gap <= tol, (what, jax.tree_util.keystr(path), gap)


def _assert_equal(got, want, what):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(flat, jax.tree.leaves(want)):
        np.testing.assert_array_equal(
            a, b, err_msg=what + jax.tree_util.keystr(path))


@pytest.mark.parametrize("model", NAMES)
def test_matches_numpy_dense_adagrad_on_every_row(model):
    tr, before, after, _ = _plain(model)
    batches = _batches(model)

    @jax.jit
    def grads(p, batch):
        def loss(p):
            logits, _ = tr.model.apply(
                p, before.model_state, batch["feat_ids"],
                batch["feat_vals"], train=True, rng=jax.random.PRNGKey(0))
            return tr._mean_loss(logits, batch)
        return jax.grad(loss)(p)

    f32 = np.float32
    params = before.params
    acc = jax.tree.map(lambda x: np.full_like(x, ACC0), params)
    for batch in batches:
        g = _host(grads(params, batch))          # the table-shaped gradient
        acc = jax.tree.map(lambda s, x: s + x * x, acc, g)
        params = jax.tree.map(
            lambda p, s, x: p - f32(LR) * x * np.where(
                s > 0, f32(1) / np.sqrt(s + f32(EPS)), f32(0)),
            params, acc, g)
    # Leaf by leaf in norm, to 1e-5: the model's backward pass is two
    # compilations and sums in two orders.
    _assert_close(after.params, params, 1e-5, "params")
    _assert_close(_accumulator(after.opt_state), acc, 1e-5, "accumulator")

    touched = np.unique(np.concatenate(
        [_looked_up(model, b).ravel() for b in batches]))
    idle = np.setdiff1d(np.arange(tr.model.padded_vocab), touched)
    assert idle.size >= V - ID_RANGE
    got_acc = _accumulator(after.opt_state)
    for name in tr.model.embedding_param_names():
        np.testing.assert_array_equal(after.params[name][idle],
                                      before.params[name][idle])
        np.testing.assert_array_equal(
            got_acc[name][idle],
            _accumulator(before.opt_state)[name][idle])
    assert (after.params["fm_v"][touched]      # dcnv2 never reads fm_w
            != before.params["fm_v"][touched]).any(axis=1).all()


@pytest.mark.parametrize("model", NAMES)
def test_equals_the_table_shaped_form(model):
    _, _, after, rows = _plain(model)
    _, _, dense, plain = _run(model, eligible=False)
    for tree in ("params", "opt_state"):
        _assert_close(getattr(after, tree), getattr(dense, tree), 1e-6, tree)
    assert [m["loss"] for m in rows] == pytest.approx(
        [m["loss"] for m in plain], rel=1e-6)
    assert all("embed_distinct_rows" not in m for m in plain)


@pytest.mark.parametrize("model", NAMES)
def test_one_row_takes_every_id_of_a_field(model):
    batches = _batches(model, one_row_field=F - 1)
    _, _, after, rows = _run(model, batches=batches)
    _, _, dense, _ = _run(model, eligible=False, batches=batches)
    for tree in ("params", "opt_state"):
        _assert_close(getattr(after, tree), getattr(dense, tree), 1e-6, tree)
    assert all(m["embed_row_trips"] == 1 for m in rows)


def _update_rows(model, capacity, monkeypatch):
    """``Trainer._update_rows`` alone, on fixed cotangents: (tables,
    accumulators, counts) on the host."""
    monkeypatch.setattr(loop, "ROW_UPDATE_CAPACITY", capacity)
    tr = Trainer(_cfg(model))
    state = _seeded(tr)
    names = tr.model.embedding_param_names()
    rng = np.random.default_rng(9)
    ids = tr.model.lookup_ids(_batches(model)[0]["feat_ids"])
    tabs = {n: state.params[n] for n in names}
    # accumulators with history in them, so that a row's step is its own
    acc = {n: jnp.asarray(ACC0 + rng.random(tabs[n].shape), jnp.float32)
           for n in names}
    opt_tabs = opt_lib.select_params(
        optax.tree_utils.tree_set(state.opt_state, sum_of_squares={
            **_accumulator(state.opt_state), **acc}), state.params, tabs)
    g_views = {n: jnp.asarray(rng.normal(size=ids.shape + tabs[n].shape[1:]),
                              jnp.float32) for n in names}
    return _host(jax.jit(tr._update_rows)(tabs, opt_tabs, ids, g_views))


@pytest.mark.parametrize("trips", [2, 3])
@pytest.mark.parametrize("model", NAMES)
def test_more_distinct_rows_than_capacity(monkeypatch, model, trips):
    """Another trip, never another answer. The row update alone, on the same
    cotangents: every element within a rounding of the one-trip result (the
    arithmetic is elementwise, but another capacity is another compilation,
    and XLA:CPU contracts a multiply-add in one and not the other on a few
    elements: one unit in the last place), rows no id names bit for bit.
    The whole step likewise, by norm."""
    distinct = [len(np.unique(_looked_up(model, b)))
                for b in _batches(model)]
    capacity = -(-distinct[0] // trips)
    one = _update_rows(model, 8192, monkeypatch)
    many = _update_rows(model, capacity, monkeypatch)
    assert (one[2]["embed_row_trips"], many[2]["embed_row_trips"]) == (
        1, trips)
    idle = np.setdiff1d(np.arange(V), _looked_up(model, _batches(model)[0]))
    for a, b in zip(jax.tree.leaves(many[:2]), jax.tree.leaves(one[:2])):
        np.testing.assert_allclose(a, b, rtol=3e-7, atol=1e-9)
        np.testing.assert_array_equal(a[idle], b[idle])

    _, _, whole_one, _ = _plain(model)
    _, _, whole_many, rows = _run(model)        # capacity still patched
    assert [m["embed_row_trips"] for m in rows] == [
        -(-d // capacity) for d in distinct]
    for tree in ("params", "opt_state"):
        _assert_close(getattr(whole_many, tree), getattr(whole_one, tree),
                      1e-6, tree)


@pytest.mark.parametrize("model", NAMES)
def test_counters_against_numpy_unique(model):
    _, _, _, rows = _plain(model)
    for m, batch in zip(rows, _batches(model)):
        distinct = len(np.unique(_looked_up(model, batch)))
        assert m["embed_distinct_rows"] == distinct
        assert m["embed_row_trips"] == math.ceil(
            distinct / loop.ROW_UPDATE_CAPACITY) == 1


@pytest.mark.parametrize("model", NAMES)
def test_scanned_dispatch_reports_the_last_steps_counters(model):
    tr = Trainer(_cfg(model))
    batches = _batches(model)
    stacked = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
    _, m = tr.multi_step(_seeded(tr), jax.device_put(stacked))
    assert int(m["embed_distinct_rows"]) == len(
        np.unique(_looked_up(model, batches[-1])))
    assert int(m["embed_row_trips"]) == 1


NOT_ELIGIBLE = {
    "adam": dict(optimizer="Adam"),
    "l2": dict(l2_reg=1e-4),
    "two_data_replicas": dict(mesh_data=2),
    "grad_accum": dict(grad_accum_steps=2, steps_per_loop=4),
    "sparse_update": dict(optimizer="Adam", embedding_update="sparse"),
}


@pytest.mark.parametrize("model,why", [
    (m, w) for m in NAMES for w in sorted(NOT_ELIGIBLE)
    # config.py refuses the sparse plane for dlrm_dcnv2
    if (m, w) != ("dlrm_dcnv2", "sparse_update")])
def test_everything_else_compiles_the_table_shaped_step(model, why):
    tr = Trainer(_cfg(model, **NOT_ELIGIBLE[why]))
    assert not tr._row_local_eligible()
    if why == "sparse_update":
        return      # another plane, another state tree: not this step
    ops = profiling.hlo_table_ops(tr.step_hlo_text(), tr.model.padded_vocab)
    sweeps = [o for o in ops if o["scope"] == "opt" and o["tables"]
              and not o["primitive"].startswith("scatter")]
    assert sweeps, ops          # the optimizer passes over the table


@pytest.mark.parametrize("model", NAMES)
def test_eligible_step_makes_nothing_table_tall_but_its_row_writes(model):
    tr, _, after, _ = _plain(model)
    ops = profiling.hlo_table_ops(tr.step_hlo_text(), tr.model.padded_vocab)
    assert ops
    # (a copy of a table would be listed; that the scatters alias their
    # operand is the TPU backend's to say: scripts/step_table_ops.py)
    for op in ops:
        assert op["primitive"] == "scatter" and op["scope"] == "embed", op
    # one row write a table and a table's accumulator
    assert len(ops) == 2 * len(tr.model.embedding_param_names()), ops
    # and the state is the dense step's tree
    dense = Trainer(_cfg(model, optimizer="Adam"))
    assert (jax.tree.structure(after.params)
            == jax.tree.structure(jax.eval_shape(dense.init_state).params))
    for name in tr.model.embedding_param_names():
        assert (_accumulator(after.opt_state)[name].shape
                == after.params[name].shape)


OPTIMIZERS = ["adam", "adagrad", "momentum", "sgd", "ftrl"]


@pytest.mark.parametrize("name", OPTIMIZERS)
def test_zero_gradient_predicate_against_optax(name):
    """``zero_grad_keeps_row`` may only say yes where ``build_optimizer``'s
    update, on a zero gradient, returns parameter and state bit for bit; and
    the three it refuses do move a row (sgd would qualify: left out)."""
    cfg = _cfg("deepfm", optimizer=name)
    tx = opt_lib.build_optimizer(cfg)
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)}
    state = tx.init(params)
    for _ in range(2):          # a state with history in it
        g = {"w": jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)}
        updates, state = tx.update(g, state, params)
        params = optax.apply_updates(params, updates)
    zero = jax.tree.map(jnp.zeros_like, params)
    updates, new_state = tx.update(zero, state, params)
    new_params = optax.apply_updates(params, updates)
    kept = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves((new_params, new_state)),
                        jax.tree.leaves((params, state)))
        if np.ndim(a))         # a step counter is no row's state
    if opt_lib.zero_grad_keeps_row(cfg):
        assert kept
    assert kept == (name in ("adagrad", "sgd"))
    assert opt_lib.zero_grad_keeps_row(cfg) == (name == "adagrad")


@pytest.mark.parametrize("n,rows", [(37, 20), (1000, 50), (1000, 100000),
                                    (513, 3), (16, 16), (4096, 700)])
def test_sum_rows_against_numpy(n, rows):
    """Negative ids count from the end, ids past ``valid_rows`` receive
    nothing, every later slot is a distinct id past the table."""
    rng = np.random.default_rng(n + rows)
    ids = rng.integers(-3, rows + 5, size=(n,)).astype(np.int32)
    cots = rng.normal(size=(n, 5)).astype(np.float32)
    valid = rows - 2
    got = jax.jit(lambda i, c: emb_ops.sum_rows(i, c, rows, valid,
                                                multiple=16))(ids, cots)
    norm = np.where(ids < 0, ids + rows, ids)
    ok = (norm >= 0) & (norm < valid)
    uids = np.unique(norm[ok])
    want = np.stack([cots[norm == u].astype(np.float64).sum(0)
                     for u in uids])
    count = int(got.count)
    assert count == len(uids)
    np.testing.assert_array_equal(got.uids[:count], uids)
    np.testing.assert_allclose(got.sums[:count], want, atol=1e-5)
    assert got.uids.shape[0] % 16 == 0
    assert (np.asarray(got.uids[count:]) >= rows).all()
    assert (np.diff(np.asarray(got.uids)) > 0).all()
