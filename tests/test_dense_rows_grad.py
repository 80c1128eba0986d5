"""The dense-gradient step's table gradient, built from the batch's distinct
rows (``Trainer._grad_by_rows`` / ``_table_grads``).

AD hands a dense-update step its table gradient as a scatter-add of every
position's cotangent into a table of zeros: one latency-bound row update a
position, 122 ns each at 16.9M rows (PERF.md §6, PR 36). Where the trainer
can see that the model reads its tables through ``_emb_lookup`` alone, the
step differentiates the tables' ``[B, F, ...]`` views, sums the cotangents
per distinct row in a batch-tall array (``ops.embedding.sum_rows``) and
scatters only the distinct rows, a trip of ``ROW_UPDATE_CAPACITY`` at a
time: the same gradient up to the order of a float32 sum, dense Adam with
L2 unchanged after it. Held here, at small size in float32 on the CPU:

* the trained state against the same trainer with the tables left to AD,
  over {deepfm, dcnv2, multitask} x {1, 2 data replicas} x {l2_reg 0, 1e-4};
* the shapes that stress the rows: one row taking every id of a field, more
  distinct rows than a (patched-small) trip holds;
* ``_table_grads`` against NumPy: pad rows, negative ids and ids past the
  table receive nothing;
* the counts against NumPy's ``unique``;
* the compiled step: no table-tall scatter of a batch's positions where the
  step is eligible, and the scatter of every position wherever it is not.

Under data replicas the step sums that gradient over the ``data`` axis by
exchanging rows, not tables (PERF.md §6, PR 38): a trip gathers every
replica's ``ROW_UPDATE_CAPACITY`` (row id, summed cotangent) pairs and every
chip scatter-adds them all. Held on 2 and 4 virtual devices:

* the same trained state as AD's scatter followed by its ``psum``;
* the replicas' tables and both moments bit for bit the same after 3 steps;
* replicas that need different numbers of trips, a row every replica holds,
  and the ids that receive nothing, on every replica;
* ``embed_exchanged_rows`` (all replicas' rows together) against NumPy;
* the compiled step: no collective with a ``[V, k > 1]`` result.

A table whose row is one word (``fm_w``) stays out of that exchange (PERF.md
§6, PR 41): its gradient is the replica's own rows in one scatter-add beside
the trips' loop, all-reduced as a table. Held on the compiled step: the
trips' body scatters into no ``[V]`` operand and their pairs are as wide as
the wide tables alone; the one table-tall collective is that table's; a
model without such a table compiles none; one device compiles the loop it
compiled, both tables in its trips.

The views themselves are read by the same sort of (id, position), made
ahead of the forward (PERF.md §6, PR 42): a table whose row is narrower than
a lane line is gathered once a distinct row of the batch
(``ops.embedding.take_planned``) and the positions copy their slot. Held:

* the views against ``jnp.take(table, ids, axis=0)`` bit for bit: repeated
  ids, an all-distinct batch, more distinct rows than one trip and than
  two, negative ids, pad rows, ids past the table (which read the fill);
* the step against the same step with every table gathered a position (the
  parent's forward), bit for bit: loss, counts, tables and both moments,
  on 1, 2 and 4 data replicas and in the row-local update;
* the compiled step: no gather of a batch's positions from a table whose
  row is narrow, and the parent's gather from one whose row is a lane line.
"""

import functools
import itertools
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepfm_tpu.config import Config
from deepfm_tpu.ops import embedding as emb_ops
from deepfm_tpu.train import Trainer, loop
from deepfm_tpu.utils import profiling

V, F, B, STEPS = 300, 6, 32, 3
ID_RANGE = 200          # rows >= ID_RANGE are real and never touched

MODELS = {"deepfm": {}, "dcnv2": {"model": "dcnv2"},
          "multitask": {"tasks": "ctr,cvr", "multitask": "mmoe",
                        "mmoe_experts": 2}}
REPLICAS = (2, 4)
CASES = [pytest.param(m, d, l2, id=f"{m}-{d}dev-l2_{l2:g}")
         for m, d, l2 in itertools.product(MODELS, (1,) + REPLICAS,
                                           (0.0, 1e-4))]


def _cfg(model="deepfm", devices=1, l2_reg=1e-4, **over):
    flags = dict(
        feature_size=V, field_size=F, embedding_size=4, deep_layers="8,4",
        dropout="1.0,1.0", batch_size=B, compute_dtype="float32",
        l2_reg=l2_reg, learning_rate=0.01, log_steps=0, seed=11,
        scale_lr_by_world=False, mesh_data=devices, mesh_model=1,
        steps_per_loop=STEPS, transfer_ahead=0)
    flags.update(MODELS.get(model, {"model": model}))
    flags.update(over)
    return Config(**flags)


def _batches(two_label=False, one_row_field=None, steps=STEPS, seed=5):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        label = rng.integers(0, 2, size=(B, 1)).astype(np.float32)
        batch = {
            "feat_ids": rng.integers(0, ID_RANGE, (B, F)).astype(np.int32),
            "feat_vals": rng.normal(size=(B, F)).astype(np.float32),
            "label": label}
        if one_row_field is not None:
            batch["feat_ids"][:, one_row_field] = 77
        if two_label:
            batch["label2"] = (label * rng.integers(0, 2, (B, 1))
                               ).astype(np.float32)
        out.append(batch)
    return out


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _trainer(model="deepfm", devices=1, l2_reg=1e-4, by="rows", **flags):
    """The trainer of these flags, built once a module: a test that runs its
    step shares the compiled step with every other that does, and a test
    that reads its text (``_text``) the compiled text. ``by``: the tables'
    gradient and views by ``rows`` (the program's own), the gradient left to
    ``ad``, or the views gathered by ``positions``. The trips' capacity is
    read when the step is traced, so it is part of the key."""
    return _built(model, devices, l2_reg, by, loop.ROW_UPDATE_CAPACITY,
                  tuple(sorted(flags.items())))


@functools.lru_cache(maxsize=None)
def _built(model, devices, l2_reg, by, capacity, flags):
    tr = Trainer(_cfg(model, devices, l2_reg, **dict(flags)))
    if by == "ad":
        tr._grad_by_rows = lambda: False
    elif by == "positions":     # the parent's forward: the plan is made
        tr._looked_up_by_rows = lambda tabs: ()     # where the sums are
    return tr


@functools.lru_cache(maxsize=None)
def _text(tr):
    return tr.step_hlo_text()


def _run(model="deepfm", devices=1, l2_reg=1e-4, by_rows=True, batches=None):
    """(trainer, state after, metrics of each step), by single
    ``train_step`` calls so that every step's counts come back; with
    ``by_rows`` off the tables are left to AD (the reference)."""
    tr = _trainer(model, devices, l2_reg, by="rows" if by_rows else "ad")
    assert by_rows == tr._grad_by_rows() and not tr._row_local_eligible()
    state, metrics = _fit_steps(
        tr, batches or _batches(tr.cfg.num_tasks > 1))
    assert tr.embed_grad == _how(devices if by_rows else 0)
    return tr, state, metrics


def _fit_steps(tr, batches):
    state, metrics = tr.init_state(), []
    for batch in batches:
        state, m = tr.train_step(state, tr.put_batch(batch))
        metrics.append({k: float(v) for k, v in m.items()})
    return _host(state), metrics


def _how(devices):
    """``Trainer.embed_grad`` of an eligible step on that many data replicas
    (0: the tables left to AD)."""
    return {0: "positions", 1: "rows"}.get(devices,
                                           "rows, exchanged over data")


@functools.lru_cache(maxsize=None)
def _by_ad(model, devices, l2_reg):
    return _run(model, devices, l2_reg, by_rows=False)


def _assert_close(got, want, what, tol=1e-6):
    """Leaf by leaf in norm: the two steps sum a row's cotangents in two
    orders (and on two data replicas reduce them in two), nothing else."""
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(flat_got, jax.tree.leaves(want)):
        if not np.ndim(a):
            assert a == b, (what, jax.tree_util.keystr(path))
            continue
        gap = np.linalg.norm((a - b).ravel()) / max(
            np.linalg.norm(b.ravel()), 1e-30)
        assert gap <= tol, (what, jax.tree_util.keystr(path), gap)


@pytest.mark.parametrize("model,devices,l2_reg", CASES)
def test_equals_the_gradient_ad_builds(model, devices, l2_reg):
    _, want, plain = _by_ad(model, devices, l2_reg)
    tr, got, rows = _run(model, devices, l2_reg)
    for tree in ("params", "opt_state"):
        _assert_close(getattr(got, tree), getattr(want, tree), tree)
    assert [m["loss"] for m in rows] == pytest.approx(
        [m["loss"] for m in plain], rel=1e-6)
    assert all(loop.ROW_COUNTS[0] not in m for m in plain)
    assert all((loop.EXCHANGED_ROWS in m) == (devices > 1) for m in rows)
    # Pad rows stay exactly zero, parameters and moments.
    adam = got.opt_state[0]
    for name in tr.model.embedding_param_names():
        for leaf in (got.params[name], adam.mu[name], adam.nu[name]):
            assert not leaf[V:].any(), name


@pytest.mark.parametrize("model", sorted(MODELS))
def test_one_row_takes_every_id_of_a_field(model):
    batches = _batches(model == "multitask", one_row_field=F - 1)
    _, got, rows = _run(model, batches=batches)
    _, want, _ = _run(model, by_rows=False, batches=batches)
    for tree in ("params", "opt_state"):
        _assert_close(getattr(got, tree), getattr(want, tree), tree)
    assert all(m["embed_row_trips"] == 1 for m in rows)


@pytest.mark.parametrize("capacity,trips", [(256, 1), (64, 2), (8, None),
                                            (1, None)])
def test_more_distinct_rows_than_a_trip_holds(monkeypatch, capacity, trips):
    _, want, _ = _by_ad("deepfm", 1, 1e-4)
    monkeypatch.setattr(loop, "ROW_UPDATE_CAPACITY", capacity)
    _, got, rows = _run("deepfm")
    for tree in ("params", "opt_state"):
        _assert_close(getattr(got, tree), getattr(want, tree), tree)
    for m, batch in zip(rows, _batches()):
        distinct = len(np.unique(batch["feat_ids"]))
        assert m["embed_distinct_rows"] == distinct
        assert m["embed_row_trips"] == math.ceil(distinct / capacity)
        if trips is not None:
            assert m["embed_row_trips"] == trips
        else:
            assert m["embed_row_trips"] > 2


@pytest.mark.parametrize("devices", (1,) + REPLICAS)
def test_counts_against_numpy_unique(devices):
    """On data replicas each shard counts its own slice of the batch; the
    step reports the fullest shard's, and all shards' rows together: what
    every chip scatter-added."""
    _, _, rows = _run("deepfm", devices)
    for m, batch in zip(rows, _batches()):
        distinct = [len(np.unique(s))
                    for s in np.split(batch["feat_ids"], devices)]
        assert m["embed_distinct_rows"] == max(distinct)
        assert m["embed_row_trips"] == 1
        if devices > 1:
            assert m["embed_exchanged_rows"] == sum(distinct)
        else:
            assert "embed_exchanged_rows" not in m


@pytest.mark.parametrize("capacity", [4, 16, 2048])
def test_pad_rows_negative_ids_and_ids_past_the_table_receive_nothing(
        monkeypatch, capacity):
    """``_table_grads`` alone, against NumPy: ids count from the end where
    negative (as ``jnp.take`` reads them); a pad row (``feature_size`` and
    past it) and an id past the table receive nothing."""
    monkeypatch.setattr(loop, "ROW_UPDATE_CAPACITY", capacity)
    tr = _trainer("deepfm")
    rows = tr.model.padded_vocab
    assert rows > V
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 40, size=(B, F)).astype(np.int32)
    ids[0, :] = [-1, -rows, -(rows - 3), V, rows - 1, rows + 5]
    ids[1, 0] = V - 1
    g = {"fm_v": rng.normal(size=(B, F, 4)).astype(np.float32),
         "fm_w": rng.normal(size=(B, F)).astype(np.float32)}
    tabs = {n: jnp.zeros((rows,) + v.shape[2:], jnp.float32)
            for n, v in g.items()}
    got, counts = jax.jit(tr._table_grads)(tabs, jnp.asarray(ids), g)
    flat = ids.reshape(-1)
    norm = np.where(flat < 0, flat + rows, flat)
    ok = (norm >= 0) & (norm < V)
    for name, cot in g.items():
        want = np.zeros(tabs[name].shape, np.float64)
        np.add.at(want, norm[ok], cot.reshape((flat.size,) + cot.shape[2:])[ok])
        np.testing.assert_allclose(got[name], want, atol=1e-5)
        assert not np.asarray(got[name])[V:].any()
    distinct = len(np.unique(norm[ok]))
    assert int(counts["embed_distinct_rows"]) == distinct
    assert int(counts["embed_row_trips"]) == math.ceil(distinct / capacity)
    # rows 0 (from -rows), 3 (from -(rows - 3)) and V - 1 are real
    assert np.asarray(got["fm_w"])[[0, 3, V - 1]].all()


def _replica_copies(state, name_of):
    """Per leaf of the trained ``state``'s params and optimizer state that
    ``name_of`` keeps, every device's copy."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        (state.params, state.opt_state))
    return {jax.tree_util.keystr(path): [np.asarray(s.data)
                                         for s in leaf.addressable_shards]
            for path, leaf in flat if name_of(jax.tree_util.keystr(path))}


@pytest.mark.parametrize("devices", REPLICAS)
def test_replicas_stay_bit_identical(devices):
    """Every chip scatter-adds the same gathered pairs in the same order,
    so the replicated tables and both of Adam's moments cannot drift apart:
    after 3 steps each device's copy is the first's bit for bit."""
    tr = _trainer("deepfm", devices)
    state = tr.init_state()
    for batch in _batches():
        state, _ = tr.train_step(state, tr.put_batch(batch))
    names = tr.model.embedding_param_names()
    copies = _replica_copies(state, lambda k: any(n in k for n in names))
    # the tables, and mu and nu of each
    assert len(copies) == 3 * len(names), sorted(copies)
    for key, per_device in copies.items():
        assert len(per_device) == devices, key
        assert per_device[0].any(), key
        for other in per_device[1:]:
            assert other.tobytes() == per_device[0].tobytes(), key


def _exchanged_table_grads(tr, ids, g):
    """``_table_grads`` of a trainer on data replicas, the batch split over
    them as a step splits it: (gradients, counts), replicated."""
    rows = tr.model.padded_vocab
    tabs = {n: jnp.zeros((rows,) + v.shape[2:], jnp.float32)
            for n, v in g.items()}
    axis = tr.mesh_info.data_axis
    fn = shard_map(
        functools.partial(tr._table_grads, sum_axis=axis),
        mesh=tr.mesh_info.mesh, in_specs=(P(), P(axis), P(axis)),
        out_specs=P(), check_vma=True)
    return jax.jit(fn)(tabs, jnp.asarray(ids), g)


def _numpy_table_grads(ids, g, rows):
    flat = ids.reshape(-1)
    norm = np.where(flat < 0, flat + rows, flat)
    ok = (norm >= 0) & (norm < V)
    want = {}
    for name, cot in g.items():
        want[name] = np.zeros((rows,) + cot.shape[2:], np.float64)
        np.add.at(want[name], norm[ok],
                  cot.reshape((flat.size,) + cot.shape[2:])[ok])
    return want, norm, ok


def _cotangents(rng):
    return {"fm_v": rng.normal(size=(B, F, 4)).astype(np.float32),
            "fm_w": rng.normal(size=(B, F)).astype(np.float32)}


@pytest.mark.parametrize("devices,capacity", [
    (d, c) for d in REPLICAS for c in (4, 16, 2048)])
def test_replicas_that_need_different_trips_lose_nothing(
        monkeypatch, devices, capacity):
    """The first replica holds as many distinct rows as it has positions
    (many trips at a small capacity), the last one row; every replica
    makes the fullest one's trips and the others hand in spare slots."""
    monkeypatch.setattr(loop, "ROW_UPDATE_CAPACITY", capacity)
    tr = _trainer("deepfm", devices)
    rng = np.random.default_rng(3)
    per = B // devices
    ids = rng.integers(0, 40, size=(B, F)).astype(np.int32)
    ids[:per] = np.arange(per * F).reshape(per, F)      # all distinct
    ids[-per:] = 123                                    # one row
    g = _cotangents(rng)
    got, counts = _exchanged_table_grads(tr, ids, g)
    want, _, _ = _numpy_table_grads(ids, g, tr.model.padded_vocab)
    for name in g:
        np.testing.assert_allclose(got[name], want[name], atol=1e-5)
    distinct = [len(np.unique(s)) for s in np.split(ids, devices)]
    assert distinct[0] == per * F and distinct[-1] == 1
    assert int(counts["embed_distinct_rows"]) == per * F
    assert int(counts["embed_row_trips"]) == math.ceil(per * F / capacity)
    assert int(counts["embed_exchanged_rows"]) == sum(distinct)


@pytest.mark.parametrize("devices", REPLICAS)
def test_a_row_every_replica_holds_receives_the_sum_of_all(devices):
    """Two replicas' pairs for one row are two slots of one scatter-add."""
    tr = _trainer("deepfm", devices)
    rng = np.random.default_rng(4)
    ids = rng.integers(0, ID_RANGE, size=(B, F)).astype(np.int32)
    ids[:, 0] = 5           # every example of every replica looks row 5 up
    g = _cotangents(rng)
    got, _ = _exchanged_table_grads(tr, ids, g)
    where = ids == 5
    np.testing.assert_allclose(
        got["fm_w"][5], g["fm_w"][where].astype(np.float64).sum(), rtol=1e-5)
    np.testing.assert_allclose(
        got["fm_v"][5], g["fm_v"][where].astype(np.float64).sum(0),
        rtol=1e-5, atol=1e-6)
    want, _, _ = _numpy_table_grads(ids, g, tr.model.padded_vocab)
    for name in g:
        np.testing.assert_allclose(got[name], want[name], atol=1e-5)


@pytest.mark.parametrize("devices,capacity", [
    (d, c) for d in REPLICAS for c in (4, 2048)])
def test_ids_that_receive_nothing_receive_nothing_on_any_replica(
        monkeypatch, devices, capacity):
    """The one-device case's ids, a few on every replica: pad rows,
    negative ids and ids past the table."""
    monkeypatch.setattr(loop, "ROW_UPDATE_CAPACITY", capacity)
    tr = _trainer("deepfm", devices)
    rows = tr.model.padded_vocab
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 40, size=(B, F)).astype(np.int32)
    ids[::B // devices, :] = [-1, -rows, -(rows - 3), V, rows - 1, rows + 5]
    g = _cotangents(rng)
    got, counts = _exchanged_table_grads(tr, ids, g)
    want, norm, ok = _numpy_table_grads(ids, g, rows)
    for name in g:
        np.testing.assert_allclose(got[name], want[name], atol=1e-5)
        assert not np.asarray(got[name])[V:].any()
    shards = np.split(np.where(ok, norm, -1).reshape(B, F), devices)
    distinct = [len(np.setdiff1d(s, [-1])) for s in shards]
    assert int(counts["embed_distinct_rows"]) == max(distinct)
    assert int(counts["embed_exchanged_rows"]) == sum(distinct)
    assert np.asarray(got["fm_w"])[[0, 3]].all()


@pytest.mark.parametrize("devices", REPLICAS)
def test_more_distinct_rows_than_a_trip_holds_on_replicas(monkeypatch,
                                                          devices):
    """The whole step over several exchanged trips against AD's."""
    _, want, _ = _by_ad("deepfm", devices, 1e-4)
    monkeypatch.setattr(loop, "ROW_UPDATE_CAPACITY", 8)
    _, got, rows = _run("deepfm", devices)
    for tree in ("params", "opt_state"):
        _assert_close(getattr(got, tree), getattr(want, tree), tree)
    for m, batch in zip(rows, _batches()):
        distinct = [len(np.unique(s))
                    for s in np.split(batch["feat_ids"], devices)]
        assert m["embed_row_trips"] == math.ceil(max(distinct) / 8) > 2
        assert m["embed_exchanged_rows"] == sum(distinct)


def _table_scatters(hlo_text, rows):
    """Per scatter of the compiled program into an array ``rows`` tall:
    (that array's row shape, how many rows of updates it is handed, whether
    it sits in the body of the trips' ``while``)."""
    found, shapes = [], {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[(\d*)", line)
        if m:
            shapes[m.group(1)] = int(m.group(2) or 0)
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\][^ ]* "
                     r"scatter\(%[\w.\-]+, %[\w.\-]+, %([\w.\-]+)\)", line)
        if m:
            dims = tuple(int(d) for d in m.group(1).split(","))
            if dims[0] == rows:
                found.append((dims[1:], shapes[m.group(2)],
                              "embed/while/body" in line))
    return found


def _table_scatter_heights(hlo_text, rows):
    return [height for _, height, _ in _table_scatters(hlo_text, rows)]


def _own_slots(devices):
    """The slots of one replica's ``RowSums``: its positions, rounded up to
    whole trips."""
    cap = loop.ROW_UPDATE_CAPACITY
    return -(-(B // devices * F) // cap) * cap


@pytest.mark.parametrize("model,devices", [
    (m, d) for m in sorted(MODELS) for d in (1,) + REPLICAS])
def test_eligible_step_scatters_trips_of_rows_never_positions(model, devices):
    """A trip scatters ``ROW_UPDATE_CAPACITY`` rows of every data replica;
    on replicas the one-word-row table takes the replica's own rows, every
    slot in one scatter."""
    tr = _trainer(model, devices)
    assert tr._grad_by_rows()
    text = _text(tr)
    scatters = _table_scatters(text, tr.model.padded_vocab)
    in_trips = {h for row, h, _ in scatters if row or devices == 1}
    assert in_trips == {devices * loop.ROW_UPDATE_CAPACITY}, scatters
    if devices > 1:
        assert [h for row, h, _ in scatters if not row] == [
            _own_slots(devices)], scatters
    assert tr.embed_grad == _how(devices)
    # and Adam still sweeps every row
    ops = profiling.hlo_table_ops(text, tr.model.padded_vocab)
    assert [o for o in ops if o["scope"] == "opt" and o["tables"]
            and not o["primitive"].startswith("scatter")], ops


@pytest.mark.parametrize("devices", (1,) + REPLICAS)
def test_the_trips_scatter_into_no_one_word_row_table_on_replicas(devices):
    """On replicas the ``[V]`` table's one scatter lies beside the trips'
    ``while``, whose body holds the wide table's alone; on one device the
    loop is as it was, both tables' scatters in its body."""
    tr = _trainer("deepfm", devices)
    scatters = _table_scatters(_text(tr), tr.model.padded_vocab)
    assert sorted((row, inside) for row, _, inside in scatters) == [
        ((), devices == 1), ((4,), True)], scatters
    assert tr.embed_grad_by_table == ("fm_w" if devices > 1 else "")


def _collective_shapes(hlo_text):
    """The dimensions of every array a collective of the compiled program
    results in (a tuple's every member)."""
    shapes = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) (?:all-reduce|"
                     r"all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start)?\(", line)
        if m:
            shapes += [tuple(int(d) for d in dims.split(",") if d) for dims
                       in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]
    return shapes


def _as_tall_as(shapes, height):
    return sorted(s for s in shapes if s[:1] == (height,))


@pytest.mark.parametrize("model,devices", [
    (m, d) for m in sorted(MODELS) for d in REPLICAS])
def test_only_one_word_row_tables_cross_as_tables_on_data_replicas(model,
                                                                   devices):
    """What crosses the interconnect is a trip's pairs, as wide as the wide
    table alone, the ``[V]`` table's gradient, the dense leaves' and
    scalars; with the tables left to AD it is every table."""
    tr = _trainer(model, devices)
    rows = tr.model.padded_vocab
    shapes = _collective_shapes(_text(tr))
    pairs = devices * loop.ROW_UPDATE_CAPACITY
    assert _as_tall_as(shapes, pairs) == [(pairs,), (pairs, 4)], shapes
    assert _as_tall_as(shapes, rows) == [(rows,)], shapes
    assert tr.embed_grad_by_table == "fm_w"
    by_ad = _trainer(model, devices, by="ad")
    assert (rows, 4) in _collective_shapes(_text(by_ad))
    assert by_ad.embed_grad_by_table == "fm_w,fm_v"


@pytest.mark.parametrize("devices", REPLICAS)
def test_a_model_without_a_one_word_row_table_compiles_no_table_collective(
        devices):
    """``dlrm_dcnv2`` holds ``fm_v`` alone: every table's rows ride the
    trips, as before."""
    tr = _trainer(
        "dlrm_dcnv2", devices, numeric_fields=2, bottom_layers="8,4",
        cross_layers=2, cross_rank=2, deep_layers="8,4", dropout="1,1")
    assert tr._grad_by_rows() and not tr._row_local_eligible()
    rows = tr.model.padded_vocab
    shapes = _collective_shapes(_text(tr))
    pairs = devices * loop.ROW_UPDATE_CAPACITY
    assert _as_tall_as(shapes, pairs) == [(pairs,), (pairs, 4)], shapes
    assert not _as_tall_as(shapes, rows), shapes
    assert (tr.embed_grad, tr.embed_grad_by_table) == (_how(devices), "")
    assert {(row, inside) for row, _, inside in _table_scatters(
        _text(tr), rows)} == {((4,), True)}


def test_one_device_compiles_no_collective():
    tr = _trainer("deepfm", 1)
    assert not _collective_shapes(_text(tr))
    assert (tr.embed_grad, tr.embed_grad_by_table) == ("rows", "")


NOT_ELIGIBLE = {
    # why: (flags, height of the local table, positions a scatter takes)
    "row_shards": (dict(mesh_model=2), 320 // 2,
                   B * F),
    "hashed": (dict(embedding_buckets="64,64"), 64, B * F),
    "history_model": (dict(history_max_len=5), 320, None),
    "accumulation": (dict(grad_accum_steps=2, steps_per_loop=4), 320, B * F),
    "sparse_update": (dict(embedding_update="sparse"), 320, None),
}


@pytest.mark.parametrize("why", sorted(NOT_ELIGIBLE))
def test_everything_else_compiles_the_step_it_compiled(why):
    """Each reason alone keeps the tables with AD: the predicate says no,
    and the compiled step scatters the batch's positions as it did, not
    trips of rows (the sparse plane has its own row plan and another state
    tree: only the predicate is held there)."""
    flags, height, positions = NOT_ELIGIBLE[why]
    tr = _trainer("din" if why == "history_model" else "deepfm", **flags)
    assert not tr._grad_by_rows() and not tr._row_local_eligible()
    if why == "sparse_update":
        return
    heights = _table_scatter_heights(_text(tr), height)
    assert tr.embed_grad == "positions"
    assert heights and loop.ROW_UPDATE_CAPACITY not in heights, heights
    if positions is not None:
        assert set(heights) == {positions}, heights


# ---------------------------------------------------------------------------
# The forward reads each distinct row once (PR 42)
# ---------------------------------------------------------------------------

ROWS = 320              # the tables' height: V = 300 real rows, 20 pad rows


def _id_cases():
    rng = np.random.default_rng(9)
    some = rng.integers(0, 40, size=(B, F)).astype(np.int32)
    odd = some.copy()
    odd[0, :] = [-1, -ROWS, -(ROWS - 3), -ROWS - 5, -7, -(ROWS - V)]
    pad = some.copy()
    pad[0, :] = [V, V + 1, ROWS - 1, V, 5, V - 1]
    past = some.copy()
    past[0, :] = [ROWS, ROWS + 5, 2 ** 31 - 1, -ROWS - 1, -2 ** 31, ROWS]
    return {
        "repeated": some,
        "one-row": np.full((B, F), 77, np.int32),
        "all-distinct": rng.permutation(V)[:B * F].reshape(B, F).astype(
            np.int32),
        "negative": odd, "pad-rows": pad, "past-the-table": past,
        "all-past-the-table": np.full((B, F), ROWS + 3, np.int32),
    }


ID_CASES = _id_cases()


@pytest.mark.parametrize("trip", [4, 64, 100, 2048])
@pytest.mark.parametrize("case", sorted(ID_CASES))
def test_views_equal_take_bit_for_bit(case, trip):
    """A ``[V]`` and a ``[V, K]`` table together, at trips that one, two
    and many of hold the distinct rows (an all-distinct batch has 192: two
    trips of 100, three of 64, 48 of 4): every view is ``jnp.take``'s, the
    NaN an id past the table reads included."""
    ids = ID_CASES[case]
    rng = np.random.default_rng(2)
    tables = [jnp.asarray(rng.normal(size=shape).astype(np.float32))
              for shape in ((ROWS,), (ROWS, 4), (ROWS, 2, 3))]

    def views(tables, ids):
        plan = emb_ops.plan_rows(ids, ROWS, V, multiple=trip,
                                 keep_pad_rows=True)
        return emb_ops.take_planned(tables, plan, ids.shape, trip), plan

    got, plan = jax.jit(views)(tables, jnp.asarray(ids))
    for table, view in zip(tables, got):
        want = jnp.take(table, jnp.asarray(ids), axis=0)
        assert view.shape == want.shape and view.dtype == want.dtype
        assert np.asarray(view).tobytes() == np.asarray(want).tobytes()
    flat = ids.reshape(-1).astype(np.int64)
    norm = np.where(flat < 0, flat + ROWS, flat)
    in_table = (norm >= 0) & (norm < ROWS)
    assert int(plan.held) == len(np.unique(norm[in_table]))
    assert int(plan.count) == len(np.unique(norm[(norm >= 0) & (norm < V)]))
    if case == "past-the-table":
        assert np.isnan(np.asarray(got[0])[0]).all()


def test_tables_of_two_types_are_read_as_their_own():
    """The rows of one trip lie side by side in one array of the wider
    type; each view comes back in its table's."""
    ids = jnp.asarray(ID_CASES["negative"])
    rng = np.random.default_rng(3)
    tables = [jnp.asarray(rng.normal(size=(ROWS, 4)), jnp.bfloat16),
              jnp.asarray(rng.normal(size=(ROWS,)), jnp.float32)]
    plan = emb_ops.plan_rows(ids, ROWS, V, multiple=64, keep_pad_rows=True)
    for table, view in zip(tables, emb_ops.take_planned(tables, plan,
                                                        ids.shape, 64)):
        want = jnp.take(table, ids, axis=0)
        assert view.dtype == table.dtype
        assert np.asarray(view).tobytes() == np.asarray(want).tobytes()


def _assert_same_bits(got, want):
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(flat_got, jax.tree.leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), (
            jax.tree_util.keystr(path))


STEP_CASES = [pytest.param(m, d, c, id=f"{m}-{d}dev-trip{c}")
              for m in sorted(MODELS) for d in (1,) + REPLICAS
              for c in (8, 2048)]


@pytest.mark.parametrize("model,devices,capacity", STEP_CASES)
def test_step_equals_the_step_that_gathers_a_position(monkeypatch, model,
                                                      devices, capacity):
    """A gather is a copy and the cotangents are summed in the same order,
    so nothing after the views may differ by a bit: the loss and the counts
    of every step, the tables, both moments, the dense leaves."""
    monkeypatch.setattr(loop, "ROW_UPDATE_CAPACITY", capacity)
    batches = _batches(model == "multitask")
    # negative ids and pad rows (read, as zeros; an id past the table would
    # read NaN into both losses)
    batches[1]["feat_ids"][0, :] = [-1, -ROWS, V, ROWS - 1, -(ROWS - V), 7]
    tr = _trainer(model, devices)
    got, got_m = _fit_steps(tr, batches)
    want, want_m = _fit_steps(_trainer(model, devices, by="positions"),
                              batches)
    assert tr.embed_lookup == "rows"
    assert got_m == want_m
    for tree in ("params", "opt_state"):
        _assert_same_bits(getattr(got, tree), getattr(want, tree))


@pytest.mark.parametrize("capacity", [8, 2048])
def test_row_local_step_equals_the_step_that_gathers_a_position(monkeypatch,
                                                                capacity):
    monkeypatch.setattr(loop, "ROW_UPDATE_CAPACITY", capacity)
    row_local = dict(l2_reg=0.0, optimizer="Adagrad")
    tr = _trainer(**row_local)
    assert tr._row_local_eligible()
    got, got_m = _fit_steps(tr, _batches())
    want, want_m = _fit_steps(_trainer(by="positions", **row_local),
                              _batches())
    assert tr.embed_lookup == "rows" and got_m == want_m
    for tree in ("params", "opt_state"):
        _assert_same_bits(getattr(got, tree), getattr(want, tree))


@pytest.mark.parametrize("devices", REPLICAS)
def test_replicas_stay_bit_identical_with_the_views_read_by_rows(devices):
    """Each replica plans and reads its own slice of the batch; what they
    exchange and add is what it was, so their copies cannot drift."""
    tr = _trainer("deepfm", devices)
    state = tr.init_state()
    for batch in _batches(steps=2):
        state, _ = tr.train_step(state, tr.put_batch(batch))
    assert tr.embed_lookup == "rows"
    names = tr.model.embedding_param_names()
    copies = _replica_copies(state, lambda k: any(n in k for n in names))
    for key, per_device in copies.items():
        assert len(per_device) == devices and per_device[0].any(), key
        assert all(other.tobytes() == per_device[0].tobytes()
                   for other in per_device[1:]), key


def _table_gathers(hlo_text, rows):
    """Per gather of the compiled program from an array ``rows`` tall:
    (that array's row shape, how many rows it gathers)."""
    found, shapes = [], {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]", line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        shapes[m.group(1)] = dims
        g = re.search(r" gather\(%([\w.\-]+), %([\w.\-]+)\)", line)
        if g and shapes.get(g.group(1), ())[:1] == (rows,):
            taken = shapes[g.group(2)]
            found.append((shapes[g.group(1)][1:],
                          math.prod(taken[:-1] if len(taken) > 1 else taken)))
    return found


LOOKUP_CASES = {
    # flags: (embed_lookup, {row shape: rows its gathers take})
    "deepfm-k4": (dict(), "rows", {(): "trip", (4,): "trip"}),
    "deepfm-k128": (dict(embedding_size=128), "fm_w:rows,fm_v:positions",
                    {(): "trip", (128,): "positions"}),
    "deepfm-k127": (dict(embedding_size=127), "rows",
                    {(): "trip", (127,): "trip"}),
    "dlrm_dcnv2-k128": (dict(
        model="dlrm_dcnv2", numeric_fields=2, bottom_layers="8,128",
        cross_layers=2, cross_rank=2, deep_layers="8,4", dropout="1,1",
        embedding_size=128), "positions", {(128,): "positions"}),
    "row-local-k4": (dict(optimizer="Adagrad", l2_reg=0.0), "rows",
                     {(): "trip", (4,): "trip"}),
}


@pytest.mark.parametrize("case", sorted(LOOKUP_CASES))
def test_which_tables_are_read_by_rows_is_the_rows_shape(case):
    """A row narrower than one 128-lane line is gathered a trip of distinct
    rows at a time and never a position; a row of whole lines compiles to
    the gather it compiled, every position straight from the table, and a
    step with no narrow table to the parent's step."""
    flags, lookup, want = LOOKUP_CASES[case]
    # (one call compiles both: the text carries its callers' lines and
    # columns)
    (tr, text), (_, parent) = [(t, _text(t)) for t in (
        _trainer(**flags), _trainer(by="positions", **flags))]
    assert tr.embed_lookup == lookup
    positions = tr.model.lookup_ids(np.zeros((B, F), np.int32)).size
    cap = loop.ROW_UPDATE_CAPACITY
    forward = {}
    for row, taken in _table_gathers(text, tr.model.padded_vocab):
        forward.setdefault(row, set()).add(taken)
    # (a row-local step also gathers its trips of rows to update them)
    assert {row: ({positions} if how == "positions" else {cap})
            for row, how in want.items()} == forward, forward
    assert (text == parent) == (lookup == "positions")
