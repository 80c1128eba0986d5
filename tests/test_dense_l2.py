"""The dense-update step's L2 term (``Trainer._add_dense_l2``).

Dense L2 is charged on every real row at every optimizer apply, and its
gradient ``l2_reg * w`` has to reach Adam as arithmetic inside the sweep:
never as a table of its own that the batch's scatter-add then takes as its
operand (XLA rewrites ``scatter-add(zeros) + X`` into ``scatter-add(X)``; on
the chip that table costs two passes over 2.16 GB a step — PERF.md §6,
PR 26). Three checks over {deepfm, dcnv2, multitask} x {one device, two
data-parallel virtual devices} x {l2_reg 0, 1e-4}:

* the mathematics, against a plain float32 dense Adam + L2 written out here;
* the structure, in the step as XLA compiled it (the CPU backend makes the
  same rewrite, so the unfenced formulation fails here too);
* one definition: the accumulating step over a single microbatch is the plain
  step, bit for bit.
"""

import functools
import itertools
import re

import numpy as np
import pytest

import jax

from deepfm_tpu.config import Config
from deepfm_tpu.train import Trainer
from deepfm_tpu.utils import profiling

V, F, B, STEPS = 300, 6, 32, 3
ID_RANGE = 200          # rows >= ID_RANGE are real and never touched
LR, B1, B2, EPS = 0.01, 0.9, 0.999, 1e-8

MODELS = {"deepfm": {}, "dcnv2": {"model": "dcnv2"},
          "multitask": {"tasks": "ctr,cvr", "multitask": "mmoe",
                        "mmoe_experts": 2}}
CASES = [pytest.param(m, d, l2, id=f"{m}-{d}dev-l2_{l2:g}")
         for m, d, l2 in itertools.product(MODELS, (1, 2), (0.0, 1e-4))]


def _cfg(model, devices, l2_reg):
    return Config(
        feature_size=V, field_size=F, embedding_size=4, deep_layers="8,4",
        dropout="1.0,1.0", batch_size=B, compute_dtype="float32",
        l2_reg=l2_reg, learning_rate=LR, log_steps=0, seed=11,
        scale_lr_by_world=False, mesh_data=devices, mesh_model=1,
        steps_per_loop=STEPS, transfer_ahead=0, **MODELS[model])


def _batches(two_label):
    rng = np.random.default_rng(5)
    out = []
    for _ in range(STEPS):
        label = rng.integers(0, 2, size=(B, 1)).astype(np.float32)
        batch = {
            "feat_ids": rng.integers(0, ID_RANGE, (B, F)).astype(np.int32),
            "feat_vals": rng.normal(size=(B, F)).astype(np.float32),
            "label": label}
        if two_label:
            batch["label2"] = (label * rng.integers(0, 2, (B, 1))
                               ).astype(np.float32)
        out.append(batch)
    return out


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _run(model, devices, l2_reg, accumulating=False):
    """(trainer, state before, state after STEPS steps), on the host."""
    tr = Trainer(_cfg(model, devices, l2_reg))
    if accumulating:
        # Every step goes through the accumulating step as a group of one
        # microbatch, inside the same scan, jit and shard_map.
        tr._step_impl = lambda st, batch, **axes: tr._accum_step_impl(
            st, jax.tree.map(lambda x: x[None], batch), **axes)
    state = tr.init_state()
    before = _host(state)
    state, out = tr.fit(state, iter(_batches(model == "multitask")))
    assert out["steps"] == STEPS
    return tr, before, _host(state)


@functools.lru_cache(maxsize=None)
def _plain(model, devices, l2_reg):
    return _run(model, devices, l2_reg)


def _reference(tr, before, batches, l2_reg):
    """STEPS steps of dense Adam with L2 on every real row, in float32
    NumPy; only the data loss's gradient comes from the model."""
    real = np.arange(tr.model.padded_vocab) < V
    params = before.params

    @jax.jit
    def data_grads(p, batch):
        def loss(p):
            logits, _ = tr.model.apply(
                p, before.model_state, batch["feat_ids"],
                batch["feat_vals"], train=True,
                rng=jax.random.PRNGKey(0))
            return tr._mean_loss(logits, batch)
        return jax.grad(loss)(p)

    f32 = np.float32
    mu = jax.tree.map(np.zeros_like, params)
    nu = jax.tree.map(np.zeros_like, params)
    for t, batch in enumerate(batches, 1):
        g = _host(data_grads(params, batch))
        for name in tr.model.embedding_param_names():
            keep = real.reshape((-1,) + (1,) * (params[name].ndim - 1))
            g[name] = np.where(keep, g[name] + f32(l2_reg) * params[name],
                               f32(0))
        mu = jax.tree.map(lambda m, x: f32(B1) * m + f32(1 - B1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: f32(B2) * v + f32(1 - B2) * x * x,
                          nu, g)
        params = jax.tree.map(
            lambda p, m, v: p - f32(LR) * (m / (1 - f32(B1) ** t)) / (
                np.sqrt(v / (1 - f32(B2) ** t)) + f32(EPS)), params, mu, nu)
    return params, mu, nu


def _assert_close(got, want, what):
    """Leaf by leaf in norm, to 1e-5: the model's backward pass is compiled
    here and in the trainer as two programs and sums in two orders (up to
    2e-6 on the cross layers). The L2 term itself is held to 1e-6 below,
    on the rows that nothing else moves."""
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(flat_got, jax.tree.leaves(want)):
        gap = np.linalg.norm((a - b).ravel()) / max(
            np.linalg.norm(b.ravel()), 1e-30)
        assert gap <= 1e-5, (what, jax.tree_util.keystr(path), gap)


@pytest.mark.parametrize("model,devices,l2_reg", CASES)
def test_matches_plain_dense_adam_with_l2(model, devices, l2_reg):
    tr, before, after = _plain(model, devices, l2_reg)
    batches = _batches(model == "multitask")
    params, mu, nu = _reference(tr, before, batches, l2_reg)
    adam = after.opt_state[0]
    _assert_close(after.params, params, "params")
    _assert_close(adam.mu, mu, "mu")
    _assert_close(adam.nu, nu, "nu")

    touched = np.unique(np.concatenate(
        [b["feat_ids"].ravel() for b in batches]))
    idle = np.setdiff1d(np.arange(V), touched)
    assert idle.size >= V - ID_RANGE
    for name in tr.model.embedding_param_names():
        w0, w = before.params[name], after.params[name]
        # Pad rows: exactly zero, parameters and moments.
        for leaf in (w, adam.mu[name], adam.nu[name]):
            assert not leaf[V:].any(), name
        # Untouched real rows: moved by the L2 term alone.
        if l2_reg:
            # (to a millionth of the move, or of one Adam step)
            np.testing.assert_allclose(
                (w - w0)[idle], (params[name] - w0)[idle], rtol=1e-6,
                atol=1e-6 * LR)
            assert (w[idle] != w0[idle]).all(), name
        else:
            np.testing.assert_array_equal(w[idle], w0[idle])
            assert not adam.mu[name][idle].any()
            assert not adam.nu[name][idle].any()


def _carried_in(hlo_text, name):
    """What a loop was handed for the element of its carry that ``name``
    reads in the loop's body (``get-tuple-element(body parameter),
    index=k`` -> operand k of the tuple the ``while`` starts from)."""
    computation = None
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ENTRY\s+)?%([\w.\-]+) \(.*\{\s*$", line)
        if m:
            computation = m.group(1)
        m = re.match(r"\s*%" + re.escape(name) + r" = .*get-tuple-element\("
                     r"%[\w.\-]+\), index=(\d+)", line)
        if m:
            index, body = int(m.group(1)), computation
            break
    else:
        raise AssertionError(f"{name} reads no tuple")
    start = re.search(r" while\(%([\w.\-]+)\), condition=%[\w.\-]+, "
                      r"body=%" + re.escape(body) + r"[,\s]", hlo_text)
    assert start, (name, body)
    made = re.search(r"%" + re.escape(start.group(1)) + r" = \(.*?\) "
                     r"tuple\((.*?)\)[,\s]", hlo_text)
    return re.findall(r"%([\w.\-]+)", made.group(1))[index]


@pytest.mark.parametrize("model,devices,l2_reg", CASES)
def test_l2_term_makes_no_table_of_its_own(model, devices, l2_reg):
    tr, _, _ = _plain(model, devices, l2_reg)
    text = tr.step_hlo_text()
    ops = profiling.hlo_table_ops(text, tr.model.padded_vocab)
    by_name = {o["name"]: o for o in ops}
    scatters = [o for o in ops if "scatter" in o["name"]]
    assert scatters, ops
    for op in scatters:
        assert op["tables"], op
        for name in op["tables"]:
            # The scatter-add's own table is a fill: made from no table. The
            # table gradient is built from the batch's distinct rows a trip
            # at a time (``Trainer._table_grads``), so the scatter reads the
            # loop's carry, and the fill is what the loop starts from.
            if name not in by_name:
                name = _carried_in(text, name)
            assert by_name[name]["primitive"] == "broadcast_in_dim", (
                op, by_name[name])
            assert not by_name[name]["tables"], (op, by_name[name])


@pytest.mark.parametrize("model,devices,l2_reg", CASES)
def test_accumulating_step_is_the_plain_step(model, devices, l2_reg):
    """Bit for bit, though the plain step builds its table gradient from the
    batch's distinct rows (``Trainer._table_grads``) and the accumulating
    one leaves it to AD's scatter-add of every position: ``sum_rows``'
    stable sort keeps a row's positions in batch order and XLA:CPU's
    scatter-adds take their updates in order, so both add a row's
    cotangents in the same order here. (A backend that reorders a
    scatter-add's updates would need a summation-order tolerance; the
    gradient's own parity is held in ``tests/test_dense_rows_grad.py``.)"""
    _, _, plain = _plain(model, devices, l2_reg)
    _, _, accumulated = _run(model, devices, l2_reg, accumulating=True)
    for tree in ("params", "opt_state"):
        flat, _ = jax.tree_util.tree_flatten_with_path(getattr(plain, tree))
        for (path, a), b in zip(flat,
                                jax.tree.leaves(getattr(accumulated, tree))):
            np.testing.assert_array_equal(
                a, b, err_msg=tree + jax.tree_util.keystr(path))
