"""Embedding-plane kernel legs vs NumPy oracles, plus the kernel-selection
gates and the trainer's kill-switch parity contract.

The compiled Pallas take kernels run only on TPU; the ``pallas``-marked
tests exercise the identical kernel bodies through the Pallas interpreter on
CPU, and ``test_pallas_kernels_lower_for_tpu`` cross-lowers every kernel in
``deepfm_tpu/ops`` for TPU at the reference shape — which is where the
deleted plan/install kernels were refused (``Cannot store scalars to
VMEM``). The parity tests pin the ``--embedding_kernels`` contract:

* ``auto`` vs ``xla``: bit-identical (same fused formulation, A/B legs
  are element-identical).
* hashed layout, ``off`` vs ``auto``: bit-identical (plan-path swap only
  — counting and sort builds emit identical plans, the select-writeback
  companions are stripped by the trainer).
* monolithic, ``off`` vs ``auto``: the fused vocab-space formulation.
  Gradients are bit-identical; lazy Adam's bias-correction tail rounds
  1-2 ULP apart between the row-space and table-sweep programs (XLA:CPU
  fuses the [U]- and [rows]-shaped chains differently), so the
  trajectory is pinned within a tight tolerance and the per-step losses
  are pinned bit-equal.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.ops import embedding as emb_ops
from deepfm_tpu.ops import pallas_embedding as pemb
from deepfm_tpu.train import Trainer

pytestmark = []


def _ids(shape, rows, seed=0, oob=False):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, rows, shape).astype(np.int32)
    if oob:
        ids.reshape(-1)[:: 7] = rows  # the OOB fill id (masked positions)
    return ids


# ---------------------------------------------------------------------------
# Seam 1: device-side plan build
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,rows,seed", [
    ((8, 3), 32, 0), ((16, 5), 64, 1), ((4, 4), 16, 2),
])
def test_counting_plan_matches_numpy_oracle(shape, rows, seed):
    ids = _ids(shape, rows, seed)
    got = emb_ops.make_plan_counting(jnp.asarray(ids), rows)
    uids, inv, touched, rank = pemb.reference_plan_numpy(ids, rows)
    np.testing.assert_array_equal(np.asarray(got.uids), uids)
    np.testing.assert_array_equal(np.asarray(got.inv), inv)
    np.testing.assert_array_equal(np.asarray(got.touched), touched)
    # rank is only defined under touched (oracle zeros elsewhere).
    np.testing.assert_array_equal(
        np.asarray(got.rank)[touched], rank[touched])


def test_plan_legs_match():
    """Both plan legs must emit bit-identical uids/inv (the plan is part of
    the numerics contract: rows order decides scatter order)."""
    ids = jnp.asarray(_ids((12, 4), 40, seed=3))
    c = emb_ops.make_plan_counting(ids, 40)
    s = emb_ops.make_plan(ids, 40)
    np.testing.assert_array_equal(np.asarray(c.uids), np.asarray(s.uids))
    np.testing.assert_array_equal(np.asarray(c.inv), np.asarray(s.inv))


def test_plan_build_gates():
    """Leg selection: off => sort-based seed; oversized tables keep the
    sort build even under auto/xla (the counting pass scales with rows);
    CPU auto/xla => counting (no compiled pallas off-TPU)."""
    ids = jnp.asarray(_ids((4, 2), 8))
    assert pemb.plan_build(ids, 8, mode="off").touched is None
    assert pemb.plan_build(ids, 8, mode="auto").touched is not None
    assert pemb.resolve("auto", "plan", num_rows=8, n_ids=8) == "opt"
    big = pemb.PLAN_COUNT_MAX_ROWS + 1
    assert pemb.resolve("auto", "plan", num_rows=big, n_ids=8) == "ref"
    assert pemb.resolve("off", "plan", num_rows=8, n_ids=8) == "ref"
    with pytest.raises(ValueError, match="embedding_kernels"):
        pemb.resolve("bogus", "plan", num_rows=8, n_ids=8)
    assert not pemb.supported("plan", num_rows=8, n_ids=8)  # CPU backend


def test_resolve_only_returns_legs_that_exist(monkeypatch):
    """The Pallas plan-build and install kernels were deleted (the TPU
    lowering refuses them), so no mode on any backend may select a
    ``pallas`` leg for those seams; ``take`` keeps its kernel, on TPU, while
    the working set fits the VMEM limit it is compiled with. The ``pallas``
    mode went with them: asking for it is a config error, not a quiet XLA
    leg."""
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(pemb.jax, "default_backend", lambda b=backend: b)
        for mode in pemb.MODES:
            for seam in ("plan", "install"):
                assert pemb.resolve(mode, seam, num_rows=117_581,
                                    n_ids=39_936, width=33) in ("opt", "ref")
        want = "pallas" if backend == "tpu" else "opt"
        assert pemb.resolve("auto", "take", num_rows=39_936, n_ids=39_936,
                            width=33) == want
        assert pemb.resolve("xla", "take", num_rows=39_936, n_ids=39_936,
                            width=33) == "opt"
        assert pemb.resolve("auto", "take", num_rows=400_000, n_ids=39_936,
                            width=33) == "opt"   # past the VMEM limit
    assert "pallas" not in pemb.MODES
    with pytest.raises(ValueError, match="embedding_kernels"):
        pemb.resolve("pallas", "take", num_rows=8, n_ids=8)
    with pytest.raises(ValueError, match="embedding_kernels"):
        Config(embedding_update="sparse", embedding_kernels="pallas")


def test_pallas_kernels_lower_for_tpu():
    """Every pallas_call in deepfm_tpu/ops cross-lowers for TPU at the
    reference shape (B=1024, F=39, K=32; U = N = 39,936 rows of width 33).
    Needs no chip, and catches the class of kernel the TPU lowering refuses
    before Mosaic ever sees it; that Mosaic then compiles them is what
    chip_smoke.py proves on the device."""
    from jax import export as jax_export

    from deepfm_tpu.ops import pallas_fm

    b, f, k = 1024, 39, 32
    n = b * f

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def lowers(fn, *specs):
        exp = jax_export.export(jax.jit(fn), platforms=("tpu",))(*specs)
        assert "tpu_custom_call" in exp.mlir_module()

    for dt in (jnp.float32, jnp.bfloat16):
        fm_specs = (sds((b, f), dt), sds((b, f), dt), sds((b, f, k), dt))
        lowers(lambda w, v, xv: pallas_fm.fused_fm(w, v, xv, False),
               *fm_specs)
        lowers(jax.grad(lambda w, v, xv: jnp.sum(
            pallas_fm.fused_fm(w, v, xv, False)), argnums=(0, 1, 2)),
            *fm_specs)
    take_specs = (sds((n, 33), jnp.float32), sds((b, f), jnp.int32))
    lowers(lambda r, i: pemb.take_rows_pallas(r, i, False), *take_specs)
    lowers(jax.grad(lambda r, i: jnp.sum(
        pemb.take_rows_pallas(r, i, False))), *take_specs)


# ---------------------------------------------------------------------------
# Seam 2: fused gather forward + segment-sum backward
# ---------------------------------------------------------------------------


@pytest.mark.pallas
@pytest.mark.parametrize("u,n,d,seed", [(6, 24, 4, 0), (17, 40, 8, 1)])
def test_take_kernel_forward_and_vjp_match_oracle(u, n, d, seed):
    rng = np.random.default_rng(seed)
    rows = rng.standard_normal((u, d)).astype(np.float32)
    inv = rng.integers(0, u, (n,)).astype(np.int32)
    g = rng.standard_normal((n, d)).astype(np.float32)

    out, vjp = jax.vjp(
        lambda r: pemb.take_rows_pallas(r, jnp.asarray(inv), interpret=True),
        jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(out), rows[inv])
    (d_rows,) = vjp(jnp.asarray(g))
    oracle = np.zeros_like(rows)
    for p in range(n):  # same accumulation order as the kernel's fori_loop
        oracle[inv[p]] += g[p]
    np.testing.assert_allclose(np.asarray(d_rows), oracle, rtol=1e-6,
                               atol=1e-6)


def test_take_rows_xla_leg_is_jnp_take():
    rows = jnp.asarray(np.random.default_rng(0)
                       .standard_normal((5, 3)).astype(np.float32))
    inv = jnp.asarray(np.array([0, 4, 2, 2], np.int32))
    np.testing.assert_array_equal(
        np.asarray(pemb.take_rows(rows, inv, mode="auto")),
        np.asarray(jnp.take(rows, inv, axis=0)))


# ---------------------------------------------------------------------------
# Seam 3: fused install/evict scatter
# ---------------------------------------------------------------------------


def test_fused_install_matches_numpy_oracle():
    rng = np.random.default_rng(4)
    H, D, n, p = 12, 4, 5, 8
    w = rng.standard_normal((H, D)).astype(np.float32)
    m = rng.standard_normal((H, D)).astype(np.float32)
    v = rng.standard_normal((H, D)).astype(np.float32)
    tau = rng.integers(0, 9, (H,)).astype(np.int32)
    slots = np.full((p,), H, np.int32)           # pow2 pad: OOB dropped
    slots[:n] = rng.choice(H, n, replace=False)
    wv = np.zeros((p, D), np.float32)
    wv[:n] = rng.standard_normal((n, D))
    mv = np.zeros((p, D), np.float32)
    mv[:n] = rng.standard_normal((n, D))
    vv = np.zeros((p, D), np.float32)
    vv[:n] = rng.standard_normal((n, D))
    tv = np.zeros((p,), np.int32)
    tv[:n] = 11
    got = pemb._install_fused_xla(
        jnp.asarray(w), jnp.asarray(m), jnp.asarray(v), jnp.asarray(tau),
        jnp.asarray(slots), jnp.asarray(wv), jnp.asarray(mv),
        jnp.asarray(vv), jnp.asarray(tv))
    ew, em, ev, et = w.copy(), m.copy(), v.copy(), tau.copy()
    ew[slots[:n]] = wv[:n]
    em[slots[:n]] = mv[:n]
    ev[slots[:n]] = vv[:n]
    et[slots[:n]] = tv[:n]
    for a, b in zip(got, (ew, em, ev, et)):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_install_rows_ref_leg_returns_none():
    z = jnp.zeros((4, 2), jnp.float32)
    t = jnp.zeros((4,), jnp.int32)
    s = jnp.zeros((2,), jnp.int32)
    zv = jnp.zeros((2, 2), jnp.float32)
    tv = jnp.zeros((2,), jnp.int32)
    assert pemb.install_rows(z, z, z, t, s, zv, zv, zv, tv,
                             mode="off") is None
    assert pemb.install_rows(z, z, z, t, s, zv, zv, zv, tv,
                             mode="xla") is not None


# ---------------------------------------------------------------------------
# Writeback legs: select-over-ids vs scatter must be element-identical
# ---------------------------------------------------------------------------


def test_select_writeback_matches_scatter_writeback():
    """The counting plan's touched/rank companions enable a select-based
    writeback; it must place exactly the same rows as the ids scatter.
    (The trainer still strips it — the vocab-shaped where perturbs the
    backward's fusion at ~1 ULP — but the leg itself is element-exact.)"""
    rng = np.random.default_rng(6)
    rows_n, d = 20, 3
    ids = jnp.asarray(_ids((6, 3), rows_n, seed=6))
    plan = emb_ops.make_plan_counting(ids, rows_n)
    assert plan.touched is not None and plan.rank is not None
    table = jnp.asarray(rng.standard_normal((rows_n, d)).astype(np.float32))
    new_rows = jnp.asarray(
        rng.standard_normal((int(plan.uids.shape[0]), d)).astype(np.float32))
    got_select = emb_ops.scatter_rows(table, plan, new_rows)
    stripped = plan._replace(touched=None, rank=None)
    got_scatter = emb_ops.scatter_rows(table, stripped, new_rows)
    np.testing.assert_array_equal(np.asarray(got_select),
                                  np.asarray(got_scatter))
    cnt = jnp.asarray(9, jnp.int32)
    tau = jnp.zeros((rows_n,), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(emb_ops.set_rows_scalar(tau, plan, cnt)),
        np.asarray(emb_ops.set_rows_scalar(tau, stripped, cnt)))


# ---------------------------------------------------------------------------
# Trainer kill-switch parity (the --embedding_kernels contract)
# ---------------------------------------------------------------------------


def _pcfg(**kw):
    base = dict(
        feature_size=120, field_size=7, embedding_size=4,
        deep_layers="8,4", dropout="1.0,1.0", batch_size=16,
        compute_dtype="float32", l2_reg=0.0, learning_rate=1e-3,
        log_steps=0, seed=0, scale_lr_by_world=False,
        mesh_data=1, mesh_model=1, steps_per_loop=1,
        embedding_update="sparse")
    base.update(kw)
    return Config(**base)


def _train(kernels, steps=4, l2=0.0, buckets=""):
    cfg = _pcfg(l2_reg=l2, embedding_kernels=kernels,
                embedding_buckets=buckets)
    tr = Trainer(cfg)
    state = tr.init_state()
    step = tr._make_train_step()
    rng = np.random.RandomState(11)
    losses = []
    for _ in range(steps):
        batch = {
            "feat_ids": rng.randint(0, 120, (16, 7)).astype(np.int32),
            "feat_vals": rng.rand(16, 7).astype(np.float32),
            "label": (rng.rand(16, 1) > 0.5).astype(np.float32),
        }
        state, m = step(state, tr.put_batch(batch))
        losses.append(np.asarray(m["loss"]))
    return state, losses


def _leaves(state):
    return ([np.asarray(x) for x in jax.tree.leaves(state.params)]
            + [np.asarray(x) for x in jax.tree.leaves(
                state.opt_state["embed"])])


@pytest.mark.embedding
def test_auto_vs_xla_bitexact():
    sa, la = _train("auto")
    sx, lx = _train("xla")
    for a, b in zip(_leaves(sa), _leaves(sx)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(la, lx):
        np.testing.assert_array_equal(a, b)


@pytest.mark.embedding
def test_hashed_off_vs_auto_bitexact():
    so, _ = _train("off", buckets="48,32")
    sa, _ = _train("auto", buckets="48,32")
    for a, b in zip(_leaves(so), _leaves(sa)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.embedding
def test_fused_vs_seed_trajectory_pinned():
    """Monolithic off-vs-auto: losses bit-equal every step, params within
    the pinned ULP band (the Adam-tail rounding — see module docstring)."""
    so, lo = _train("off", l2=1e-4)
    sa, la = _train("auto", l2=1e-4)
    for a, b in zip(lo, la):
        np.testing.assert_array_equal(a, b)  # losses: bit-equal
    for a, b in zip(_leaves(so), _leaves(sa)):
        if a.dtype == np.int32:  # tau touch stamps: exact
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


@pytest.mark.embedding
def test_fused_grad_bitexact_vs_seed_plan_grad():
    """The fused formulation's per-table gradient (one vocab-space
    scatter-add over all names) must be BIT-identical to the seed plan
    path's segment-sums scattered to vocab space."""
    cfg = _pcfg(embedding_kernels="off")
    tr = Trainer(cfg)
    state = tr.init_state()
    emb = tr.model.emb
    rng = np.random.RandomState(12)
    batch = jax.device_put({
        "feat_ids": rng.randint(0, 120, (16, 7)).astype(np.int32),
        "feat_vals": rng.rand(16, 7).astype(np.float32),
        "label": (rng.rand(16, 1) > 0.5).astype(np.float32),
    })
    rngk = jax.random.fold_in(state.rng, state.step)
    tabs = {n: state.params[n] for n in tr._embed_names}
    rest0 = {k: v for k, v in state.params.items()
             if k not in tr._embed_names}

    @jax.jit
    def seed_grads(state, batch):
        plan = emb.sparse_plan(batch["feat_ids"])
        rows0 = {n: emb.gather_rows(state.params[n], plan)
                 for n in tr._embed_names}

        def loss_fn(rows):
            params = {**rest0, **tabs}
            logits, _ = tr.model.apply(
                params, state.model_state, batch["feat_ids"],
                batch["feat_vals"], train=True, rng=rngk, shard_axis=None,
                data_axis=None, emb_rows=rows, emb_plan=plan)
            return jnp.mean(tr._per_example_loss(
                logits, tr._batch_labels(batch)))

        g_rows = jax.grad(loss_fn)(rows0)
        out = {}
        for n in tr._embed_names:
            e = plan[emb.MONO]
            g = g_rows[n][emb.MONO]
            w = (jnp.arange(e.uids.shape[0]) < e.num_rows)
            w = w.reshape((-1,) + (1,) * (g.ndim - 1))
            out[n] = jnp.zeros_like(
                tabs[n], jnp.float32).at[e.uids].add(jnp.where(w, g, 0))
        return out

    @jax.jit
    def fused_grads(state, batch):
        ids = batch["feat_ids"]
        views0 = {n: jnp.take(tabs[n], ids, axis=0)
                  for n in tr._embed_names}

        def loss_fn(views):
            params = {**rest0, **tabs}
            logits, _ = tr.model.apply(
                params, state.model_state, batch["feat_ids"],
                batch["feat_vals"], train=True, rng=rngk, shard_axis=None,
                data_axis=None,
                emb_rows={n: {emb.MONO: views[n]} for n in tr._embed_names},
                emb_plan=None)
            return jnp.mean(tr._per_example_loss(
                logits, tr._batch_labels(batch)))

        g_views = jax.grad(loss_fn)(views0)
        gext = tr._fused_grad_ext(tabs, ids, g_views)
        out, o = {}, 1
        for n in tr._embed_names:
            d = 1 if tabs[n].ndim == 1 else tabs[n].shape[-1]
            out[n] = gext[:, o:o + d].reshape(tabs[n].shape)
            o += d
        return out

    gs = seed_grads(state, batch)
    gf = fused_grads(state, batch)
    for n in tr._embed_names:
        np.testing.assert_array_equal(np.asarray(gs[n]), np.asarray(gf[n]))


@pytest.mark.embedding
def test_fused_gates_off_for_hashed_and_oversized():
    cfg = _pcfg(embedding_kernels="auto", embedding_buckets="48,32")
    tr = Trainer(cfg)
    assert not tr._use_fused_backward()  # hashed: plan path
    cfg2 = _pcfg(embedding_kernels="off")
    tr2 = Trainer(cfg2)
    assert not tr2._use_fused_backward()  # kill switch
    tr3 = Trainer(cfg2.replace(embedding_kernels="auto"))
    assert tr3._use_fused_backward()
    big = jnp.zeros((pemb.PLAN_COUNT_MAX_ROWS + 64, 2), jnp.float32)
    assert not tr3._fused_tables_ok({"fm_v": big})
    assert tr3._fused_tables_ok(
        {n: tr3.init_state().params[n] for n in tr3._embed_names})
