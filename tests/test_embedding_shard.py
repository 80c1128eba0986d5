"""Row-sharded embedding tests (--embedding_shard rows).

Contracts pinned here:

- ``build_exchange``/``exchange_rows`` move exactly the plan's touched
  rows between owner shards: the reassembled [U, ...] block is
  BIT-identical to gathering from the full table, for any shard count
  that divides the rows (NumPy oracle + shard_map runs).
- ``owner_scatter_add`` partitions the full-table scatter: concatenating
  every shard's owner-local grad equals the unsharded table-space
  scatter, bit for bit.
- ``--embedding_shard rows`` on ONE device routes to the unchanged
  single-device sparse program — trajectories are bit-identical to
  ``off`` (the tentpole's safety pin).
- Mesh trajectories (1x2, 4x2, hashed) track the single-device sparse
  run within the established mesh tolerance band (``shard``-marked:
  gated on the mesh_bitexact probe like every mesh-vs-single parity
  claim in this suite).
- Checkpoints are mesh-portable: a 2-shard run's params AND lazy-Adam
  moments (m/v/tau) restore bit-exactly unsharded and onto a different
  shard count (vocab padding is a mesh-independent multiple).
- ``grad_payload_bytes`` reports sharded leaves once per owner under
  rows — unit-tested against the analytic value.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepfm_tpu.config import Config
from deepfm_tpu.ops import embedding as emb_ops
from deepfm_tpu.ops import pallas_embedding as pemb
from deepfm_tpu.parallel import mesh as mesh_lib
from deepfm_tpu.train import Trainer
from deepfm_tpu.utils import checkpoint as ckpt_lib


def _cfg(**kw):
    base = dict(
        feature_size=500, field_size=6, embedding_size=8,
        deep_layers="16,8", dropout="1.0,1.0", batch_size=64,
        compute_dtype="float32", l2_reg=1e-4, learning_rate=0.01,
        shuffle_buffer=500, log_steps=0, seed=11,
        scale_lr_by_world=False, mesh_data=1, mesh_model=1,
        embedding_update="sparse", embedding_shard="rows",
    )
    base.update(kw)
    return Config(**base)


def _batches(n, bs, fields=6, seed=3, feature_size=500):
    rng = np.random.RandomState(seed)
    return [{
        "feat_ids": rng.randint(
            0, feature_size, (bs, fields)).astype(np.int32),
        "feat_vals": rng.rand(bs, fields).astype(np.float32),
        "label": (rng.rand(bs, 1) < 0.3).astype(np.float32),
    } for _ in range(n)]


def _fit(cfg, n_steps=8):
    tr = Trainer(cfg)
    state = tr.init_state()
    state, out = tr.fit(state, iter(_batches(n_steps, cfg.batch_size)))
    return tr, state, out


def _embed_leaves(state):
    """(params, m, v, tau) arrays for fm_v's first physical table."""
    tabs = state.params["fm_v"]
    oe = state.opt_state["embed"]["fm_v"]
    key = "table" if not isinstance(tabs, dict) else "t0"
    tab = tabs if not isinstance(tabs, dict) else tabs["t0"]
    return (np.asarray(tab), np.asarray(oe[key].m),
            np.asarray(oe[key].v), np.asarray(oe[key].tau))


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------


class TestConfigValidation:
    def test_rows_requires_sparse(self):
        with pytest.raises(ValueError, match="sparse row plane"):
            _cfg(embedding_update="dense")

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="embedding_shard"):
            _cfg(embedding_shard="cols")

    def test_rows_excludes_tiering(self):
        with pytest.raises(ValueError, match="TUNING"):
            _cfg(embedding_tiering="hot_cold", embedding_hot_rows=64)

    def test_rows_excludes_accum(self):
        with pytest.raises(ValueError, match="single-device"):
            _cfg(grad_accum_steps=2, steps_per_loop=4)

    def test_sparse_mesh_needs_rows(self):
        with pytest.raises(ValueError, match="embedding_shard rows"):
            _cfg(embedding_shard="off", mesh_model=2)

    def test_rows_excludes_history_transitively(self):
        # rows requires sparse; history requires dense -> no rows+history.
        with pytest.raises(ValueError, match="embedding_update=dense"):
            _cfg(model="din", history_max_len=4)

    def test_buckets_must_divide(self):
        with pytest.raises(ValueError, match="divisible"):
            _cfg(mesh_model=2, embedding_buckets="255,128")
        _cfg(mesh_model=2, embedding_buckets="256,128")  # ok


# ---------------------------------------------------------------------------
# Exchange machinery vs NumPy oracle (forward-only collectives)
# ---------------------------------------------------------------------------


def _mesh(d):
    return Mesh(np.asarray(jax.devices()[:d]), ("model",))


def _plan_from_ids(ids, rows):
    return emb_ops.make_plan(jnp.asarray(ids, jnp.int32), rows)


class TestExchangeOracle:
    @pytest.mark.parametrize("d", [2, 4])
    def test_build_exchange_matches_oracle(self, d):
        rows = 64
        rng = np.random.default_rng(5)
        ids = rng.integers(0, rows, size=(24,))
        plan = _plan_from_ids(ids, rows)

        def f():
            ex = emb_ops.build_exchange(plan, d, "model")
            return ex.reqs, ex.flat_idx

        reqs, flat_idx = jax.jit(shard_map(
            f, mesh=_mesh(d), in_specs=(),
            out_specs=(P("model"), P("model"))))()
        reqs = np.asarray(reqs).reshape(d, d, -1)
        flat_idx = np.asarray(flat_idx).reshape(d, -1)
        for r in range(d):
            want_reqs, want_flat = pemb.reference_exchange_numpy(
                np.asarray(plan.uids), rows, d, r)
            np.testing.assert_array_equal(reqs[r], want_reqs)
            np.testing.assert_array_equal(flat_idx[r], want_flat)

    @pytest.mark.parametrize("d", [2, 4, 8])
    @pytest.mark.parametrize("trailing", [(), (5,)])
    def test_exchange_rows_bit_equals_full_gather(self, d, trailing):
        rows = 64
        rng = np.random.default_rng(7)
        table = rng.normal(size=(rows, *trailing)).astype(np.float32)
        ids = rng.integers(0, rows, size=(30,))
        plan = _plan_from_ids(ids, rows)
        want = np.asarray(emb_ops.gather_rows(jnp.asarray(table), plan))

        def f(local):
            ex = emb_ops.build_exchange(plan, d, "model")
            return emb_ops.exchange_rows(local, ex, "model")

        got = jax.jit(shard_map(
            f, mesh=_mesh(d),
            in_specs=(P("model", *([None] * len(trailing))),),
            out_specs=P()))(jnp.asarray(table))
        np.testing.assert_array_equal(np.asarray(got), want)

    @pytest.mark.parametrize("d", [2, 4])
    def test_owner_scatter_add_partitions_full_scatter(self, d):
        rows, k = 64, 3
        rng = np.random.default_rng(9)
        ids = rng.integers(0, rows, size=(20,))
        plan = _plan_from_ids(ids, rows)
        g_rows = rng.normal(size=(plan.uids.shape[0], k)).astype(np.float32)
        # unsharded oracle: plain table-space scatter of the valid uids
        full = np.zeros((rows, k), np.float32)
        uids = np.asarray(plan.uids)
        for j, uid in enumerate(uids):
            if uid < rows:
                full[uid] += g_rows[j]
        full_touched = np.zeros((rows,), bool)
        full_touched[uids[uids < rows]] = True

        def f():
            return emb_ops.owner_scatter_add(
                jnp.asarray(g_rows), plan, d, "model")

        grad, touched = jax.jit(shard_map(
            f, mesh=_mesh(d), in_specs=(),
            out_specs=(P("model"), P("model"))))()
        np.testing.assert_array_equal(np.asarray(grad), full)
        np.testing.assert_array_equal(np.asarray(touched), full_touched)

    def test_owner_scatter_add_unsharded_degenerates(self):
        rows = 32
        ids = np.array([3, 3, 7, 31])
        plan = _plan_from_ids(ids, rows)
        g = np.ones((plan.uids.shape[0], 2), np.float32)
        grad, touched = jax.jit(
            lambda: emb_ops.owner_scatter_add(jnp.asarray(g), plan, 1, None))()
        assert np.asarray(grad).shape == (rows, 2)
        assert set(np.flatnonzero(np.asarray(touched))) == {3, 7, 31}

    def test_build_exchange_rejects_indivisible(self):
        plan = _plan_from_ids(np.array([1, 2]), 65)
        with pytest.raises(ValueError, match="divisible"):
            emb_ops.build_exchange(plan, 2, "model")

    def test_payload_bytes_analytic(self):
        assert emb_ops.exchange_payload_bytes(100, 8, 1) == 0
        # D=4, U=100 -> C=25, block=100: ids 400 B + 2 * 100*8 rows * 4 B
        assert emb_ops.exchange_payload_bytes(100, 8, 4) == (
            100 * 4 + 2 * 100 * 8 * 4)


# ---------------------------------------------------------------------------
# Trainer: 1-device bit identity + sharded runs
# ---------------------------------------------------------------------------


@pytest.mark.embedding
class TestOneDeviceBitIdentity:
    def test_rows_equals_off_bitwise(self):
        _, s_off, _ = _fit(_cfg(embedding_shard="off"))
        _, s_rows, _ = _fit(_cfg())
        for la, lb in zip(jax.tree.leaves(s_off.params),
                          jax.tree.leaves(s_rows.params)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        for la, lb in zip(jax.tree.leaves(s_off.opt_state),
                          jax.tree.leaves(s_rows.opt_state)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


class TestShardedRun:
    """Structure + liveness of the sharded program (no cross-program
    numerics — those are the shard-marked parity tests below)."""

    def test_tables_and_moments_sharded(self):
        tr, state, out = _fit(_cfg(mesh_data=1, mesh_model=2))
        assert np.isfinite(out["loss"])
        assert state.params["fm_v"].sharding.spec[0] == "model"
        half = tr.model.padded_vocab // 2
        shapes = {tuple(s.data.shape)
                  for s in state.params["fm_v"].addressable_shards}
        assert shapes == {(half, 8)}
        oe = state.opt_state["embed"]["fm_v"]["table"]
        assert oe.m.sharding.spec[0] == "model"
        assert oe.tau.sharding.spec[0] == "model"
        assert {s.data.shape[0] for s in oe.tau.addressable_shards} == {half}

    def test_dp_mp_run_and_payload(self):
        tr, state, out = _fit(_cfg(mesh_data=2, mesh_model=2))
        assert np.isfinite(out["loss"])
        # padding rows never receive gradient
        pad = np.asarray(state.params["fm_v"])[500:]
        assert (pad == 0).all()
        assert tr._grad_payload_bytes() > 0

    def test_eval_predict_on_sharded_state(self):
        cfg = _cfg(mesh_data=1, mesh_model=2)
        tr = Trainer(cfg)
        state = tr.init_state()
        state, _ = tr.fit(state, iter(_batches(4, cfg.batch_size)))
        ev = tr.evaluate(state, iter(_batches(2, cfg.batch_size)))
        assert np.isfinite(ev["loss"]) and 0.0 <= ev["auc"] <= 1.0
        probs = np.concatenate(list(
            tr.predict(state, iter(_batches(2, cfg.batch_size)))), axis=0)
        assert probs.shape[0] == 2 * cfg.batch_size
        assert np.isfinite(probs).all()

    def test_hashed_sharded_run(self):
        cfg = _cfg(mesh_data=1, mesh_model=2, embedding_buckets="256,128")
        tr, state, out = _fit(cfg)
        assert np.isfinite(out["loss"])
        assert state.params["fm_v"]["t0"].sharding.spec[0] == "model"
        ev = tr.evaluate(state, iter(_batches(2, cfg.batch_size)))
        assert np.isfinite(ev["loss"])


# ---------------------------------------------------------------------------
# Mesh-vs-single trajectory parity (gated like every such claim)
# ---------------------------------------------------------------------------


@pytest.mark.shard
class TestShardedParity:
    def _single(self):
        return _fit(_cfg())

    def test_mp2_matches_single(self):
        _, s1, _ = self._single()
        _, s2, _ = _fit(_cfg(mesh_data=1, mesh_model=2))
        np.testing.assert_allclose(
            np.asarray(s1.params["fm_v"])[:500],
            np.asarray(s2.params["fm_v"])[:500], rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(s1.params["fm_w"])[:500],
            np.asarray(s2.params["fm_w"])[:500], rtol=1e-3, atol=1e-5)

    def test_dp4_mp2_matches_single(self):
        """Element for element to ``rtol`` 1e-3, ``atol`` 1e-5, but for the
        few elements where Adam's division amplifies a rounding. Four data
        replicas add a row's cotangents in another order than one device: a
        gap of one ULP (4.7e-9 at row 10, step 2). At a later touch of such
        a row the first moment nearly cancels (an update of 1.6e-4 or 1.9e-4
        where a step is lr = 1e-2: row 128, step 2) and ``m / sqrt(v)``
        turns that ULP into a fraction of a step, which stays. Read here
        (XLA:CPU, 8 steps): 3 of 4,000 elements, rows 10, 128 and 458, all
        in the last column, 2.9e-5, 5.1e-5 and 1.4e-5 apart: under 1% of one
        step each. So: nearly every element to the tight tolerance, and none
        further off than 1% of a step (``tests/test_trainer.py``'s
        ``test_dp8_matches_single`` holds ``fm_b`` to 2e-4 for the same
        cause; ``conftest.py``'s note on the ``mesh_bitexact`` probe)."""
        _, s1, ev1 = self._single()
        cfg = _cfg(mesh_data=4, mesh_model=2)
        _, s8, ev8 = _fit(cfg)
        one = np.asarray(s1.params["fm_v"])[:500]
        mesh = np.asarray(s8.params["fm_v"])[:500]
        gap = np.abs(one - mesh)
        off = gap > 1e-5 + 1e-3 * np.abs(mesh)
        assert off.mean() < 0.005, np.argwhere(off)
        assert gap.max() < 0.01 * cfg.learning_rate, gap.max()
        assert abs(ev1["loss"] - ev8["loss"]) < 1e-3

    def test_hashed_mp2_matches_single(self):
        cfg1 = _cfg(embedding_buckets="256,128")
        cfg2 = _cfg(mesh_data=1, mesh_model=2, embedding_buckets="256,128")
        _, s1, _ = _fit(cfg1)
        _, s2, _ = _fit(cfg2)
        for key in ("t0", "t1"):
            np.testing.assert_allclose(
                np.asarray(s1.params["fm_v"][key]),
                np.asarray(s2.params["fm_v"][key]), rtol=1e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# Checkpoint resharding
# ---------------------------------------------------------------------------


class TestCheckpointReshard:
    def _trained_2shard(self, tmp_path):
        cfg = _cfg(mesh_data=1, mesh_model=2)
        tr = Trainer(cfg)
        state = tr.init_state()
        state, _ = tr.fit(state, iter(_batches(4, cfg.batch_size)))
        d = str(tmp_path / "ck")
        with ckpt_lib.CheckpointManager(d) as mgr:
            mgr.save(4, state)
        return d, state

    @pytest.mark.parametrize("mesh_kw", [
        dict(embedding_shard="off", mesh_data=1, mesh_model=1),
        dict(mesh_data=1, mesh_model=4),
        dict(mesh_data=4, mesh_model=2),
    ])
    def test_restore_bit_exact_across_shardings(self, tmp_path, mesh_kw):
        d, state = self._trained_2shard(tmp_path)
        tr2 = Trainer(_cfg(**mesh_kw))
        with ckpt_lib.CheckpointManager(d) as mgr:
            restored = mgr.restore(tr2.init_state())
        t_a, m_a, v_a, tau_a = _embed_leaves(state)
        t_b, m_b, v_b, tau_b = _embed_leaves(restored)
        np.testing.assert_array_equal(t_a, t_b)
        np.testing.assert_array_equal(m_a, m_b)
        np.testing.assert_array_equal(v_a, v_b)
        np.testing.assert_array_equal(tau_a, tau_b)
        # and the restored state trains on the new mesh
        restored, out = tr2.fit(
            restored, iter(_batches(2, 64)), max_steps=2)
        assert np.isfinite(out["loss"])


# ---------------------------------------------------------------------------
# grad_payload_bytes accounting
# ---------------------------------------------------------------------------


class TestGradPayloadBytes:
    def _params(self):
        return {
            "fm_w": jnp.zeros((128,), jnp.float32),
            "fm_v": jnp.zeros((128, 8), jnp.float32),
            "mlp": jnp.zeros((16, 4), jnp.float32),
        }

    def test_rows_counts_each_row_once(self):
        p = self._params()
        # rows, 2 shards: embedding leaves /2, + one int32 touched-union
        # mask [rows_local] counted against the first embedding name.
        got = mesh_lib.grad_payload_bytes(
            p, ("fm_w", "fm_v"), 2, embedding_shard="rows")
        want = (128 * 4) // 2 + (128 * 8 * 4) // 2 + (128 // 2) * 4 \
            + 16 * 4 * 4
        assert got == want

    def test_rows_single_shard_is_full_table(self):
        p = self._params()
        got = mesh_lib.grad_payload_bytes(
            p, ("fm_w", "fm_v"), 1, embedding_shard="rows")
        want = 128 * 4 + 128 * 8 * 4 + 128 * 4 + 16 * 4 * 4
        assert got == want

    def test_dense_unchanged(self):
        p = self._params()
        assert mesh_lib.grad_payload_bytes(p, ("fm_w", "fm_v"), 2) == (
            (128 * 4) // 2 + (128 * 8 * 4) // 2 + 16 * 4 * 4)
        assert mesh_lib.grad_payload_bytes(p, ("fm_w", "fm_v"), 1) == (
            128 * 4 + 128 * 8 * 4 + 16 * 4 * 4)

    def test_trainer_uses_sharded_accounting(self):
        tr_rows = Trainer(_cfg(mesh_data=2, mesh_model=2))
        tr_dense = Trainer(_cfg(embedding_update="dense",
                                embedding_shard="off",
                                mesh_data=2, mesh_model=2))
        # same mesh, same tables: the rows plane adds the touched mask on
        # top of the identical /model_size embedding payload.
        assert tr_rows._grad_payload_bytes() > 0
        assert tr_dense._grad_payload_bytes() > 0
