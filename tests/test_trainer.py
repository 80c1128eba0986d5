"""Trainer tests: single-device end-to-end training, distributed parity on
the 8-device virtual CPU mesh (DP and DP x embedding-row-sharding), eval,
predict. The parity tests are the framework's core correctness claim: the
shard_map step must be numerically equivalent to the single-device step."""

import jax
import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.data import libsvm, pipeline
from deepfm_tpu.models import registered_models
from deepfm_tpu.parallel import mesh as mesh_lib
from deepfm_tpu.train import Trainer, metrics


def _cfg(**kw):
    base = dict(
        feature_size=500, field_size=6, embedding_size=8,
        deep_layers="16,8", dropout="1.0,1.0", batch_size=64,
        compute_dtype="float32", l2_reg=1e-4, learning_rate=0.01,
        shuffle_buffer=500, log_steps=0, seed=11,
        scale_lr_by_world=False, mesh_data=1, mesh_model=1,
    )
    base.update(kw)
    return Config(**base)


@pytest.fixture(scope="module")
def data_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("ctr")
    files = libsvm.generate_synthetic_ctr(
        str(d), num_files=4, examples_per_file=512,
        feature_size=500, field_size=6, seed=2)
    return files


def _pipeline(cfg, files, epochs=1, shuffle=True):
    return pipeline.CtrPipeline(
        files, field_size=cfg.field_size, batch_size=cfg.batch_size,
        num_epochs=epochs, shuffle=shuffle, shuffle_files=shuffle,
        shuffle_buffer=cfg.shuffle_buffer, seed=cfg.seed,
        use_native_decoder=False, prefetch_batches=0,
        num_labels=cfg.num_tasks)


# Registry-driven zoo: every single-task graph plus one multi-task config,
# so new registry entries inherit the distributed/checkpoint tests for free.
_ZOO = registered_models() + ["mmoe"]


def _zoo_cfg(model, **kw):
    if model == "mmoe":
        return _cfg(model="deepfm", tasks="ctr,cvr", multitask="mmoe",
                    mmoe_experts=2, **kw)
    if model == "dlrm_dcnv2":    # its two own flags
        kw = {"numeric_fields": 2, "bottom_layers": "6,8", **kw}
    return _cfg(model=model, **kw)


class TestSingleDevice:
    def test_loss_decreases_and_auc_learns(self, data_files):
        cfg = _cfg()
        tr = Trainer(cfg)
        state = tr.init_state()
        first_losses, last_losses = [], []

        def hook(s, m):
            losses.append(float(m["loss"]))

        losses = []
        state, summary = tr.fit(state, _pipeline(cfg, data_files, epochs=4),
                                hooks=[hook])
        assert summary["steps"] == 4 * (4 * 512 // 64)
        assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.02
        ev = tr.evaluate(state, _pipeline(cfg, data_files, shuffle=False))
        assert ev["auc"] > 0.65, ev

    def test_predict_shapes_and_range(self, data_files):
        cfg = _cfg()
        tr = Trainer(cfg)
        state = tr.init_state()
        probs = list(tr.predict(state, _pipeline(cfg, data_files, shuffle=False)))
        assert all(p.shape == (64,) for p in probs)
        cat = np.concatenate(probs)
        assert (cat >= 0).all() and (cat <= 1).all()

    def test_eval_auc_matches_host_oracle(self, data_files):
        """Device-streamed AUC == exact NumPy AUC on the same predictions."""
        cfg = _cfg(auc_num_thresholds=400)
        tr = Trainer(cfg)
        state = tr.init_state()
        state, _ = tr.fit(state, _pipeline(cfg, data_files))
        ev = tr.evaluate(state, _pipeline(cfg, data_files, shuffle=False))
        probs = np.concatenate(
            list(tr.predict(state, _pipeline(cfg, data_files, shuffle=False))))
        labels = np.concatenate(
            [b["label"][:, 0] for b in _pipeline(cfg, data_files, shuffle=False)])
        exact = metrics.auc_numpy_reference(probs, labels)
        assert abs(ev["auc"] - exact) < 0.01, (ev["auc"], exact)


class TestDistributedParity:
    """Same data, same seed: mesh runs must match the single-device run."""

    def _run(self, cfg, files, steps=12):
        tr = Trainer(cfg)
        state = tr.init_state()
        state, _ = tr.fit(state, _pipeline(cfg, files, shuffle=False),
                          max_steps=steps)
        ev = tr.evaluate(state, _pipeline(cfg, files, shuffle=False))
        return tr, state, ev

    @pytest.mark.mesh_bitexact
    def test_dp8_matches_single(self, data_files):
        _, s1, ev1 = self._run(_cfg(), data_files)
        _, s8, ev8 = self._run(_cfg(mesh_data=8), data_files)
        np.testing.assert_allclose(
            np.asarray(s1.params["fm_b"]), np.asarray(s8.params["fm_b"]),
            rtol=5e-3, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(s1.params["fm_v"]), np.asarray(s8.params["fm_v"]),
            rtol=1e-3, atol=1e-5)
        assert abs(ev1["auc"] - ev8["auc"]) < 5e-3
        assert abs(ev1["loss"] - ev8["loss"]) < 1e-4

    @pytest.mark.mesh_bitexact
    def test_dp4_x_rowshard2_matches_single(self, data_files):
        _, s1, ev1 = self._run(_cfg(), data_files)
        cfg = _cfg(mesh_data=4, mesh_model=2, feature_size=500)
        tr, s, ev = self._run(cfg, data_files)
        # padded vocab (mesh-independent multiple): compare real rows only
        fm_v = np.asarray(s.params["fm_v"])[:500]
        np.testing.assert_allclose(
            np.asarray(s1.params["fm_v"])[:500], fm_v, rtol=1e-3, atol=1e-5)
        assert abs(ev1["auc"] - ev["auc"]) < 5e-3
        # padding rows stay exactly zero
        pad = np.asarray(s.params["fm_v"])[500:]
        assert pad.shape[0] == tr.model.padded_vocab - 500
        assert (pad == 0).all()

    @pytest.mark.mesh_bitexact
    def test_rowshard_only_mesh(self, data_files):
        """model-axis-only mesh (1x8): pure embedding sharding."""
        cfg = _cfg(mesh_data=1, mesh_model=8)
        _, s1, ev1 = self._run(_cfg(), data_files, steps=6)
        _, s8, ev8 = self._run(cfg, data_files, steps=6)
        np.testing.assert_allclose(
            np.asarray(s1.params["fm_w"])[:500],
            np.asarray(s8.params["fm_w"])[:500], rtol=1e-3, atol=1e-5)
        assert abs(ev1["loss"] - ev8["loss"]) < 1e-4

    def test_embedding_actually_sharded(self, data_files):
        cfg = _cfg(mesh_data=4, mesh_model=2)
        tr = Trainer(cfg)
        state = tr.init_state()
        shardings = state.params["fm_v"].sharding
        assert shardings.spec[0] == "model"
        # 2-way row shard: each device holds half the (padded) rows
        shard_shapes = {tuple(s.data.shape) for s in state.params["fm_v"].addressable_shards}
        assert shard_shapes == {(tr.model.padded_vocab // 2, 8)}

    @pytest.mark.mesh_bitexact
    def test_allgather_lookup_matches_masked_psum(self, data_files):
        """Both sharded-lookup strategies train to the same weights (the
        collective pattern is an implementation detail of the same gather);
        see TUNING.md for when each wins."""
        _, s_psum, ev_psum = self._run(
            _cfg(mesh_data=4, mesh_model=2), data_files, steps=6)
        _, s_ag, ev_ag = self._run(
            _cfg(mesh_data=4, mesh_model=2,
                 embedding_lookup="allgather_table"), data_files, steps=6)
        np.testing.assert_allclose(
            np.asarray(s_psum.params["fm_v"]), np.asarray(s_ag.params["fm_v"]),
            rtol=1e-4, atol=1e-6)
        assert abs(ev_psum["loss"] - ev_ag["loss"]) < 1e-5

    @pytest.mark.mesh_bitexact
    def test_bn_cross_replica_parity(self, data_files):
        cfg1 = _cfg(batch_norm=True)
        cfg8 = _cfg(batch_norm=True, mesh_data=8)
        _, s1, ev1 = self._run(cfg1, data_files, steps=8)
        _, s8, ev8 = self._run(cfg8, data_files, steps=8)
        np.testing.assert_allclose(
            np.asarray(s1.model_state["bn"][0]["mean"]),
            np.asarray(s8.model_state["bn"][0]["mean"]), rtol=1e-3, atol=1e-5)
        assert abs(ev1["loss"] - ev8["loss"]) < 1e-3

    @pytest.mark.parametrize("model", _ZOO)
    def test_model_zoo_distributed(self, data_files, model):
        cfg = _zoo_cfg(model, mesh_data=4, mesh_model=2)
        tr, state, ev = self._run(cfg, data_files, steps=8)
        assert np.isfinite(ev["loss"])
        assert 0.0 <= ev["auc"] <= 1.0

    @pytest.mark.parametrize("model", _ZOO)
    def test_zoo_checkpoint_roundtrip(self, data_files, tmp_path, model):
        """Save/restore must reproduce eval exactly for every zoo entry."""
        from deepfm_tpu.utils import checkpoint as ckpt_lib
        cfg = _zoo_cfg(model)
        tr = Trainer(cfg)
        state, _ = tr.fit(tr.init_state(), _pipeline(cfg, data_files),
                          max_steps=4)
        ev = tr.evaluate(state, _pipeline(cfg, data_files, shuffle=False))
        d = str(tmp_path / "zoo")
        with ckpt_lib.CheckpointManager(d) as mgr:
            mgr.save(4, state)
        tr2 = Trainer(cfg)
        with ckpt_lib.CheckpointManager(d) as mgr:
            restored = mgr.restore(tr2.init_state())
        ev2 = tr2.evaluate(restored, _pipeline(cfg, data_files,
                                               shuffle=False))
        assert ev2["auc"] == pytest.approx(ev["auc"], abs=1e-6)
        assert ev2["loss"] == pytest.approx(ev["loss"], abs=1e-6)

    @pytest.mark.mesh_bitexact
    def test_checkpoint_portable_across_meshes(self, data_files, tmp_path):
        """A checkpoint trained row-sharded restores on a DIFFERENT mesh
        (resize after preemption, single-chip eval of a pod-trained model).
        Works because vocab padding is a mesh-independent multiple — with
        per-mesh padding the table shapes would differ and restore fails."""
        from deepfm_tpu.utils import checkpoint as ckpt_lib
        cfg42 = _cfg(mesh_data=4, mesh_model=2, feature_size=501)
        tr42 = Trainer(cfg42)
        state42, _ = tr42.fit(tr42.init_state(),
                              _pipeline(cfg42, data_files), max_steps=4)
        d = str(tmp_path / "x")
        with ckpt_lib.CheckpointManager(d) as mgr:
            mgr.save(4, state42)
        ev42 = tr42.evaluate(state42, _pipeline(cfg42, data_files,
                                                shuffle=False))

        for mesh_kw in (dict(mesh_data=8, mesh_model=1),
                        dict(mesh_data=2, mesh_model=4)):
            cfg2 = _cfg(feature_size=501, **mesh_kw)
            tr2 = Trainer(cfg2)
            with ckpt_lib.CheckpointManager(d) as mgr:
                restored = mgr.restore(tr2.init_state())
            ev2 = tr2.evaluate(restored, _pipeline(cfg2, data_files,
                                                   shuffle=False))
            assert ev2["auc"] == pytest.approx(ev42["auc"], abs=1e-5), mesh_kw
            assert ev2["loss"] == pytest.approx(ev42["loss"], abs=1e-5), mesh_kw

    @pytest.mark.mesh_bitexact
    @pytest.mark.parametrize("opt", ["Adagrad", "Momentum", "ftrl"])
    def test_optimizer_zoo_distributed_parity(self, data_files, opt):
        _, s1, ev1 = self._run(_cfg(optimizer=opt), data_files, steps=6)
        _, s8, ev8 = self._run(_cfg(optimizer=opt, mesh_data=4, mesh_model=2),
                               data_files, steps=6)
        np.testing.assert_allclose(
            np.asarray(s1.params["fm_v"])[:500],
            np.asarray(s8.params["fm_v"])[:500], rtol=2e-3, atol=1e-5)
        assert abs(ev1["loss"] - ev8["loss"]) < 1e-3


class TestStepsPerLoop:
    """steps_per_loop (lax.scan multi-step dispatch) must be numerically
    identical to sequential single-step training — same rng folding, same
    update order — on one device and on the mesh."""

    def _run_k(self, k, files, mesh=False, n_batches=11):
        cfg = _cfg(steps_per_loop=k, transfer_ahead=2,
                   **({"mesh_data": 4, "mesh_model": 2} if mesh else {}))
        tr = Trainer(cfg)
        state = tr.init_state()
        state, summary = tr.fit(
            state, _pipeline(cfg, files, shuffle=False), max_steps=n_batches)
        return state, summary

    @pytest.mark.parametrize(
        "mesh", [False, pytest.param(True, marks=pytest.mark.mesh_bitexact)])
    def test_k4_matches_k1(self, data_files, mesh):
        # 11 batches: 2 full scan groups of 4 + 3 tail single steps.
        s1, sum1 = self._run_k(1, data_files, mesh)
        s4, sum4 = self._run_k(4, data_files, mesh)
        assert sum1["steps"] == sum4["steps"] == 11
        assert int(s1.step) == int(s4.step) == 11
        paths1 = jax.tree_util.tree_leaves_with_path(s1.params)
        leaves4 = jax.tree.leaves(s4.params)
        for (path, a), b in zip(paths1, leaves4):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"param {path} diverges between k=1 and k=4")
        np.testing.assert_array_equal(
            np.asarray(s1.rng), np.asarray(s4.rng))

    def test_dropout_rng_advances_per_scanned_step(self, data_files):
        # With real dropout, scanned steps must use distinct fold_in keys:
        # k=2 must still match sequential exactly.
        cfg1 = _cfg(dropout="0.5,0.5", steps_per_loop=1)
        cfg2 = _cfg(dropout="0.5,0.5", steps_per_loop=2)
        tr1, tr2 = Trainer(cfg1), Trainer(cfg2)
        st1, st2 = tr1.init_state(), tr2.init_state()
        st1, _ = tr1.fit(st1, _pipeline(cfg1, data_files, shuffle=False),
                         max_steps=4)
        st2, _ = tr2.fit(st2, _pipeline(cfg2, data_files, shuffle=False),
                         max_steps=4)
        for a, b in zip(jax.tree.leaves(st1.params),
                        jax.tree.leaves(st2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestScannedEvalPredict:
    """The K-batch scanned eval/predict dispatch (eval_multi_step /
    predict_multi_step) must be bit-identical to per-batch dispatch — the
    scan merges accumulators / emits outputs in batch order, so only the
    dispatch count may differ (VERDICT r3 #2)."""

    def _trained(self, files, k, mesh):
        cfg = _cfg(steps_per_loop=k,
                   **({"mesh_data": 4, "mesh_model": 2} if mesh else {}))
        tr = Trainer(cfg)
        state = tr.init_state()
        state, _ = tr.fit(state, _pipeline(cfg, files, shuffle=False),
                          max_steps=4)
        return cfg, tr, state

    @pytest.mark.parametrize(
        "mesh", [False, pytest.param(True, marks=pytest.mark.mesh_bitexact)])
    def test_eval_k4_matches_k1(self, data_files, mesh):
        # 11 batches per variant: 2 full scan groups of 4 + 3 tail singles
        # on the k=4 side (plus a ragged final pipeline batch exercising the
        # zero-weight padding inside the scanned group).
        _, tr1, st1 = self._trained(data_files, 1, mesh)
        ev1 = tr1.evaluate(st1, _pipeline(_cfg(), data_files, shuffle=False))
        cfg4, tr4, st4 = self._trained(data_files, 4, mesh)
        ev4 = tr4.evaluate(st4, _pipeline(cfg4, data_files, shuffle=False))
        assert ev1["batches"] == ev4["batches"]
        assert ev1["auc"] == ev4["auc"]          # bit-identical, not approx
        assert ev1["loss"] == ev4["loss"]

    @pytest.mark.parametrize(
        "mesh", [False, pytest.param(True, marks=pytest.mark.mesh_bitexact)])
    def test_predict_k4_matches_k1(self, data_files, mesh):
        from deepfm_tpu.train.loop import pad_batch
        _, tr1, st1 = self._trained(data_files, 1, mesh)
        cfg4, tr4, st4 = self._trained(data_files, 4, mesh)

        def padded(cfg):
            for b in _pipeline(cfg, data_files, shuffle=False):
                n = b["label"].shape[0]
                yield pad_batch(b, cfg.batch_size) if n < cfg.batch_size else b

        p1 = np.concatenate(list(tr1.predict(st1, padded(_cfg()))))
        p4 = np.concatenate(list(tr4.predict(st4, padded(cfg4))))
        assert p1.shape == p4.shape
        np.testing.assert_array_equal(p1, p4)


class TestStageMultiprocessProtocol:
    """Unit pin for the lockstep min-truncate protocol in
    Trainer._stage_multiprocess (the 2-OS-process tests exercise it for
    real; this pins the round arithmetic — dispatch exactly min(counts)
    per round, stop at the first short round, drop local leftovers —
    against a simulated slower sibling rank, without process spawns)."""

    def _batches(self, n, bs=64, fields=6):
        rng = np.random.default_rng(0)
        return [{
            "feat_ids": rng.integers(0, 500, (bs, fields)).astype(np.int32),
            "feat_vals": rng.normal(size=(bs, fields)).astype(np.float32),
            "label": (rng.random((bs, 1)) < 0.3).astype(np.float32),
        } for _ in range(n)]

    def _run(self, monkeypatch, local_batches, other_counts, k):
        from jax.experimental import multihost_utils

        tr = Trainer(_cfg(steps_per_loop=k))
        other = iter(other_counts)

        def fake_allgather(x):
            mine = int(np.asarray(x).reshape(-1)[0])
            return np.asarray([[mine], [next(other)]])

        monkeypatch.setattr(multihost_utils, "process_allgather",
                            fake_allgather)
        return list(tr._stage_multiprocess(iter(local_batches), k, depth=1))

    def test_truncates_to_global_min_and_stops(self, monkeypatch):
        # This rank pulls rounds of [2, 2, 1]; the sibling reports [2, 2, 0]:
        # two full scanned rounds run, the third dispatches min(1,0)=0 and
        # terminates — the leftover local batch is dropped (cross-rank
        # drop_remainder), never half-dispatched.
        out = self._run(monkeypatch, self._batches(5), [2, 2, 0], k=2)
        assert [steps for _, steps, _ in out] == [2, 2]
        assert sum(n for _, _, n in out) == 4 * 64

    def test_short_final_round_dispatches_singles(self, monkeypatch):
        # Both ranks agree the final round is short (min=1 < k): the agreed
        # prefix re-dispatches as single steps, not a scanned group.
        out = self._run(monkeypatch, self._batches(3), [2, 1], k=2)
        assert [steps for _, steps, _ in out] == [2, 1]

    def test_exhausted_rank_stops_everyone(self, monkeypatch):
        # This rank still has data but the sibling is empty on round 1.
        out = self._run(monkeypatch, self._batches(4), [0], k=2)
        assert out == []
