"""Multi-task graph subsystem tests.

Covers the PR-9 acceptance criteria end to end:

* DeepFM / Wide&Deep / DCN-v2 are the graph classes under their public
  names, and a 5-step training trajectory repeats bit for bit at one seed;
* an MMoE CTR+CVR run trains end-to-end, publishes a servable and serves
  named per-task probabilities through ServingEngine;
* the two-label input contract (codec byte-identity, native/Python decode
  parity, pipeline label2 column);
* tiering-aware checkpointing restores bit-exact across tiered/untiered
  and differently-sized-hot-cache configs (both directions);
* every registered model (and every --multitask mode) survives a 2-step
  CPU smoke.
"""

import json
import os

import jax
import numpy as np
import pytest

import deepfm_tpu.models as models_pkg
from deepfm_tpu.config import Config
from deepfm_tpu.data import example_codec, libsvm, pipeline, tfrecord
from deepfm_tpu.models import graph, registered_models
from deepfm_tpu.native import loader
from deepfm_tpu.serve import ServingEngine
from deepfm_tpu.train import Trainer, tasks
from deepfm_tpu.utils import checkpoint as ckpt_lib
from deepfm_tpu.utils import export as export_lib

V, F, B = 200, 5, 32


def _cfg(**kw):
    base = dict(
        feature_size=V, field_size=F, embedding_size=8,
        deep_layers="16,8", dropout="1.0,1.0", batch_size=B,
        compute_dtype="float32", l2_reg=1e-4, learning_rate=0.01,
        log_steps=0, seed=11, scale_lr_by_world=False,
        mesh_data=1, mesh_model=1,
    )
    base.update(kw)
    if base.get("model") == "dlrm_dcnv2":    # its two own flags
        base = {"numeric_fields": 2, "bottom_layers": "6,8", **base}
    return Config(**base)


def _batches(nb, seed=3, two_label=False, v=V, b=B):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(nb):
        label = rng.integers(0, 2, size=(b, 1)).astype(np.float32)
        batch = dict(
            feat_ids=rng.integers(0, v, size=(b, F)).astype(np.int32),
            feat_vals=rng.normal(size=(b, F)).astype(np.float32),
            label=label)
        if two_label:
            # click-gated conversions, like the synthetic generator
            batch["label2"] = (label *
                               rng.integers(0, 2, size=(b, 1))).astype(
                                   np.float32)
        out.append(batch)
    return out


_GRAPH = {"deepfm": graph.GraphDeepFM,
          "widedeep": graph.GraphWideDeep,
          "dcnv2": graph.GraphDCNv2}
_PUBLIC = {"deepfm": "DeepFM", "widedeep": "WideDeep", "dcnv2": "DCNv2"}


class TestGraphPublicNames:
    """``DeepFM`` / ``WideDeep`` / ``DCNv2`` are the graph classes under
    their public names: one class per model, registered under its own
    ``name``, and a fit from one seed is one trajectory."""

    @pytest.mark.parametrize("name", sorted(_GRAPH))
    def test_public_name_is_the_graph_class(self, name):
        assert getattr(models_pkg, _PUBLIC[name]) is _GRAPH[name]
        assert models_pkg._REGISTRY[name] is _GRAPH[name]
        assert _GRAPH[name].name == name

    @pytest.mark.parametrize("name", sorted(_GRAPH))
    def test_five_step_trajectory_repeats_bit_identical(self, name):
        cfg = _cfg(model=name)

        def _run():
            losses = []
            tr = Trainer(cfg)
            state, _ = tr.fit(
                tr.init_state(), _batches(5),
                hooks=[lambda s, m: losses.append(float(m["loss"]))])
            assert type(tr.model) is _GRAPH[name]
            return losses, state

        losses_a, s_a = _run()
        losses_b, s_b = _run()
        assert len(losses_a) == 5 and losses_a == losses_b  # floats, exact
        for a, b in zip(jax.tree.leaves(s_a.params),
                        jax.tree.leaves(s_b.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestZooTwoStepSmoke:
    """Fast tier-1 smoke: every registered model and every --multitask mode
    must build and take 2 optimizer steps on CPU."""

    @pytest.mark.parametrize(
        "name", registered_models() + ["mmoe", "shared_bottom", "esmm"])
    def test_two_steps(self, name):
        if name in ("mmoe", "shared_bottom", "esmm"):
            cfg = _cfg(model="deepfm", tasks="ctr,cvr", multitask=name,
                       mmoe_experts=2)
        else:
            cfg = _cfg(model=name)
        tr = Trainer(cfg)
        losses = []
        state, summary = tr.fit(
            tr.init_state(), _batches(2, two_label=cfg.num_tasks > 1),
            hooks=[lambda s, m: losses.append(float(m["loss"]))])
        assert summary["steps"] == 2
        assert all(np.isfinite(l) for l in losses)


@pytest.fixture(scope="module")
def mt_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("mt")
    data = str(d / "data")
    libsvm.generate_synthetic_ctr(
        data, num_files=3, examples_per_file=256, feature_size=300,
        field_size=5, prefix="tr", seed=7, num_labels=2)
    libsvm.generate_synthetic_ctr(
        data, num_files=1, examples_per_file=256, feature_size=300,
        field_size=5, prefix="va", seed=8, num_labels=2)
    libsvm.generate_synthetic_ctr(
        data, num_files=1, examples_per_file=128, feature_size=300,
        field_size=5, prefix="te", seed=9, num_labels=2)
    return d


def _mt_cfg(mt_dir, **kw):
    base = dict(
        feature_size=300, field_size=5, embedding_size=8,
        deep_layers="16,8", dropout="1.0,1.0", batch_size=64,
        compute_dtype="float32", learning_rate=0.05, num_epochs=2,
        data_dir=str(mt_dir / "data"), val_data_dir=str(mt_dir / "data"),
        model_dir=str(mt_dir / "ckpt"), log_steps=0,
        save_checkpoints_steps=5, mesh_data=1, mesh_model=1,
        scale_lr_by_world=False, seed=3,
        tasks="ctr,cvr", multitask="mmoe", mmoe_experts=2,
    )
    base.update(kw)
    return Config(**base)


@pytest.fixture(scope="module")
def mt_trained(mt_dir):
    """One MMoE CTR+CVR train → publish run shared by the e2e tests."""
    cfg = _mt_cfg(mt_dir, servable_model_dir=str(mt_dir / "servable"))
    # (the e2e tests read the StableHLO artifact; the TensorFlow sidecar, 40
    # of this fixture's 48 s in a worker's first export, is
    # ``test_savedmodel_export``'s)
    with pytest.MonkeyPatch.context() as patched:
        patched.setenv("DEEPFM_TPU_SKIP_TF_EXPORT", "1")
        result = tasks.run(cfg)
    [sub] = os.listdir(str(mt_dir / "servable"))
    return result, str(mt_dir / "servable" / sub)


class TestMultiTaskEndToEnd:
    def test_train_reports_per_task_auc(self, mt_trained):
        result, _ = mt_trained
        assert "auc_ctr" in result and "auc_cvr" in result, result
        assert 0.0 <= result["auc_ctr"] <= 1.0
        assert 0.0 <= result["auc_cvr"] <= 1.0
        # CTR is learnable on the synthetic data; the headline auc is task 0
        assert result["auc"] == result["auc_ctr"]
        assert result["auc_ctr"] > 0.55, result

    def test_artifact_declares_named_outputs(self, mt_trained):
        _, artifact = mt_trained
        meta = json.load(open(os.path.join(artifact, "model_config.json")))
        assert set(meta["signature"]["outputs"]) == {"ctr", "cvr"}

    def test_load_serving_returns_named_probs(self, mt_trained):
        _, artifact = mt_trained
        serve = export_lib.load_serving(artifact)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 300, (16, 5)).astype(np.int32)
        vals = rng.normal(size=(16, 5)).astype(np.float32)
        out = serve(ids, vals)
        assert set(out) == {"ctr", "cvr"}
        for arr in out.values():
            arr = np.asarray(arr)
            assert arr.shape == (16,)
            assert ((arr >= 0) & (arr <= 1)).all()

    def test_serving_engine_demuxes_named_outputs(self, mt_trained):
        _, artifact = mt_trained
        serve = export_lib.load_serving(artifact)
        rng = np.random.default_rng(1)
        ids = rng.integers(0, 300, (5, 5)).astype(np.int32)
        vals = rng.normal(size=(5, 5)).astype(np.float32)
        with ServingEngine(serve, max_batch=8, max_delay_ms=5) as eng:
            got = eng.predict(ids, vals, timeout=60)
        assert set(got) == {"ctr", "cvr"}
        want = export_lib.padded_predict(serve, ids, vals, (8,))
        for k in ("ctr", "cvr"):
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))

    def test_infer_writes_two_columns(self, mt_dir, mt_trained):
        out = tasks.run(_mt_cfg(mt_dir, task_type="infer"))
        assert out["num_predictions"] == 128
        lines = open(os.path.join(str(mt_dir / "data"),
                                  "pred.txt")).read().splitlines()
        assert len(lines) == 128
        rows = np.array([[float(v) for v in ln.split()] for ln in lines])
        assert rows.shape == (128, 2)
        assert ((rows >= 0) & (rows <= 1)).all()


class TestEngineWireShapes:
    """ServingEngine demux is shape-agnostic; the single-output wire shape
    is a compatibility contract and must not change."""

    def test_single_output_keeps_old_wire_shape(self):
        def pred(ids, vals):
            return vals[:, 0]

        with ServingEngine(pred, max_batch=8, max_delay_ms=5) as eng:
            ids = np.zeros((3, F), np.int32)
            vals = np.arange(3 * F, dtype=np.float32).reshape(3, F)
            got = eng.predict(ids, vals, timeout=60)
        assert isinstance(got, np.ndarray)  # NOT a dict
        assert got.shape == (3,)
        np.testing.assert_array_equal(got, vals[:, 0])

    def test_dict_outputs_demuxed_row_for_row(self):
        def pred(ids, vals):
            return {"a": vals[:, 0], "b": 2.0 * vals[:, 0]}

        with ServingEngine(pred, max_batch=16, max_delay_ms=20,
                           buckets=(16,)) as eng:
            futs = [eng.submit(np.zeros((n, F), np.int32),
                               np.full((n, F), float(i), np.float32))
                    for i, n in enumerate((2, 3, 1))]
            outs = [f.result(timeout=60) for f in futs]
        for i, (out, n) in enumerate(zip(outs, (2, 3, 1))):
            assert set(out) == {"a", "b"}
            np.testing.assert_array_equal(out["a"], np.full(n, float(i)))
            np.testing.assert_array_equal(out["b"], np.full(n, 2.0 * i))


def _tier_cfg(**kw):
    base = dict(
        feature_size=400, field_size=F, embedding_size=8,
        deep_layers="16,8", dropout="1.0,1.0", batch_size=B,
        compute_dtype="float32", l2_reg=1e-4, learning_rate=1e-3,
        log_steps=0, seed=11, scale_lr_by_world=False,
        mesh_data=1, mesh_model=1, embedding_update="sparse",
    )
    base.update(kw)
    return Config(**base)


class TestTieringCheckpoint:
    """Hot/cold runs checkpoint the DENSIFIED table: restores must be
    bit-exact into untiered configs and into differently sized hot caches,
    in both directions."""

    def _eval_batches(self):
        return _batches(4, seed=17, v=400)

    def test_tiered_checkpoint_restores_untiered_and_resized(self, tmp_path):
        cfg = _tier_cfg(embedding_tiering="hot_cold",
                        embedding_hot_rows=256)
        tr = Trainer(cfg)
        state, _ = tr.fit(tr.init_state(), _batches(6, v=400))
        ev = tr.evaluate(state, self._eval_batches())
        d = str(tmp_path / "tiered")
        with ckpt_lib.CheckpointManager(d) as mgr:
            mgr.save(6, tr._tier.checkpoint_state(state))

        # direction A: restore into an untiered (dense-table) config
        tr_dense = Trainer(_tier_cfg())
        with ckpt_lib.CheckpointManager(d) as mgr:
            restored = mgr.restore(tr_dense.init_state())
        ev_dense = tr_dense.evaluate(restored, self._eval_batches())
        assert ev_dense["auc"] == ev["auc"]
        assert ev_dense["loss"] == ev["loss"]

        # direction A': restore into a DIFFERENTLY sized hot cache
        cfg2 = _tier_cfg(embedding_tiering="hot_cold",
                         embedding_hot_rows=320)
        tr2 = Trainer(cfg2)
        with ckpt_lib.CheckpointManager(d) as mgr:
            template = tr2.init_state(tiered=False)
            restored2 = tr2._tier.adopt(mgr.restore(template))
        ev2 = tr2.evaluate(restored2, self._eval_batches())
        assert ev2["auc"] == ev["auc"]
        assert ev2["loss"] == ev["loss"]

    def test_dense_checkpoint_restores_into_tiered(self, tmp_path):
        cfg = _tier_cfg()
        tr = Trainer(cfg)
        state, _ = tr.fit(tr.init_state(), _batches(6, v=400))
        ev = tr.evaluate(state, self._eval_batches())
        d = str(tmp_path / "dense")
        with ckpt_lib.CheckpointManager(d) as mgr:
            mgr.save(6, state)

        cfg_t = _tier_cfg(embedding_tiering="hot_cold",
                          embedding_hot_rows=256)
        tr_t = Trainer(cfg_t)
        with ckpt_lib.CheckpointManager(d) as mgr:
            template = tr_t.init_state(tiered=False)
            restored = tr_t._tier.adopt(mgr.restore(template))
        ev_t = tr_t.evaluate(restored, self._eval_batches())
        assert ev_t["auc"] == ev["auc"]
        assert ev_t["loss"] == ev["loss"]


class TestLabel2Codec:
    """Two-label input contract: byte-identity for single-label encodes,
    round-trip, defaulting, native/Python mirror parity, pipeline column."""

    def _example(self, seed=0):
        rng = np.random.default_rng(seed)
        ids = rng.integers(0, 1000, F).astype(np.int64)
        vals = rng.normal(size=F).astype(np.float32)
        return ids, vals

    def test_single_label_encode_byte_identical(self):
        ids, vals = self._example()
        assert (example_codec.encode_ctr_example(1.0, ids, vals) ==
                example_codec.encode_ctr_example(1.0, ids, vals,
                                                 label2=None))

    def test_round_trip_and_default(self):
        ids, vals = self._example(1)
        buf = example_codec.encode_ctr_example(1.0, ids, vals, label2=1.0)
        lab, lab2, rid, rval = example_codec.decode_ctr_example2(buf, F)
        assert (lab, lab2) == (1.0, 1.0)
        np.testing.assert_array_equal(rid, ids)
        np.testing.assert_array_equal(rval, vals)
        # one-label decode still reads two-label bytes (ignores label2)
        lab_1, _, _ = example_codec.decode_ctr_example(buf, F)
        assert lab_1 == 1.0
        # two-label decode defaults label2=0.0 on single-label bytes
        buf1 = example_codec.encode_ctr_example(1.0, ids, vals)
        _, lab2_default, _, _ = example_codec.decode_ctr_example2(buf1, F)
        assert lab2_default == 0.0

    @pytest.mark.skipif(
        not (loader.available() and loader.has_labels2()),
        reason="native two-label decoder unavailable")
    def test_native_decode_matches_python_mirror(self, tmp_path):
        [path] = libsvm.generate_synthetic_ctr(
            str(tmp_path), num_files=1, examples_per_file=200,
            feature_size=500, field_size=F, seed=5, num_labels=2)
        records = tfrecord.read_all_records(path)
        l_n, l2_n, ids_n, vals_n = loader.decode_batch2(records, F)
        for i, rec in enumerate(records):
            lab, lab2, rid, rval = example_codec.decode_ctr_example2(rec, F)
            assert l_n[i] == np.float32(lab)
            assert l2_n[i] == np.float32(lab2)
            np.testing.assert_array_equal(ids_n[i], rid.astype(np.int32))
            np.testing.assert_array_equal(vals_n[i], rval)

    def test_pipeline_emits_label2_column(self, tmp_path):
        files = libsvm.generate_synthetic_ctr(
            str(tmp_path), num_files=1, examples_per_file=128,
            feature_size=100, field_size=F, seed=1, num_labels=2)
        p = pipeline.CtrPipeline(
            files, field_size=F, batch_size=32, num_epochs=1,
            shuffle=False, prefetch_batches=0, num_labels=2)
        batches = list(p)
        assert sum(b["label"].shape[0] for b in batches) == 128
        lab = np.concatenate([b["label"][:, 0] for b in batches])
        lab2 = np.concatenate([b["label2"][:, 0] for b in batches])
        assert all(b["label2"].shape == (b["label"].shape[0], 1)
                   for b in batches)
        # conversions are click-gated in the generator
        assert (lab2 <= lab).all()
        assert lab2.sum() > 0

    def test_single_label_files_read_as_all_negative_task2(self, tmp_path):
        files = libsvm.generate_synthetic_ctr(
            str(tmp_path), num_files=1, examples_per_file=64,
            feature_size=100, field_size=F, seed=2)
        p = pipeline.CtrPipeline(
            files, field_size=F, batch_size=32, num_epochs=1,
            shuffle=False, prefetch_batches=0, num_labels=2)
        for b in p:
            assert (b["label2"] == 0.0).all()
