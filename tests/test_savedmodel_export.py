"""TF SavedModel serving-artifact parity: the reference's export target is a
SavedModel with signature {feat_ids: int64[None,F], feat_vals: f32[None,F]}
-> {prob} (``1-ps-cpu/...py:458-467``). The export now emits that exact
artifact via jax2tf alongside the StableHLO one; a TF-Serving deployment (or
tf.saved_model.load) consumes it directly and must agree with the JAX path.
"""

import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.train import Trainer
from deepfm_tpu.utils import export as export_lib


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    cfg = Config(
        feature_size=120, field_size=5, embedding_size=4, deep_layers="8",
        dropout="1.0", batch_size=32, compute_dtype="float32",
        mesh_data=1, log_steps=0, seed=7)
    trainer = Trainer(cfg)
    state = trainer.init_state()
    out = str(tmp_path_factory.mktemp("sv") / "1")
    export_lib.export_serving(trainer.model, state, cfg, out)
    return out


def test_savedmodel_exists_and_matches_jax(artifact):
    tf = pytest.importorskip("tensorflow")
    sm_dir = f"{artifact}/saved_model"
    loaded = tf.saved_model.load(sm_dir)
    sig = loaded.signatures["serving_default"]

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 120, (16, 5))
    vals = rng.normal(size=(16, 5)).astype(np.float32)

    tf_probs = sig(feat_ids=tf.constant(ids, tf.int64),
                   feat_vals=tf.constant(vals))["prob"].numpy()

    jax_serve = export_lib.load_serving(artifact)
    jax_probs = jax_serve(ids.astype(np.int32), vals)

    assert tf_probs.shape == (16,)
    np.testing.assert_allclose(tf_probs, jax_probs, rtol=1e-5, atol=1e-6)


# The other signatures the sidecar is written with, held here, where the
# TensorFlow import is already paid: the launcher and task tests of these
# models export with ``DEEPFM_TPU_SKIP_TF_EXPORT`` set and serve the StableHLO
# artifact (PR 43).
SIGNATURES = {
    # {task: probs}: one named head a task
    "multitask": dict(
        feature_size=120, field_size=5, embedding_size=4, deep_layers="8",
        dropout="1.0", tasks="ctr,cvr", multitask="mmoe", mmoe_experts=2),
    # numeric columns through the bottom MLP beside the categorical ones
    "dlrm_dcnv2": dict(
        model="dlrm_dcnv2", feature_size=120, field_size=7, numeric_fields=2,
        embedding_size=4, bottom_layers="4,4", cross_layers=1, cross_rank=2,
        deep_layers="8,4", dropout="1,1", optimizer="Adagrad"),
}


@pytest.mark.parametrize("name", sorted(SIGNATURES))
def test_savedmodel_of_each_signature_matches_jax(name, tmp_path):
    import tensorflow as tf
    cfg = Config(**SIGNATURES[name], batch_size=32, compute_dtype="float32",
                 mesh_data=1, log_steps=0, seed=7)
    trainer = Trainer(cfg)
    out = str(tmp_path / "1")
    export_lib.export_serving(trainer.model, trainer.init_state(), cfg, out)
    assert export_lib.saved_model_status(out) == "written"
    sig = tf.saved_model.load(f"{out}/saved_model").signatures[
        "serving_default"]

    rng = np.random.default_rng(1)
    ids = rng.integers(0, 120, (16, cfg.field_size))
    vals = rng.normal(size=(16, cfg.field_size)).astype(np.float32)
    got = {k: v.numpy() for k, v in sig(
        feat_ids=tf.constant(ids, tf.int64),
        feat_vals=tf.constant(vals)).items()}
    want = export_lib.load_serving(out)(ids.astype(np.int32), vals)
    if not isinstance(want, dict):
        want = {"prob": want}
    assert set(got) == set(want) == (
        {"ctr", "cvr"} if name == "multitask" else {"prob"})
    for task in want:
        assert got[task].shape == (16,)
        np.testing.assert_allclose(got[task], want[task], rtol=1e-5,
                                   atol=1e-6)


def test_artifact_without_program_refuses_to_load(artifact, tmp_path):
    """export_serving writes serving_fn.stablehlo or raises, so an artifact
    without it is damaged: load_serving must not rebuild a predict function
    from the config and serve through a program nobody exported."""
    import os
    import shutil
    degraded = str(tmp_path / "degraded")
    shutil.copytree(artifact, degraded)
    os.remove(os.path.join(degraded, "serving_fn.stablehlo"))
    with pytest.raises(export_lib.ArtifactIncomplete,
                       match="serving_fn.stablehlo"):
        export_lib.load_serving(degraded)


def test_export_on_tpu_backend_carries_program_and_loads_on_cpu(
        tmp_path, monkeypatch):
    """What a TPU host writes: with use_pallas=True and the backend reporting
    ``tpu`` the FM block is eligible for the compiled Pallas kernel, whose
    CPU lowering does not exist. The export must still produce the
    serialized program (symbolic batch -> portable formulation) and that
    artifact must serve under the CPU backend, matching the trainer."""
    import os
    cfg = Config(
        feature_size=120, field_size=5, embedding_size=4, deep_layers="8",
        dropout="1.0", batch_size=32, compute_dtype="float32",
        mesh_data=1, log_steps=0, seed=7, use_pallas=True)
    trainer = Trainer(cfg)
    state = trainer.init_state()
    out = str(tmp_path / "1")
    monkeypatch.setenv("DEEPFM_TPU_SKIP_TF_EXPORT", "1")
    with monkeypatch.context() as m:
        m.setattr(export_lib.jax, "default_backend", lambda: "tpu")
        export_lib.export_serving(trainer.model, state, cfg, out)
    assert os.path.exists(os.path.join(out, "serving_fn.stablehlo"))
    assert export_lib.saved_model_status(out).startswith("skipped:")

    rng = np.random.default_rng(2)
    ids = rng.integers(0, 120, (8, 5)).astype(np.int32)
    vals = rng.normal(size=(8, 5)).astype(np.float32)
    batch = {"feat_ids": ids, "feat_vals": vals,
             "label": np.zeros((8, 1), np.float32)}
    want = np.concatenate(list(trainer.predict(state, [batch])))
    np.testing.assert_allclose(export_lib.load_serving(out)(ids, vals), want,
                               rtol=1e-5, atol=1e-6)


def test_savedmodel_batch_polymorphic(artifact):
    tf = pytest.importorskip("tensorflow")
    loaded = tf.saved_model.load(f"{artifact}/saved_model")
    sig = loaded.signatures["serving_default"]
    for b in (1, 7, 64):
        out = sig(feat_ids=tf.zeros((b, 5), tf.int64),
                  feat_vals=tf.zeros((b, 5), tf.float32))["prob"]
        assert out.shape == (b,)
