"""Model math tests: golden values vs a NumPy oracle (SURVEY.md §4 strategy).

The oracle re-implements the reference model_fn equations
(1-ps-cpu/...py:149-292) directly in NumPy; the JAX models must match to
float tolerance in float32 compute mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.models import get_model, registered_models
from deepfm_tpu.models.common import l2_half_sum


def _cfg(**kw):
    base = dict(
        feature_size=100, field_size=5, embedding_size=4,
        deep_layers="8,4", dropout="1.0,1.0", batch_size=8,
        compute_dtype="float32", l2_reg=1e-3, batch_norm=False,
    )
    base.update(kw)
    if base.get("model") == "dlrm_dcnv2":    # its two own flags
        base = {"numeric_fields": 2, "bottom_layers": "6,4", **base}
    return Config(**base)


def _batch(cfg, n=8, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.feature_size, size=(n, cfg.field_size)).astype(np.int32)
    vals = rng.normal(size=(n, cfg.field_size)).astype(np.float32)
    return ids, vals


def _numpy_deepfm(params, ids, vals, layers):
    """NumPy oracle of the reference forward pass."""
    fm_b = np.asarray(params["fm_b"])
    fm_w = np.asarray(params["fm_w"])
    fm_v = np.asarray(params["fm_v"])
    y_w = np.sum(fm_w[ids] * vals, axis=1)
    xv = fm_v[ids] * vals[..., None]
    sum_sq = np.square(xv.sum(axis=1))
    sq_sum = np.square(xv).sum(axis=1)
    y_v = 0.5 * (sum_sq - sq_sum).sum(axis=1)
    h = xv.reshape(ids.shape[0], -1)
    for layer in params["tower"]["layers"]:
        h = np.maximum(h @ np.asarray(layer["w"]) + np.asarray(layer["b"]), 0.0)
    out = h @ np.asarray(params["tower"]["out"]["w"]) + np.asarray(params["tower"]["out"]["b"])
    return fm_b[0] + y_w + y_v + out[:, 0]


class TestDeepFM:
    def test_matches_numpy_oracle(self):
        cfg = _cfg()
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))
        ids, vals = _batch(cfg)
        logits, _ = model.apply(params, state, ids, vals, train=False)
        expected = _numpy_deepfm(params, ids, vals, cfg.deep_layer_sizes)
        np.testing.assert_allclose(np.asarray(logits), expected, rtol=2e-5, atol=2e-5)

    def test_l2_matches_tf_l2_loss_semantics(self):
        cfg = _cfg()
        model = get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        got = float(model.l2_loss(params))
        want = cfg.l2_reg * 0.5 * (
            np.square(np.asarray(params["fm_w"])).sum()
            + np.square(np.asarray(params["fm_v"])).sum())
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_dropout_train_only_and_stochastic(self):
        cfg = _cfg(dropout="0.5,0.5")
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))
        ids, vals = _batch(cfg)
        eval_logits, _ = model.apply(params, state, ids, vals, train=False)
        eval_logits2, _ = model.apply(params, state, ids, vals, train=False)
        np.testing.assert_array_equal(np.asarray(eval_logits), np.asarray(eval_logits2))
        t1, _ = model.apply(params, state, ids, vals, train=True,
                            rng=jax.random.PRNGKey(1))
        t2, _ = model.apply(params, state, ids, vals, train=True,
                            rng=jax.random.PRNGKey(2))
        assert not np.array_equal(np.asarray(t1), np.asarray(t2))

    def test_batch_norm_updates_state(self):
        cfg = _cfg(batch_norm=True)
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))
        assert len(state["bn"]) == 2
        ids, vals = _batch(cfg)
        _, new_state = model.apply(params, state, ids, vals, train=True,
                                   rng=jax.random.PRNGKey(1))
        assert not np.allclose(np.asarray(new_state["bn"][0]["mean"]),
                               np.asarray(state["bn"][0]["mean"]))
        # eval must not touch state
        _, eval_state = model.apply(params, new_state, ids, vals, train=False)
        np.testing.assert_array_equal(
            np.asarray(eval_state["bn"][0]["mean"]),
            np.asarray(new_state["bn"][0]["mean"]))

    def test_bfloat16_close_to_float32(self):
        cfg32, cfg16 = _cfg(), _cfg(compute_dtype="bfloat16")
        m32, m16 = get_model(cfg32), get_model(cfg16)
        params, state = m32.init(jax.random.PRNGKey(0))
        ids, vals = _batch(cfg32)
        l32, _ = m32.apply(params, state, ids, vals, train=False)
        l16, _ = m16.apply(params, state, ids, vals, train=False)
        np.testing.assert_allclose(np.asarray(l32), np.asarray(l16),
                                   rtol=0.1, atol=0.15)


class TestWideDeep:
    def test_no_fm_term(self):
        """WideDeep == DeepFM minus the second-order interaction."""
        cfg_fm = _cfg()
        cfg_wd = _cfg(model="widedeep")
        fm, wd = get_model(cfg_fm), get_model(cfg_wd)
        params, state = fm.init(jax.random.PRNGKey(0))
        ids, vals = _batch(cfg_fm)
        l_fm, _ = fm.apply(params, state, ids, vals, train=False)
        l_wd, _ = wd.apply(params, state, ids, vals, train=False)
        fm_v = np.asarray(params["fm_v"])
        xv = fm_v[ids] * vals[..., None]
        y_v = 0.5 * (np.square(xv.sum(1)) - np.square(xv).sum(1)).sum(1)
        np.testing.assert_allclose(
            np.asarray(l_fm) - np.asarray(l_wd), y_v, rtol=1e-4, atol=1e-4)


class TestDCNv2:
    def test_cross_layer_math(self):
        cfg = _cfg(model="dcnv2", cross_layers=2, deep_layers="8")
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))
        ids, vals = _batch(cfg)
        logits, _ = model.apply(params, state, ids, vals, train=False)
        # NumPy oracle
        fm_v = np.asarray(params["fm_v"])
        xv = fm_v[ids] * vals[..., None]
        x0 = xv.reshape(ids.shape[0], -1)
        x = x0
        for layer in params["cross"]:
            x = x0 * (x @ np.asarray(layer["w"]) + np.asarray(layer["b"])) + x
        h = x0
        for layer in params["tower"]["layers"]:
            h = np.maximum(h @ np.asarray(layer["w"]) + np.asarray(layer["b"]), 0)
        comb = np.concatenate([x, h], axis=1)
        out = comb @ np.asarray(params["head"]["w"]) + np.asarray(params["head"]["b"])
        expected = np.asarray(params["fm_b"])[0] + out[:, 0]
        np.testing.assert_allclose(np.asarray(logits), expected, rtol=2e-4, atol=2e-4)

    def test_low_rank_cross(self):
        cfg = _cfg(model="dcnv2", cross_layers=2, cross_rank=3)
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))
        assert "u" in params["cross"][0]
        ids, vals = _batch(cfg)
        logits, _ = model.apply(params, state, ids, vals, train=False)
        assert np.isfinite(np.asarray(logits)).all()


class TestDLRM:
    def test_dot_interaction_oracle(self):
        cfg = _cfg(model="dlrm")
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))
        ids, vals = _batch(cfg)
        logits, _ = model.apply(params, state, ids, vals, train=False)
        # NumPy oracle: first-order + tower over [flat xv, pairwise dots].
        fm_b = np.asarray(params["fm_b"])
        fm_w = np.asarray(params["fm_w"])
        fm_v = np.asarray(params["fm_v"])
        y_first = np.sum(fm_w[ids] * vals, axis=1)
        xv = fm_v[ids] * vals[..., None]
        f = xv.shape[1]
        iu, ju = np.triu_indices(f, k=1)
        gram = np.einsum("bik,bjk->bij", xv, xv)
        top_in = np.concatenate(
            [xv.reshape(ids.shape[0], -1), gram[:, iu, ju]], axis=1)
        h = top_in
        for layer in params["tower"]["layers"]:
            h = np.maximum(h @ np.asarray(layer["w"])
                           + np.asarray(layer["b"]), 0.0)
        out = (h @ np.asarray(params["tower"]["out"]["w"])
               + np.asarray(params["tower"]["out"]["b"]))
        expected = fm_b[0] + y_first + out[:, 0]
        np.testing.assert_allclose(np.asarray(logits), expected,
                                   rtol=2e-5, atol=2e-5)

    def test_pair_count(self):
        cfg = _cfg(model="dlrm")
        model = get_model(cfg)
        f, k = cfg.field_size, cfg.embedding_size
        assert model.top_input_dim() == f * k + f * (f - 1) // 2


class TestModelRegistry:
    """Every registered model (DLRM included) inherits the basic forward /
    gradient / schema contracts — the satellite parametrization that keeps
    new zoo entries honest without bespoke tests."""

    @pytest.mark.parametrize("name", sorted(registered_models()))
    def test_forward_finite_and_deterministic(self, name):
        cfg = _cfg(model=name)
        model = get_model(cfg)
        assert model.name == name
        params, state = model.init(jax.random.PRNGKey(0))
        ids, vals = _batch(cfg)
        l1, _ = model.apply(params, state, ids, vals, train=False)
        l2, _ = model.apply(params, state, ids, vals, train=False)
        assert np.asarray(l1).shape == (ids.shape[0],)
        assert np.isfinite(np.asarray(l1)).all()
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))

    @pytest.mark.parametrize("name", sorted(registered_models()))
    def test_grads_finite_and_flow_to_embeddings(self, name):
        cfg = _cfg(model=name)
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))
        ids, vals = _batch(cfg)
        labels = (np.arange(ids.shape[0]) % 2).astype(np.float32)

        def loss(p):
            logits, _ = model.apply(p, state, ids, vals, train=False)
            return jnp.mean(
                jnp.maximum(logits, 0) - logits * labels
                + jnp.log1p(jnp.exp(-jnp.abs(logits))))

        grads = jax.grad(loss)(params)
        for leaf in jax.tree.leaves(grads):
            assert np.isfinite(np.asarray(leaf)).all()
        assert float(np.abs(np.asarray(grads["fm_v"])).sum()) > 0.0

    @pytest.mark.parametrize("name", sorted(registered_models()))
    def test_embedding_schema_names(self, name):
        cfg = _cfg(model=name)
        model = get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        for pname in model.embedding_param_names():
            assert pname in params
            assert params[pname].shape[0] == model.padded_vocab
