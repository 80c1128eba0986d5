"""Retrieval→ranking cascade: twin tower, candidate index, end-to-end serving.

The executable acceptance for the cascade tentpole (README "Retrieval→ranking
cascade"): a twin tower trained on click-gated synthetic histories, a
candidate index over its item matrix (brute recall == 1.0 by construction —
measured anyway; ANN recall@50 >= 0.95, stamped into the artifact), and a
``CascadeEngine`` serving retrieve→rank over a published artifact through at
least one atomic hot swap with zero failures. Empty-history requests must be
finite end-to-end (the masked-softmax / l2-normalize NaN regressions).
"""

import json
import os

import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.data import libsvm, pipeline
from deepfm_tpu.models.twin_tower import TwinTower, train_twin_tower
from deepfm_tpu.rec.cascade import (
    ITEM_SLOT, TOWERS_CONFIG_FILE, TOWERS_FILE, CascadeEngine,
    _fit_history, cascade_extra_export, export_cascade, load_towers,
    save_towers)
from deepfm_tpu.rec.index import (
    INDEX_FILE, INDEX_META_FILE, CandidateIndex)
from deepfm_tpu.utils import export as export_lib

FEATURE_SIZE = 120
FIELD_SIZE = 5
HIST_LEN = 6
BATCH = 32


def _cfg(**kw):
    base = dict(
        feature_size=FEATURE_SIZE, field_size=FIELD_SIZE, embedding_size=4,
        deep_layers="8", dropout="1.0", batch_size=BATCH,
        compute_dtype="float32", mesh_data=1, log_steps=0, seed=3,
        scale_lr_by_world=False, model="din", history_max_len=HIST_LEN)
    base.update(kw)
    return Config(**base)


@pytest.fixture(scope="module")
def hist_batches(tmp_path_factory):
    """Pipeline batches over click-gated synthetic history data."""
    data_dir = tmp_path_factory.mktemp("cascade_data")
    files = libsvm.generate_synthetic_ctr(
        str(data_dir), num_files=1, examples_per_file=256,
        feature_size=FEATURE_SIZE, field_size=FIELD_SIZE, seed=7,
        history=HIST_LEN)
    p = pipeline.CtrPipeline(
        files, field_size=FIELD_SIZE, batch_size=BATCH, num_epochs=1,
        shuffle=False, prefetch_batches=0, history=True,
        history_max_len=HIST_LEN)
    batches = list(p)
    assert batches and all("hist_ids" in b for b in batches)
    return batches


@pytest.fixture(scope="module")
def towers(hist_batches):
    """(model, params, stats) — twin tower fit on the history batches."""
    return train_twin_tower(_cfg(), hist_batches, item_slot=ITEM_SLOT)


# ---------------------------------------------------------------------------
# Twin tower
# ---------------------------------------------------------------------------

class TestTwinTower:
    def test_training_converges_finite(self, towers):
        _, _, stats = towers
        assert np.isfinite(stats["loss"]), stats
        assert stats["positive_rows"] > 0, stats
        assert stats["steps"] == 256 // BATCH

    def test_embeddings_unit_norm(self, towers):
        model, params, _ = towers
        rng = np.random.default_rng(0)
        ids = rng.integers(1, FEATURE_SIZE, (8, HIST_LEN)).astype(np.int32)
        mask = np.ones((8, HIST_LEN), np.float32)
        u = np.asarray(model.user_embed(params, ids, mask))
        v = np.asarray(model.item_embed(
            params, np.arange(8, dtype=np.int32)))
        np.testing.assert_allclose(np.linalg.norm(u, axis=1), 1.0, atol=1e-5)
        np.testing.assert_allclose(np.linalg.norm(v, axis=1), 1.0, atol=1e-5)

    def test_empty_history_embeds_finite(self, towers):
        """All-masked history pools zeros; the tower must stay finite (the
        l2-normalize NaN-gradient regression, forward flavor)."""
        model, params, _ = towers
        u = np.asarray(model.user_embed(
            params, np.zeros((2, HIST_LEN), np.int32),
            np.zeros((2, HIST_LEN), np.float32)))
        assert np.all(np.isfinite(u))

    def test_loss_gradient_finite_with_empty_history_rows(self, towers):
        """The backward flavor: a zero-weighted empty-history row must not
        poison the batch gradient with NaN."""
        import jax
        import jax.numpy as jnp
        model, params, _ = towers
        hist_ids = np.zeros((4, HIST_LEN), np.int32)
        hist_mask = np.zeros((4, HIST_LEN), np.float32)
        hist_ids[:2] = np.arange(1, HIST_LEN + 1)
        hist_mask[:2] = 1.0                      # rows 2,3: empty history
        items = np.arange(4, dtype=np.int32)
        weights = np.array([1, 1, 0, 0], np.float32)
        grads = jax.grad(model.loss)(
            params, jnp.asarray(hist_ids), jnp.asarray(hist_mask),
            jnp.asarray(items), jnp.asarray(weights))
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)

    def test_requires_history_batches(self):
        with pytest.raises(ValueError, match="history batches"):
            train_twin_tower(_cfg(), [{
                "label": np.zeros((4, 1), np.float32),
                "feat_ids": np.zeros((4, FIELD_SIZE), np.int32),
                "feat_vals": np.zeros((4, FIELD_SIZE), np.float32)}])

    def test_towers_save_load_roundtrip(self, towers, tmp_path):
        model, params, _ = towers
        save_towers(params, _cfg(), str(tmp_path))
        model2, params2 = load_towers(str(tmp_path))
        ids = np.arange(16, dtype=np.int32)
        np.testing.assert_array_equal(
            np.asarray(model.item_embed(params, ids)),
            np.asarray(model2.item_embed(params2, ids)))


# ---------------------------------------------------------------------------
# Candidate index
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def item_matrix(towers):
    model, params, _ = towers
    vecs = model.all_item_embeddings(params, FEATURE_SIZE)
    assert vecs.shape == (FEATURE_SIZE, model.dim)
    return vecs


@pytest.fixture(scope="module")
def user_queries(towers, hist_batches):
    model, params, _ = towers
    b = hist_batches[0]
    return np.asarray(model.user_embed(
        params, b["hist_ids"], b["hist_mask"]))


class TestCandidateIndex:
    def test_brute_recall_is_exactly_one(self, item_matrix, user_queries):
        idx = CandidateIndex(item_matrix, kind="brute")
        assert idx.recall_at_k(user_queries, 10) == 1.0
        assert idx.recall_at_k(user_queries, 50) == 1.0

    def test_ann_recall_meets_bar(self, item_matrix, user_queries):
        idx = CandidateIndex(item_matrix, kind="ann", seed=0)
        assert idx.recall_at_k(user_queries, 50) >= 0.95

    def test_brute_matches_numpy_argmax(self, item_matrix, user_queries):
        idx = CandidateIndex(item_matrix, kind="brute")
        ids, scores = idx.search(user_queries[:4], 5)
        ref = np.argsort(-(user_queries[:4] @ item_matrix.T), axis=1)[:, :5]
        np.testing.assert_array_equal(ids, ref)
        assert np.all(np.diff(scores, axis=1) <= 1e-6)  # best first

    def test_k_clamped_to_corpus(self, item_matrix, user_queries):
        idx = CandidateIndex(item_matrix, kind="brute")
        ids, _ = idx.search(user_queries[:1], 10 * FEATURE_SIZE)
        assert ids.shape == (1, FEATURE_SIZE)
        assert len(set(map(int, ids[0]))) == FEATURE_SIZE

    def test_custom_ids_mapping(self, item_matrix, user_queries):
        offset_ids = np.arange(FEATURE_SIZE) + 1000
        idx = CandidateIndex(item_matrix, kind="brute", ids=offset_ids)
        ids, _ = idx.search(user_queries[:2], 3)
        assert np.all(ids >= 1000)

    def test_save_load_search_identical(self, item_matrix, user_queries,
                                        tmp_path):
        idx = CandidateIndex(item_matrix, kind="ann", seed=0)
        r50 = idx.recall_at_k(user_queries, 50)
        meta = idx.save(str(tmp_path), extra_meta={"recall_at_50": r50})
        assert meta["recall_at_50"] == r50
        idx2, meta2 = CandidateIndex.load(str(tmp_path))
        assert meta2["recall_at_50"] == r50        # stamp survives the disk
        ids1, s1 = idx.search(user_queries, 10)
        ids2, s2 = idx2.search(user_queries, 10)
        np.testing.assert_array_equal(ids1, ids2)
        np.testing.assert_array_equal(s1, s2)

    def test_validation_errors(self, item_matrix):
        with pytest.raises(ValueError, match="brute\\|ann"):
            CandidateIndex(item_matrix, kind="faiss")
        with pytest.raises(ValueError, match="\\[V, D\\]"):
            CandidateIndex(item_matrix[0])
        idx = CandidateIndex(item_matrix)
        with pytest.raises(ValueError, match="query dim"):
            idx.search(np.zeros((1, idx.dim + 1), np.float32), 5)


# ---------------------------------------------------------------------------
# End-to-end cascade over a real published artifact + hot swap
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cascade_publish(tmp_path_factory, hist_batches, towers, item_matrix,
                    user_queries):
    """Publish dir with cascade version 1 live (DIN ranker + towers + ANN
    index with a measured recall stamp) and the trained pieces to publish
    more versions."""
    from deepfm_tpu.train import Trainer
    cfg = _cfg()
    trainer = Trainer(cfg)
    state = trainer.init_state()
    step_fn = trainer._make_train_step()
    for b in hist_batches:
        state, _ = step_fn(state, trainer.put_batch(b))
    _, tower_params, _ = towers
    index = CandidateIndex(item_matrix, kind="ann", seed=0)
    r50 = index.recall_at_k(user_queries, 50)
    publish_dir = str(tmp_path_factory.mktemp("cascade_pub"))
    orig = export_lib._export_tf_savedmodel
    export_lib._export_tf_savedmodel = lambda *a, **k: None  # ~10s/version
    try:
        export_cascade(
            trainer.model, state, cfg, os.path.join(publish_dir, "1"),
            tower_params=tower_params, index=index,
            index_meta={"recall_at_50": r50})
        export_lib.write_latest(publish_dir, "1")
        yield {"dir": publish_dir, "trainer": trainer, "state": state,
               "cfg": cfg, "tower_params": tower_params, "index": index,
               "recall_at_50": r50}
    finally:
        export_lib._export_tf_savedmodel = orig


@pytest.fixture(scope="module")
def engine(cascade_publish):
    eng = CascadeEngine(
        cascade_publish["dir"], retrieve_k=20, max_batch=BATCH,
        max_delay_ms=1.0, watcher_kw={"poll_secs": 3600, "start": False})
    try:
        yield eng
    finally:
        eng.close()


class TestCascadeArtifact:
    def test_marker_certifies_all_three_stages(self, cascade_publish):
        v1 = os.path.join(cascade_publish["dir"], "1")
        for name in (export_lib.COMPLETE_MARKER, TOWERS_FILE,
                     TOWERS_CONFIG_FILE, INDEX_FILE, INDEX_META_FILE,
                     "model_config.json"):
            assert os.path.exists(os.path.join(v1, name)), name

    def test_recall_stamp_in_artifact(self, cascade_publish):
        with open(os.path.join(cascade_publish["dir"], "1",
                               INDEX_META_FILE)) as f:
            meta = json.load(f)
        assert meta["kind"] == "ann"
        assert meta["recall_at_50"] == cascade_publish["recall_at_50"]
        assert meta["recall_at_50"] >= 0.95

    def test_signature_is_packed_columns(self, cascade_publish):
        with open(os.path.join(cascade_publish["dir"], "1",
                               "model_config.json")) as f:
            meta = json.load(f)
        assert meta["history_len"] == HIST_LEN
        assert meta["signature"]["inputs"]["feat_ids"][1] \
            == FIELD_SIZE + HIST_LEN


class TestCascadeServing:
    def _request(self, seed=0, hist_rows=4):
        rng = np.random.default_rng(seed)
        hist_ids = rng.integers(
            1, FEATURE_SIZE, (HIST_LEN,)).astype(np.int32)
        hist_mask = np.zeros((HIST_LEN,), np.float32)
        hist_mask[:hist_rows] = 1.0
        feat_ids = rng.integers(
            0, FEATURE_SIZE, (FIELD_SIZE,)).astype(np.int32)
        feat_vals = rng.normal(size=(FIELD_SIZE,)).astype(np.float32)
        return hist_ids, hist_mask, feat_ids, feat_vals

    def test_recommend_end_to_end(self, engine):
        hist_ids, hist_mask, feat_ids, feat_vals = self._request(seed=1)
        items, probs = engine.recommend(
            hist_ids, hist_mask, feat_ids, feat_vals, k=10)
        assert items.shape == (10,) and probs.shape == (10,)
        assert len(set(map(int, items))) == 10          # distinct candidates
        assert np.all(np.isfinite(probs))
        assert np.all((probs >= 0) & (probs <= 1))
        assert np.all(np.diff(probs) <= 0)              # ranker-sorted

    def test_empty_history_finite_end_to_end(self, engine):
        """The cascade's empty-history contract: user tower pools zeros,
        DIN attention contributes exact zeros — finite everywhere."""
        _, _, feat_ids, feat_vals = self._request(seed=2)
        items, probs = engine.recommend(
            np.zeros((HIST_LEN,), np.int32),
            np.zeros((HIST_LEN,), np.float32), feat_ids, feat_vals, k=5)
        assert np.all(np.isfinite(probs))
        assert np.all((probs >= 0) & (probs <= 1))

    def test_retrieve_stage_shapes(self, engine):
        hist_ids, hist_mask, _, _ = self._request(seed=3)
        ids, scores = engine.retrieve(hist_ids, hist_mask, k=7)
        assert ids.shape == (1, 7) and scores.shape == (1, 7)

    def test_rank_consistent_with_direct_ranker(self, engine,
                                                cascade_publish):
        """recommend()'s probabilities are the ranker's own, not a proxy:
        rebuild one candidate row by hand and compare."""
        hist_ids, hist_mask, feat_ids, feat_vals = self._request(seed=4)
        items, probs = engine.recommend(
            hist_ids, hist_mask, feat_ids, feat_vals, k=3)
        model = engine.current()
        row_ids = feat_ids.copy()
        row_ids[ITEM_SLOT] = items[0]
        h_ids, h_mask = _fit_history(hist_ids, hist_mask, model.hist_len)
        packed_ids = np.concatenate([row_ids, h_ids])[None]
        packed_vals = np.concatenate([feat_vals, h_mask])[None]
        direct = np.asarray(model(packed_ids, packed_vals)).reshape(-1)
        np.testing.assert_allclose(probs[0], direct[0], rtol=1e-5)

    def test_context_width_validated(self, engine):
        hist_ids, hist_mask, _, _ = self._request()
        with pytest.raises(ValueError, match="context fields"):
            engine.recommend(hist_ids, hist_mask,
                             np.zeros((FIELD_SIZE + 1,), np.int32),
                             np.zeros((FIELD_SIZE + 1,), np.float32))

    def test_hot_swap_is_atomic_and_prewarmed(self, engine, cascade_publish):
        """Publish version 2, drive one poll: ranker + towers + index all
        move in ONE swap, buckets prewarmed off-thread, zero failures,
        serving uninterrupted."""
        assert engine.watcher.swap_count == 1
        prewarmed_v1 = engine.watcher.prewarmed_buckets
        assert prewarmed_v1 > 0                  # satellite (a): warm before
        before = engine.current()

        pub = cascade_publish
        export_cascade(
            pub["trainer"].model, pub["state"], pub["cfg"],
            os.path.join(pub["dir"], "2"),
            tower_params=pub["tower_params"], index=pub["index"],
            index_meta={"recall_at_50": pub["recall_at_50"]})
        export_lib.write_latest(pub["dir"], "2")
        assert engine.watcher.check_once()

        after = engine.current()
        assert engine.watcher.swap_count == 2
        assert engine.watcher.swap_failures == 0
        assert after is not before
        assert after.path.endswith("2")
        # the composite moved together: new towers + new index objects
        assert after.index is not before.index
        assert after.tower_params is not before.tower_params
        assert engine.watcher.prewarmed_buckets > prewarmed_v1

        hist_ids, hist_mask, feat_ids, feat_vals = self._request(seed=5)
        items, probs = engine.recommend(
            hist_ids, hist_mask, feat_ids, feat_vals, k=10)
        assert np.all(np.isfinite(probs))
        assert engine.stats.summary()["serving_failed"] == 0

    def test_incomplete_artifact_defers_swap(self, engine, cascade_publish):
        """A marker-less version 3 must NOT swap in (and must not take the
        engine down) — LATEST stays serviceable on the previous version."""
        pub = cascade_publish
        v3 = os.path.join(pub["dir"], "3")
        os.makedirs(v3, exist_ok=True)           # torn artifact: no marker
        export_lib.write_latest(pub["dir"], "3")
        failures_before = engine.watcher.swap_failures
        try:
            assert not engine.watcher.check_once()
            assert engine.watcher.swap_failures == failures_before + 1
            assert engine.current().path.endswith("2")
            hist_ids, hist_mask, feat_ids, feat_vals = self._request(seed=6)
            _, probs = engine.recommend(
                hist_ids, hist_mask, feat_ids, feat_vals, k=4)
            assert np.all(np.isfinite(probs))
        finally:
            export_lib.write_latest(pub["dir"], "2")
            engine.watcher.check_once()


class TestPublisherIntegration:
    def test_extra_export_hook_ships_retrieval_stage(self, cascade_publish,
                                                     tmp_path):
        """The Publisher path: ``cascade_extra_export`` stamps towers +
        index into the staging dir BEFORE the marker lands, so the one
        marker certifies the whole cascade."""
        from deepfm_tpu.train.publish import Publisher
        pub = cascade_publish
        pdir = str(tmp_path / "pub")
        orig = export_lib._export_tf_savedmodel
        export_lib._export_tf_savedmodel = lambda *a, **k: None
        try:
            publisher = Publisher(
                pub["trainer"].model, pub["cfg"], pdir,
                extra_export=cascade_extra_export(
                    pub["cfg"], pub["tower_params"], pub["index"],
                    index_meta={"recall_at_50": pub["recall_at_50"]}))
            publisher.publish_now(pub["state"], 7)
            assert publisher.drain(timeout=120)
            publisher.close()
        finally:
            export_lib._export_tf_savedmodel = orig
        assert publisher.published == [7]
        v7 = os.path.join(pdir, "7")
        for name in (export_lib.COMPLETE_MARKER, TOWERS_FILE, INDEX_FILE,
                     INDEX_META_FILE):
            assert os.path.exists(os.path.join(v7, name)), name
        assert export_lib.read_latest(pdir) == v7
        # the published artifact is a complete, loadable cascade
        eng = CascadeEngine(pdir, retrieve_k=8, max_batch=BATCH,
                            watcher_kw={"poll_secs": 3600, "start": False})
        try:
            rng = np.random.default_rng(9)
            items, probs = eng.recommend(
                rng.integers(1, FEATURE_SIZE, (HIST_LEN,)).astype(np.int32),
                np.ones((HIST_LEN,), np.float32),
                rng.integers(0, FEATURE_SIZE,
                             (FIELD_SIZE,)).astype(np.int32),
                rng.normal(size=(FIELD_SIZE,)).astype(np.float32), k=4)
            assert np.all(np.isfinite(probs))
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# Fused device-side cascade program (serving fast path)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def brute_publish(tmp_path_factory, cascade_publish, item_matrix):
    """A second publish dir whose version 1 carries a BRUTE index — the
    fusable kind — over the same trained ranker and towers."""
    pub = cascade_publish
    index = CandidateIndex(item_matrix, kind="brute")
    publish_dir = str(tmp_path_factory.mktemp("cascade_pub_brute"))
    orig = export_lib._export_tf_savedmodel
    export_lib._export_tf_savedmodel = lambda *a, **k: None
    try:
        export_cascade(
            pub["trainer"].model, pub["state"], pub["cfg"],
            os.path.join(publish_dir, "1"),
            tower_params=pub["tower_params"], index=index)
        export_lib.write_latest(publish_dir, "1")
    finally:
        export_lib._export_tf_savedmodel = orig
    return publish_dir


class TestFusedCascade:
    def _request(self, seed=0, hist_rows=4):
        rng = np.random.default_rng(seed)
        hist_ids = rng.integers(
            1, FEATURE_SIZE, (HIST_LEN,)).astype(np.int32)
        hist_mask = np.zeros((HIST_LEN,), np.float32)
        hist_mask[:hist_rows] = 1.0
        feat_ids = rng.integers(
            0, FEATURE_SIZE, (FIELD_SIZE,)).astype(np.int32)
        feat_vals = rng.normal(size=(FIELD_SIZE,)).astype(np.float32)
        return hist_ids, hist_mask, feat_ids, feat_vals

    @pytest.fixture()
    def engines(self, brute_publish):
        staged = CascadeEngine(
            brute_publish, retrieve_k=16, max_batch=BATCH,
            max_delay_ms=1.0, watcher_kw={"poll_secs": 3600, "start": False})
        fused = CascadeEngine(
            brute_publish, retrieve_k=16, max_batch=BATCH,
            max_delay_ms=1.0, fused=True,
            watcher_kw={"poll_secs": 3600, "start": False})
        try:
            yield staged, fused
        finally:
            staged.close()
            fused.close()

    def test_artifact_exposes_traceable_ranker(self, engines):
        staged, fused = engines
        model = fused.current()
        assert getattr(model.rank_fn, "raw_call", None) is not None
        assert model.supports_fused

    def test_fused_matches_staged_bit_identical(self, engines):
        """The acceptance pin: the fused single-program path returns the
        SAME items, bit for bit, as the staged user_embed -> search ->
        substitute -> rank -> argsort path, and the ranker's probabilities
        to one float32 ULP. The two paths are two XLA programs (one fused,
        the ranker alone), and what XLA:CPU contracts or reassociates inside
        a fusion is the build's to choose: in this one, one probability of
        eight reads 2.98e-8 apart at 0.46, one ULP. More than one ULP, or
        another item, is the program's fault."""
        staged, fused = engines
        for seed in (1, 2, 3):
            req = self._request(seed=seed)
            s_items, s_probs = staged.recommend(*req, k=8)
            f_items, f_probs = fused.recommend(*req, k=8)
            np.testing.assert_array_equal(f_items, s_items)
            assert f_probs.dtype == s_probs.dtype == np.float32
            np.testing.assert_array_max_ulp(f_probs, s_probs, maxulp=1)
        assert fused.fused_calls >= 3
        assert staged.fused_calls == 0

    def test_fused_empty_history_finite(self, engines):
        _, fused = engines
        _, _, feat_ids, feat_vals = self._request(seed=7)
        items, probs = fused.recommend(
            np.zeros((HIST_LEN,), np.int32),
            np.zeros((HIST_LEN,), np.float32), feat_ids, feat_vals, k=5)
        assert np.all(np.isfinite(probs))
        assert np.all((probs >= 0) & (probs <= 1))

    def test_recommend_batch_matches_per_row(self, engines):
        staged, fused = engines
        reqs = [self._request(seed=s) for s in (11, 12, 13)]
        h_ids = np.stack([r[0] for r in reqs])
        h_mask = np.stack([r[1] for r in reqs])
        f_ids = np.stack([r[2] for r in reqs])
        f_vals = np.stack([r[3] for r in reqs])
        b_items, b_probs = fused.recommend_batch(
            h_ids, h_mask, f_ids, f_vals, k=6)
        assert b_items.shape == (3, 6) and b_probs.shape == (3, 6)
        for i, req in enumerate(reqs):
            items, probs = staged.recommend(*req, k=6)
            np.testing.assert_array_equal(b_items[i], items)
            # Batched dispatch changes XLA's row vectorization — float-ULP
            # agreement, not bit (the B=1 fused path is held to one ULP
            # above).
            np.testing.assert_allclose(b_probs[i], probs, rtol=1e-5)

    def test_fused_compile_cache_is_bucketed(self, engines):
        """pow2 compile discipline: batches 1 and 3 share no key with each
        other (bucket 1 vs 4) but batch 3 and 4 share one program."""
        _, fused = engines
        model = fused.current()
        before = len(model._fused_cache)
        reqs = [self._request(seed=s) for s in (21, 22, 23, 24)]
        h_ids = np.stack([r[0] for r in reqs])
        h_mask = np.stack([r[1] for r in reqs])
        f_ids = np.stack([r[2] for r in reqs])
        f_vals = np.stack([r[3] for r in reqs])
        fused.recommend_batch(h_ids[:3], h_mask[:3], f_ids[:3], f_vals[:3],
                              k=4)
        n_after_3 = len(model._fused_cache)
        fused.recommend_batch(h_ids, h_mask, f_ids, f_vals, k=4)
        assert len(model._fused_cache) == n_after_3  # 3 and 4 share bucket 4
        assert n_after_3 <= before + 1

    def test_ann_index_gates_to_staged(self, cascade_publish):
        """fused=True over an ANN artifact serves via the staged path (the
        host-side partition scan cannot be traced) — no error, no fused
        dispatch."""
        eng = CascadeEngine(
            cascade_publish["dir"], retrieve_k=8, max_batch=BATCH,
            fused=True, watcher_kw={"poll_secs": 3600, "start": False})
        try:
            assert not eng.current().supports_fused
            req = self._request(seed=31)
            items, probs = eng.recommend(*req, k=4)
            assert np.all(np.isfinite(probs))
            assert eng.fused_calls == 0
        finally:
            eng.close()


class TestNoHistoryCascade:
    def test_history_free_artifact_serves_end_to_end(
            self, tmp_path_factory, towers, item_matrix):
        """Satellite pin: a ranker exported WITHOUT history columns
        (hist_len == 0) serves the full cascade — no history fitting, no
        zero-length scratch concat, finite output on both the staged and
        fused paths."""
        from deepfm_tpu.train import Trainer
        cfg = _cfg(model="deepfm", history_max_len=0)
        trainer = Trainer(cfg)
        state = trainer.init_state()
        _, tower_params, _ = towers
        index = CandidateIndex(item_matrix, kind="brute")
        publish_dir = str(tmp_path_factory.mktemp("cascade_pub_nohist"))
        orig = export_lib._export_tf_savedmodel
        export_lib._export_tf_savedmodel = lambda *a, **k: None
        try:
            export_cascade(
                trainer.model, state, cfg,
                os.path.join(publish_dir, "1"),
                tower_params=tower_params, index=index)
            export_lib.write_latest(publish_dir, "1")
        finally:
            export_lib._export_tf_savedmodel = orig
        rng = np.random.default_rng(5)
        hist_ids = rng.integers(1, FEATURE_SIZE, (HIST_LEN,)).astype(np.int32)
        hist_mask = np.ones((HIST_LEN,), np.float32)
        feat_ids = rng.integers(0, FEATURE_SIZE,
                                (FIELD_SIZE,)).astype(np.int32)
        feat_vals = rng.normal(size=(FIELD_SIZE,)).astype(np.float32)
        for fused in (False, True):
            eng = CascadeEngine(
                publish_dir, retrieve_k=8, max_batch=BATCH, fused=fused,
                watcher_kw={"poll_secs": 3600, "start": False})
            try:
                assert eng.current().hist_len == 0
                items, probs = eng.recommend(
                    hist_ids, hist_mask, feat_ids, feat_vals, k=4)
                assert items.shape == (4,) and probs.shape == (4,)
                assert np.all(np.isfinite(probs))
            finally:
                eng.close()


class TestFitHistory:
    def test_zero_hist_len_short_circuits(self):
        ids, mask = _fit_history(np.array([3, 4], np.int32),
                                 np.array([1, 1], np.float32), 0)
        assert ids.shape == (0,) and mask.shape == (0,)
        assert ids.dtype == np.int32 and mask.dtype == np.float32

    def test_pad_short_history(self):
        ids, mask = _fit_history(np.array([3, 4], np.int32),
                                 np.array([1, 1], np.float32), 5)
        np.testing.assert_array_equal(ids, [3, 4, 0, 0, 0])
        np.testing.assert_array_equal(mask, [1, 1, 0, 0, 0])

    def test_truncate_keeps_recent_tail(self):
        ids, mask = _fit_history(
            np.arange(1, 7, dtype=np.int32), np.ones((6,), np.float32), 4)
        np.testing.assert_array_equal(ids, [3, 4, 5, 6])
        np.testing.assert_array_equal(mask, [1, 1, 1, 1])

    def test_exact_length_passthrough(self):
        src = np.array([9, 8, 7], np.int32)
        ids, mask = _fit_history(src, np.ones((3,), np.float32), 3)
        np.testing.assert_array_equal(ids, src)
