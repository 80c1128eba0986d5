"""Hot/cold tiered embedding storage: correctness of the cache protocol.

The key claim is that tiering is INVISIBLE to the optimizer: with float32
cold storage, a tiered run's densified tables must be bit-identical to
the same run with the whole table device-resident (sparse mode) — the
evict/write-back/late-fetch/install machinery changes where rows live,
never their values. Fault healing must preserve that bit-exactness too.
"""

import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.data.hot_cold import ColdStore
from deepfm_tpu.train import Trainer
from deepfm_tpu.utils import faults

pytestmark = pytest.mark.embedding

V, B, F, NB = 500, 32, 6, 12
HOT = 256


def _cfg(**kw):
    base = dict(
        feature_size=V, field_size=F, embedding_size=8,
        deep_layers="16,8", dropout="1.0,1.0", batch_size=B,
        compute_dtype="float32", l2_reg=1e-4, learning_rate=1e-3,
        log_steps=0, seed=11, scale_lr_by_world=False,
        mesh_data=1, mesh_model=1, steps_per_loop=1,
        embedding_update="sparse")
    base.update(kw)
    return Config(**base)


def _batches(nb=NB, seed=3):
    rng = np.random.default_rng(seed)
    return [dict(
        feat_ids=rng.integers(0, V, size=(B, F)).astype(np.int32),
        feat_vals=rng.normal(size=(B, F)).astype(np.float32),
        label=rng.integers(0, 2, size=(B,)).astype(np.float32))
        for _ in range(nb)]


def _run(cfg, batches=None):
    tr = Trainer(cfg)
    state = tr.init_state()
    state, _ = tr.fit(state, batches if batches is not None else _batches())
    return tr, state


class TestColdStore:
    def test_float32_roundtrip_exact(self):
        rng = np.random.default_rng(0)
        a = rng.standard_normal((40, 8)).astype(np.float32)
        cs = ColdStore(a, "float32")
        np.testing.assert_array_equal(cs.fetch(np.arange(10, 20)), a[10:20])
        new = rng.standard_normal((5, 8)).astype(np.float32)
        cs.write(np.arange(5), new)
        np.testing.assert_array_equal(cs.fetch(np.arange(5)), new)
        np.testing.assert_array_equal(cs.dense()[20:], a[20:])

    def test_int8_roundtrip_within_quant_error(self):
        rng = np.random.default_rng(1)
        a = rng.standard_normal((40, 8)).astype(np.float32)
        cs = ColdStore(a, "int8")
        got = cs.fetch(np.arange(40))
        # Per-row symmetric quant: error bounded by scale/2 = max|row|/254.
        bound = (np.abs(a).max(axis=1, keepdims=True) / 254.0) + 1e-7
        assert (np.abs(got - a) <= bound).all()
        assert cs.nbytes() < a.nbytes / 2

    def test_int8_halves_weight_bytes(self):
        a = np.ones((1000, 8), np.float32)
        assert ColdStore(a, "int8").nbytes() <= a.nbytes / 2 + 4 * 1000

    def test_fp8_roundtrip_within_quant_error(self):
        rng = np.random.default_rng(2)
        a = rng.standard_normal((40, 8)).astype(np.float32)
        cs = ColdStore(a, "fp8_e4m3")
        got = cs.fetch(np.arange(40))
        # e4m3 keeps 3 mantissa bits: relative error <= 2^-4 per element
        # (plus a whisker of slack for the scale rounding).
        assert (np.abs(got - a) <= np.abs(a) * 0.0664 + 1e-6).all()
        assert cs.nbytes() < a.nbytes / 2

    def test_fp8_beats_int8_on_outlier_rows(self):
        # One large outlier per row: int8's fixed step (row-max/127)
        # flattens the small coordinates; fp8's relative precision keeps
        # them. This asymmetry is WHY the fp8 tier exists.
        rng = np.random.default_rng(3)
        a = rng.standard_normal((64, 8)).astype(np.float32) * 1e-3
        a[:, 0] = 100.0
        e_int8 = np.abs(ColdStore(a, "int8").dense() - a)[:, 1:].max()
        e_fp8 = np.abs(ColdStore(a, "fp8_e4m3").dense() - a)[:, 1:].max()
        assert e_fp8 < e_int8 / 10

    def test_fetch_write_reuse_scratch(self):
        """fetch/write run on every cache transaction: after warmup they
        must work out of per-store scratch (no fresh row-block allocation
        per call — fetch returns a view into the reused buffer)."""
        rng = np.random.default_rng(4)
        a = rng.standard_normal((64, 8)).astype(np.float32)
        for dt in ("float32", "int8", "fp8_e4m3"):
            cs = ColdStore(a, dt)
            out1 = cs.fetch(np.arange(4, 12))
            base = out1.base
            assert base is not None, dt  # a view, not a fresh array
            assert cs.fetch(np.arange(8)).base is base, dt
            assert cs.fetch(np.arange(3)).base is base, dt  # smaller reuses
            cs.write(np.arange(5), a[:5])
            if dt != "float32":
                w = cs._write_f32
                cs.write(np.arange(2, 7), a[2:7])
                assert cs._write_f32 is w, dt
        # Growth only on outsized requests, to the next power of two.
        cs = ColdStore(a, "float32")
        cs.fetch(np.arange(5))
        cap = cs._fetch_f32.shape[0]
        assert cap == 8
        cs.fetch(np.arange(20))
        assert cs._fetch_f32.shape[0] == 32


@pytest.fixture(scope="module")
def sparse_ref():
    """Plain (untiered) sparse run — the bit-exactness reference."""
    return _run(_cfg())


@pytest.fixture(scope="module")
def tiered_run():
    return _run(_cfg(embedding_tiering="hot_cold", embedding_hot_rows=HOT,
                     transfer_ahead=2))


class TestTieredParity:
    def test_densified_bit_identical_to_sparse(self, sparse_ref, tiered_run):
        _, s_ref = sparse_ref
        tr, s_t = tiered_run
        dense = tr._tier.densified(s_t)
        for n in ("fm_w", "fm_v"):
            np.testing.assert_array_equal(
                np.asarray(s_ref.params[n], np.float32),
                np.asarray(dense.params[n], np.float32))

    def test_evictions_actually_exercised(self, tiered_run):
        tr, _ = tiered_run
        st = tr._tier.stats
        assert st["plans"] == NB
        assert st["evictions"] > 0, "HOT too large: protocol not exercised"
        assert st["installs"] >= st["evictions"]
        assert 0.0 < tr._tier.hit_rate() < 1.0

    def test_eval_matches_untiered(self, sparse_ref, tiered_run):
        tr_ref, s_ref = sparse_ref
        tr, s_t = tiered_run
        ev_ref = tr_ref.evaluate(s_ref, _batches(4, seed=9))
        ev_t = tr.evaluate(s_t, _batches(4, seed=9))
        assert abs(ev_ref["loss"] - ev_t["loss"]) < 1e-6

    def test_int8_cold_within_tolerance(self, sparse_ref):
        _, s_ref = sparse_ref
        tr, s_q = _run(_cfg(embedding_tiering="hot_cold",
                            embedding_hot_rows=HOT, transfer_ahead=2,
                            embedding_cold_dtype="int8"))
        dense = tr._tier.densified(s_q)
        for n in ("fm_w", "fm_v"):
            d = np.abs(np.asarray(s_ref.params[n], np.float32)
                       - np.asarray(dense.params[n], np.float32)).max()
            assert d < 5e-2, (n, d)

    def test_fp8_cold_within_tolerance(self, sparse_ref):
        _, s_ref = sparse_ref
        tr, s_q = _run(_cfg(embedding_tiering="hot_cold",
                            embedding_hot_rows=HOT, transfer_ahead=2,
                            embedding_cold_dtype="fp8_e4m3"))
        dense = tr._tier.densified(s_q)
        for n in ("fm_w", "fm_v"):
            d = np.abs(np.asarray(s_ref.params[n], np.float32)
                       - np.asarray(dense.params[n], np.float32)).max()
            assert d < 5e-2, (n, d)

    def test_fused_install_matches_seed_install(self):
        """The fused install (one launch per table transaction) must be
        element-identical to the seed per-array ``_jit_install`` scatters
        — the property that keeps the tiered bit-parity pins above green
        with the kernels enabled."""
        import jax.numpy as jnp

        from deepfm_tpu.data import hot_cold as hc
        from deepfm_tpu.ops import pallas_embedding as pemb

        rng = np.random.default_rng(7)
        H, D, n, p = 16, 4, 5, 8
        w = jnp.asarray(rng.standard_normal((H, D)).astype(np.float32))
        m, v = w * 0.5, w * 0.25
        tau = jnp.asarray(rng.integers(0, 9, (H,)).astype(np.int32))
        slots = np.full((p,), H, np.int32)
        slots[:n] = rng.choice(H, n, replace=False)
        wv = np.zeros((p, D), np.float32)
        wv[:n] = rng.standard_normal((n, D))
        mv, vv = wv * 2.0, wv * 3.0
        tv = np.zeros((p,), np.int32)
        tv[:n] = 7
        got = pemb.install_rows(w, m, v, tau, jnp.asarray(slots),
                                wv, mv, vv, tv, mode="xla")
        assert got is not None
        ref = (hc._jit_install(w, slots, wv), hc._jit_install(m, slots, mv),
               hc._jit_install(v, slots, vv), hc._jit_install(tau, slots, tv))
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFaults:
    @pytest.mark.faults
    def test_cold_fetch_faults_heal_bit_exact(self, tiered_run):
        """Two injected cold-fetch failures: the runtime retries, and the
        healed run's tables are bit-identical to the unfaulted one."""
        tr_ref, s_ref = tiered_run
        faults.set_cold_fetch_plan(2)
        try:
            tr, s_f = _run(_cfg(embedding_tiering="hot_cold",
                                embedding_hot_rows=HOT, transfer_ahead=2))
        finally:
            faults.set_cold_fetch_plan(0)
        assert tr._tier.stats["fetch_retries"] == 2
        ref_dense = tr_ref._tier.densified(s_ref)
        got_dense = tr._tier.densified(s_f)
        for n in ("fm_w", "fm_v"):
            np.testing.assert_array_equal(
                np.asarray(ref_dense.params[n]),
                np.asarray(got_dense.params[n]))


class TestCapacity:
    def test_too_small_cache_raises(self):
        cfg = _cfg(embedding_tiering="hot_cold", embedding_hot_rows=16,
                   transfer_ahead=0)
        tr = Trainer(cfg)
        state = tr.init_state()
        with pytest.raises(RuntimeError, match="hot cache too small"):
            tr.fit(state, _batches(2))

    def test_config_rejects_tiering_without_sparse(self):
        with pytest.raises(ValueError, match="sparse"):
            _cfg(embedding_update="dense", embedding_tiering="hot_cold",
                 embedding_hot_rows=HOT)

    def test_config_rejects_hot_rows_out_of_range(self):
        with pytest.raises(ValueError, match="embedding_hot_rows"):
            _cfg(embedding_tiering="hot_cold", embedding_hot_rows=0)
        with pytest.raises(ValueError, match="embedding_hot_rows"):
            _cfg(embedding_tiering="hot_cold", embedding_hot_rows=V)


class TestInstallCompileCache:
    def test_install_cache_bounded_by_pow2_ladder(self):
        """Every transaction size from 1..MAX must funnel into at most
        log2(pow2(MAX)) + 1 compiled fused-install programs (the pow2
        padding ladder): unbounded per-size recompiles were the seed
        ``_jit_install``'s failure mode at scale."""
        import jax.numpy as jnp

        from deepfm_tpu.data.hot_cold import _pow2_pad
        from deepfm_tpu.ops import pallas_embedding as pemb

        pemb.install_cache_clear()
        H, D, max_n = 16, 4, 64
        w = jnp.zeros((H, D), jnp.float32)
        m, v = w, w
        tau = jnp.zeros((H,), jnp.int32)
        for n in range(1, max_n + 1):
            p = _pow2_pad(n)
            slots = jnp.full((p,), H, jnp.int32)  # all OOB: no-op install
            out = pemb.install_rows(
                w, m, v, tau, slots, jnp.zeros((p, D), jnp.float32),
                jnp.zeros((p, D), jnp.float32),
                jnp.zeros((p, D), jnp.float32),
                jnp.zeros((p,), jnp.int32), mode="xla")
            assert out is not None
        import math
        assert pemb.install_cache_size() <= math.log2(_pow2_pad(max_n)) + 1
