"""Scaling & overlap suite (TUNING §2.13): gradient accumulation parity,
double-buffered device staging, hierarchical cross-host reduction.

Trajectory contracts pinned here:

- ``--grad_accum_steps k`` applies the optimizer once per k microbatches
  and is numerically the single big-batch step over the concatenated
  microbatches (equal microbatch sizes => mean-of-means == global mean);
  parity is pinned within float-reassociation tolerance for dense AND
  sparse embedding updates. k=1 compiles the exact seed program.
- ``--staging_buffers`` is purely a transfer-scheduling knob: the
  trajectory is BIT-identical across 1 and 2 slots.
- ``mesh.hierarchical_psum`` (intra-host then inter-host grouped psums)
  equals the flat psum on the virtual mesh to reassociation error
  (1-2 ULP), and the hierarchical trainer path keeps every device's
  param copy bit-identical while tracking the single-device trajectory
  (the ground truth for synchronized data parallelism).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deepfm_tpu.config import Config
from deepfm_tpu.parallel import mesh as mesh_lib
from deepfm_tpu.train import Trainer
from deepfm_tpu.train.loop import _StagingRing, _staged_size

# 2x2 virtual topology over the first 4 of conftest's 8 devices: rows
# {0,1} and {2,3} play "hosts", stage 2 reduces one representative per
# "host" ({0,2} and {1,3}).
HIER_GROUPS = ([[0, 1], [2, 3]], [[0, 2], [1, 3]])


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


def _batches(n, bs, fields=6, seed=3, feature_size=500):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        out.append({
            "feat_ids": rng.randint(
                0, feature_size, (bs, fields)).astype(np.int32),
            "feat_vals": rng.rand(bs, fields).astype(np.float32),
            "label": (rng.rand(bs, 1) < 0.3).astype(np.float32),
        })
    return out


def _leaves(state):
    return jax.tree.leaves(jax.tree.map(np.asarray, state.params))


def _fit(cfg, batches, **kw):
    tr = Trainer(cfg)
    state = tr.init_state()
    state, out = tr.fit(state, iter(batches), **kw)
    return tr, state, out


class TestGradAccumParity:
    """k microbatches + one apply == one big-batch step (k*B examples)."""

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_dense(self, k):
        micro = _batches(4, 64)
        _, st_a, out_a = _fit(
            _cfg(grad_accum_steps=k, steps_per_loop=4, transfer_ahead=0),
            micro)
        assert out_a["steps"] == 4
        big = [{key: np.concatenate([m[key] for m in micro[i:i + k]])
                for key in micro[0]} for i in range(0, 4, k)]
        _, st_b, _ = _fit(
            _cfg(batch_size=64 * k, steps_per_loop=4 // k,
                 transfer_ahead=0), big)
        # state.step counts microbatches on both sides (resume invariant).
        assert int(st_a.step) == 4
        for la, lb in zip(_leaves(st_a), _leaves(st_b)):
            if k == 1:
                # a==1 compiles the seed program: bit-identical.
                np.testing.assert_array_equal(la, lb)
            else:
                np.testing.assert_allclose(la, lb, rtol=2e-5, atol=1e-6)

    @pytest.mark.embedding
    @pytest.mark.parametrize("k", [2, 4])
    def test_sparse(self, k):
        micro = _batches(4, 64)
        tr_a, st_a, _ = _fit(
            _cfg(grad_accum_steps=k, steps_per_loop=4, transfer_ahead=0,
                 embedding_update="sparse"), micro)
        # Adam count semantics: one optimizer apply per k microbatches.
        assert int(st_a.opt_state["count"]) == 4 // k
        big = [{key: np.concatenate([m[key] for m in micro[i:i + k]])
                for key in micro[0]} for i in range(0, 4, k)]
        _, st_b, _ = _fit(
            _cfg(batch_size=64 * k, steps_per_loop=4 // k,
                 transfer_ahead=0, embedding_update="sparse"), big)
        for la, lb in zip(_leaves(st_a), _leaves(st_b)):
            np.testing.assert_allclose(la, lb, rtol=2e-5, atol=1e-6)

    @pytest.mark.parametrize("k", [1, 2])
    def test_history_model(self, k):
        # Accumulation composes with sequence models: the scanned
        # microbatch body forwards hist_ids/hist_mask like any other
        # column (no exclusion), and k microbatches still equal the
        # concatenated big batch on a DIN trajectory.
        hist = 4
        cfg_kw = dict(model="din", history_max_len=hist, field_size=5,
                      feature_size=100, deep_layers="8,4",
                      transfer_ahead=0)
        rng = np.random.default_rng(7)
        micro = []
        for _ in range(4):
            lens = rng.integers(1, hist + 1, size=64)
            micro.append({
                "feat_ids": rng.integers(
                    0, 100, size=(64, 5)).astype(np.int32),
                "feat_vals": rng.normal(size=(64, 5)).astype(np.float32),
                "label": (rng.random((64, 1)) < 0.3).astype(np.float32),
                "hist_ids": rng.integers(
                    1, 100, size=(64, hist)).astype(np.int32),
                "hist_mask": (np.arange(hist)[None, :]
                              < lens[:, None]).astype(np.float32),
            })
        _, st_a, out_a = _fit(
            _cfg(grad_accum_steps=k, steps_per_loop=4, **cfg_kw), micro)
        assert out_a["steps"] == 4 and np.isfinite(out_a["loss"])
        big = [{key: np.concatenate([m[key] for m in micro[i:i + k]])
                for key in micro[0]} for i in range(0, 4, k)]
        _, st_b, _ = _fit(
            _cfg(batch_size=64 * k, steps_per_loop=4 // k, **cfg_kw), big)
        for la, lb in zip(_leaves(st_a), _leaves(st_b)):
            if k == 1:
                np.testing.assert_array_equal(la, lb)
            else:
                # atol covers the attention output bias: its gradient is
                # ~0 so Adam's m/sqrt(v) amplifies reassociation noise on
                # a ~4e-5 value; every other leaf matches to <4e-8.
                np.testing.assert_allclose(la, lb, rtol=2e-5, atol=5e-5)

    def test_two_virtual_device_smoke(self):
        # Fast tier-1 smoke: accumulation under a 2-device data mesh —
        # scanned microbatches, one collective apply per pair, bookkeeping
        # surfaced through fit's output.
        _, st, out = _fit(
            _cfg(mesh_data=2, grad_accum_steps=2, steps_per_loop=4),
            _batches(4, 64))
        assert out["steps"] == 4 and int(st.step) == 4
        assert np.isfinite(out["loss"])
        assert out["collective_applies"] == 2.0
        assert out["collective_bytes"] > 0
        assert out["collective_strategy"] == "flat"

    def test_config_validation(self):
        with pytest.raises(ValueError):
            _cfg(grad_accum_steps=3, steps_per_loop=4)
        with pytest.raises(ValueError):
            _cfg(grad_accum_steps=0)
        with pytest.raises(ValueError):
            _cfg(staging_buffers=3)


class TestDoubleBufferedStaging:
    def test_bit_identity_across_slot_counts(self):
        outs = {}
        for buffers in (1, 2):
            outs[buffers] = _fit(
                _cfg(staging_buffers=buffers, steps_per_loop=2,
                     transfer_ahead=2), _batches(6, 64))
        s1, o1 = outs[1][1], outs[1][2]
        s2, o2 = outs[2][1], outs[2][2]
        for la, lb in zip(_leaves(s1), _leaves(s2)):
            np.testing.assert_array_equal(la, lb)
        assert o1["loss"] == o2["loss"] and o1["steps"] == o2["steps"] == 6

    def test_ring_fences(self):
        ring = _StagingRing(2)
        for i in range(4):
            assert ring.put(lambda i=i: i) == i
            ring.retire(jnp.zeros(()))
        assert ring.staged == ring.dispatched == 4
        # Transfers 3 and 4 each consumed one fence (dispatches 1 and 2);
        # the first two slots were free.
        assert ring._fences.qsize() == 2
        ring.close()
        # A closed ring never strands the staging thread on a fence.
        drained = _StagingRing(1)
        drained.put(lambda: 0)
        drained.close()
        assert drained.put(lambda: 1) == 1

    def test_staged_size(self):
        b = _batches(1, 16)[0]
        nbytes = sum(v.nbytes for v in b.values())
        assert _staged_size((b,)) == (16, nbytes)
        assert _staged_size(([b, b],)) == (32, 2 * nbytes)
        assert _staged_size((np.zeros(3), 2)) == (0, 0)


class TestHierarchicalReduction:
    def test_psum_equals_flat(self):
        # Two-stage grouped psum == flat psum on the 2x2 virtual mesh.
        # Same terms, reassociated by group — XLA compiles the two
        # programs with different reduction orders, so equality is to
        # 1-2 ULP, not bitwise (the same environmental property the
        # mesh_bitexact probe gates).
        devs = np.asarray(jax.devices()[:4]).reshape(4, 1)
        mesh = Mesh(devs, ("data", "model"))
        rng = np.random.RandomState(0)
        tree = {"a": rng.standard_normal((4, 32)).astype(np.float32),
                "b": rng.standard_normal((4, 7, 3)).astype(np.float32)}

        from jax import shard_map

        def flat(t):
            return jax.tree.map(
                lambda x: jax.lax.psum(x, "data"), t)

        def hier(t):
            return mesh_lib.hierarchical_psum(t, "data", HIER_GROUPS)

        specs = jax.tree.map(lambda _: P("data"), tree)
        kw = dict(mesh=mesh, in_specs=(specs,), out_specs=specs,
                  check_vma=False)
        out_f = jax.jit(shard_map(flat, **kw))(tree)
        out_h = jax.jit(shard_map(hier, **kw))(tree)
        for a, b in zip(jax.tree.leaves(out_f), jax.tree.leaves(out_h)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)

    def test_host_groups_single_host_is_none(self):
        # Auto-detect must stay off on a single host: the two-stage
        # program only pays off across a real DCN boundary.
        tr = Trainer(_cfg(mesh_data=4))
        assert mesh_lib.data_axis_host_groups(tr.mesh_info) is None
        assert tr._hier_groups is None

    def test_trainer_hier_keeps_devices_synchronized(self):
        # The property the two-stage reduce actually guarantees: after the
        # explicit grouped psums, every device applies the SAME gradient,
        # so the "replicated" params stay bit-identical across devices.
        tr = Trainer(_cfg(mesh_data=4))
        tr._hier_groups = HIER_GROUPS  # test seam: force the 2x2 program
        st = tr.init_state()
        st, out = tr.fit(st, iter(_batches(6, 64)), max_steps=6)
        assert out["collective_strategy"] == "hierarchical"
        for name in ("fm_w", "fm_v", "fm_b"):
            shards = [np.asarray(s.data)
                      for s in st.params[name].addressable_shards]
            assert len(shards) == 4
            for s in shards[1:]:
                np.testing.assert_array_equal(shards[0], s)

    def test_trainer_hier_matches_single_device(self):
        # Single-device training is the ground truth for synchronized
        # data parallelism; the hierarchical path must track it within
        # reassociation tolerance (mean-of-per-shard-means vs flat mean).
        tr_h = Trainer(_cfg(mesh_data=4))
        tr_h._hier_groups = HIER_GROUPS
        st_h = tr_h.init_state()
        st_h, out_h = tr_h.fit(st_h, iter(_batches(6, 64)), max_steps=6)

        _, st_1, _ = _fit(_cfg(), _batches(6, 64), max_steps=6)
        for la, lb in zip(_leaves(st_h), _leaves(st_1)):
            np.testing.assert_allclose(la, lb, rtol=5e-3, atol=2e-4)

    @pytest.mark.mesh_bitexact
    def test_trainer_hier_matches_flat_mesh(self):
        # On backends whose mesh numerics are bit-stable (probe-gated),
        # the flat psum path and the two-stage path are the same sum
        # reassociated — trajectories must agree within tolerance.
        tr_h = Trainer(_cfg(mesh_data=4))
        tr_h._hier_groups = HIER_GROUPS
        st_h = tr_h.init_state()
        st_h, _ = tr_h.fit(st_h, iter(_batches(6, 64)), max_steps=6)

        _, st_f, out_f = _fit(_cfg(mesh_data=4), _batches(6, 64),
                              max_steps=6)
        assert out_f["collective_strategy"] == "flat"
        for la, lb in zip(_leaves(st_h), _leaves(st_f)):
            np.testing.assert_allclose(la, lb, rtol=5e-3, atol=2e-4)

    def test_collective_bytes_strategy_invariant(self):
        # The payload is a property of the model + mesh, not of the
        # reduction schedule: flat and hierarchical runs report the same
        # bytes for the same number of applies.
        _, _, out_f = _fit(_cfg(mesh_data=4), _batches(4, 64), max_steps=4)
        tr_h = Trainer(_cfg(mesh_data=4))
        tr_h._hier_groups = HIER_GROUPS
        st_h = tr_h.init_state()
        st_h, out_h = tr_h.fit(st_h, iter(_batches(4, 64)), max_steps=4)
        assert out_f["collective_bytes"] == out_h["collective_bytes"] > 0
        assert out_f["collective_applies"] == out_h["collective_applies"]

    def test_grad_payload_bytes_model_sharding(self):
        params = {"emb_w": jnp.zeros((100, 8), jnp.float32),
                  "tower": {"w": jnp.zeros((48, 16), jnp.float32)}}
        full = mesh_lib.grad_payload_bytes(params, ("emb_w",), model_size=1)
        half = mesh_lib.grad_payload_bytes(params, ("emb_w",), model_size=2)
        assert full == 100 * 8 * 4 + 48 * 16 * 4
        assert half == 100 * 8 * 4 // 2 + 48 * 16 * 4
