"""Pallas fused-FM kernel numerics vs the plain-jnp oracle (interpret mode).

The compiled kernel runs only on TPU; these tests exercise the identical
kernel bodies through the Pallas interpreter on CPU, checking both the
forward value and the custom-VJP gradients against ``ops.fm`` /
``pallas_fm.reference_fm`` (the reference math at ``1-ps-cpu/...py:177-187``).
Gradients are taken through the same composition the model uses:
``xv = v * vals[..., None]`` built outside the kernel, so d(v)/d(vals)
flow via JAX's product rule plus the kernel's dxv.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.ops import fm as fm_ops
from deepfm_tpu.ops import pallas_fm


def _rand(b, f, k, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(b, f)).astype(np.float32)
    v = rng.normal(size=(b, f, k)).astype(np.float32)
    vals = rng.normal(size=(b, f)).astype(np.float32)
    return jnp.asarray(w), jnp.asarray(v), jnp.asarray(vals)


@pytest.mark.parametrize("b,f,k", [(8, 5, 4), (128, 39, 32), (200, 39, 32)])
def test_forward_matches_oracle(b, f, k):
    w, v, vals = _rand(b, f, k)
    xv = v * vals[..., None]
    got = pallas_fm.fused_fm(w, vals, xv, True)
    want = pallas_fm.reference_fm(w, vals, xv)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_forward_matches_fm_interaction():
    w, v, vals = _rand(64, 7, 8, seed=3)
    xv = v * vals[..., None]
    got = pallas_fm.fused_fm(w, vals, xv, True)
    want = jnp.sum(w * vals, axis=1) + fm_ops.fm_interaction(xv)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("b,f,k", [(16, 5, 4), (130, 11, 8)])
def test_gradients_match_oracle(b, f, k):
    w, v, vals = _rand(b, f, k, seed=7)

    def loss_pallas(w, v, vals):
        xv = v * vals[..., None]
        return jnp.sum(jnp.tanh(pallas_fm.fused_fm(w, vals, xv, True)))

    def loss_ref(w, v, vals):
        xv = v * vals[..., None]
        return jnp.sum(jnp.tanh(pallas_fm.reference_fm(w, vals, xv)))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(w, v, vals)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(w, v, vals)
    for got, want, name in zip(gp, gr, ("dw", "dv", "dvals")):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3,
                                   err_msg=name)


def test_batch_padding_exact():
    # b=1 forces maximal padding (127 pad rows): padded rows must not leak.
    w, v, vals = _rand(1, 39, 32, seed=11)
    xv = v * vals[..., None]
    got = pallas_fm.fused_fm(w, vals, xv, True)
    want = pallas_fm.reference_fm(w, vals, xv)
    assert got.shape == (1,)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_supported_gate():
    # On the CPU test environment the compiled path must be gated off.
    assert pallas_fm.supported() == (jax.default_backend() == "tpu")


def test_vmem_gate_blocks_oversized_shapes():
    # Reference shape fits at the full tile.
    assert pallas_fm._pick_block_b(39, 32) == 128
    # Wider fields shrink the tile instead of failing to compile.
    assert 0 < pallas_fm._pick_block_b(100, 32) < 128
    # Absurd shapes don't fit at any tile -> compiled path gated off.
    assert pallas_fm._pick_block_b(4096, 512) == 0
    assert not pallas_fm.supported(4096, 512)


def test_bf16_residuals_and_grad_dtypes():
    """bf16 inputs keep bf16 residuals/grads (ADVICE r1: the VJP used to
    save f32 copies, doubling residual HBM)."""
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=(16, 6)), jnp.bfloat16)
    vals = jnp.asarray(rng.normal(size=(16, 6)), jnp.bfloat16)
    xv = jnp.asarray(rng.normal(size=(16, 6, 8)), jnp.bfloat16)

    def loss(w, vals, xv):
        return jnp.sum(pallas_fm.fused_fm(w, vals, xv, True))

    dw, dvals, dxv = jax.grad(loss, argnums=(0, 1, 2))(w, vals, xv)
    assert dw.dtype == jnp.bfloat16
    assert dvals.dtype == jnp.bfloat16
    assert dxv.dtype == jnp.bfloat16

    def ref_loss(w, vals, xv):
        return jnp.sum(pallas_fm.reference_fm(w, vals, xv))

    rw, rvals, rxv = jax.grad(ref_loss, argnums=(0, 1, 2))(w, vals, xv)
    np.testing.assert_allclose(np.asarray(dxv, np.float32),
                               np.asarray(rxv, np.float32),
                               rtol=0.05, atol=0.05)
    np.testing.assert_allclose(np.asarray(dw, np.float32),
                               np.asarray(rw, np.float32),
                               rtol=0.05, atol=0.05)


def test_mesh_steps_trace_with_the_compiled_kernel(monkeypatch):
    """What a multi-chip TPU host traces: the FM block is the compiled Pallas
    kernel INSIDE the shard_map'd steps, whose check_vma refuses a
    pallas_call output that does not declare the mesh axes it varies over.
    The CPU mesh tests never see it (the kernel is gated to TPU), so report
    the backend as tpu and cross-lower the 2x2 train and predict steps."""
    from jax import export as jax_export

    from deepfm_tpu.config import Config
    from deepfm_tpu.train import Trainer
    from deepfm_tpu.train.loop import zero_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(feature_size=300, field_size=5, embedding_size=4,
                 deep_layers="8", batch_size=16, mesh_data=2, mesh_model=2,
                 steps_per_loop=2, log_steps=0)
    trainer = Trainer(cfg)
    state = trainer.init_state()
    batch = zero_batch(cfg.field_size, cfg.batch_size)
    for fn, args, kernels in (
            (trainer.multi_step,
             (state, trainer.put_superbatch([batch] * 2)), 2),   # fwd + bwd
            (trainer.predict_step, (state, trainer.put_batch(batch)), 1)):
        text = jax_export.export(fn, platforms=("tpu",))(*args).mlir_module()
        assert text.count("tpu_custom_call") == kernels
