"""``--model solar_open2``'s own blocks at small widths on the CPU, beside the
decoders' contract in ``tests/test_solar_open2.py`` (a file of their own, so
that two workers hold what one held): the reference's broken models; the
chunked scan at write strengths near 2 with keys repeated (the eigenvalue -1
case) against the position-at-a-time recurrence; the mixer's doubled write
strength; the causal block kernel through the Pallas interpreter against the
chunked XLA path at a group of 8, its visited blocks and its notes; and the
whole model by that kernel."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import reference_solar_open2 as ref  # noqa: E402
from benchmark.reference_kimi_linear import kda_recurrence  # noqa: E402
from benchmark.reference_sdar_moe import leaf_gap  # noqa: E402
from deepfm_tpu.config import Config  # noqa: E402
from deepfm_tpu.models import (get_model, kimi_linear,  # noqa: E402
                               sdar_moe, solar_open2)
from deepfm_tpu.ops import block_attention  # noqa: E402
from test_solar_open2 import (B, KINDS, L, SIZES, V, config,  # noqa: E402
                              uncut)

F32 = jnp.dtype("float32")


@pytest.mark.parametrize("broken, moved", [
    ({"beta_scale": 1.0}, True), ({"gate": False}, True),
    ({"causal": False}, True), ({}, False)],
    ids=["beta-without-its-2", "gate-left-out", "mask-not-causal", "sound"])
def test_the_references_broken_models_differ_from_the_sound_one(broken, moved):
    """What the reference's own switches leave out moves its result: the
    three mechanisms this model adds are each in the mathematics."""
    lp = {**uncut(KINDS["gqa+moe"]), **uncut(KINDS["kda+moe"])}
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    kda_kw = {k: v for k, v in broken.items() if k == "beta_scale"}
    gqa_kw = {k: v for k, v in broken.items() if k != "beta_scale"}

    @jax.jit
    def mixers(x, lp):
        xn = ref.rms_norm(x, lp["norm1"], 1e-5)
        return (ref.kda(xn, lp, SIZES, **kda_kw)
                + ref.gqa(xn, lp, SIZES, **gqa_kw),
                ref.kda(xn, lp, SIZES) + ref.gqa(xn, lp, SIZES))

    with jax.default_matmul_precision("highest"):
        got, want = mixers(x, lp)
    assert (leaf_gap(got, want) > 0.05) == moved


def scan_inputs(length, near_two, heads=2, dk=16, seed=0):
    """q, k (unit), v, g, beta as the mixer hands them on. ``near_two``:
    write strengths 2 sigmoid(z) with z about 5 (1.98 to 2) and every key
    repeated once (positions 2i and 2i + 1 share it: the second write meets
    the eigenvalue 1 - beta = -1 along the key the first just wrote)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(y):
        return y / jnp.linalg.norm(y, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (B, length, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (B, length, heads, dk)))
    v = jax.random.normal(keys[2], (B, length, heads, dk))
    g = -0.04 * jax.random.uniform(keys[3], (B, length, heads, dk),
                                   minval=0.8, maxval=1.2)
    z = jax.random.normal(keys[4], (B, length, heads))
    if near_two:
        k = jnp.repeat(k[:, ::2], 2, axis=1)[:, :length]
        z = 5.0 + 0.5 * z
    return q, k, v, g, solar_open2.BETA_SCALE * jax.nn.sigmoid(z)


def by_position(q, k, v, g, beta):
    per_head = jax.vmap(kda_recurrence, in_axes=1, out_axes=1)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(per_head)(q, k, v, g, beta)


def recurrence_and_grads(w, *args):
    """``by_position``'s output and, under the cotangent ``w``, every
    input's gradient."""
    want, vjp = jax.vjp(by_position, *args)
    return want, vjp(w)


@pytest.mark.parametrize("near_two", [True, False],
                         ids=["near-2-keys-repeated", "over-0-to-2"])
@pytest.mark.parametrize("length, chunk, sub", [
    (256, 64, 16), (200, 64, 16), (64, 64, 64), (96, 32, 8)])
def test_chunked_scan_at_strengths_to_2_matches_the_recurrence(
        length, chunk, sub, near_two):
    """Output and every input's gradient over several chunks, float32: the
    chunk's unit lower-triangular system ``(I + Diag(beta) A) U = ...`` has
    twice Kimi-Linear's off-diagonal at beta -> 2 and its solved rows grow
    faster with the chunk; the 64-position chunk still agrees with the
    token-by-token recurrence to 2e-5 of the output's size (1e-5 at
    beta <= 1, ``tests/test_kimi_linear.py``)."""
    args = scan_inputs(length, near_two)
    assert float(args[4].max()) > (1.98 if near_two else 1.5)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def chunked(*a):
        o, _ = kimi_linear.kda_scan(*a, cdt=F32, chunk=chunk, sub=sub)
        return jnp.sum(o * w), o

    # (each side one program: op by op a case takes four times as long)
    (_, got), got_grads = jax.jit(jax.value_and_grad(
        chunked, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    want, want_grads = jax.jit(functools.partial(recurrence_and_grads, w))(
        *args)
    scale = max(1.0, float(jnp.abs(want).max()))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale)
    for a, b in zip(got_grads, want_grads):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=2e-5 * max(
            1.0, float(jnp.abs(b).max())))


def test_the_mixer_doubles_the_write_strength_and_counts_it():
    lp = uncut(KINDS["kda+moe"], heads=2)
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(2), (B, L, 32))
    kw = dict(head_dim=8, eps=1e-5, cdt=F32)
    one, counts_one = kimi_linear.kda_mixer(lp, x, **kw)
    two, counts_two = kimi_linear.kda_mixer(lp, x, beta_scale=2.0, **kw)
    assert set(counts_one) == {kimi_linear.DECAY_MIN}   # Kimi-Linear's step
    assert set(counts_two) == {kimi_linear.DECAY_MIN,
                               kimi_linear.BETA_OVER_ONE}
    beta = 2.0 * jax.nn.sigmoid(sdar_moe.rms_norm(
        x, lp["norm1"], 1e-5) @ lp["kda_w_b"])
    assert int(counts_two[kimi_linear.BETA_OVER_ONE]) == int(
        jnp.sum(beta > 1.0)) > 0
    assert leaf_gap(two, one) > 0.05
    with jax.default_matmul_precision("highest"):
        want = ref.kda(ref.rms_norm(x, lp["norm1"], 1e-5), lp, SIZES)
    np.testing.assert_allclose(two, want, atol=2e-5)


def _qkv(cdt, length, group=8, head_dim=128):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, length, group, head_dim), jnp.float32)
    k = jax.random.normal(keys[1], (1, length, 1, head_dim)).astype(cdt)
    v = jax.random.normal(keys[2], (1, length, 1, head_dim)).astype(cdt)
    w = jax.random.normal(keys[3], (1, length, group * head_dim))
    return q, k, v, w


@pytest.mark.parametrize("dtype, tol", [("float32", 1e-4),
                                        ("bfloat16", 2e-2)])
def test_causal_kernel_matches_the_chunked_xla_path(monkeypatch, dtype, tol):
    """``masked_scores`` under ``kimi_linear.causal`` by the kernel
    (forward, dq, dk/dv through the Pallas interpreter, blocks of 128)
    against the XLA path on the same q/k/v: 512 positions, 8 query heads on
    1 key/value head, head_dim 128; output and the gradients of q, k, v,
    within 1e-4 in float32 and within bfloat16's rounding of an operand
    (2^-8, through three products) under bfloat16."""
    monkeypatch.setattr(sdar_moe, "_scores_kernel", functools.partial(
        sdar_moe._scores_kernel, interpret=True, kernel_block=128))
    cdt = jnp.dtype(dtype)
    q, k, v, w = _qkv(cdt, 512)

    def value_and_grads(scores_by):
        def loss(q, k, v):
            out = sdar_moe.masked_scores(
                q, k, v, mask=kimi_linear.causal, cdt=cdt,
                scores_by=scores_by).astype(jnp.float32)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *(g.astype(jnp.float32) for g in grads))

    for got, want in zip(value_and_grads("kernel"), value_and_grads("xla")):
        assert leaf_gap(got, want) < tol


@pytest.mark.parametrize("seq, kernel_block", [(8192, 512), (1024, 128),
                                               (512, 512)])
def test_causal_forward_grid_visits_the_lower_triangle(seq, kernel_block):
    """n (n + 1) / 2 of the n^2 blocks: 136 of 256 blocks of 512 at 8,192
    positions, read from the kernel's own block table; one kernel a (mask
    key, shape)."""
    kernel = sdar_moe.attn_kernel(seq, kimi_linear.causal, 8, True,
                                  kernel_block)
    n = seq // kernel_block
    assert block_attention.visited_blocks(kernel, seq, kernel_block) == (
        n * (n + 1) // 2, n * n)
    if seq == 8192:
        assert n * (n + 1) // 2 == 136
    again = sdar_moe.ScoreMask(("causal",), lambda q, k: k <= q)
    assert again == kimi_linear.causal
    assert sdar_moe.attn_kernel(seq, again, 8, True, kernel_block) is kernel
    assert sdar_moe.attn_kernel(
        seq, sdar_moe.block_diffusion(seq // 2, 4), 8, True,
        kernel_block) is not kernel


def test_attn_notes_are_one_function_for_both_models():
    assert sdar_moe.attn_notes("xla", kimi_linear.causal, 1024, 8) == {
        "attn_scores": "xla"}
    assert sdar_moe.attn_notes("kernel", kimi_linear.causal, 1024, 8) == {
        "attn_scores": "kernel", "attn_score_blocks": "3/4"}
    sdar = get_model(Config(
        model="sdar_moe", feature_size=V, field_size=1, embedding_size=32,
        history_max_len=512, decoder_layers=1, attn_q_heads=4,
        attn_kv_heads=1, attn_head_dim=128, moe_experts=4, moe_top_k=1,
        moe_expert_width=8, moe_experts_held=4, moe_pair_capacity=8,
        batch_size=1, l2_reg=0.0))
    assert sdar._attn_notes("kernel", 1024, 512) == sdar_moe.attn_notes(
        "kernel", sdar_moe.block_diffusion(512, 4), 1024, 4)


def test_model_by_the_causal_kernel_takes_the_same_step(monkeypatch):
    """The full layer's scores by the block kernel (forced on through the
    Pallas interpreter at head_dim 128, run in blocks of 128) in the whole
    model:
    loss and every leaf's gradient against the XLA path's; the notes say the
    path and the visited blocks."""
    cfg = config(history_max_len=512, attn_head_dim=128, attn_q_heads=8,
                 attn_kv_heads=1, decoder_layers=2, attn_every=2,
                 moe_pair_capacity=2 * 512 * 2)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, V, (B, 512)).astype(np.int32))

    def grads():
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))

        def loss(p):
            per_seq, _ = model.per_example_loss(
                p, state, {"hist_ids": tokens}, train=True, rng=None)
            return jnp.mean(per_seq)
        # (jitted: op by op the interpreted kernels take most of a minute)
        return model, jax.jit(jax.value_and_grad(loss))(params)

    model, (want, want_g) = grads()
    assert model.step_notes["attn_scores"] == "xla"
    monkeypatch.setattr(sdar_moe, "_scores_kernel", functools.partial(
        sdar_moe._scores_kernel, interpret=True, kernel_block=128))
    monkeypatch.setattr(solar_open2, "attn_scores_by",
                        lambda seq, head_dim, one_device=True: "kernel")
    model, (got, got_g) = grads()
    assert model.step_notes["attn_scores"] == "kernel"
    assert model.step_notes["attn_score_blocks"] == "1/1"  # blocks of 512
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        assert leaf_gap(g, w) < 1e-4, jax.tree_util.keystr(path)
