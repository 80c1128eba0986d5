"""The delta-rule scan's Pallas kernels (``deepfm_tpu/ops/pallas_kda_scan.py``)
through the Pallas interpreter on the CPU, each comparison one jitted
program: output and all five inputs' gradients against the
position-at-a-time recurrence at float32, at the strongest and the weakest
decay the seeding draws, the timed cells' and one far steeper, even and
falling on a few positions, write strengths to 1 and to 2, one and several
heads a grid step, 2 and 5 chunks (the state carried forward and its
cotangent carried back across chunks: a chunk that hands on the state it was
given fails the same comparison); the bfloat16 band; the kernels under
``jax.checkpoint``; ``kimi_linear.kda_scan`` by the kernels beside its XLA
form; and ``kda_scan_by``'s table."""

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

from benchmark.reference_sdar_moe import leaf_gap  # noqa: E402
from deepfm_tpu.models import kimi_linear  # noqa: E402
from deepfm_tpu.ops import pallas_kda_scan  # noqa: E402
from test_kimi_linear_blocks import (by_position,  # noqa: E402
                                     recurrence_and_grads, scan_inputs)

F32 = jnp.dtype("float32")
CHUNK = pallas_kda_scan.CHUNK
#: (rate, step) of ``scan_inputs``: the seeding's strongest (-1.6 a position,
#: -102 a chunk) and weakest; what the timed cells' steps read at most
#: (``kda_chunk_log_decay_min`` -208 a chunk: -3.3 a position); and -8 a
#: position, -128 a sub-chunk of 16, past what a factor taken through a
#: sub-chunk's own reference could hold.
DECAYS = {"strongest": (16.0, 0.1), "weakest": (1.0, 0.001),
          "cells": (33.0, 0.1), "steepest": (80.0, 0.1)}
SCAN = functools.partial(pallas_kda_scan.kda_scan, cdt=F32, interpret=True)


def inputs(decay, beta_to, chunks, heads=2, dk=16):
    """``scan_inputs`` at ``chunks`` whole chunks, the write strength in
    (0, ``beta_to``)."""
    *rest, beta = scan_inputs(chunks * CHUNK, *DECAYS[decay], heads=heads,
                              dk=dk)
    return (*rest, beta_to * beta)


def kernel_and_grads(scan, w, *args):
    """``scan``'s output and, under the cotangent ``w``, every input's
    gradient."""
    def loss(*a):
        o = scan(*a)
        return jnp.sum(o * w), o
    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(*args)
    return o, grads


# (one program a shape: the cases of one shape share its compilation)
by_kernels = jax.jit(functools.partial(kernel_and_grads, SCAN))
by_recurrence = jax.jit(recurrence_and_grads)


def assert_matches(got, got_grads, want, want_grads):
    np.testing.assert_allclose(got, want, atol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-5 * max(
            1.0, float(jnp.abs(b).max())))


def assert_kernels_match_the_recurrence(args, w=None):
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape) \
        if w is None else w
    got, got_grads = by_kernels(w, *args)
    want, want_grads = by_recurrence(w, *args)
    assert_matches(got, got_grads, want, want_grads)
    return got_grads


@pytest.mark.parametrize("chunks, heads, dk", [
    (2, 3, 16), (2, 2, 16), (5, 6, 16), (5, 4, 16), (2, 1, 128)],
    ids=["2x1of3", "2x2of2", "5x2of6", "5x4of4", "2x128"])
@pytest.mark.parametrize("decay, beta_to", [
    ("strongest", 1.0), ("strongest", 2.0), ("weakest", 1.0),
    ("weakest", 2.0), ("cells", 2.0), ("steepest", 2.0)])
def test_kernels_match_the_recurrence(decay, beta_to, chunks, heads, dk):
    """Output and every input's gradient, float32 to 1e-5: a log-decay of
    -102 a chunk (past what ``exp`` of a chunk-wide difference holds), the
    weakest, the timed cells' -3.3 a position and -8 a position (-128 a
    sub-chunk: no factor goes through a reference, every exponent is a sum
    of log-decays); a transition whose eigenvalue along k reaches -1; 1, 2
    and 4 heads a grid step (``heads_a_step`` of 3, 2 and 6, 4 heads)."""
    assert_kernels_match_the_recurrence(
        inputs(decay, beta_to, chunks, heads=heads, dk=dk))


@pytest.mark.parametrize("drop", [30.0, 100.0])
def test_a_decay_that_falls_on_a_few_positions_is_held(drop):
    """The log-decay uneven over a sub-chunk, as a projection of x makes it:
    a fifth of the positions lose ``drop`` on a channel and the rest 0.05,
    so that sub-chunks' totals run from -0.8 to several times ``drop`` (past
    -64, where the kernels' first form flushed a channel's terms): the pairs
    between two such positions are the recurrence's all the same."""
    q, k, v, g, beta = inputs("weakest", 2.0, 2)
    falls = jax.random.bernoulli(jax.random.PRNGKey(3), 0.2, g.shape)
    g = jnp.where(falls, -drop, -0.05)
    sub_totals = g.reshape(*g.shape[:1], -1, pallas_kda_scan.SUB,
                           *g.shape[2:]).sum(2)
    assert float(sub_totals.min()) < -2 * drop
    assert float(sub_totals.max()) > -drop
    assert_kernels_match_the_recurrence((q, k, v, g, beta))


def test_a_chunk_that_hands_on_the_state_it_was_given_is_found(monkeypatch):
    """The comparison above resolves the carry: with the state left as it
    entered (every chunk starts from zero) the output past the first chunk
    and the gradients are off by far more than 1e-5."""
    whole = pallas_kda_scan._chunk

    def no_carry(q, k, v, g, beta, state, kept=None, *, cdt):
        o, _, inverse = whole(q, k, v, g, beta, state, kept, cdt=cdt)
        return o, state, inverse
    monkeypatch.setattr(pallas_kda_scan, "_chunk", no_carry)
    args = inputs("weakest", 1.0, 2)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    # (a program of its own: ``by_kernels`` holds the sound chunk's)
    got, got_grads = jax.jit(functools.partial(kernel_and_grads, SCAN))(
        w, *args)
    want, want_grads = by_recurrence(w, *args)
    np.testing.assert_allclose(got[:, :CHUNK], want[:, :CHUNK], atol=1e-5)
    assert float(jnp.abs(got - want).max()) > 1e-2
    with pytest.raises(AssertionError):
        assert_matches(got, got_grads, want, want_grads)


def test_a_cotangent_on_the_last_chunk_reaches_the_first():
    """The state's cotangent is carried back: with the output's cotangent on
    the last chunk alone, the first chunk's k, v, g and beta still get the
    recurrence's gradients, and they are not zero."""
    args = inputs("weakest", 2.0, 3)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    w = w.at[:, : 2 * CHUNK].set(0.0)
    got_grads = assert_kernels_match_the_recurrence(args, w)
    for grad in got_grads[1:]:
        assert float(jnp.abs(grad[:, :CHUNK]).max()) > 1e-4
    # q of an earlier chunk reads nothing the last chunk's output depends on
    assert float(jnp.abs(got_grads[0][:, : 2 * CHUNK]).max()) == 0.0


def test_bfloat16_operands_stay_within_their_band():
    """``test_kimi_linear_blocks``' band for the XLA form, held by the
    kernels: operands of the products with the state rounded to bfloat16
    move the output by under 2% of its size, and by more than float32's
    1e-5."""
    args = scan_inputs(128, 4.0, 0.01)
    want = by_position(*args)
    got = jax.jit(functools.partial(
        pallas_kda_scan.kda_scan, cdt=jnp.dtype("bfloat16"),
        interpret=True))(*args)
    gap = leaf_gap(got, want)
    assert 1e-4 < gap < 0.02, gap


def test_under_checkpoint_the_gradients_are_the_same():
    args = inputs("strongest", 2.0, 2)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    @jax.jit
    def both(*a):
        return (kernel_and_grads(SCAN, w, *a),
                kernel_and_grads(jax.checkpoint(SCAN), w, *a))
    (o, grads), (o_again, grads_again) = both(*args)
    np.testing.assert_array_equal(o, o_again)
    for a, b in zip(grads, grads_again):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("beta_to", [1.0, 2.0])
def test_the_models_scan_by_the_kernels_is_its_xla_form(beta_to):
    """``kimi_linear.kda_scan(by="kernel")``: the XLA form's output and
    gradients to 1e-5, and its count (the most negative whole-chunk
    log-decay) to the bit of a sum's order."""
    args = inputs("strongest", beta_to, 2)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def run(**how):
        def loss(*a):
            o, low = kimi_linear.kda_scan(*a, cdt=F32, **how)
            return jnp.sum(o * w), (o, low)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)

    (_, (got, low)), got_grads = jax.jit(
        run(by="kernel", interpret=True))(*args)
    (_, (want, want_low)), want_grads = jax.jit(run())(*args)
    assert_matches(got, got_grads, want, want_grads)
    assert float(low) == pytest.approx(float(want_low), rel=1e-6)
    assert float(low) < -88.0


def test_where_the_kernels_engage():
    by = kimi_linear.kda_scan_by
    assert by(8192, 128, backend="tpu") == "kernel"
    assert by(8192, 256, backend="tpu") == "kernel"
    # off a TPU (here), across data replicas, at ragged lengths and at heads
    # narrower than a line: the XLA form
    assert by(8192, 128) == "xla"
    assert by(100, 64, one_device=False) == "xla"
    assert by(8192, 128, one_device=False, backend="tpu") == "xla"
    assert by(8192 + 32, 128, backend="tpu") == "xla"
    assert by(8192, 64, backend="tpu") == "xla"
    assert kimi_linear.kda_scan_note("kernel") == "kernel chunk64"
    assert kimi_linear.kda_scan_note("xla") == "chunk64/sub16"
    assert [pallas_kda_scan.heads_a_step(h) for h in (8, 6, 4, 2, 3)] == [
        4, 2, 4, 2, 1]


def test_the_kernels_take_no_other_chunk():
    """``kimi_linear.kda_scan``'s ``chunk`` and ``sub`` are the XLA form's
    to vary; by the kernels any other than theirs is refused, not ignored."""
    args = inputs("weakest", 1.0, 2)
    kimi_linear.kda_scan(*args, cdt=F32, by="kernel", interpret=True)
    for other in ({"chunk": 32}, {"sub": 8}):
        with pytest.raises(AssertionError):
            kimi_linear.kda_scan(*args, cdt=F32, by="kernel", interpret=True,
                                 **other)
