"""``--model kimi_linear``'s own blocks at small widths on the CPU, beside the
decoders' contract in ``tests/test_kimi_linear.py`` (a file of their own, so
that two workers hold what one held): the chunked delta-rule scan against
the position-at-a-time recurrence (``benchmark/reference_kimi_linear.py``)
at chunk lengths that do and do not divide the sequence, at the strongest
and the weakest decay the seeding draws; the scan's bfloat16 band; the
convolution's first positions; the sigmoid router by hand; and the
contract's tests of the expert layers' rows, read through that file's
``SPEC``: pairs over a small buffer, and the whole model by the row kernels
against the XLA rows."""

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

from benchmark import reference_kimi_linear as ref  # noqa: E402
from benchmark.reference_sdar_moe import leaf_gap  # noqa: E402
from decoder_contract import RowKernels, SmallBuffer  # noqa: E402
from deepfm_tpu.models import kimi_linear, sdar_moe  # noqa: E402
from test_kimi_linear import Kimi  # noqa: E402

B = 2
F32 = jnp.dtype("float32")


def scan_inputs(length, rate, step, heads=2, dk=16, seed=0):
    """q, k (unit), v, g, beta as the mixer hands them on, the log-decay
    ``-rate * step`` a position on every channel but for a spread of 20%."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(y):
        return y / jnp.linalg.norm(y, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (B, length, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (B, length, heads, dk)))
    v = jax.random.normal(keys[2], (B, length, heads, dk))
    g = -rate * step * jax.random.uniform(
        keys[3], (B, length, heads, dk), minval=0.8, maxval=1.2)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, length, heads)))
    return q, k, v, g, beta


def by_position(q, k, v, g, beta):
    per_head = jax.vmap(ref.kda_recurrence, in_axes=1, out_axes=1)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(per_head)(q, k, v, g, beta)


def recurrence_and_grads(w, *args):
    """``by_position``'s output and, under the cotangent ``w``, every
    input's gradient."""
    want, vjp = jax.vjp(by_position, *args)
    return want, vjp(w)


@pytest.mark.parametrize("rate, step", [(16.0, 0.1), (1.0, 0.001)],
                         ids=["strongest", "weakest"])
@pytest.mark.parametrize("length, chunk, sub", [
    (128, 64, 16), (100, 64, 16), (37, 16, 4), (64, 64, 64), (96, 32, 8)])
def test_chunked_scan_matches_the_recurrence(length, chunk, sub, rate, step):
    """Output and every input's gradient, float32 to 1e-5: the strongest
    decay the seeding draws (rate 16, step 0.1: a log-decay of -1.6 a
    position, -102 a chunk of 64, past what ``exp`` of a chunk-wide
    difference holds in float32) and the weakest."""
    args = scan_inputs(length, rate, step)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def chunked(*a):
        o, low = kimi_linear.kda_scan(*a, cdt=F32, chunk=chunk, sub=sub)
        return jnp.sum(o * w), (o, low)

    # (each side one program: op by op a case takes four times as long)
    (_, (got, low)), got_grads = jax.jit(jax.value_and_grad(
        chunked, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    want, want_grads = jax.jit(functools.partial(recurrence_and_grads, w))(
        *args)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-5 * max(
            1.0, float(jnp.abs(b).max())))
    # the count: the most negative cumulative log-decay of a chunk
    whole = -(-length // chunk) * chunk
    g = np.zeros((B, whole, 2, 16), np.float32)
    g[:, :length] = args[3]
    assert float(low) == pytest.approx(
        g.reshape(B, -1, chunk, 2, 16).sum(axis=2).min(), rel=1e-5)
    if rate == 16.0 and chunk == 64:
        assert float(low) < -88.0       # float32's exp range, passed


def test_bfloat16_operands_stay_within_their_band():
    """With operands of the products with the state rounded to bfloat16
    (2^-8 an operand) the output moves by under 2% of its size, and by more
    than float32's 1e-5."""
    args = scan_inputs(128, 4.0, 0.01)
    want = by_position(*args)
    got, _ = kimi_linear.kda_scan(*args, cdt=jnp.dtype("bfloat16"))
    gap = leaf_gap(got, want)
    assert 1e-4 < gap < 0.02, gap


def test_convolution_reads_zeros_before_the_first_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    got = kimi_linear.causal_conv(x, w)
    np.testing.assert_allclose(got, ref.short_conv(x, w), atol=1e-6)
    # position 0 sees itself through the last tap alone, position 2 three
    np.testing.assert_allclose(got[0, 0], w[3] * x[0, 0], atol=1e-6)
    np.testing.assert_allclose(
        got[0, 2], w[3] * x[0, 2] + w[2] * x[0, 1] + w[1] * x[0, 0],
        atol=1e-6)
    np.testing.assert_allclose(
        got[0, 3], sum(w[j] * x[0, j] for j in range(4)), atol=1e-6)
    # and nothing of a later position
    moved = kimi_linear.causal_conv(x.at[0, 4].add(1.0), w)
    np.testing.assert_array_equal(moved[0, :4], got[0, :4])


def test_sigmoid_router_by_hand():
    x = jnp.eye(2, 4)                                 # two tokens
    router = jnp.array([[0.0, 1.0, 2.0, -1.0], [3.0, 1.0, 0.0, 3.0],
                        [0.0, 0.0, 0.0, 0.0], [0.0, 0.0, 0.0, 0.0]])
    by = functools.partial(sdar_moe.route, score=jax.nn.sigmoid, scale=2.446)
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))      # noqa: E731
    experts, weights, _ = by(x, router, 2)
    # token 1's experts 0 and 3 tie at sigmoid(3): the lower index first
    np.testing.assert_array_equal(experts, [[2, 1], [0, 3]])
    np.testing.assert_allclose(
        weights, [[2.446 * sig(2) / (sig(2) + sig(1)),
                   2.446 * sig(1) / (sig(2) + sig(1))], [1.223, 1.223]],
        rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 2.446, rtol=1e-6)
    # a bias moves the selection and not the weights' values
    bias = jnp.array([0.0, 0.0, -1.0, 0.5])
    experts_b, weights_b, moved = by(x, router, 2, bias=bias)
    np.testing.assert_array_equal(experts_b, [[3, 1], [3, 0]])
    assert int(moved) == 1      # token 0's; token 1 keeps its two, reordered
    np.testing.assert_allclose(
        weights_b[0], [2.446 * sig(-1) / (sig(1) + sig(-1)),
                       2.446 * sig(1) / (sig(1) + sig(-1))], rtol=1e-6)
    np.testing.assert_allclose(weights_b[1], [1.223, 1.223], rtol=1e-6)
    # the reference's dense weights say the same, tie and bias included
    sizes = {"top_k": 2, "route_scale": 2.446}
    for b_, (e_, w_) in ((None, (experts, weights)),
                         (bias, (experts_b, weights_b))):
        dense = np.asarray(ref.router_weights(x, router, sizes, bias=b_))
        assert (np.count_nonzero(dense, axis=-1) == 2).all()
        for t in range(2):
            np.testing.assert_allclose(dense[t, np.asarray(e_[t])], w_[t],
                                       rtol=1e-6)


# ------------------------------------------ the expert layers' rows, whole

class TestKimiLinearExpertRows(Kimi, SmallBuffer, RowKernels):

    def test_model_by_the_row_kernels_takes_the_same_step(self, monkeypatch,
                                                          pass_most):
        # the buffer has spare rows: nothing over it in either pass
        assert self.row_kernels_step(monkeypatch, pass_most) < 64
