"""The head's loss (``sdar_moe.weighted_nll``) at small shapes on the CPU,
``HEAD_CHUNK`` set to 8: its gradients against ``jax.grad`` of the unchunked
float32 ``log_softmax`` form under cotangents that differ a sequence; the
loss against the parent's formula (PR 48's: ``jax.nn.log_softmax`` a chunk
under ``jax.checkpoint``) bit for bit; the products the traced forms hold (a
differentiated chunk three with a V-wide operand, an undifferentiated one
one, the form made again four); ``logits_of`` closing over traced parameters,
a ``stop_gradient`` inside it; the form picked by bytes and what
``step_notes`` says of it."""

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

from deepfm_tpu.models import get_model, sdar_moe  # noqa: E402
import test_glm4_moe_lite  # noqa: E402
import test_kimi_linear  # noqa: E402
import test_sdar_moe  # noqa: E402

D, V, CHUNK = 16, 40, 8
#: a sequence's cotangent: none is another's
COTANGENT = jnp.array([1.0, -2.0, 0.375])


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    monkeypatch.setattr(sdar_moe, "HEAD_CHUNK", CHUNK)


def logits(p, h, *, cdt=jnp.float32, tied=False):
    """Final norm and head product as the models make them; a tied head's
    matrix is the table's first V rows."""
    hn = sdar_moe.rms_norm(h, p["final_norm"], 1e-6)
    head = p["tok_emb"][:V].T if tied else p["head"]
    return sdar_moe._dot(hn, head, jnp.dtype(cdt))


def inputs(batch, length, tied=False, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = {"final_norm": 1 + 0.1 * jax.random.normal(k[0], (D,))}
    if tied:
        p["tok_emb"] = 0.3 * jax.random.normal(k[1], (V + 3, D))
    else:
        p["head"] = 0.3 * jax.random.normal(k[1], (D, V))
    h = jax.random.normal(k[2], (batch, length, D))
    labels = jax.random.randint(k[3], (batch, length), 0, V)
    # a third of the positions weigh nothing
    weight = jax.random.uniform(k[4], (batch, length), minval=0.5, maxval=2) \
        * (jax.random.uniform(k[5], (batch, length)) > 0.33)
    return p, h, labels, weight


def unchunked(logits_of, h, labels, weight):
    logp = jax.nn.log_softmax(logits_of(h).astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weight, axis=1)


def parents(logits_of, h, labels, weight):
    """``weighted_nll`` as the parent commit had it."""
    b, length, _ = h.shape
    chunk = CHUNK if length % CHUNK == 0 else length

    @jax.checkpoint
    def one_chunk(args):
        h_c, tok_c, w_c = args
        logp = jax.nn.log_softmax(logits_of(h_c), axis=-1)
        nll = -jnp.take_along_axis(logp, tok_c[..., None], axis=-1)
        return jnp.sum(nll[..., 0] * w_c, axis=1)

    def chunks(x):
        return jnp.moveaxis(
            x.reshape(b, length // chunk, chunk, *x.shape[2:]), 1, 0)

    return jnp.sum(jax.lax.map(one_chunk, (chunks(h), chunks(labels),
                                           chunks(weight))), axis=0)


def weighed(form, logits_of):
    """(p, h, weight) -> the sequences' sums under ``COTANGENT``."""
    def loss(p, h, labels, weight):
        sums = form(functools.partial(logits_of, p), h, labels, weight)
        return jnp.sum(COTANGENT[: h.shape[0]] * sums)
    return loss


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("cdt", ["float32", "bfloat16"])
@pytest.mark.parametrize("length", [24, 20], ids=["chunks3", "ragged"])
@pytest.mark.parametrize("batch", [1, 2, 3])
def test_gradients_under_cotangents_that_differ_a_sequence(
        batch, length, cdt, tied):
    """The stream's, the norm's, the head's (the table's, tied) and the
    weight's gradient against AD of the unchunked form."""
    p, h, labels, weight = inputs(batch, length, tied)
    logits_of = functools.partial(logits, cdt=cdt, tied=tied)
    got = jax.jit(jax.grad(weighed(sdar_moe.weighted_nll, logits_of),
                           argnums=(0, 1, 3)))(p, h, labels, weight)
    want = jax.jit(jax.grad(weighed(unchunked, logits_of),
                            argnums=(0, 1, 3)))(p, h, labels, weight)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    # (a bfloat16 operand's gradient is rounded to bfloat16 on its way back
    # through the cast, a chunk's here and the whole sequence's there)
    tol = 2e-5 if cdt == "float32" else 2.0 ** -7
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * float(jnp.max(jnp.abs(w))))
    if tied:        # the rows past the vocabulary are no head's
        assert not np.any(np.asarray(got[0]["tok_emb"][V:]))
    # a position that weighs nothing moves nothing
    assert not np.any(np.asarray(got[1])[np.asarray(weight) == 0])


@pytest.mark.parametrize("differentiated", [False, True],
                         ids=["plain", "differentiated"])
@pytest.mark.parametrize("cdt", ["float32", "bfloat16"])
@pytest.mark.parametrize("length", [24, 20], ids=["chunks3", "ragged"])
@pytest.mark.parametrize("batch", [1, 3])
def test_the_loss_is_the_parents_to_the_bit(batch, length, cdt,
                                            differentiated):
    """Operation by operation (``jax.disable_jit``): the formula rounds as
    the parent's does. (A compiled program rounds as its fusions do: the two
    differ there by a last bit here and there.)"""
    p, h, labels, weight = inputs(batch, length, seed=1)
    logits_of = functools.partial(logits, cdt=cdt)

    def value(form):
        def sums(p):
            out = form(functools.partial(logits_of, p), h, labels, weight)
            return jnp.sum(out), out
        if differentiated:
            return jax.value_and_grad(sums, has_aux=True)(p)[0][1]
        return sums(p)[1]

    with jax.disable_jit():
        np.testing.assert_array_equal(value(sdar_moe.weighted_nll),
                                      value(parents))
    jitted = jax.jit(value, static_argnums=0)
    np.testing.assert_allclose(jitted(sdar_moe.weighted_nll),
                               jitted(parents), rtol=3e-7)


def wide_products(jaxpr, in_loop=False):
    """(``dot_general``s with a V-wide operand or result inside the
    jaxpr's loops, those outside them)."""
    inside = outside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                V in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            inside, outside = inside + in_loop, outside + (not in_loop)
        loop = in_loop or eqn.primitive.name in ("scan", "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            a, b = wide_products(sub, loop)
            inside, outside = inside + a, outside + b
    return inside, outside


@pytest.mark.parametrize("batch", [1, 2])
def test_a_differentiated_chunk_holds_three_products_and_a_plain_one_one(
        batch):
    """The chunk's loop is traced once: its products are a chunk's. The
    backward rule makes none."""
    p, h, labels, weight = inputs(batch, 24)
    loss = weighed(sdar_moe.weighted_nll, logits)
    assert wide_products(jax.make_jaxpr(loss)(
        p, h, labels, weight).jaxpr) == (1, 0)
    assert wide_products(jax.make_jaxpr(jax.value_and_grad(
        loss, argnums=(0, 1)))(p, h, labels, weight).jaxpr) == (3, 0)
    # the parent's: the chunk, the chunk again, its two gradients
    assert wide_products(jax.make_jaxpr(jax.value_and_grad(
        weighed(parents, logits), argnums=(0, 1)))(
            p, h, labels, weight).jaxpr) == (4, 0)


@pytest.mark.parametrize("order", ["jit(grad)", "grad(jit)"])
def test_logits_of_may_close_over_traced_parameters(order):
    """... and a ``stop_gradient`` inside it leaves that leaf's gradient
    zero and the others' as they were."""
    p, h, labels, weight = inputs(2, 24)

    def stopped(p, h):
        return logits({**p, "head": jax.lax.stop_gradient(p["head"])}, h)

    def grads(logits_of):
        loss = weighed(sdar_moe.weighted_nll, logits_of)
        if order == "jit(grad)":
            return jax.jit(jax.grad(loss, argnums=(0, 1)))(
                p, h, labels, weight)
        return jax.grad(jax.jit(loss), argnums=(0, 1))(p, h, labels, weight)

    whole, cut = grads(logits), grads(stopped)
    assert np.any(np.asarray(whole[0]["head"]))
    assert not np.any(np.asarray(cut[0]["head"]))
    np.testing.assert_array_equal(cut[0]["final_norm"],
                                  whole[0]["final_norm"])
    np.testing.assert_array_equal(cut[1], whole[1])


def test_the_unwrapped_form_has_no_scope_of_its_own():
    """``glm4_moe_lite`` calls it under ``mtp_head``."""
    p, h, labels, weight = inputs(1, 24)
    np.testing.assert_array_equal(
        sdar_moe.weighted_nll.__wrapped__(
            functools.partial(logits, p), h, labels, weight),
        sdar_moe.weighted_nll(functools.partial(logits, p), h, labels,
                              weight))


GB = 10 ** 9


@pytest.mark.parametrize("batch, param_bytes, limit, by", [
    (2, 317 * 10 ** 6, 0, "forward"),           # nothing known of the device
    (64, 317 * 10 ** 6, 0, "forward"),
    (1, 403 * 10 ** 6, 1, "forward"),           # one sequence: nothing more
    (2, 317 * 10 ** 6, 16_909_336_064, "forward"),      # GLM-4.7-Flash
    (3, 189 * 10 ** 6, 16_909_336_064, "forward"),
    (4, 317 * 10 ** 6, 16_909_336_064, "forward"),      # 0.95 of 1.06 GB
    (5, 317 * 10 ** 6, 16_909_336_064, "recomputed"),   # 1.27
    (2, 16 * GB // 16 + 1, 16 * GB, "recomputed"),
    (2, 16 * GB // 16, 16 * GB, "forward"),
], ids=str)
def test_the_forms_rule(batch, param_bytes, limit, by):
    assert sdar_moe.head_grad_by(batch, param_bytes, limit) == by
    # a step across data replicas makes the chunk again whatever fits
    assert sdar_moe.head_grad_by(batch, param_bytes, limit,
                                 one_device=False) == "recomputed"
    assert sdar_moe.head_grad_note(by, batch, param_bytes) == (
        "recomputed" if by == "recomputed" else
        "forward 3 products/chunk, %.2f GB kept" % (
            batch * param_bytes / 1e9))


#: float32 bytes of what ``logits`` reads: the norm's gains and the matrix
READ = 4 * (D + D * V)


@pytest.mark.parametrize("batch, spare, products", [
    (1, -READ, (3, 0)),                 # one sequence keeps nothing more
    (2, 0, (3, 0)),
    (2, -1, (4, 0)),
    (3, -1, (4, 0)),
    (3, 0, (3, 0)),
], ids=str)
def test_the_form_is_picked_by_bytes(monkeypatch, batch, spare, products):
    """``spare``: bytes the described memory has beyond ``HEAD_KEPT_DIVISOR``
    times what the sequences keep beyond their sum. The form made again
    gives the same sums and gradients."""
    p, h, labels, weight = inputs(batch, 24)

    def loss():     # (a new function a call: a traced one is remembered)
        return jax.value_and_grad(weighed(sdar_moe.weighted_nll, logits),
                                  argnums=(0, 1))

    want = jax.jit(loss())(p, h, labels, weight)
    monkeypatch.setattr(
        sdar_moe, "device_memory_bytes",
        lambda: sdar_moe.HEAD_KEPT_DIVISOR * (batch - 1) * READ + spare)
    assert wide_products(jax.make_jaxpr(loss())(
        p, h, labels, weight).jaxpr) == products
    got = jax.jit(loss())(p, h, labels, weight)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-6)


def traced_notes(spec, batch):
    """(the model, its parameters' shapes, what it says of the traced loss
    of ``batch`` sequences)"""
    model = get_model(spec.config())
    params, state = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(spec.sequences(batch, 3))
    jax.eval_shape(
        lambda p: model.per_example_loss(
            p, state, {"hist_ids": tokens}, train=True,
            rng=jax.random.PRNGKey(1))[0], params)
    return model, params, dict(model.step_notes)


@pytest.mark.parametrize("spec, passes", [
    (test_sdar_moe.SPEC, ["head_grad"]),
    (test_kimi_linear.SPEC, ["head_grad"]),
    (test_glm4_moe_lite.SPEC, ["head_grad", "mtp_head_grad"]),
], ids=["sdar_moe", "kimi_linear", "glm4_moe_lite"])
def test_the_step_notes_say_each_head_pass(monkeypatch, spec, passes):
    model, params, notes = traced_notes(spec, 2)
    read = 4 * (params["final_norm"].size + params["head"].size)
    for name in passes:
        assert notes[name] == "forward 3 products/chunk, %.2f GB kept" % (
            2 * read / 1e9)
    assert [k for k in sorted(notes) if k.endswith("head_grad")] == passes
    monkeypatch.setattr(sdar_moe, "device_memory_bytes",
                        lambda: sdar_moe.HEAD_KEPT_DIVISOR * read - 1)
    notes = traced_notes(spec, 2)[2]
    assert [notes[name] for name in passes] == ["recomputed"] * len(passes)


@pytest.mark.parametrize("spec, passes", [
    (test_kimi_linear.SPEC, 1), (test_glm4_moe_lite.SPEC, 2),
], ids=["kimi_linear", "glm4_moe_lite"])
def test_the_held_bytes_count_what_the_head_passes_keep(monkeypatch, spec,
                                                        passes):
    """``KimiLinear._held_bytes``: a sequence's copy of what each pass
    reads, for every sequence beyond the first, where the pass keeps
    them."""
    model, params, _ = traced_notes(spec, 1)
    read = 4 * (params["final_norm"].size + params["head"].size)

    def held(batch):
        return model._held_bytes(params, jnp.zeros((batch, 8), jnp.int32))

    assert held(3) == held(1) + passes * 2 * read
    monkeypatch.setattr(sdar_moe, "device_memory_bytes",
                        lambda: sdar_moe.HEAD_KEPT_DIVISOR * read)
    assert held(2) == held(1) + passes * read
    assert held(3) == held(1)           # made again: nothing more
