"""The dense SwiGLU's first products kept for the backward pass
(``kimi_linear.MLP_KEPT``, ``sdar_moe.kept_by``, ``KimiLinear._keeps``), at
small widths on the CPU, each comparison one jitted program: a layer that
keeps them gives the output and every leaf's gradient of the layer that
keeps nothing (``mlp_``, ``shared_`` and ``phi4_flash.mlp``'s one-matrix
form; beside the scan kernels' ``KEPT`` through the Pallas interpreter),
and its backward pass holds no second first product; the rule's table; what
the rule is handed (the optimizer's copies, the parameters' bytes); which
layers keep and what ``step_notes`` says where the device's memory is
described; off a TPU nothing is kept and the step is the one without the
names. (The cells' own steps with the memory described:
``tests/test_tpu_compile_*.py``.)"""

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

from decoder_contract import all_eqns, off_one  # noqa: E402
from deepfm_tpu.models import get_model, kimi_linear, sdar_moe  # noqa: E402
from deepfm_tpu.train.optimizers import build_optimizer  # noqa: E402
import test_kimi_linear  # noqa: E402
import test_phi4_flash  # noqa: E402

B, L, D = 2, 24, 32
GB = 10 ** 9

#: form -> (the model's spec, the layer's kind, the first products' widths)
FORMS = {
    "mlp_": (test_kimi_linear.SPEC, ("mla", "mlp"), (64, 64)),
    "shared_": (test_kimi_linear.SPEC, ("mla", "moe"), (16, 16)),
    "one-matrix": (test_phi4_flash.SPEC, ("window_attention", "mlp"), (96,)),
}


def a_layer(form):
    spec, kind, widths = FORMS[form]
    model = get_model(spec.config())
    lp = off_one(jax.random.PRNGKey(4),
                 model._init_layer(jax.random.PRNGKey(3), *kind))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, L, D))
    return model, kind, lp, x, widths


def value_and_grads(model, kind, paths, keep, x, lp):
    """A layer's stream and, under a fixed cotangent, the gradient of the
    stream it was handed and of every leaf, through ``_run_layer``'s own
    ``jax.checkpoint``."""
    w = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def loss(x, lp):
        out = model._run_layer(0, kind, x, lp, {}, paths,
                               {kimi_linear.MLP_KEPT: keep})[0]
        return jnp.sum(out * w), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                         has_aux=True)(x, lp)
    return out, grads


def assert_same(got, want):
    """Streams (or losses) and gradients to float32's tolerance."""
    np.testing.assert_allclose(got[0], want[0], atol=1e-6, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=1e-5 * max(
            1.0, float(jnp.abs(b).max())))


def products_of(jaxpr, shape) -> int:
    """``dot_general``s of ``jaxpr`` (and of every jaxpr inside it) whose
    result has ``shape``."""
    return sum(eqn.primitive.name == "dot_general"
               and tuple(eqn.outvars[0].aval.shape) == tuple(shape)
               for eqn in all_eqns(jaxpr))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_layer_that_keeps_its_products_is_the_layer_that_keeps_nothing(
        form):
    """The same formulas on the same float32 arrays: the stream and every
    gradient to float32's tolerance, and of the first products' shape one
    product fewer a first product in the kept layer's forward and backward
    (the one the backward pass made again)."""
    model, kind, lp, x, widths = a_layer(form)
    run = functools.partial(value_and_grads, model, kind, {})
    assert_same(jax.jit(functools.partial(run, True))(x, lp),
                jax.jit(functools.partial(run, False))(x, lp))
    for width in set(widths):
        shape = (B, L, width)
        made = [products_of(jax.make_jaxpr(functools.partial(run, keep))(
            x, lp).jaxpr, shape) for keep in (False, True)]
        assert made[0] - made[1] == widths.count(width), (made, width)


def test_beside_the_scan_kernels_kept_the_gradients_are_the_same(monkeypatch):
    """A KDA layer whose scan is the kernels' (through the Pallas
    interpreter: heads of 128 lanes, one chunk of 64) keeps ``KEPT`` with
    and without ``MLP_KEPT`` beside it: one policy of both names."""
    monkeypatch.setattr(kimi_linear, "kda_scan", functools.partial(
        kimi_linear.kda_scan, interpret=True))
    model = get_model(test_kimi_linear.SPEC.config(
        kda_heads=1, kda_head_dim=128, history_max_len=64))
    lp = off_one(jax.random.PRNGKey(4),
                 model._init_layer(jax.random.PRNGKey(3), "kda", "mlp"))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, D))
    run = functools.partial(value_and_grads, model, ("kda", "mlp"),
                            {"scan_by": "kernel"})
    assert_same(jax.jit(functools.partial(run, True))(x, lp),
                jax.jit(functools.partial(run, False))(x, lp))
    jaxpr = jax.make_jaxpr(functools.partial(run, True))(x, lp).jaxpr
    assert products_of(jaxpr, (1, 64, 64)) == 3     # gate, up, d(mid)


RESERVE = sdar_moe.KEEP_RESERVE


@pytest.mark.parametrize("layer_bytes, room, kept", [
    ([4, 4, 4, 4], None, 0),            # no memory figure
    ([4, 4, 4, 4], 16, 4),              # room for all
    ([4, 4, 4, 4], 400, 4),
    ([4, 4, 4, 4], 14, 3),              # room for 3.5 layers' bytes
    ([4, 4, 4, 4], 3, 0),
    ([4, 4, 4, 4], -5, 0),              # the step itself does not fit
    ([0, 0], 100, 0),                   # bytes of zero
    ([], 100, 0),
    ([9, 1, 1, 1, 1], 4, 4),            # from the last layer back
    ([9, 1, 1, 1, 1], 12, 4),
    ([9, 1, 1, 1, 1], 13, 5),
], ids=str)
def test_the_rules_table(layer_bytes, room, kept):
    """``room`` is what the limit leaves beyond what the step holds and the
    reserve of its positions (None: the device said nothing)."""
    positions, held = 100, 7 * GB
    limit = 0 if room is None else held + RESERVE * positions + room
    assert sdar_moe.kept_by(layer_bytes, positions=positions,
                                   limit=limit, held=held) == kept


#: the readings the reserve was set from (``sdar_moe.KEEP_RESERVE``'s
#: comment; PERF.md section 6, PR 54): a stack's held bytes (16 a parameter,
#: and GLM's two head passes' second sequence), positions, the attention
#: layers' kept bytes, the SwiGLUs', and what the rule has to say of both
READINGS = {
    # Trinity-Mini: the dense layer's products give way to the kernels'
    "trinity": (11_287_588_864, 16384, [136_314_880] * 5,
                [805_306_368] + [134_217_728] * 4, (5, 4)),
    # Phi-4-flash: three attention layers, five of six products
    "phi4": (16 * 697_094_272, 8192, [85_196_800] * 3,
             [8192 * 2 * 10240 * 4] * 6, (3, 5)),
    # the same stack of eight layers (published 12-19): four attention
    # layers' kernel tensors and no product
    "phi4-eight": (16 * 915_352_576, 8192, [85_196_800] * 4,
                   [8192 * 2 * 10240 * 4] * 8, (4, 0)),
    # GLM-4.7-Flash: six blocks' kernel tensors, the five shared experts'
    "glm": (11_839_091_712, 16384, [41_943_040 + 327_680] * 6,
            [1_342_177_280] + [201_326_592] * 5, (6, 5)),
}


@pytest.mark.parametrize("stack", sorted(READINGS))
def test_the_reserve_holds_the_cells_steps(stack):
    """The constant against the compiled steps it was set from (a v5e's
    16.9 GB): the rule, attention first and the products in what is left,
    says of each stack what its compiled step was read with."""
    held, positions, attn, mlp, want = READINGS[stack]
    limit = 16_909_336_064
    kept = sdar_moe.kept_by(attn, positions=positions, limit=limit,
                            held=held)
    assert (kept, sdar_moe.kept_by(
        mlp, positions=positions, limit=limit,
        held=held + sum(attn[len(attn) - kept:]))) == want


@pytest.mark.parametrize("optimizer", sorted(sdar_moe.OPTIMIZER_COPIES))
def test_the_optimizers_copies_are_their_states(optimizer):
    """``OPTIMIZER_COPIES`` against ``build_optimizer``'s own state, and
    ``_held_bytes``: the parameters, those copies, one of gradients."""
    cfg = test_kimi_linear.SPEC.config(optimizer=optimizer)
    model = get_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))[0]
    own = sum(x.size for x in jax.tree.leaves(params))
    state = jax.eval_shape(build_optimizer(cfg).init, params)
    copies = sum(x.size for x in jax.tree.leaves(state) if x.ndim) / own
    assert copies == sdar_moe.OPTIMIZER_COPIES[optimizer]
    assert model._held_bytes(params, jnp.zeros((1, L), jnp.int32)) \
        == 4 * own * (2 + copies)


#: the whole models at two layers: Kimi-Linear's a dense layer and an expert
#: layer under the MLA mixer, Phi-4-flash's the two attention layers (the
#: scans' XLA forms compile for half a minute)
WHOLE = {"kimi": (test_kimi_linear.SPEC, dict(decoder_layers=2,
                                              attn_every=1)),
         "phi4": (test_phi4_flash.SPEC, dict(
             decoder_layers=2,
             layer_types="window_attention,full_attention"))}


def described(monkeypatch, limit):
    monkeypatch.setattr(sdar_moe, "device_memory_bytes", lambda: limit)


@pytest.mark.parametrize("which, spare, keeps, note", [
    ("kimi", None, "-----", "0/5"),
    ("kimi", 10 ** 6, "+++++", "5/5 layers 0.00 GB"),
    # 4 shared experts' products are 4 x 6,144 bytes, the dense MLP's 24,576
    ("kimi", 6144 * 4 + 24575, "-++++", "4/5 layers 0.00 GB"),
    ("kimi", 6144 * 2.5, "---++", "2/5 layers 0.00 GB"),
    ("kimi", -1, "-----", "0/5"),
    ("phi4", None, "------", "0/6"),
    ("phi4", 18432 * 6, "++++++", "6/6 layers 0.00 GB"),
    ("phi4", 18432 * 3.5, "---+++", "3/6 layers 0.00 GB"),
])
def test_which_layers_keep_where_the_memory_is_described(
        monkeypatch, which, spare, keeps, note):
    """``spare`` bytes beyond the held ones and the reserve (None: off a
    TPU, the CPU's own answer)."""
    model = get_model(WHOLE[which][0].config())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))[0]
    ids = jnp.zeros((B, L), jnp.int32)
    if spare is not None:
        described(monkeypatch, int(model._held_bytes(params, ids)
                                   + RESERVE * B * L + spare))
    got = model._keeps(params, ids, {})
    assert "".join("-+"[k[kimi_linear.MLP_KEPT]] for k in got) == keeps
    assert model.step_notes["mlp_kept"] == note


def test_a_backend_that_is_no_tpu_or_says_nothing_keeps_nothing(monkeypatch):
    assert sdar_moe.device_memory_bytes() == 0          # the CPU
    # a TPU backend whose device reports no limit (here: the CPU's None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sdar_moe.device_memory_bytes() == 0
    assert sdar_moe.kept_note(0, 4, 0) == "0/4"
    assert sdar_moe.kept_note(6, 6, 4_026_531_840) \
        == "6/6 layers 4.03 GB"


@pytest.mark.parametrize("which", sorted(WHOLE))
def test_the_model_whose_layers_all_keep_takes_the_same_step(monkeypatch,
                                                             which):
    """Loss and every leaf's gradient of the whole model with the memory
    described (every layer keeps) against the CPU's own (none does): the
    traced loss holds a first product fewer a layer's first product."""
    spec, flags = WHOLE[which]
    model = get_model(spec.config(**flags))
    params, state = model.init(jax.random.PRNGKey(2))
    params = off_one(jax.random.PRNGKey(5), params)
    batch = {"hist_ids": jnp.asarray(spec.sequences(B, 3))}

    def loss(p):
        per_seq, _ = model.per_example_loss(p, state, batch, train=True,
                                            rng=None)
        return jnp.mean(per_seq)

    want = jax.jit(jax.value_and_grad(loss))(params)
    off_tpu = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    assert model.step_notes["mlp_kept"].startswith("0/")
    described(monkeypatch, 16 * GB)
    got = jax.jit(jax.value_and_grad(loss))(params)
    kept = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    layers = len(model.kinds)
    assert model.step_notes["mlp_kept"].startswith(f"{layers}/{layers} ")
    assert_same(got, want)
    # the first products' shapes: Kimi-Linear's dense MLP (64) and its
    # shared expert (16) once each, two products each; Phi-4-flash's one
    # matrix (2 x 48) twice
    fewer = {"kimi": {64: 2, 16: 2}, "phi4": {96: 2}}[which]
    for width, n in fewer.items():
        assert products_of(off_tpu, (B, L, width)) \
            - products_of(kept, (B, L, width)) == n
