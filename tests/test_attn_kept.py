"""The block kernel's output and log-sum-exp kept across a layer's
recomputation (``ops/block_attention.KEPT``; ``SdarMoE._attn_keep``,
``KimiLinear._keeps``), at small widths on the CPU with the kernels through
the Pallas interpreter (one block of 128 a query head): at each of the three
places a layer is checkpointed, the differentiated loss holds the forward
kernel once a (layer, mask) where the names are kept and twice where they
are not, and loss and every leaf's gradient are the same bits; the order the
shared room is given out in; what ``step_notes`` says. (The cells' own steps
with the memory described: ``tests/test_tpu_compile_*.py``.)"""

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

from decoder_contract import all_eqns  # noqa: E402
from deepfm_tpu.models import (afmoe, get_model, kimi_linear,  # noqa: E402
                               phi4_flash, sdar_moe)
from deepfm_tpu.ops import block_attention  # noqa: E402
import test_afmoe  # noqa: E402
import test_phi4_flash  # noqa: E402
import test_sdar_moe  # noqa: E402

S = 128         # positions the kernel sees: one block
ATTN = dict(attn_head_dim=64, attn_q_heads=2, attn_kv_heads=1, batch_size=1)
#: site -> (the spec, its flags, the module whose ``attn_scores_by`` the
#: model asks, (kernel layer, mask) pairs of the stack)
SITES = {
    # the scanned layer: [noisy ; clean] of 64 tokens
    "sdar": (test_sdar_moe.SPEC, dict(
        ATTN, history_max_len=S // 2, decoder_layers=2,
        moe_pair_capacity=S * 2), sdar_moe, 1),
    # ``KimiLinear._run_layer``: a windowed and a full layer, a dense SwiGLU
    # and a shared expert
    "afmoe": (test_afmoe.SPEC, dict(
        ATTN, history_max_len=S, decoder_layers=2, attn_window=40,
        layer_types="window_attention,full_attention",
        moe_pair_capacity=S * 2), afmoe, 2),
    # ``Phi4Flash._run_layer``: the three attention mixers (the cross layer
    # reads the full layer's keys and values)
    "phi4": (test_phi4_flash.SPEC, dict(
        ATTN, attn_q_heads=4, attn_kv_heads=2, history_max_len=S,
        decoder_layers=3, attn_window=40,
        layer_types="window_attention,full_attention,cross_attention"),
        phi4_flash, 3),
}
GB = 10 ** 9


def by_the_kernel(monkeypatch, module):
    monkeypatch.setattr(sdar_moe, "_scores_kernel", functools.partial(
        sdar_moe._scores_kernel, interpret=True, kernel_block=S))
    # (the notes' tables are built at the kernel's own block)
    monkeypatch.setattr(sdar_moe, "ATTN_BLOCK", S)
    kernel = sdar_moe.attn_kernel
    monkeypatch.setattr(
        sdar_moe, "attn_kernel",
        lambda seq, mask, heads, interpret=False, kernel_block=S: kernel(
            seq, mask, heads, interpret, kernel_block))
    monkeypatch.setattr(module, "attn_scores_by", lambda *a, **k: "kernel")


def described(monkeypatch, limit):
    monkeypatch.setattr(sdar_moe, "device_memory_bytes", lambda: limit)


def a_model(site):
    spec, flags, module, pairs = SITES[site]
    model = get_model(spec.config(**flags))
    params, state = model.init(jax.random.PRNGKey(2))
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, spec.V - spec.reserved_rows,
        (1, flags["history_max_len"])).astype(np.int32))

    def loss(p):
        per_seq, _ = model.per_example_loss(
            p, state, {"hist_ids": tokens}, train=True,
            rng=jax.random.PRNGKey(4))
        return jnp.mean(per_seq)
    return model, params, loss, module, pairs


def kernel_calls(jaxpr, name) -> int:
    """``pallas_call``s named ``name`` in ``jaxpr`` and every jaxpr inside
    it."""
    return sum(eqn.primitive.name == "pallas_call"
               and eqn.params["name"] == name for eqn in all_eqns(jaxpr))


@pytest.mark.parametrize("site", sorted(SITES))
def test_the_forward_kernel_runs_once_a_layer_and_the_step_is_the_same_bits(
        monkeypatch, site):
    """The loss differentiated with nothing known of the device's memory
    (nothing kept: the forward kernel in the forward pass and again in the
    layer's recomputation) and with the memory described (the names kept:
    once), the backward kernels once each either way; the loss and every
    leaf's gradient bitwise equal; ``attn_kept`` says which."""
    model, params, loss, module, pairs = a_model(site)
    by_the_kernel(monkeypatch, module)

    def traced():
        grad = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
        return ({name: kernel_calls(grad, "splash_mqa_" + name)
                 for name in ("fwd_residuals", "dkv_no_residuals",
                              "dq_no_residuals")},
                # (op by op: the two are different programs to XLA, which
                # may fuse, and so round, a jitted sum otherwise)
                jax.value_and_grad(loss)(params),
                dict(model.step_notes))

    calls, want, notes = traced()
    assert calls == {"fwd_residuals": 2 * pairs, "dkv_no_residuals": pairs,
                     "dq_no_residuals": pairs}
    layers = len(model.kinds) if site != "sdar" else 2
    assert notes["attn_scores"] == "kernel"
    assert notes["attn_kept"] == f"0/{layers}"
    described(monkeypatch, 16 * GB)
    calls, got, notes = traced()
    assert calls == {"fwd_residuals": pairs, "dkv_no_residuals": pairs,
                     "dq_no_residuals": pairs}
    assert notes["attn_kept"] == f"{layers}/{layers} layers 0.00 GB"
    assert np.asarray(got[0]).tobytes() == np.asarray(want[0]).tobytes()
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), path
        assert np.any(np.asarray(a)) or "bk" in str(path), path


@pytest.mark.parametrize("site", sorted(SITES))
def test_on_the_xla_path_nothing_is_kept_whatever_the_memory(monkeypatch,
                                                             site):
    """The CPU's own step with a memory described: the scores are XLA's,
    there is no forward kernel to keep anything of, and the note says so."""
    model, params, loss, _, _ = a_model(site)
    described(monkeypatch, 16 * GB)
    grad = jax.make_jaxpr(jax.grad(loss))(params)
    assert model.step_notes["attn_scores"] == "xla"
    assert model.step_notes["attn_kept"].startswith("0/")
    assert block_attention.KEPT not in str(grad)


def test_kimi_linears_own_latent_attention_keeps_nothing(monkeypatch):
    """Its scores are XLA's on every backend: its latent-attention layers
    are counted and none keeps, whatever the memory."""
    import test_kimi_linear
    model = get_model(test_kimi_linear.SPEC.config())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))[0]
    described(monkeypatch, 16 * GB)
    ids = jnp.zeros((2, 24), jnp.int32)
    keeps = model._keeps(params, ids, model._paths(ids, True))
    assert not any(k[block_attention.KEPT] for k in keeps)
    mla = sum(mixer == "mla" for mixer, _ in model.kinds)
    assert mla and model.step_notes["attn_kept"] == f"0/{mla}"


def test_the_kept_bytes_are_an_output_and_a_log_sum_exp_a_query():
    """Trinity-Mini's layer: 16,384 positions x 32 heads of 128 in bfloat16
    and a float32 a query; Phi-4-flash's: 40 heads' pairs of values."""
    assert block_attention.kept_bytes(16384 * 32, 128, 2) \
        == 16384 * 4096 * 2 + 16384 * 32 * 4 == 136_314_880
    assert block_attention.kept_bytes(8192 * 40, 128, 2) == 85_196_800


#: the budget's stacks: (spec, flags): an ``afmoe`` stack of three
#: attention layers (a dense SwiGLU and two shared experts) and the
#: Phi-4-flash cut (three attention layers among six dense SwiGLUs)
STACKS = {"afmoe": (test_afmoe.SPEC, {}),
          "phi4": (test_phi4_flash.SPEC, {})}
#: what a layer keeps there at [2, 24] tokens, float32: the kernel's tensors
#: (afmoe: 4 heads of 8; phi4: 8 heads' values of 16) and the first products
#: (afmoe: the dense SwiGLU's 2 x 48, a shared expert's 2 x 16; phi4: 2 x 48)
ATTN_BYTES = {"afmoe": 48 * 4 * (8 * 4 + 4), "phi4": 48 * 8 * (16 * 4 + 4)}
MLP_BYTES = {"afmoe": [48 * 8 * 48, 48 * 8 * 16, 48 * 8 * 16],
             "phi4": [48 * 8 * 48] * 6}


@pytest.mark.parametrize("stack, spare, attn, mlp, attn_note, mlp_note", [
    # nothing known of the memory, or no room: nothing kept
    ("afmoe", None, "---", "---", "0/3", "0/3"),
    ("afmoe", -1, "---", "---", "0/3", "0/3"),
    ("phi4", None, "------", "------", "0/3", "0/6"),
    # room for all: every attention layer, and the products as before
    ("afmoe", 10 ** 6, "+++", "+++", "3/3 layers 0.00 GB",
     "3/3 layers 0.00 GB"),
    ("phi4", 10 ** 6, "-+-+-+", "++++++", "3/3 layers 0.00 GB",
     "6/6 layers 0.00 GB"),
    # room for less than all: attention first, the products from the last
    # layer back in what it leaves
    ("afmoe", 3 * ATTN_BYTES["afmoe"] + 2 * 6144, "+++", "-++",
     "3/3 layers 0.00 GB", "2/3 layers 0.00 GB"),
    ("afmoe", 3 * ATTN_BYTES["afmoe"] + 6143, "+++", "---",
     "3/3 layers 0.00 GB", "0/3"),
    ("phi4", 3 * ATTN_BYTES["phi4"] + 18432 * 2.5, "-+-+-+", "----++",
     "3/3 layers 0.00 GB", "2/6 layers 0.00 GB"),
    # what held all six products alone holds three attention layers and one
    ("phi4", 18432 * 6, "-+-+-+", "-----+", "3/3 layers 0.00 GB",
     "1/6 layers 0.00 GB"),
    # room for less than the attention layers: from the last layer back too
    ("afmoe", 2.5 * ATTN_BYTES["afmoe"], "-++", "---",
     "2/3 layers 0.00 GB", "0/3"),
    ("phi4", 1.5 * ATTN_BYTES["phi4"], "-----+", "------",
     "1/3 layers 0.00 GB", "0/6"),
])
def test_attention_is_placed_first_and_the_products_in_what_is_left(
        monkeypatch, stack, spare, attn, mlp, attn_note, mlp_note):
    """``spare`` bytes beyond the held ones and the reserve (None: nothing
    known of the memory), the scores the kernel's."""
    spec, flags = STACKS[stack]
    model = get_model(spec.config(**flags))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))[0]
    ids = jnp.zeros((2, 24), jnp.int32)
    if spare is not None:
        described(monkeypatch, int(model._held_bytes(params, ids)
                                   + sdar_moe.KEEP_RESERVE * ids.size
                                   + spare))
    got = model._keeps(params, ids, {"scores_by": "kernel"})
    assert "".join("-+"[k[block_attention.KEPT]] for k in got) == attn
    assert "".join("-+"[k[kimi_linear.MLP_KEPT]] for k in got) == mlp
    assert model.step_notes["attn_kept"] == attn_note
    assert model.step_notes["mlp_kept"] == mlp_note


@pytest.mark.parametrize("spare, note", [
    (None, "0/2"), (-1, "0/2"), (10 ** 6, "2/2 layers 0.00 GB"),
    # the layers are one scan: every layer or none
    (1.5 * 96 * 4 * (8 * 4 + 4), "0/2"),
    (2 * 96 * 4 * (8 * 4 + 4), "2/2 layers 0.00 GB"),
])
def test_the_scanned_layers_keep_all_or_none(monkeypatch, spare, note):
    """SDAR's two layers at [2, 2 x 24] tokens, 4 heads of 8, float32."""
    model = get_model(test_sdar_moe.SPEC.config())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))[0]
    ids = jnp.zeros((2, 48), jnp.int32)
    if spare is not None:
        head = model._head_grad(params, 2, True)
        described(monkeypatch, int(
            sdar_moe.held_bytes(params, "adam", [head], 2)
            + sdar_moe.KEEP_RESERVE * ids.size + spare))
    assert model._attn_keep(params, ids, "kernel") == note.startswith("2/")
    assert model.step_notes["attn_kept"] == note
    assert not model._attn_keep(params, ids, "xla")
    assert model.step_notes["attn_kept"] == "0/2"
