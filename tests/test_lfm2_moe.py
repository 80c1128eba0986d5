"""``--model lfm2_moe`` (gated short-convolution mixers 3:1 with rotary
QK-norm grouped-query attention, sigmoid router with a selection bias and no
shared expert, tied token table) at small widths on the CPU, from seeded
weights, against the plain reference (``benchmark/reference_lfm2_moe.py``).
The decoders' shared tests are ``tests/decoder_contract.py``'s, read through
``SPEC`` (each layer kind's forward; loss, every leaf's gradient and three
Adam steps of the stack, float32 and bfloat16; the share test: the four
expert shares of a 4-way layer add up to the uncut reference's layer; what
``Config`` refuses; the scopes and notes of the compiled step; a fit from
TFRecord shards), this model's state carrying a selection bias that the
reference is handed and that three steps leave bit for bit. This model's own
are here: the reference's broken mixers; the convolution against a loop over
positions; selection by score + bias with weights by score; the parameter
counts at the published widths from the model's own leaves;
``sdar_moe.attention`` under the block-diffusion mask unchanged by the mask
becoming an argument; the kernel path at heads half a lane line wide through
the Pallas interpreter; and the stack's shapes. (The cell's own step, every
width, compiled for a described v5e: ``tests/test_tpu_compile_lfm2.py``.)"""

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

from benchmark import reference_lfm2_moe as ref  # noqa: E402
from benchmark import reference_sdar_moe  # noqa: E402
from benchmark.reference_sdar_moe import leaf_gap  # noqa: E402
from decoder_contract import (DecoderContract, HybridStack,  # noqa: E402
                              Spec, off_one)
from deepfm_tpu.config import Config  # noqa: E402
from deepfm_tpu.models import (get_model, kimi_linear, lfm2_moe,  # noqa: E402
                               registered_models, sdar_moe)
from deepfm_tpu.ops import block_attention  # noqa: E402

V, L, B = 60, 24, 2
#: The cut's own order at small widths: the dense layer, the full layer,
#: then convolution layers with experts.
SMALL = dict(model="lfm2_moe", feature_size=V, field_size=1,
             embedding_size=32, history_max_len=L, decoder_layers=4,
             layer_types="conv,full_attention,conv,conv", conv_taps=3,
             dense_layers=1, dense_mlp_width=48,
             attn_q_heads=4, attn_kv_heads=2, attn_head_dim=8,
             rope_theta=1e6, rms_norm_eps=1e-5,
             moe_experts=8, moe_top_k=2, moe_expert_width=16,
             moe_experts_held=4, moe_first_expert=2,
             moe_pair_capacity=B * L * 2, batch_size=B, l2_reg=0.0,
             learning_rate=1e-3, steps_per_loop=1)
SIZES = dict(head_dim=8, eps=1e-5, theta=1e6, top_k=2, route_scale=1.0,
             first_expert=2)
F32 = jnp.dtype("float32")
KINDS = {"conv+mlp": ("conv", "mlp"), "conv+moe": ("conv", "moe"),
         "full_attention+moe": ("full_attention", "moe")}
#: The shortest stack with every kind of layer: what the tests of a whole
#: trainer step compile.
TRIO = dict(decoder_layers=3, layer_types="conv,full_attention,conv")


def a_bias(model, seed=7, scale=0.05):
    """A selection bias large enough to move picks at these widths."""
    return scale * jax.random.normal(
        jax.random.PRNGKey(seed), model.init_bias().shape, jnp.float32)


def a_layer(kind, experts=8, held=8, d=32, **kw):
    """One layer's leaves, gains off one, and its selection bias."""
    cfg = SPEC.config(moe_experts=experts, moe_experts_held=held,
                      moe_first_expert=0, embedding_size=d, **kw)
    lp = off_one(jax.random.PRNGKey(4),
                 get_model(cfg)._init_layer(jax.random.PRNGKey(3), *kind))
    if kind[1] == "moe":
        lp["select_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(9), (experts,), jnp.float32)
    return lp


SPEC = Spec(
    ref=ref, small=SMALL, sizes=SIZES, stack=TRIO,
    scopes=frozenset({"embed", "conv", "conv_taps", "attn", "attn_scores",
                      "mlp", "moe", "head", "opt"}),
    no_scopes=frozenset({"kda", "kda_scan"}),
    notes=lambda trainer: {
        "conv_taps_by": "xla", "attn_scores": "xla", "moe_rows": "xla",
        "head_grad": "forward 3 products/chunk, 0.00 GB kept",
        # the dense layers' SwiGLUs (no shared expert): none kept off a TPU
        "mlp_kept": "0/%d" % sum(
            ffn == "mlp" for _, ffn in trainer.model.kinds),
        # the full-attention layers: XLA's scores, nothing to keep
        "attn_kept": "0/%d" % sum(
            mixer == "full_attention" for mixer, _ in trainer.model.kinds),
        "moe_products": "xla",
        "moe_rows_moved": "{moe_pairs_held}/%d" % (2 * 2 * B * L)},
    kinds=KINDS,
    layer_counts={"moe_pairs_held": "moe", sdar_moe.BIAS_MOVED: "moe"},
    layer_flags=dict(moe_experts_held=8, moe_first_expert=0),
    layer_sizes={"first_expert": 0}, layer_leaves=a_layer,
    # 4 expert shares of an expert layer (8 of 32 experts each:
    # ``--moe_first_expert`` 0, 8, 16, 24; top-4 with the selection bias),
    # the mixer, the router and the norms whole on each; no shared expert
    share_kinds=("conv+moe",),
    share_leaves=functools.partial(a_layer, experts=32, held=32, moe_top_k=4),
    expert_shares=4, share_experts=32, shared_expert=False,
    refusals=(
        ({"layer_types": "conv,full_attention,conv"}, "layer_types"),
        ({"layer_types": "conv,kda,conv,conv"}, "layer_types"),
        ({"conv_taps": 0}, "conv_taps"),
        ({"attn_q_heads": 3}, "attn_q_heads"),
        ({"attn_head_dim": 7}, "attn_head_dim"),
        ({"dense_layers": 5}, "dense_layers"),
        ({"dense_mlp_width": 0}, "dense_mlp_width"),
        ({"moe_top_k": 9}, "moe_top_k"),
        ({"moe_first_expert": 6}, "moe_experts_held"),
        ({"moe_pair_capacity": 0}, "moe_pair_capacity"),
        ({"history_max_len": 1}, "history_max_len"),
        ({"moe_shared_width": 16}, "moe_shared_width"),
        ({"kda_heads": 2}, "kda_heads"),
        ({"attn_every": 2}, "attn_every"),
        ({"mla_latent_dim": 8}, "mla_"),
        ({"task_type": "infer"}, "infer/export"),
        ({"task_type": "export"}, "infer/export"),
        ({"online_mode": True}, "online_mode"),
        ({"mesh_model": 2}, "mesh_model"),
        ({"loss_type": "square_loss"}, "loss_type"),
    ))
config, flat = SPEC.config, SPEC.flat


class TestLfm2Moe(DecoderContract, HybridStack):
    spec = SPEC

    def _seeded(self, cfg):
        """... and a state with a bias."""
        model, params, state = super()._seeded(cfg)
        return model, params, {**state, lfm2_moe.SELECT_BIAS: a_bias(model)}

    def reference_loss(self, params, tokens, state, rng):
        return ref.forward_loss(params, tokens, state[lfm2_moe.SELECT_BIAS],
                                SIZES)

    def start_state(self, trainer):
        state = trainer.init_state(seed=3)
        return state.replace(model_state={
            **state.model_state, lfm2_moe.SELECT_BIAS: jax.device_put(
                np.asarray(a_bias(trainer.model)),
                jax.tree.leaves(state.model_state)[0].sharding)})

    def follower(self, start, state, learning_rate):
        return ref.Follower(
            start, np.asarray(state.model_state[lfm2_moe.SELECT_BIAS]),
            SIZES, learning_rate)

    def step_metrics_hold(self, metrics):
        assert lfm2_moe.SELECT_BIAS not in metrics
        assert int(metrics[sdar_moe.BIAS_MOVED]) > 0

    def test_logits_and_loss_match_the_reference(self, seeded):
        model, params, state = seeded
        counts = self.logits_and_loss(seeded)
        assert int(counts["moe_pairs_held"]) > 0
        # three expert layers of B x L positions: the bias moved some picks
        assert 0 < int(counts[sdar_moe.BIAS_MOVED]) < 3 * B * L
        # the state hands the bias on as it came; the metrics leave it out
        np.testing.assert_array_equal(counts[lfm2_moe.SELECT_BIAS],
                                      state[lfm2_moe.SELECT_BIAS])
        assert lfm2_moe.SELECT_BIAS not in model.step_counts(counts)
        assert sdar_moe.BIAS_MOVED in model.step_counts(counts)
        assert "head" not in params     # the head is the table

    def test_gradients_of_every_leaf_match_the_reference(self, short):
        got = self.gradients(short)
        # tied: every row of the table has a gradient, a token's or the
        # head's
        assert np.all(np.abs(got["tok_emb"]).sum(axis=1) > 0)

    @pytest.mark.parametrize("n_dev", [1, 2])
    def test_three_adam_steps_match_the_reference(self, n_dev, program,
                                                  followed):
        """... and the bias is after three steps what it was, bit for
        bit."""
        state = self.three_steps(n_dev, program, followed)
        before = np.asarray(a_bias(program.trainer.model))
        after = np.asarray(state.model_state[lfm2_moe.SELECT_BIAS])
        assert before.tobytes() == after.tobytes() and np.any(before != 0)

    def test_fit_trains_from_tfrecord_shards(self, tmp_path):
        seen, state = self.fit_from_shards(tmp_path)
        # the model's own start: a zero bias moves no pick and stays zero
        assert int(seen[-1][sdar_moe.BIAS_MOVED]) == 0
        assert not np.any(np.asarray(
            state.model_state[lfm2_moe.SELECT_BIAS]))


def _mixers(lp, x, sizes, **broken):
    xn = ref.rms_norm(x, lp["norm1"], 1e-5)
    conv_kw = {k: v for k, v in broken.items() if k in ("taps_ahead", "gate")}
    attn_kw = {k: v for k, v in broken.items() if k == "rotate_k"}
    return ref.conv(xn, lp, **conv_kw) + ref.attention(xn, lp, sizes,
                                                       **attn_kw)


@pytest.mark.parametrize("broken, moved", [
    ({"taps_ahead": 1}, True), ({"gate": False}, True),
    ({"rotate_k": False}, True), ({}, False)],
    ids=["conv-a-tap-ahead", "output-gate-left-out", "k-not-rotated",
         "sound"])
def test_the_references_broken_mixers_differ_from_the_sound_ones(broken,
                                                                 moved):
    """What the reference's own switches leave out moves its result: each
    mechanism of the two mixers is in the mathematics."""
    lp = {**a_layer(KINDS["conv+moe"]), **a_layer(KINDS["full_attention+moe"])}
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    with jax.default_matmul_precision("highest"):
        got = _mixers(lp, x, SIZES, **broken)
        want = _mixers(lp, x, SIZES)
    assert (leaf_gap(got, want) > 0.05) == moved


# ------------------------------------------------- the gated convolution

def test_the_convolution_is_a_loop_over_positions():
    """``conv_mixer`` against the equations written a position at a time:
    ``c_t = sum_j w_j z_{t-2+j}`` with ``z = B * u``, nothing read before the
    first position, the output gated by C."""
    lp = a_layer(KINDS["conv+mlp"])
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(2), (B, L, 32))
    got = lfm2_moe.conv_mixer(lp, x, eps=1e-5, cdt=F32)
    xn = np.asarray(ref.rms_norm(x, lp["norm1"], 1e-5), np.float64)
    bcu = xn @ np.asarray(lp["conv_w_in"], np.float64)
    b_, c_, u = bcu[..., :32], bcu[..., 32:64], bcu[..., 64:]
    z, w = b_ * u, np.asarray(lp["conv_w"], np.float64)
    y = np.zeros_like(z)
    for t in range(L):
        for j in range(3):
            if t - 2 + j >= 0:
                y[:, t] += w[j] * z[:, t - 2 + j]
    want = (c_ * y) @ np.asarray(lp["conv_w_out"], np.float64)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # causal: a later position moves no earlier output
    x2 = x.at[:, L // 2:].add(1.0)
    got2 = lfm2_moe.conv_mixer(lp, x2, eps=1e-5, cdt=F32)
    np.testing.assert_array_equal(got[:, :L // 2], got2[:, :L // 2])
    assert leaf_gap(got2[:, L // 2:], got[:, L // 2:]) > 0.01


# ------------------------------------------------------ the biased router

def test_the_bias_moves_the_pick_and_never_the_weights():
    """Scores 0.9 / 0.8 / 0.7 / 0.1 with a bias of +0.25 on the third: the
    two picked are experts 0 and 2 (0.9, 0.95), not 0 and 1, and the weights
    are 0.9 and 0.7 over their sum + 1e-6: the bias is in no weight."""
    scores = np.asarray([0.9, 0.8, 0.7, 0.1])
    logits = jnp.asarray(np.log(scores / (1 - scores)), jnp.float32)[None]
    xn, router = jnp.ones((1, 1), jnp.float32), logits
    bias = jnp.asarray([0.0, 0.0, 0.25, 0.0])
    route_by = get_model(config()).route_by
    plain_e, plain_w, plain_moved = route_by(xn, router, 2)
    top_e, top_w, moved = route_by(xn, router, 2, bias=bias)
    assert sorted(np.asarray(plain_e[0])) == [0, 1]
    assert sorted(np.asarray(top_e[0])) == [0, 2]
    assert (int(plain_moved), int(moved)) == (0, 1)
    # a bias that leaves the two largest the two largest moves nothing
    assert int(route_by(xn, router, 2, bias=bias / 5)[2]) == 0
    want = {0: 0.9 / (1.6 + 1e-6), 2: 0.7 / (1.6 + 1e-6)}
    for e, w in zip(np.asarray(top_e[0]), np.asarray(top_w[0])):
        assert w == pytest.approx(want[int(e)], rel=1e-6)
    # the reference says the same, and its broken form weighs by the pick
    sizes = {"top_k": 2, "route_scale": 1.0}
    with jax.default_matmul_precision("highest"):
        w_ref = np.asarray(ref.router_weights(xn, router, bias, sizes))[0]
        w_bad = np.asarray(ref.router_weights(xn, router, bias, sizes,
                                              weigh_by_pick=True))[0]
    np.testing.assert_allclose(w_ref, [want[0], 0.0, want[2], 0.0],
                               rtol=1e-6)
    assert abs(w_bad[2] - want[2]) > 0.05


def test_the_layer_counts_the_picks_the_bias_moved():
    lp = a_layer(KINDS["conv+moe"])
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    model = get_model(config(moe_experts_held=8, moe_first_expert=0))
    kw = dict(top_k=2, first_expert=0, capacity=2 * B * L, eps=1e-5, cdt=F32,
              route_by=model.route_by)
    _, counts = sdar_moe.expert_layer(lp, x, **kw)
    xn = ref.rms_norm(x, lp["norm2"], 1e-5).reshape(-1, 32)
    s = np.asarray(jax.nn.sigmoid(xn @ lp["router"]))
    biased = np.sort(np.argsort(-(s + np.asarray(lp["select_bias"])),
                                axis=-1, kind="stable")[:, :2], axis=-1)
    plain = np.sort(np.argsort(-s, axis=-1, kind="stable")[:, :2], axis=-1)
    moved = int(np.any(biased != plain, axis=-1).sum())
    assert int(counts[sdar_moe.BIAS_MOVED]) == moved > 0
    # no bias, no count; a zero bias, a count of zero
    _, none = sdar_moe.expert_layer(
        {k: v for k, v in lp.items() if k != "select_bias"}, x, **kw)
    assert sdar_moe.BIAS_MOVED not in none
    _, zero = sdar_moe.expert_layer(
        {**lp, "select_bias": jnp.zeros((8,))}, x, **kw)
    assert int(zero[sdar_moe.BIAS_MOVED]) == 0


# ------------------------------------- the parameters at the published widths

def test_parameter_counts_at_the_published_widths():
    """ISSUE 40's table from the model's own leaves (``jax.eval_shape``:
    nothing is allocated): the cut's five layers, 8 of 32 experts, a quarter
    of the vocabulary, every width as published."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        import json
        flags = json.load(f)["flags"]
    model = get_model(Config(**flags))
    shapes, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree, *names):
        return sum(int(np.prod(x.shape)) for n, x in tree.items()
                   if not names or n in names)

    layers = shapes["layers"]
    conv = count(layers["0"], "conv_w_in", "conv_w", "conv_w_out")
    attn = count(layers["1"], "wq", "wk", "wv", "wo", "q_norm", "k_norm")
    assert conv == 12_582_912 + 6_144 + 4_194_304 == 16_783_360
    assert attn == 10_485_888
    assert count(layers["0"], "norm1", "norm2") == 4_096
    assert count(layers["0"], "mlp_w_gate", "mlp_w_up",
                 "mlp_w_down") == 44_040_192
    assert count(layers["2"], "w_gate", "w_up", "w_down") == 88_080_384
    assert count(layers["2"], "router") == 65_536
    assert [count(layers[str(i)]) for i in range(5)] == [
        60_827_648, 98_635_904, 104_933_376, 104_933_376, 104_933_376]
    assert count(shapes, "tok_emb") == 33_554_432 and "head" not in shapes
    assert count(shapes, "final_norm") == 2_048
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == 507_820_160
    assert round(16 * total / 1e9, 2) == 8.13
    assert round(12 * total / 1e9, 2) == 6.09
    # the bias is no parameter: 32 a layer in the model state
    assert state[lfm2_moe.SELECT_BIAS].shape == (4, 32)


# --------------------- the shared attention block, its mask now an argument

def test_sdar_attention_under_its_own_mask_is_what_it_was():
    """``sdar_moe.attention`` took ``length`` and ``block`` and built
    ``block_diffusion`` itself; it now takes the mask. Under that mask it is
    the equations the SDAR reference has always held it to, and handing the
    same mask twice gives the same bits."""
    length, block, d, hd = 16, 4, 32, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    lp = {"norm1": 1.0 + 0.1 * jax.random.normal(keys[0], (d,)),
          "wq": jax.random.normal(keys[1], (d, 4 * hd)) * 0.2,
          "wk": jax.random.normal(keys[2], (d, 2 * hd)) * 0.2,
          "wv": jax.random.normal(keys[3], (d, 2 * hd)) * 0.2,
          "q_norm": 1.0 + 0.1 * jax.random.normal(keys[4], (hd,)),
          "k_norm": 1.0 + 0.1 * jax.random.normal(keys[5], (hd,)),
          "wo": jax.random.normal(keys[6], (4 * hd, d)) * 0.2}
    x = jax.random.normal(keys[7], (B, 2 * length, d))
    positions = jnp.arange(2 * length) % length
    kw = dict(head_dim=hd, eps=1e-6, theta=1e6, cdt=F32)
    got = sdar_moe.attention(lp, x, positions,
                             mask=sdar_moe.block_diffusion(length, block),
                             **kw)
    again = sdar_moe.attention(lp, x, positions,
                               mask=sdar_moe.block_diffusion(length, block),
                               scores_scope="attn_scores", **kw)
    np.testing.assert_array_equal(got, again)       # a scope is metadata
    with jax.default_matmul_precision("highest"):
        want = reference_sdar_moe.attention(
            reference_sdar_moe.rms_norm(x, lp["norm1"], 1e-6), lp,
            {"head_dim": hd, "eps": 1e-6, "theta": 1e6},
            jnp.asarray(reference_sdar_moe.block_diffusion_mask(
                length, block)), positions)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and under another mask it is another function
    causal = sdar_moe.attention(lp, x, positions, mask=kimi_linear.causal,
                                **kw)
    assert leaf_gap(causal, got) > 0.05


# ------------------------------- heads half a lane line wide on the kernel

@pytest.mark.parametrize("head_dim, takes", [(128, True), (256, True),
                                             (64, True), (192, True),
                                             (32, False), (96, False)])
def test_the_kernel_takes_whole_and_half_lane_lines(head_dim, takes):
    assert block_attention.supported("tpu", 1024, head_dim, 512) == takes
    assert not block_attention.supported("cpu", 1024, head_dim, 512)


@pytest.mark.parametrize("backend, seq, head_dim, one_device, want", [
    ("tpu", 8192, 64, True, "kernel"), ("tpu", 8192, 128, True, "kernel"),
    ("tpu", 8192, 64, False, "xla"), ("cpu", 8192, 64, True, "xla"),
    ("tpu", 8192 + 64, 64, True, "xla"), ("tpu", 8192, 32, True, "xla")])
def test_attn_scores_by_takes_heads_of_64_on_a_tpu(backend, seq, head_dim,
                                                   one_device, want):
    assert sdar_moe.attn_scores_by(seq, head_dim, one_device=one_device,
                                   backend=backend) == want


@pytest.mark.parametrize("dtype, tol", [("float32", 1e-4),
                                        ("bfloat16", 2e-2)])
def test_the_kernel_at_64_lanes_matches_the_chunked_xla_path(monkeypatch,
                                                             dtype, tol):
    """``masked_scores`` under ``kimi_linear.causal`` at head_dim 64 by the
    kernel (the half line as it is; forward, dq, dk/dv through the Pallas
    interpreter, blocks of 128) against ``_scores_xla`` on the
    same q/k/v: 512 positions, 4 query heads on each of 2 key/value heads;
    output and the gradients of q, k, v, within 1e-4 in float32 and within
    bfloat16's rounding of an operand under bfloat16; the scale is the real
    width's, 1/8."""
    monkeypatch.setattr(sdar_moe, "_scores_kernel", functools.partial(
        sdar_moe._scores_kernel, interpret=True, kernel_block=128))
    cdt = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, 512, 8, 64), jnp.float32)
    k = jax.random.normal(keys[1], (1, 512, 2, 64)).astype(cdt)
    v = jax.random.normal(keys[2], (1, 512, 2, 64)).astype(cdt)
    w = jax.random.normal(keys[3], (1, 512, 8 * 64))

    def value_and_grads(scores_by):
        def loss(q, k, v):
            out = sdar_moe.masked_scores(
                q, k, v, mask=kimi_linear.causal, cdt=cdt,
                scores_by=scores_by).astype(jnp.float32)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *(g.astype(jnp.float32) for g in grads))

    got, want = value_and_grads("kernel"), value_and_grads("xla")
    assert got[0].shape == (1, 512, 8 * 64)
    for a, b in zip(got, want):
        assert a.shape == b.shape and leaf_gap(a, b) < tol


def test_the_notes_of_a_narrow_head_are_a_whole_lines():
    assert sdar_moe.attn_notes("kernel", kimi_linear.causal, 1024, 4) == {
        "attn_scores": "kernel", "attn_score_blocks": "3/4"}
    assert sdar_moe.attn_notes("xla", kimi_linear.causal, 1024, 4) == {
        "attn_scores": "xla"}


# ------------------------------------------------------------ configuration


@pytest.mark.parametrize("model", ["deepfm", "sdar_moe", "kimi_linear",
                                   "solar_open2"])
def test_the_convolution_flags_belong_to_this_model(model):
    with pytest.raises(ValueError):
        Config(model=model, layer_types="conv")
    with pytest.raises(ValueError):
        Config(model=model, conv_taps=4)


def test_the_model_is_a_stack_by_its_list():
    assert "lfm2_moe" not in registered_models()
    model = get_model(config())
    assert isinstance(model, kimi_linear.KimiLinear) and model.owns_loss
    assert model.kinds == (("conv", "mlp"), ("full_attention", "moe"),
                           ("conv", "moe"), ("conv", "moe"))
    assert model.moe_layers == (1, 2, 3)
    params, state = model.init(jax.random.PRNGKey(0))
    assert set(params["layers"]["1"]) == {
        "norm1", "norm2", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
        "router", "w_gate", "w_up", "w_down"}       # no shared expert
    assert set(params["layers"]["0"]) == {
        "norm1", "norm2", "conv_w_in", "conv_w", "conv_w_out", "mlp_w_gate",
        "mlp_w_up", "mlp_w_down"}
    assert params["layers"]["0"]["conv_w"].shape == (3, 32)
    assert not np.any(np.asarray(state[lfm2_moe.SELECT_BIAS]))


def test_model_by_the_kernel_at_64_lanes_takes_the_same_step(monkeypatch):
    """The whole model with its causal scores by the kernel (interpreted)
    at heads of 64 against the XLA path: the same loss and gradients."""
    cfg = config(history_max_len=512, attn_head_dim=64, attn_q_heads=2,
                 attn_kv_heads=1, decoder_layers=2,
                 layer_types="conv,full_attention", batch_size=1,
                 moe_pair_capacity=1024)
    model = get_model(cfg)
    params, state = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, V, (1, 512)).astype(np.int32))

    def value_and_grad():
        def loss(p):
            per_seq, _ = model.per_example_loss(
                p, state, {"hist_ids": tokens}, train=True, rng=None)
            return jnp.mean(per_seq)
        return jax.jit(jax.value_and_grad(loss))(params)

    want, want_g = value_and_grad()
    assert model.step_notes["attn_scores"] == "xla"
    monkeypatch.setattr(sdar_moe, "_scores_kernel", functools.partial(
        sdar_moe._scores_kernel, interpret=True, kernel_block=128))
    monkeypatch.setattr(lfm2_moe, "attn_scores_by",
                        lambda *a, **k: "kernel")
    got, got_g = value_and_grad()
    assert model.step_notes["attn_scores"] == "kernel"
    assert model.step_notes["attn_score_blocks"] == "1/1"  # blocks of 512
    assert "attn_head_lanes" not in model.step_notes
    assert abs(float(got) - float(want)) < 1e-5
    for name, g in flat(got_g).items():
        assert leaf_gap(g, flat(want_g)[name]) < 1e-4, name
