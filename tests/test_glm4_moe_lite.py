"""``--model glm4_moe_lite`` (latent attention with a low-rank query and a
rotated shared key in every layer, a sigmoid router with a selection bias
beside a shared expert, a multi-token-prediction module with its second
loss) at small widths on the CPU, from seeded weights, against the plain
reference (``benchmark/reference_glm4_moe_lite.py``). The decoders' shared
tests are ``tests/decoder_contract.py``'s, read through ``SPEC`` (each layer
kind's forward; the loss ``L1 + lambda L2``, every leaf's gradient and three
Adam steps of the stack with its module, float32 and bfloat16; the share
test: four head shares' ``W_o`` sums and eight expert shares' sums add up to
the uncut reference's layer; what ``Config`` refuses; the scopes and notes
of the compiled step; a fit from TFRecord shards), this model's state
carrying a selection bias the reference is handed and both losses. This
model's own are here: the rotation (a shift of all positions leaves a block
unchanged); the module (``L2`` against the reference, the table's and the
head's gradients the sum of both uses', the rolled rows a second lookup's);
the reference's broken mixers; the six faults of ISSUE 48, each told apart;
the kernel path at heads of 256 through the Pallas interpreter; the
parameter counts at the published widths. (The cell's own step, every width,
compiled for a described v5e: ``tests/test_tpu_compile_glm4_moe_lite.py``.)"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import reference_glm4_moe_lite as ref  # noqa: E402
from benchmark.reference_sdar_moe import leaf_gap  # noqa: E402
from decoder_contract import (DecoderContract, HybridStack,  # noqa: E402
                              Spec, cut_columns, cut_rows, highest, off_one)
from deepfm_tpu.config import Config  # noqa: E402
from deepfm_tpu.models import (get_model, glm4_moe_lite,  # noqa: E402
                               kimi_linear, lfm2_moe, registered_models,
                               sdar_moe)

V, L, B = 60, 24, 2
LAMBDA = 0.1
#: The cut's own order at small widths: the dense layer, expert layers, the
#: module after them. (A query rank, a latent and the model's width that
#: differ: a norm is told by its width.)
SMALL = dict(model="glm4_moe_lite", feature_size=V, field_size=1,
             embedding_size=32, history_max_len=L, decoder_layers=3,
             dense_layers=1, dense_mlp_width=48,
             attn_q_heads=2, attn_kv_heads=2, mla_q_rank=12,
             mla_latent_dim=16, mla_nope_dim=6, mla_rope_dim=4,
             mla_value_dim=8, rope_theta=1e6, rms_norm_eps=1e-5,
             moe_experts=8, moe_top_k=2, moe_expert_width=16,
             moe_shared_width=16, moe_route_scale=1.8,
             moe_experts_held=4, moe_first_expert=2,
             moe_pair_capacity=B * L * 2, mtp_depth=1,
             mtp_loss_weight=LAMBDA, batch_size=B, l2_reg=0.0,
             learning_rate=1e-3, steps_per_loop=1)
SIZES = dict(nope_dim=6, rope_dim=4, eps=1e-5, theta=1e6, top_k=2,
             route_scale=1.8, first_expert=2, mtp_weight=LAMBDA)
F32 = jnp.dtype("float32")
KINDS = {"mla+mlp": ("mla", "mlp"), "mla+moe": ("mla", "moe")}
#: The shortest stack with every kind of block: the dense layer, an expert
#: layer, the module.
PAIR = dict(decoder_layers=2)


def a_bias(model, seed=7, scale=0.05):
    """A selection bias large enough to move picks at these widths."""
    return scale * jax.random.normal(
        jax.random.PRNGKey(seed), model.init_bias().shape, jnp.float32)


def a_layer(kind, experts=8, held=8, **kw):
    """One layer's leaves, gains off one, and its selection bias."""
    cfg = SPEC.config(moe_experts=experts, moe_experts_held=held,
                      moe_first_expert=0, **kw)
    lp = off_one(jax.random.PRNGKey(4),
                 get_model(cfg)._init_layer(jax.random.PRNGKey(3), *kind))
    if kind[1] == "moe":
        lp["select_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(9), (experts,), jnp.float32)
    return lp


def head_share(lp, mixer, r, heads=8, n=2):
    """A layer's leaves with heads ``r n .. (r + 1) n`` of its MLA's
    ``heads``: their columns of ``W_qb`` and ``W_kvb`` and rows of ``W_o``;
    ``W_qa``, ``W_kva`` and the norms whole."""
    nope, rope, value = (SMALL[k] for k in (
        "mla_nope_dim", "mla_rope_dim", "mla_value_dim"))
    return {**lp,
            "mla_w_qb": cut_columns(lp["mla_w_qb"], r * n, n, heads,
                                    nope + rope),
            "mla_w_kvb": cut_columns(lp["mla_w_kvb"], r * n, n, heads,
                                     nope + value),
            "mla_wo": cut_rows(lp["mla_wo"], r * n, n, heads, value)}


SPEC = Spec(
    ref=ref, small=SMALL, sizes=SIZES, stack=PAIR,
    scopes=frozenset({"embed", "attn", "attn_scores", "mlp", "moe", "mtp",
                      "head", "mtp_head", "opt"}),
    no_scopes=frozenset({"kda", "kda_scan", "conv"}),
    notes=lambda trainer: {
        "attn_scores": "xla", "moe_rows": "xla",
        # the two head passes, each its gradient beside its loss
        "head_grad": "forward 3 products/chunk, 0.00 GB kept",
        "mtp_head_grad": "forward 3 products/chunk, 0.00 GB kept",
        # the dense layer's SwiGLU and each expert block's shared expert:
        # none kept off a TPU
        "mlp_kept": "0/%d" % len(trainer.model.block_kinds),
        # every block mixes by attention: XLA's scores, nothing to keep
        "attn_kept": "0/%d" % len(trainer.model.block_kinds),
        # the expert layers and the module's block, one pass each
        "moe_products": "xla",
        "moe_rows_moved": "{moe_pairs_held}/%d" % (
            sum(ffn == "moe" for _, ffn in trainer.model.block_kinds)
            * 2 * B * L)},
    kinds=KINDS,
    layer_counts={"moe_pairs_held": "moe", sdar_moe.BIAS_MOVED: "moe"},
    layer_flags=dict(moe_experts_held=8, moe_first_expert=0),
    layer_sizes={"first_expert": 0}, layer_leaves=a_layer,
    # the configuration's layout at small widths: 4 head shares of an MLA of
    # 8 heads (2 each), 8 expert shares of 32 experts (4 each), top-4 with
    # the selection bias; W_qa, W_kva, their norms, the router, the shared
    # expert and the dense MLP whole on each
    share_kinds=("mla+mlp", "mla+moe"),
    share_leaves=functools.partial(a_layer, experts=32, held=32,
                                   moe_top_k=4, attn_q_heads=8,
                                   attn_kv_heads=8),
    head_share=head_share, head_shares=4, expert_shares=8, share_experts=32,
    refusals=(
        ({"mtp_depth": 2}, "mtp_depth"),
        ({"mtp_loss_weight": 0.0}, "mtp_loss_weight"),
        ({"mesh_model": 2}, "mesh_model"),
        ({"attn_q_heads": 3}, "attn_q_heads"),
        ({"mla_q_rank": 0}, "mla_q_rank"),
        ({"mla_rope_dim": 3}, "mla_rope_dim"),
        ({"mla_value_dim": 0}, "mla_value_dim"),
        ({"dense_layers": 4}, "dense_layers"),
        ({"dense_mlp_width": 0}, "dense_mlp_width"),
        ({"moe_top_k": 9}, "moe_top_k"),
        ({"moe_shared_width": 0}, "moe_shared_width"),
        ({"moe_first_expert": 6}, "moe_experts_held"),
        ({"moe_pair_capacity": 0}, "moe_pair_capacity"),
        ({"history_max_len": 2}, "history_max_len"),
        ({"kda_heads": 2}, "kda_heads"),
        ({"attn_every": 2}, "attn_every"),
        ({"layer_types": "conv"}, "layer_types"),
        ({"task_type": "infer"}, "infer/export"),
        ({"online_mode": True}, "online_mode"),
        ({"loss_type": "square_loss"}, "loss_type"),
    ))
config, flat = SPEC.config, SPEC.flat


class TestGlm4MoeLite(DecoderContract, HybridStack):
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
        """Both losses ride the metrics, the total is their weighted sum,
        and the bias does not."""
        assert lfm2_moe.SELECT_BIAS not in metrics
        assert int(metrics[sdar_moe.BIAS_MOVED]) > 0
        xent, mtp = float(metrics["xent"]), float(metrics["mtp_xent"])
        assert 0 < xent and 0 < mtp and xent != mtp
        assert float(metrics["loss"]) == pytest.approx(xent + LAMBDA * mtp,
                                                       rel=1e-6)

    def test_logits_and_loss_match_the_reference(self, seeded):
        """... and each of the two losses by itself is the reference's."""
        model, params, state = seeded
        counts = self.logits_and_loss(seeded)
        assert int(counts["moe_pairs_held"]) > 0
        # two expert layers and the module's block of B x L positions
        assert 0 < int(counts[sdar_moe.BIAS_MOVED]) < 3 * B * L
        np.testing.assert_array_equal(counts[lfm2_moe.SELECT_BIAS],
                                      state[lfm2_moe.SELECT_BIAS])
        assert lfm2_moe.SELECT_BIAS not in model.step_counts(counts)
        tokens = jnp.asarray(SPEC.sequences(B, 0))
        _, said = jax.jit(lambda p, s: model.per_example_loss(
            p, s, {"hist_ids": tokens}, train=True, rng=None))(params, state)
        with highest():
            l1, l2, _ = jax.jit(lambda p: ref.forward_losses(
                p, tokens, state[lfm2_moe.SELECT_BIAS], SIZES))(
                    {k: jnp.asarray(v) for k, v in flat(params).items()})
        np.testing.assert_allclose(said["xent"], l1, rtol=1e-6)
        np.testing.assert_allclose(said["mtp_xent"], l2, rtol=1e-6)
        assert abs(float(l1) - float(l2)) > 1e-3
        assert set(model.loss_parts) <= set(model.step_counts(said))
        assert "head" in params and set(params["mtp"]) == {
            "enorm", "hnorm", "w_eh", "block", "final_norm"}

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
        assert float(seen[-1]["mtp_xent"]) < float(seen[0]["mtp_xent"])
        # the model's own start: a zero bias moves no pick and stays zero
        assert int(seen[-1][sdar_moe.BIAS_MOVED]) == 0
        assert not np.any(np.asarray(
            state.model_state[lfm2_moe.SELECT_BIAS]))


# ------------------------------------------------------------ the rotation

def test_a_shift_of_all_positions_leaves_a_block_unchanged():
    """Rotary positions are relative: the module's block at positions
    i + 1 is the block at positions i; without the rotation of the shared
    key, or of the queries' rotated columns, it is not."""
    model = get_model(config(moe_experts_held=8, moe_first_expert=0))
    lp = a_layer(KINDS["mla+moe"])
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    block = jax.jit(lambda x, lp, p0: model._layer(
        "mla", "moe", x, lp, position0=p0)[0])
    at0 = block(x, lp, 0)
    np.testing.assert_allclose(block(x, lp, 1), at0, atol=2e-5)
    np.testing.assert_allclose(block(x, lp, 1000), at0, atol=2e-4)
    xn = ref.rms_norm(x, lp["norm1"], 1e-5)
    with highest():
        mla = jax.jit(lambda p0, rotate_k: ref.mla(
            xn, lp, SIZES, position0=p0, rotate_k=rotate_k),
            static_argnums=1)
        assert leaf_gap(mla(1, True), mla(0, True)) < 1e-5
        # a key left unrotated reads the query's absolute position
        assert leaf_gap(mla(1, False), mla(0, False)) > 1e-3


@pytest.mark.parametrize("broken, moved", [
    ({"rotate_k": False}, True), ({"norm_q": False}, True), ({}, False)],
    ids=["shared-key-unrotated", "query-latent-unnormed", "sound"])
def test_the_references_broken_mixers_differ_from_the_sound_ones(broken,
                                                                 moved):
    lp = a_layer(KINDS["mla+moe"])
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    xn = ref.rms_norm(x, lp["norm1"], 1e-5)
    with highest():
        got, want = ref.mla(xn, lp, SIZES, **broken), ref.mla(xn, lp, SIZES)
    assert (leaf_gap(got, want) > 0.05) == moved


def test_the_references_blocks_and_chunks_change_nothing(monkeypatch):
    """The reference makes its scores a block of queries and its head passes
    a chunk of positions at a time so that it fits at the timed sizes, a
    ragged last one padded (the module has L - 1 positions): at blocks of 8
    and chunks of 5 over 24 positions both losses and a gradient are what
    they are whole."""
    model = get_model(config(**PAIR))
    params, state = model.init(jax.random.PRNGKey(0))
    leaves = {k: jnp.asarray(v) for k, v in flat(params).items()}
    bias, tokens = a_bias(model), jnp.asarray(SPEC.sequences(B, 6))

    def losses_and_gradient():
        def both(p):
            l1, l2, _ = ref.forward_losses(p, tokens, bias, SIZES)
            return l1 + l2, (l1, l2)
        with highest():     # (jitted anew: the blocks are read at trace)
            (_, parts), g = jax.jit(jax.value_and_grad(
                both, has_aux=True))(leaves)
        return (*parts, g["mtp.w_eh"], g["layers.0.mla_w_qb"])

    whole = losses_and_gradient()
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 5)
    for got, want in zip(losses_and_gradient(), whole):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)


# -------------------------------------------------------------- the module

@pytest.fixture(scope="module")
def sound():
    """(config, tokens, params, state, the sound program's gradients) of
    the shortest stack the faults show in: one expert layer and the
    module."""
    cfg = config(decoder_layers=1, dense_layers=0)
    tokens = jnp.asarray(SPEC.sequences(B, 1))
    model = get_model(cfg)
    params, state = model.init(jax.random.PRNGKey(0))
    params = off_one(jax.random.PRNGKey(5), params)
    state = {**state, lfm2_moe.SELECT_BIAS: a_bias(model)}
    return cfg, tokens, params, state, loss_gradients(model, params, state,
                                                      tokens)


def loss_gradients(model, params, state, tokens):
    def loss(p):
        per_seq, _ = model.per_example_loss(
            p, state, {"hist_ids": tokens}, train=True, rng=None)
        return jnp.mean(per_seq)
    return flat(jax.jit(jax.grad(loss))(params))


def test_the_table_and_the_head_sum_the_gradients_of_both_uses(sound):
    """``tok_emb`` is looked up for the stack and (rolled) for the module,
    ``head`` closes both passes: the program's gradient of each is the
    reference's of ``L1`` plus lambda times the reference's of ``L2``, both
    parts there."""
    cfg, tokens, params, state, got = sound
    leaves = {k: jnp.asarray(v) for k, v in flat(params).items()}
    bias = state[lfm2_moe.SELECT_BIAS]
    with highest():
        g1, g2 = (jax.jit(jax.grad(lambda p, i=i: ref.forward_losses(
            p, tokens, bias, SIZES)[i]))(leaves) for i in (0, 1))
    for name in ("tok_emb", "head"):
        first, second = np.asarray(g1[name]), LAMBDA * np.asarray(g2[name])
        assert leaf_gap(got[name], first + second) < SPEC.grad_tol
        # each use alone misses it by the other's share
        assert leaf_gap(got[name], first) > 100 * SPEC.grad_tol, name
        assert leaf_gap(got[name], second) > 100 * SPEC.grad_tol, name
    # the module's own leaves have the second loss's gradient only
    assert not np.any(np.asarray(g1["mtp.w_eh"]))
    assert leaf_gap(got["mtp.w_eh"], LAMBDA * np.asarray(g2["mtp.w_eh"])) \
        < SPEC.grad_tol


def test_the_rolled_rows_are_a_second_lookups():
    """``mtp_input`` over the looked-up rows rolled by one position is the
    reference's over ``Emb(t_{i+1})`` looked up again, at every position
    that has a next token."""
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    table = 3.0 * jax.random.normal(keys[0], (V, 32))
    tokens = jnp.asarray(SPEC.sequences(B, 4))
    h = jax.random.normal(keys[1], (B, L, 32))
    mp = {"enorm": 1.0 + 0.1 * jax.random.normal(keys[2], (32,)),
          "hnorm": jnp.ones((32,)),
          "w_eh": 0.2 * jax.random.normal(keys[3], (64, 32))}
    got = glm4_moe_lite.mtp_input(mp, jnp.take(table, tokens, axis=0), h,
                                  eps=1e-5, cdt=F32)
    with highest():
        want = ref.mtp_input(jnp.take(table, tokens[:, 1:], axis=0),
                             h[:, :-1], mp, SIZES)
    np.testing.assert_allclose(got[:, :-1], want, atol=2e-5)


# ---------------------------------------------------- ISSUE 48's six faults

def key_unrotated(monkeypatch):
    """The shared key (the one head every head reads) left unrotated."""
    whole = glm4_moe_lite.rotary
    monkeypatch.setattr(
        glm4_moe_lite, "rotary", lambda x, positions, theta:
        x if x.shape[2] == 1 else whole(x, positions, theta))


def query_norm_skipped(monkeypatch):
    """The query latent's RMS norm skipped (told by its gain's width)."""
    whole = glm4_moe_lite.rms_norm

    def skipping(x, gain, eps):
        return x.astype(jnp.float32) if x.shape[-1] not in (
            32, 16, 2048, 512) else whole(x, gain, eps)
    monkeypatch.setattr(glm4_moe_lite, "rms_norm", skipping)


def module_input(monkeypatch, emb_of=lambda emb: emb, h_of=lambda h: h):
    whole = glm4_moe_lite.mtp_input
    monkeypatch.setattr(
        glm4_moe_lite, "mtp_input", lambda mp, emb, h, **kw:
        whole(mp, emb_of(emb), h_of(h), **kw))


def second_head_gradient_dropped(monkeypatch):
    whole = glm4_moe_lite.Glm4MoeLite.mtp_logits
    monkeypatch.setattr(
        glm4_moe_lite.Glm4MoeLite, "mtp_logits", lambda self, params, m:
        whole(self, {**params, "head": jax.lax.stop_gradient(
            params["head"])}, m))


def lambda_zero(monkeypatch):
    whole = glm4_moe_lite.Glm4MoeLite.__init__

    def init(self, cfg):
        whole(self, cfg)
        self.mtp_weight = 0.0
    monkeypatch.setattr(glm4_moe_lite.Glm4MoeLite, "__init__", init)


FAULTS = {
    "shared-key-unrotated": key_unrotated,
    "query-latent-norm-skipped": query_norm_skipped,
    # Emb(t_i) in place of Emb(t_{i+1}): the rows rolled back
    "embedding-of-this-token": functools.partial(
        module_input, emb_of=lambda emb: jnp.roll(emb, 1, axis=1)),
    "module-cotangent-into-h-dropped": functools.partial(
        module_input, h_of=jax.lax.stop_gradient),
    "second-head-gradient-dropped": second_head_gradient_dropped,
    "lambda-zero": lambda_zero,
}
#: fault -> (the leaves of the cut whose gradient it has to move, by at
#: least how much)
MOVES = {
    "shared-key-unrotated": (("layers.0.mla_w_kva", "mtp.block.mla_w_qb"),
                             0.05),
    "query-latent-norm-skipped": (("layers.0.mla_w_qa",
                                   "mtp.block.mla_w_qb"), 0.05),
    "embedding-of-this-token": (("mtp.w_eh", "mtp.enorm"), 0.05),
    # (lambda's tenth of a gradient: a tenth of these leaves' moves)
    "module-cotangent-into-h-dropped": (("layers.0.mla_wo",
                                         "layers.0.shared_w_down"), 0.05),
    "second-head-gradient-dropped": (("head",), 0.05),
    "lambda-zero": (("mtp.w_eh", "mtp.block.mla_wo"), 0.99),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_broken_program_is_told_apart(monkeypatch, sound, fault):
    """The six faults of ISSUE 48 at small widths: each moves the gradient
    of the leaves it should by far more than the contract's tolerance,
    where the sound program's are the reference's (the contract's test)."""
    cfg, tokens, params, state, grads = sound
    FAULTS[fault](monkeypatch)
    broken = loss_gradients(get_model(cfg), params, state, tokens)
    names, least = MOVES[fault]
    assert least >= 100 * SPEC.grad_tol
    for name in names:
        assert leaf_gap(broken[name], grads[name]) > least, name


# ------------------------------------------------- heads of 256 by the kernel

def test_model_by_the_kernel_at_256_lanes_takes_the_same_step(monkeypatch):
    """The whole model with every block's causal scores by the kernel
    (interpreted, blocks of 128) at keys and values of 256, one key/value
    head a query head, against the XLA path: the same loss and
    gradients."""
    cfg = config(history_max_len=512, mla_nope_dim=192, mla_rope_dim=64,
                 mla_value_dim=256, attn_q_heads=1, attn_kv_heads=1,
                 decoder_layers=1, dense_layers=1, batch_size=1,
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
    monkeypatch.setattr(glm4_moe_lite, "attn_scores_by",
                        lambda *a, **k: "kernel")
    got, got_g = value_and_grad()
    assert model.step_notes["attn_scores"] == "kernel"
    assert model.step_notes["attn_score_blocks"] == "1/1"  # blocks of 512
    assert abs(float(got) - float(want)) < 1e-5
    for name, g in flat(got_g).items():
        assert leaf_gap(g, flat(want_g)[name]) < 1e-4, name


@pytest.mark.parametrize("widths, want", [
    ((192, 64, 256), "kernel"), ((128, 64, 128), "kernel"),
    ((192, 64, 96), "xla"), ((100, 64, 128), "xla")])
def test_the_kernel_takes_keys_and_values_it_has_the_lanes_for(monkeypatch,
                                                               widths, want):
    """Keys (nope + rope) and values both have to be whole or half lane
    lines; on a CPU it is XLA's path whatever the widths."""
    nope, rope, value = widths
    model = get_model(config(mla_nope_dim=nope, mla_rope_dim=rope,
                             mla_value_dim=value))
    ids = jnp.zeros((B, 1024), jnp.int32)
    assert model._paths(ids, True)["scores_by"] == "xla"
    monkeypatch.setattr(sdar_moe.jax, "default_backend", lambda: "tpu")
    assert model._paths(ids, True)["scores_by"] == want
    assert model._paths(ids, False)["scores_by"] == "xla"


# ------------------------------------- the parameters at the published widths

def test_parameter_counts_at_the_published_widths():
    """ISSUE 48's table from the model's own leaves (``jax.eval_shape``:
    nothing is allocated): layers 0-4 and the module, 5 of 20 heads, 8 of 64
    experts, a quarter of the vocabulary, every width as published."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        flags = json.load(f)["flags"]
    model = get_model(Config(**flags))
    shapes, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree, *names):
        return sum(int(np.prod(x.shape)) for n, x in tree.items()
                   if not names or n in names)

    layers = shapes["layers"]
    mla = ("mla_w_qa", "mla_q_norm", "mla_w_qb", "mla_w_kva", "mla_kv_norm",
           "mla_w_kvb", "mla_wo")
    assert count(layers["0"], *mla) == 7_505_152
    assert count(layers["0"], "mlp_w_gate", "mlp_w_up",
                 "mlp_w_down") == 62_914_560
    assert count(layers["1"], "w_gate", "w_up", "w_down") == 8 * 9_437_184
    assert count(layers["1"], "shared_w_gate", "shared_w_up",
                 "shared_w_down") == 9_437_184
    assert count(layers["1"], "router") == 131_072
    assert [count(layers[str(i)]) for i in range(5)] == [
        70_423_808] + [92_574_976] * 4
    mtp = shapes["mtp"]
    assert count(mtp["block"]) == 92_574_976
    assert count(mtp, "enorm", "hnorm", "w_eh", "final_norm") == 8_394_752
    assert count(shapes, "tok_emb", "head", "final_norm") == 158_599_168
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == 700_292_608
    assert round(16 * total / 1e9, 2) == 11.20
    assert round(12 * total / 1e9, 2) == 8.40
    # the bias is no parameter: 64 an expert block in the model state
    assert state[lfm2_moe.SELECT_BIAS].shape == (5, 64)
    assert {state[k].dtype for k in model.loss_parts} == {F32}


def test_the_model_is_a_stack_with_a_module_after_it():
    assert "glm4_moe_lite" not in registered_models()
    model = get_model(config())
    assert isinstance(model, kimi_linear.KimiLinear) and model.owns_loss
    assert model.kinds == (("mla", "mlp"), ("mla", "moe"), ("mla", "moe"))
    assert model.block_kinds == model.kinds + (("mla", "moe"),)
    assert model.moe_layers == (1, 2)
    assert model.init_bias().shape == (3, 8)
    bare = get_model(config(mtp_depth=0))
    assert bare.block_kinds == bare.kinds
    params, state = bare.init(jax.random.PRNGKey(0))
    assert "mtp" not in params and state[lfm2_moe.SELECT_BIAS].shape == (2, 8)
    tokens = jnp.asarray(SPEC.sequences(B, 2))
    per_seq, said = jax.jit(lambda p, s: bare.per_example_loss(
        p, s, {"hist_ids": tokens}, train=True, rng=None))(params, state)
    # without a module the loss is the main model's
    np.testing.assert_allclose(jnp.mean(per_seq), said["xent"], rtol=1e-6)
    assert float(said["mtp_xent"]) == 0.0


@pytest.mark.parametrize("model", ["deepfm", "sdar_moe", "kimi_linear",
                                   "lfm2_moe"])
def test_the_modules_flags_belong_to_this_model(model):
    for flag in ({"mla_q_rank": 12}, {"mla_nope_dim": 6},
                 {"mla_value_dim": 8}, {"mtp_depth": 1},
                 {"mtp_loss_weight": 0.3}):
        with pytest.raises(ValueError):
            Config(model=model, **flag)
