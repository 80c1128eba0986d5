"""``--model kimi_linear`` (hybrid KDA / MLA mixture-of-experts decoder) at
small widths on the CPU, from seeded weights, against the plain reference
(``benchmark/reference_kimi_linear.py``): each layer kind's forward; the
chunked delta-rule scan against the position-at-a-time recurrence at chunk
lengths that do and do not divide the sequence, at the strongest and the
weakest decay the seeding draws; the convolution's first positions; the
router's rules; the share test (every share's partial results of each layer
kind add up to the uncut reference's); loss, every leaf's gradient and three
Adam steps of the five-layer stack; the benchmark's placed router; pairs over
a small buffer; what ``Config`` refuses; the scopes in the compiled step;
and a fit from TFRecord shards."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_kimi_linear as ref  # noqa: E402
from benchmark.drivers import _program  # noqa: E402
from benchmark.reference_sdar_moe import leaf_gap, worst_leaf_gap  # noqa: E402
from deepfm_tpu.config import Config  # noqa: E402
from deepfm_tpu.data import example_codec, tfrecord  # noqa: E402
from deepfm_tpu.models import (get_model, kimi_linear,  # noqa: E402
                               registered_models, sdar_moe)
from deepfm_tpu.parallel import mesh as mesh_lib  # noqa: E402
from deepfm_tpu.train import Trainer  # noqa: E402

V, L, B = 60, 24, 2
SMALL = dict(model="kimi_linear", feature_size=V, field_size=1,
             embedding_size=32, history_max_len=L, decoder_layers=5,
             kda_heads=2, kda_head_dim=8, kda_conv=4, attn_every=4,
             attn_q_heads=2, attn_kv_heads=2, attn_head_dim=8,
             mla_latent_dim=16, mla_rope_dim=4, dense_layers=1,
             dense_mlp_width=64, moe_experts=8, moe_top_k=2,
             moe_expert_width=16, moe_experts_held=4, moe_first_expert=2,
             moe_pair_capacity=B * L * 2, moe_shared_width=16,
             moe_route_scale=2.446, rms_norm_eps=1e-5, batch_size=B,
             l2_reg=0.0, learning_rate=1e-3, steps_per_loop=1)
SIZES = dict(kda_head_dim=8, head_dim=8, rope_dim=4, eps=1e-5, top_k=2,
             route_scale=2.446, first_expert=2)
F32 = jnp.dtype("float32")
#: float32 program against float32 reference; bfloat16 compute has to miss it.
TOL = 2e-4


def config(**kw):
    return Config(**{**SMALL, "compute_dtype": "float32", **kw})


def flat(params):
    """The program's parameter tree under the reference's names, the token
    table cut to the vocabulary's rows."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    out = {_program.leaf_name(p): np.asarray(x) for p, x in leaves}
    out["tok_emb"] = out["tok_emb"][:V]
    return out


def sequences(n, seed):
    return np.random.default_rng(seed).integers(0, V, (n, L)).astype(np.int32)


def trainer_on(n_dev, cfg):
    return Trainer(cfg, mesh_info=mesh_lib.build_mesh(
        cfg, devices=jax.devices()[:n_dev]))


def batch_of(tokens):
    n = tokens.shape[0]
    return {"feat_ids": np.zeros((n, 1), np.int32),
            "feat_vals": np.ones((n, 1), np.float32),
            "label": np.zeros((n, 1), np.float32), "hist_ids": tokens,
            "hist_mask": np.ones(tokens.shape, np.float32)}


def off_one(key, tree):
    """``tree`` with every gain (a leaf of ones) moved off one."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        x + 0.1 * jax.random.normal(k, x.shape)
        if x.ndim == 1 and bool(jnp.all(x == 1.0)) else x
        for k, x in zip(keys, leaves)])


@pytest.fixture(scope="module")
def seeded():
    """(model, params with gains moved off one, state)."""
    model = get_model(config())
    params, state = model.init(jax.random.PRNGKey(0))
    return model, off_one(jax.random.PRNGKey(5), params), state


def uncut(kind, heads=8, experts=32, d=32):
    """One layer's leaves for ``heads`` heads of both mixers and ``experts``
    experts, every published ratio kept at small widths (head 8, latent 16,
    shared key 4, top-4), gains off one."""
    cfg = config(kda_heads=heads, attn_q_heads=heads, attn_kv_heads=heads,
                 moe_experts=experts, moe_experts_held=experts,
                 moe_first_expert=0, moe_top_k=4, embedding_size=d)
    lp = get_model(cfg)._init_layer(jax.random.PRNGKey(3), *kind)
    return off_one(jax.random.PRNGKey(4), lp)


KINDS = {"kda+mlp": ("kda", "mlp"), "mla+moe": ("mla", "moe"),
         "kda+moe": ("kda", "moe")}


# ------------------------------------------------ each layer kind's forward

@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_layer_matches_the_reference(kind):
    cfg = config(moe_top_k=4, moe_experts=32, moe_experts_held=32,
                 moe_first_expert=0, kda_heads=8, attn_q_heads=8,
                 attn_kv_heads=8, moe_pair_capacity=4 * B * L)
    model = get_model(cfg)
    lp = uncut(KINDS[kind])
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    got, counts = model._layer(*KINDS[kind], x, lp)
    with jax.default_matmul_precision("highest"):
        want = ref.layer(x, lp, {**SIZES, "top_k": 4, "first_expert": 0})
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert ("moe_pairs_held" in counts) == (kind != "kda+mlp")
    assert (kimi_linear.DECAY_MIN in counts) == (kind != "mla+moe")


def test_logits_and_loss_match_the_reference(seeded):
    model, params, state = seeded
    tokens = jnp.asarray(sequences(B, 0))
    logits, counts = model.apply(params, state, None, None, train=True,
                                 hist_ids=tokens)
    per_seq, _ = model.per_example_loss(params, state, {"hist_ids": tokens},
                                        train=True, rng=None)
    with jax.default_matmul_precision("highest"):
        want_loss, want_logits = ref.forward_loss(
            {k: jnp.asarray(v) for k, v in flat(params).items()}, tokens,
            SIZES)
    assert logits.shape == (B, L, V)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(jnp.mean(per_seq), want_loss, rtol=1e-6)
    assert int(counts["moe_pairs_over_buffer"]) == 0
    assert int(counts["moe_pairs_held"]) > 0
    assert float(counts[kimi_linear.DECAY_MIN]) < 0.0


# -------------------------------------------------- the delta-rule scan

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

    (_, (got, low)), got_grads = jax.value_and_grad(
        chunked, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    want, vjp = jax.vjp(by_position, *args)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for a, b in zip(got_grads, vjp(w)):
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


# ----------------------------------------------------------------- router

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


# ------------------------------------------------------------- the shares

@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """8 heads of each mixer and 32 experts top-4 over 4 shares: the shares'
    ``wo`` partial sums and routed experts' partial sums added, the shared
    expert, the dense MLP and the residual stream counted once, are the
    uncut reference's layer."""
    mixer, ffn = KINDS[kind]
    shares, heads, experts = 4, 8, 32
    lp = uncut(KINDS[kind], heads, experts)
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(2), (B, L, 32))
    sizes = {**SIZES, "top_k": 4, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        want = ref.layer(x, lp, sizes)

    def cols(a, r, per):        # share r's heads' columns, each ``per`` wide
        return a.reshape(*a.shape[:-1], heads, per)[
            ..., r * 2:(r + 1) * 2, :].reshape(*a.shape[:-1], 2 * per)

    def rows(a, r, per):
        return a.reshape(heads, per, -1)[r * 2:(r + 1) * 2].reshape(
            2 * per, -1)

    def share(r):
        out = dict(lp)
        if mixer == "kda":
            for n in ("kda_wq", "kda_wk", "kda_wv", "kda_conv_q",
                      "kda_conv_k", "kda_conv_v", "kda_w_fb", "kda_w_gb",
                      "kda_dt_bias"):
                out[n] = cols(lp[n], r, 8)
            out["kda_a_log"] = lp["kda_a_log"][r * 2:(r + 1) * 2]
            out["kda_w_b"] = lp["kda_w_b"][:, r * 2:(r + 1) * 2]
            out["kda_wo"] = rows(lp["kda_wo"], r, 8)
        else:
            out["mla_wq"] = cols(lp["mla_wq"], r, 12)
            out["mla_w_kvb"] = cols(lp["mla_w_kvb"], r, 16)
            out["mla_wo"] = rows(lp["mla_wo"], r, 8)
        if ffn == "moe":
            per = experts // shares
            for n in ("w_gate", "w_up", "w_down"):
                out[n] = lp[n][r * per:(r + 1) * per]
        return out

    mixed, routed, held = 0.0, 0.0, 0
    for r in range(shares):
        cfg = config(moe_top_k=4, moe_experts=experts,
                     moe_experts_held=experts // shares,
                     moe_first_expert=r * experts // shares,
                     moe_pair_capacity=4 * B * L)
        model, sp = get_model(cfg), share(r)
        if mixer == "kda":
            part, _ = kimi_linear.kda_mixer(sp, x, head_dim=8, eps=1e-5,
                                            cdt=F32)
        else:
            part = kimi_linear.mla_mixer(sp, x, head_dim=8, rope_dim=4,
                                         eps=1e-5, cdt=F32)
        mixed = mixed + part
    h = x + mixed
    for r in range(shares):
        if ffn == "moe":
            part, counts = sdar_moe.expert_layer(
                share(r), h, top_k=4, first_expert=r * experts // shares,
                capacity=4 * B * L, eps=1e-5, cdt=F32,
                route_by=get_model(config()).route_by)
            routed = routed + part
            held += int(counts["moe_pairs_held"])
    whole = kimi_linear.swiglu(lp, "mlp_" if ffn == "mlp" else "shared_", h,
                               eps=1e-5, cdt=F32)       # on every chip: once
    np.testing.assert_allclose(h + routed + whole, want, atol=3e-5)
    if ffn == "moe":
        assert held == B * L * 4        # every pair, once


# ---------------------------------------------- gradients and Adam's steps

def test_gradients_of_every_leaf_match_the_reference(seeded):
    model, params, state = seeded
    tokens = jnp.asarray(sequences(B, 1))

    def loss(p):
        per_seq, _ = model.per_example_loss(p, state, {"hist_ids": tokens},
                                            train=True, rng=None)
        return jnp.mean(per_seq)

    got = flat(jax.grad(loss)(params))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: ref.forward_loss(p, tokens, SIZES)[0])(
            {k: jnp.asarray(v) for k, v in flat(params).items()})
    assert set(got) == set(want)
    for name in want:
        assert leaf_gap(got[name], want[name]) < 1e-4, name
        assert np.linalg.norm(want[name]) > 0, name


def follow(compute_dtype, n_dev=1, steps=3):
    """(worst first-moment gap, worst parameter-change gap, losses) of
    ``steps`` trainer steps, on ``n_dev`` data replicas, against the
    reference's follower."""
    cfg = config(compute_dtype=compute_dtype, mesh_data=n_dev)
    trainer = trainer_on(n_dev, cfg)
    state = trainer.init_state(seed=3)
    start = flat(jax.tree.map(np.asarray, state.params))
    follower = ref.Follower(start, SIZES, cfg.learning_rate * n_dev)
    losses = []
    for step in range(steps):
        tokens = sequences(B, 10 + step)
        state, m = trainer.train_step(state,
                                      trainer.put_batch(batch_of(tokens)))
        losses.append((float(m["xent"]), follower.step(tokens)))
    got = flat(jax.tree.map(np.asarray, state.params))
    mu = flat(jax.tree.map(np.asarray, optax.tree_utils.tree_get(
        state.opt_state, "mu")))
    return (worst_leaf_gap(mu, follower.mu)[0],
            worst_leaf_gap({k: got[k] - start[k] for k in got},
                           {k: follower.params[k] - start[k]
                            for k in got})[0], losses)


@pytest.mark.parametrize("n_dev", [1, 2])
def test_three_adam_steps_match_the_reference(n_dev):
    mu_gap, change_gap, losses = follow("float32", n_dev)
    for got, want in losses:
        assert abs(got - want) < 1e-5 * max(1.0, abs(want))
    assert mu_gap < TOL
    assert change_gap < 0.02


def test_bfloat16_compute_misses_the_tolerance():
    mu_gap, change_gap, _ = follow("bfloat16")
    assert mu_gap > 10 * TOL and change_gap > 0.02


# ------------------------------------------------- the benchmark's seeding

def test_the_placed_router_holds_half_a_layers_positions():
    """At the cell's widths and traffic (2 x 8,192 tokens, Zipf 1.05 over
    20,480 rows), the seeded router by itself (a position's stream taken as
    its token's row of the table): every expert layer's held pairs lie
    within the configuration file's band of T/2."""
    from benchmark import harness, traffic_sequences, weights
    from benchmark.drivers import _program_kimi_linear as seeding

    conf = harness.load_json("configs", "kimi-linear-48b-a3b.json")
    cfg = _program.make_config(conf["flags"])
    plan = seeding.router_plan(cfg)
    heavy = list(traffic_sequences.tokens_of_ranks(np.arange(8), 20480))
    assert list(plan["rows"]) == heavy
    placed = plan["boost"] > 0
    assert not placed[0].any()                          # the dense layer
    assert (placed[1:].sum(-1) == cfg.moe_top_k).all()
    here = placed[1:, :, :cfg.moe_experts_held].sum(-1)
    assert (here.sum(0) == 2).all()         # a class: one in two layers
    assert (here.sum(1) == 4).all()         # a layer: every second class
    assert (placed[1:, :, :cfg.moe_experts_held].sum(1) <= 1).all()
    seed = 2 ** 31 + 5
    kw = {"feature_size": 20480, "padded_vocab": 20480,
          "embedding_scale": conf["assumed"]["embedding_scale"],
          "router_plan": plan}
    tokens = traffic_sequences.generate_tokens(
        2, 8192, 20480, seed, {"zipf_exponent": 1.05}).reshape(-1)
    ids, counts = np.unique(tokens, return_counts=True)
    table_salt = weights.leaf_salt(seed, "tok_emb")
    rows = weights.leaf_values(table_salt, (20480, 2304), rows=ids,
                               **{k: v for k, v in kw.items()
                                  if k != "router_plan"})
    xn = rows / np.sqrt(np.mean(rows * rows, axis=1, keepdims=True))
    low, high = conf["assumed"]["router_placement_band"]
    for layer in range(1, 5):
        name = f"layers.{layer}.router"
        router = seeding.seeded_leaf(
            {name: weights.leaf_salt(seed, name), "tok_emb": table_salt},
            name, (2304, 256), kw)
        top = np.argsort(-(xn @ router), axis=-1)[:, :8]
        held = ((top < 16).sum(-1) * counts).sum()
        assert low <= held / (len(tokens) / 2) <= high, (layer, held)
        for c, tok in enumerate(heavy):     # the classes go where placed
            assert set(top[list(ids).index(tok)]) == set(
                np.nonzero(placed[layer, c])[0])


def test_pairs_over_a_small_buffer_are_counted_not_lost():
    trainer = trainer_on(1, config(moe_pair_capacity=4))
    state = trainer.init_state(seed=1)
    seen = []
    for step in range(2):
        state, m = trainer.train_step(
            state, trainer.put_batch(batch_of(sequences(B, step))))
        seen.append(int(m["moe_pairs_over_buffer"]))
        assert float(m[kimi_linear.DECAY_MIN]) < 0.0
    assert 0 < seen[0] < seen[1]
    assert int(state.model_state["moe_pairs_over_buffer"]) == seen[1]


# ---------------------------------------------------------------- the rest

@pytest.mark.parametrize("change, says", [
    ({"tasks": "ctr,cvr"}, "tasks"),
    ({"embedding_update": "sparse"}, "embedding_update=sparse"),
    ({"task_type": "infer"}, "infer/export"),
    ({"servable_model_dir": "/tmp/x"}, "servable_model_dir"),
    ({"batch_norm": True}, "batch_norm"),
    ({"history_max_len": 1}, "history_max_len >= 2"),
    ({"decoder_layers": 0}, "decoder_layers"),
    ({"attn_every": 0}, "attn_every"),
    ({"kda_heads": 0}, "kda_heads"),
    ({"attn_kv_heads": 1}, "attn_q_heads = attn_kv_heads"),
    ({"mla_latent_dim": 0}, "mla_latent_dim"),
    ({"dense_layers": 6}, "dense_layers"),
    ({"dense_mlp_width": 0}, "dense_mlp_width"),
    ({"moe_shared_width": 0}, "moe_shared_width"),
    ({"moe_top_k": 9}, "moe_top_k"),
    ({"moe_first_expert": 6}, "moe_experts_held"),
    ({"moe_pair_capacity": 0}, "moe_pair_capacity"),
    ({"model": "sdar_moe"}, "belong to --model kimi_linear"),
    ({"model": "deepfm", "history_max_len": 0}, "belong to --model"),
])
def test_config_says_plainly_what_the_model_does_not_take(change, says):
    with pytest.raises(ValueError, match=says):
        config(**change)


def test_the_model_is_a_stack_of_three_layer_shapes():
    assert "kimi_linear" not in registered_models()    # the rankers' zoo
    model = get_model(config())
    assert model.kinds == (("kda", "mlp"), ("kda", "moe"), ("kda", "moe"),
                           ("mla", "moe"), ("kda", "moe"))
    assert model.embedding_param_names() == ("tok_emb",)
    assert model.uses_history and model.owns_loss
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert params["tok_emb"].shape == (model.padded_vocab, 32)
    assert params["head"].shape == (32, V)
    assert params["layers"]["0"]["mlp_w_gate"].shape == (32, 64)
    assert "router" not in params["layers"]["0"]
    assert params["layers"]["3"]["mla_w_kva"].shape == (32, 16 + 4)
    assert params["layers"]["4"]["w_gate"].shape == (4, 32, 16)
    assert params["layers"]["4"]["shared_w_gate"].shape == (32, 16)
    assert set(state) == {*kimi_linear.COUNT_NAMES, kimi_linear.DECAY_MIN}
    # the decays' rate and step size start in their published ranges
    lp = model.init(jax.random.PRNGKey(1))[0]["layers"]["1"]
    rate = np.exp(lp["kda_a_log"])
    step = np.log1p(np.exp(lp["kda_dt_bias"]))
    assert (rate >= 1).all() and (rate <= 16).all()
    assert (step > 0.99e-3).all() and (step < 0.101).all()


def test_compiled_step_carries_each_blocks_scope():
    trainer = trainer_on(1, config())
    scopes = set(trainer.step_op_scopes().values())
    assert {"embed", "kda", "kda_scan", "attn", "mlp", "moe", "head",
            "opt"} <= scopes
    assert not {"fm", "tower", "cross", "bottom"} & scopes
    layers = sum(ffn == "moe" for _, ffn in trainer.model.kinds)
    assert trainer.model.step_notes == {
        "kda_scan": "chunk64/sub16", "mla_scores": "xla", "moe_rows": "xla",
        "moe_rows_moved": "{moe_pairs_held}/%d" % (
            layers * trainer.cfg.moe_pair_capacity)}


def test_fit_trains_from_tfrecord_shards(tmp_path):
    """``Trainer.fit`` over the normal file pipeline (the tokens ride the
    record's history list), one step a dispatch: the loss falls and the
    counts ride the metrics."""
    from deepfm_tpu.train import tasks

    rng = np.random.default_rng(0)
    path = str(tmp_path / "tr-0.tfrecord")
    with tfrecord.TFRecordWriter(path) as w:
        for _ in range(16):
            # a sequence a model can learn: a walk of +1 from a random start
            row = (rng.integers(0, V) + np.arange(L)) % V
            w.write(example_codec.encode_ctr_example(
                0.0, np.zeros(1), np.ones(1), hist_ids=row))
    cfg = config(learning_rate=1e-2, log_steps=1000)
    trainer = trainer_on(1, cfg)
    pipeline = tasks.make_pipeline(cfg, [path], epochs=6)
    seen = []
    try:
        state, out = trainer.fit(trainer.init_state(seed=0), pipeline,
                                 hooks=[lambda s, m: seen.append(m)])
    finally:
        pipeline.close()
    losses = [float(m["xent"]) for m in seen]
    assert len(losses) == 6 * 16 // B
    assert losses[-1] < 0.6 * losses[0]
    assert np.isfinite(float(out["loss"]))
    assert int(seen[-1]["moe_pairs_held"]) > 0
    assert float(seen[-1][kimi_linear.DECAY_MIN]) < 0.0


@pytest.mark.parametrize("pass_most", [20480, 32], ids=["one-pass",
                                                        "two-passes"])
def test_model_by_the_row_kernels_takes_the_same_step(monkeypatch, pass_most):
    """The expert layers' rows taken and added by the row kernels
    (``ops/pallas_moe_rows``, forced on through the Pallas interpreter at
    rows of one 128-lane line) under this model's sigmoid router and shared
    expert: loss, counts and every leaf's gradient against the XLA rows, in
    one pass a layer and in two; the notes say which moved them."""
    import functools
    from deepfm_tpu.models import sdar_moe
    from deepfm_tpu.ops import pallas_moe_rows
    monkeypatch.setattr(sdar_moe, "PASS_ROWS", pass_most)
    cfg = config(embedding_size=128, decoder_layers=2, attn_every=2,
                 moe_pair_capacity=64)
    tokens = jnp.asarray(sequences(B, 3))

    def grads():
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))

        def loss(p):
            per_seq, counts = model.per_example_loss(
                p, state, {"hist_ids": tokens}, train=True, rng=None)
            return jnp.mean(per_seq), counts
        return model, jax.value_and_grad(loss, has_aux=True)(params)

    model, ((want, want_counts), want_g) = grads()
    assert model.step_notes["moe_rows"] == "xla"
    for name in ("gather", "combine"):
        monkeypatch.setattr(pallas_moe_rows, name, functools.partial(
            getattr(pallas_moe_rows, name), interpret=True))
    monkeypatch.setattr(pallas_moe_rows, "supported",
                        lambda width, positions, rows, backend=None: True)
    model, ((got, got_counts), got_g) = grads()
    assert model.step_notes["moe_rows"] == "kernel"
    assert model.step_notes["moe_rows_moved"] == "{moe_pairs_held}/64"
    assert 0 < int(got_counts["moe_pairs_held"]) == int(
        want_counts["moe_pairs_held"]) < 64
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        np.testing.assert_allclose(g, w, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))
