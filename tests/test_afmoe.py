"""``--model afmoe`` (rotated windowed layers and positionless global layers
of gated QK-normed grouped-query attention in one stack, norms on the
sublayers' outputs inside the residual sum, a scaled embedding, a sigmoid
router with a selection bias beside a shared expert, an untied head) at small
widths on the CPU, from seeded weights, against the plain reference
(``benchmark/reference_afmoe.py``). The decoders' shared tests are
``tests/decoder_contract.py``'s, read through ``SPEC`` (each layer kind's
forward; loss, every leaf's gradient and three Adam steps of the stack,
float32 and bfloat16; what ``Config`` refuses; the scopes and notes of the
compiled step; a fit from TFRecord shards), this model's state carrying a
selection bias that the reference is handed. This model's own are here: the
window's edge and the unrotated full layer, each against its neighbouring
form; the six forms ISSUE 53 breaks (``FAULTS``: what the cell's check has to
refuse at the timed sizes), each told apart from the sound step by the
contract's own tolerances; the 8 shares of an expert layer adding up to the
uncut reference's layer with the norm of the sum taken once; the reference's
blocks of queries changing nothing; the configuration file's ``parameters``
from the model's own leaves; which of ``sdar_moe.attention``'s steps each of
its four stacks takes; and the two masks' kernels through the Pallas
interpreter in the whole model. (The cell's own step, every width, compiled
for a described v5e: ``tests/test_tpu_compile_afmoe.py``.)"""

import dataclasses
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

from benchmark import reference_afmoe as ref  # noqa: E402
from benchmark.reference_sdar_moe import leaf_gap  # noqa: E402
from decoder_contract import (DecoderContract, HybridStack,  # noqa: E402
                              Spec, highest, off_one)
from deepfm_tpu.config import Config  # noqa: E402
from deepfm_tpu.models import (afmoe, get_model, kimi_linear,  # noqa: E402
                               lfm2_moe, registered_models, sdar_moe,
                               solar_open2)

V, L, B, WINDOW = 60, 24, 2, 5
#: The cut's kinds at small widths: the dense windowed layer, the full layer
#: and a windowed layer with experts.
TYPES = ("window_attention", "full_attention", "window_attention")
SMALL = dict(model="afmoe", feature_size=V, field_size=1,
             embedding_size=32, history_max_len=L, decoder_layers=3,
             layer_types=",".join(TYPES), attn_window=WINDOW,
             dense_layers=1, dense_mlp_width=48,
             attn_q_heads=4, attn_kv_heads=2, attn_head_dim=8,
             rope_theta=1e4, rms_norm_eps=1e-5,
             moe_experts=8, moe_top_k=2, moe_expert_width=16,
             moe_shared_width=16, moe_route_scale=2.826,
             moe_experts_held=4, moe_first_expert=2,
             moe_pair_capacity=B * L * 2, batch_size=B, l2_reg=0.0,
             learning_rate=1e-3, steps_per_loop=1)
SIZES = dict(head_dim=8, eps=1e-5, theta=1e4, window=WINDOW, top_k=2,
             route_scale=2.826, first_expert=2, layer_types=TYPES)
F32 = jnp.dtype("float32")
KINDS = {"window_attention+mlp": ("window_attention", "mlp"),
         "window_attention+moe": ("window_attention", "moe"),
         "full_attention+moe": ("full_attention", "moe")}


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


SPEC = Spec(
    ref=ref, small=SMALL, sizes=SIZES,
    scopes=frozenset({"embed", "attn", "attn_scores", "attn_scores_window",
                      "mlp", "moe", "head", "opt"}),
    no_scopes=frozenset({"kda", "kda_scan", "conv", "mamba"}),
    notes=lambda trainer: {
        "attn_scores": "xla", "moe_rows": "xla", "moe_products": "xla",
        "head_grad": "forward 3 products/chunk, 0.00 GB kept",
        # the dense SwiGLU and the two shared experts: none kept off a TPU
        "mlp_kept": "0/3",
        # the three attention layers: XLA's scores, nothing to keep
        "attn_kept": "0/3",
        "moe_rows_moved": "{moe_pairs_held}/%d" % (2 * 2 * B * L)},
    kinds=KINDS,
    layer_counts={"moe_pairs_held": "moe", sdar_moe.BIAS_MOVED: "moe"},
    layer_flags=dict(moe_experts_held=8, moe_first_expert=0),
    layer_sizes={"first_expert": 0}, layer_leaves=a_layer,
    # 8 expert shares of an expert layer (4 of 32 experts each), top-4 with
    # the selection bias; the mixer, the router, the shared expert and the
    # four norms whole on each
    share_kinds=("window_attention+moe", "full_attention+moe"),
    share_leaves=functools.partial(a_layer, experts=32, held=32, moe_top_k=4),
    expert_shares=8, share_experts=32,
    refusals=(
        ({"layer_types": "window_attention,full_attention"}, "layer_types"),
        ({"layer_types": "window_attention,conv,full_attention"},
         "layer_types"),
        ({"attn_window": 0}, "attn_window"),
        ({"attn_q_heads": 3}, "attn_q_heads"),
        ({"attn_head_dim": 7}, "attn_head_dim"),
        ({"dense_layers": 4}, "dense_layers"),
        ({"dense_mlp_width": 0}, "dense_mlp_width"),
        ({"moe_top_k": 9}, "moe_top_k"),
        ({"moe_shared_width": 0}, "moe_shared_width"),
        ({"moe_first_expert": 6}, "moe_experts_held"),
        ({"moe_pair_capacity": 0}, "moe_pair_capacity"),
        ({"history_max_len": 1}, "history_max_len"),
        ({"kda_heads": 2}, "kda_heads"),
        ({"attn_every": 2}, "attn_every"),
        ({"mla_latent_dim": 8}, "mla_"),
        ({"conv_taps": 4}, "conv_taps"),
        ({"first_layer": 1}, "first_layer"),
        ({"mtp_depth": 1}, "mtp_depth"),
        ({"task_type": "infer"}, "infer/export"),
        ({"online_mode": True}, "online_mode"),
        ({"mesh_model": 2}, "mesh_model"),
        ({"loss_type": "square_loss"}, "loss_type"),
    ))
config, flat = SPEC.config, SPEC.flat


class TestAfmoe(DecoderContract, HybridStack):
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
        # two expert layers of B x L positions: the bias moved some picks
        assert 0 < int(counts[sdar_moe.BIAS_MOVED]) < 2 * B * L
        np.testing.assert_array_equal(counts[lfm2_moe.SELECT_BIAS],
                                      state[lfm2_moe.SELECT_BIAS])
        assert lfm2_moe.SELECT_BIAS not in model.step_counts(counts)
        assert "head" in params     # untied

    @pytest.mark.parametrize("n_dev", [1, 2])
    def test_three_adam_steps_match_the_reference(self, n_dev, program,
                                                  followed):
        """... and the bias is after three steps what it was, bit for
        bit."""
        state = self.three_steps(n_dev, program, followed)
        before = np.asarray(a_bias(program.trainer.model))
        after = np.asarray(state.model_state[lfm2_moe.SELECT_BIAS])
        assert before.tobytes() == after.tobytes() and np.any(before != 0)

    def test_a_layer_matches_the_reference(self, kind, monkeypatch):
        """(the reference is told the layer's kind beside its sizes: both
        kinds have the same leaves)"""
        monkeypatch.setattr(TestAfmoe, "spec", dataclasses.replace(
            SPEC, layer_sizes={**SPEC.layer_sizes, "kind": KINDS[kind][0]}))
        super().test_a_layer_matches_the_reference(kind)

    def test_the_shares_add_up_to_the_uncut_layer(self, kind):
        """The configuration's layout at small widths: 8 expert shares of 4
        of 32 experts, top-4 with the bias. The routed experts' partial sums
        added, with what every chip computes alike counted once — the mixer
        with its output norm, the shared expert, and **the norm of the
        whole feed-forward sum**, which is taken of the reduced sum and not
        of a share's part — are the uncut reference's layer."""
        mixer, _ = KINDS[kind]
        experts, held, pairs, eps = 32, 4, 4 * B * L, 1e-5
        lp = SPEC.share_leaves(KINDS[kind])
        x = 2.0 * jax.random.normal(jax.random.PRNGKey(2), (B, L, 32))
        sizes = {**SIZES, "top_k": 4, "first_expert": 0}
        with highest():
            want = jax.jit(lambda x, lp: ref.layer(x, lp, sizes, mixer))(
                x, lp)
        model = get_model(config(
            moe_top_k=4, moe_experts=experts, moe_experts_held=held,
            moe_first_expert=0, moe_pair_capacity=pairs))
        a = x + sdar_moe.rms_norm(
            jax.jit(lambda sp: model._mixer(mixer, sp, x)[0])(lp),
            lp["norm1_post"], eps)
        routed = jax.jit(lambda sp, first: sdar_moe.expert_layer(
            sp, a, top_k=4, first_expert=first, capacity=pairs, eps=eps,
            cdt=F32, route_by=model.route_by))
        total, seen = kimi_linear.swiglu(lp, "shared_", a, eps=eps, cdt=F32), 0
        for first in range(0, experts, held):
            part, counts = routed(
                {**lp, **{n: lp[n][first:first + held]
                          for n in ("w_gate", "w_up", "w_down")}}, first)
            total = total + part
            seen += int(counts["moe_pairs_held"])
        assert seen == pairs            # every pair, once
        out = a + sdar_moe.rms_norm(total, lp["norm2_post"], eps)
        np.testing.assert_allclose(out, want, atol=3e-5)
        # and one share's layer alone is the reference's given that share
        share = {**lp, **{n: lp[n][8:12] for n in ("w_gate", "w_up",
                                                   "w_down")}}
        held_model = get_model(config(
            moe_top_k=4, moe_experts=experts, moe_experts_held=held,
            moe_first_expert=8, moe_pair_capacity=pairs))
        got, _ = jax.jit(functools.partial(held_model._layer, *KINDS[kind]))(
            x, share)
        with highest():
            want = ref.layer(x, share, {**sizes, "first_expert": 8}, mixer)
        np.testing.assert_allclose(got, want, atol=3e-5)


# --------------------------------- the two masks and what each kind rotates

def _mixer_out(kind, lp, x, **flags):
    model = get_model(config(**flags))
    return model._mixer(kind, lp, x)[0]


def test_the_windows_edge_is_exact():
    """Query i reads key j where ``0 <= i - j < window``: ``i - j = window -
    1`` is read and ``i - j = window`` is not, in the mask and in the layer
    (the reference at a window one narrower or one wider is another
    function)."""
    allowed = np.asarray(kimi_linear.window(WINDOW)(jnp.arange(L),
                                                    jnp.arange(L)))
    i, j = np.indices((L, L))
    np.testing.assert_array_equal(allowed, (j <= i) & (i - j < WINDOW))
    assert allowed[10, 10 - (WINDOW - 1)] and not allowed[10, 10 - WINDOW]
    lp = a_layer(KINDS["window_attention+moe"])
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    got = _mixer_out("window_attention", lp, x)
    xn = ref.rms_norm(x, lp["norm1"], 1e-5)
    with highest():
        gaps = {w: leaf_gap(got, ref.attention(xn, lp, {**SIZES, "window": w},
                                               True))
                for w in (WINDOW - 1, WINDOW, WINDOW + 1)}
    assert gaps[WINDOW] < 1e-5
    assert min(gaps[WINDOW - 1], gaps[WINDOW + 1]) > 0.01
    # a window as long as the sequence is the causal mask, rotated
    with highest():
        wide = ref.attention(xn, lp, {**SIZES, "window": L}, True)
    assert leaf_gap(_mixer_out("window_attention", lp, x, attn_window=L),
                    wide) < 1e-5


def test_the_full_layer_rotates_nothing_and_the_windowed_layer_does():
    lp = a_layer(KINDS["full_attention+moe"])
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    xn = ref.rms_norm(x, lp["norm1"], 1e-5)
    with highest():
        unrotated = ref.attention(xn, lp, SIZES, False)
        # (the rotated causal layer: a window as long as the sequence)
        rotated = ref.attention(xn, lp, {**SIZES, "window": L}, True)
    assert leaf_gap(rotated, unrotated) > 0.05
    assert leaf_gap(_mixer_out("full_attention", lp, x), unrotated) < 1e-5
    # no position reaches a full layer: it is the same function of a
    # sequence's tokens wherever the sequence starts (a prefix of other
    # tokens moves later outputs only through what they read)
    got = _mixer_out("window_attention", lp, x, attn_window=L)
    assert leaf_gap(got, rotated) < 1e-5


# ------------------------------ the forms the cell's check has to refuse

def _no_rotation_choice(monkeypatch):
    """The full layer rotated as the windowed ones are."""
    sound = afmoe.Afmoe._mixer

    def rotated(self, mixer, lp, x, **kw):
        if mixer != "full_attention":
            return sound(self, mixer, lp, x, **kw)
        return sdar_moe.attention(
            lp, x, jnp.arange(x.shape[1]), mask=self.masks[mixer],
            head_dim=self.cfg.attn_head_dim, eps=self.cfg.rms_norm_eps,
            theta=self.cfg.rope_theta, cdt=self.cdt,
            scores_scope="attn_scores", **kw), {}
    monkeypatch.setattr(afmoe.Afmoe, "_mixer", rotated)


def _no_gate(monkeypatch):
    sound = sdar_moe.attention
    monkeypatch.setattr(afmoe, "attention", lambda lp, *a, **kw: sound(
        {k: v for k, v in lp.items() if k != "wg"}, *a, **kw))


def _no_post_norm(monkeypatch):
    """The feed-forward's output norm skipped (the mixer's stays)."""
    sound = kimi_linear.KimiLinear._layer
    monkeypatch.setattr(
        kimi_linear.KimiLinear, "_layer",
        lambda self, mixer, ffn, x, lp, **kw: sound(
            self, mixer, ffn, x,
            {k: v for k, v in lp.items() if k != "norm2_post"}, **kw))


def _no_embed_scale(monkeypatch):
    sound = afmoe.Afmoe.__init__

    def init(self, cfg):
        sound(self, cfg)
        self.embed_scale = 1.0
    monkeypatch.setattr(afmoe.Afmoe, "__init__", init)


def _route_scale_one(monkeypatch):
    sound = afmoe.Afmoe.__init__

    def init(self, cfg):
        sound(self, cfg)
        self.route_by = functools.partial(self.route_by, scale=1.0)
    monkeypatch.setattr(afmoe.Afmoe, "__init__", init)


def _windowed_run_causal(monkeypatch):
    monkeypatch.setattr(afmoe, "window", lambda width: kimi_linear.causal)


#: ISSUE 53's injected faults: name -> what breaks the program so
#: (``monkeypatch``-like: anything with ``setattr(target, name, value)``).
FAULTS = {"full_layer_rotated": _no_rotation_choice,
          "gate_dropped": _no_gate,
          "post_norm_skipped": _no_post_norm,
          "embed_scale_dropped": _no_embed_scale,
          "route_scale_one": _route_scale_one,
          "windowed_run_causal": _windowed_run_causal}


@pytest.fixture(scope="module")
def sound_gradient():
    model, params, state, tokens = _gradient_inputs()
    return _gradient(model, params, state, tokens)


def _gradient_inputs():
    model = get_model(config())
    params, state = model.init(jax.random.PRNGKey(0))
    params = off_one(jax.random.PRNGKey(5), params)
    state = {**state, lfm2_moe.SELECT_BIAS: a_bias(model)}
    return model, params, state, jnp.asarray(SPEC.sequences(B, 1))


def _gradient(model, params, state, tokens):
    def loss(p):
        per_seq, _ = model.per_example_loss(
            p, state, {"hist_ids": tokens}, train=True, rng=None)
        return jnp.mean(per_seq)
    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    return float(value), flat(grads)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_injected_fault_misses_the_contracts_tolerance(
        monkeypatch, fault, sound_gradient):
    """A program with one of the six forms dropped is another function:
    its loss or some leaf's gradient is further from the sound program's
    than the contract lets the sound program be from the reference
    (``grad_tol`` 1e-4), a hundredfold."""
    FAULTS[fault](monkeypatch)
    _, params, state, tokens = _gradient_inputs()
    loss, grads = _gradient(get_model(config()), params, state, tokens)
    want_loss, want = sound_gradient
    worst = max(leaf_gap(grads[n], want[n]) for n in want)
    assert worst > 100 * SPEC.grad_tol or abs(loss - want_loss) > 1e-3


# ------------------------------------------------ the reference's blocks

def test_the_references_blocks_of_queries_change_nothing(monkeypatch):
    """At a block smaller than the sequence and ragged against it (24
    positions in blocks of 16: the last block padded, its rows dropped) the
    reference's loss and logits are what they are in one block, under both
    masks."""
    model, params, state, tokens = _gradient_inputs()
    p = {k: jnp.asarray(v) for k, v in flat(params).items()}
    with highest():
        whole = ref.forward_loss(p, tokens, state[lfm2_moe.SELECT_BIAS],
                                 SIZES)
        monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
        blocked = ref.forward_loss(p, tokens, state[lfm2_moe.SELECT_BIAS],
                                   SIZES)
    np.testing.assert_allclose(blocked[0], whole[0], rtol=1e-6)
    np.testing.assert_allclose(blocked[1], whole[1], atol=2e-5)


# ------------------------------------- the parameters at the published widths

def test_the_files_parameters_are_the_models_own_leaves():
    """ISSUE 53's table from the model's own leaves (``jax.eval_shape``:
    nothing is allocated): the cut's five layers, 16 of 128 experts, an
    eighth of the vocabulary, every width as published."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        file = json.load(f)
    model = get_model(Config(**file["flags"]))
    shapes, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree, *names):
        return sum(int(np.prod(x.shape)) for n, x in tree.items()
                   if not names or n in names)

    layers = shapes["layers"]
    assert model.kinds == (
        ("window_attention", "mlp"), ("window_attention", "moe"),
        ("full_attention", "moe"), ("window_attention", "moe"),
        ("window_attention", "moe"))
    attn = count(layers["0"], "wq", "wk", "wv", "wg", "wo", "q_norm",
                 "k_norm")
    assert attn == 3 * 8_388_608 + 2 * 1_048_576 + 256 == 27_263_232
    assert count(layers["0"], "norm1", "norm2", "norm1_post",
                 "norm2_post") == 8_192
    assert count(layers["0"], "mlp_w_gate", "mlp_w_up",
                 "mlp_w_down") == 37_748_736
    assert count(layers["1"], "w_gate", "w_up", "w_down") == 100_663_296
    assert count(layers["1"], "shared_w_gate", "shared_w_up",
                 "shared_w_down") == 6_291_456
    assert count(layers["1"], "router") == 262_144
    assert [count(layers[str(i)]) for i in range(5)] == [
        65_020_160] + [134_488_320] * 4
    assert count(shapes, "tok_emb", "head") == 2 * 25_024 * 2_048
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == file["parameters"] == 705_473_792
    assert round(16 * total / 1e9, 2) == 11.29
    # the bias is no parameter: 128 an expert layer in the model state
    assert state[lfm2_moe.SELECT_BIAS].shape == (4, 128)
    # the file's widths are the catalog row's; the cut is in ``reduced``
    assert file["reduced"] == ["num_hidden_layers", "num_experts_held",
                               "vocabulary_rows"]
    assert (file["hidden_size"], file["num_attention_heads"],
            file["num_key_value_heads"], file["head_dim"],
            file["sliding_window"], file["intermediate_size"],
            file["moe_intermediate_size"], file["num_experts"],
            file["num_experts_per_tok"], file["route_scale"]) == (
                2048, 32, 4, 128, 2048, 6144, 1024, 128, 8, 2.826)
    assert len(file["source"]) <= 200


# --------------------------- one attention function, each stack its steps

@pytest.mark.parametrize("stack, norms, rotates, gates", [
    ("sdar_moe", True, True, False), ("lfm2_moe", True, True, False),
    ("solar_open2", False, False, True), ("afmoe_window", True, True, True),
    ("afmoe_full", True, False, True)])
def test_each_stack_takes_its_own_steps_of_the_one_attention(
        stack, norms, rotates, gates):
    """``sdar_moe.attention`` with a step's leaves absent is the function
    without that step: against the equations written out here, under the
    causal mask, for what each of the four stacks hands it."""
    d, hd, s = 32, 8, 12
    keys = jax.random.split(jax.random.PRNGKey(0), 10)
    lp = {"norm1": 1.0 + 0.1 * jax.random.normal(keys[0], (d,)),
          "wq": jax.random.normal(keys[1], (d, 4 * hd)) * 0.2,
          "wk": jax.random.normal(keys[2], (d, 2 * hd)) * 0.2,
          "wv": jax.random.normal(keys[3], (d, 2 * hd)) * 0.2,
          "wo": jax.random.normal(keys[6], (4 * hd, d)) * 0.2}
    if norms:
        lp["q_norm"] = 1.0 + 0.1 * jax.random.normal(keys[4], (hd,))
        lp["k_norm"] = 1.0 + 0.1 * jax.random.normal(keys[5], (hd,))
    if gates:
        lp["wg"] = jax.random.normal(keys[8], (d, 4 * hd)) * 0.2
    x = jax.random.normal(keys[7], (B, s, d))
    theta = 1e4 if rotates else None
    if stack == "solar_open2":      # through the model's own names
        got = solar_open2.gqa_mixer(
            {"norm1": lp["norm1"], "gqa_w_gate": lp["wg"],
             **{"gqa_" + w: lp[w] for w in ("wq", "wk", "wv", "wo")}}, x,
            head_dim=hd, eps=1e-5, cdt=F32)
    else:
        got = sdar_moe.attention(lp, x, jnp.arange(s),
                                 mask=kimi_linear.causal, head_dim=hd,
                                 eps=1e-5, theta=theta, cdt=F32)
    with highest():
        xn = ref.rms_norm(x, lp["norm1"], 1e-5)
        q, k, v = ((xn @ lp[w]).reshape(B, s, -1, hd)
                   for w in ("wq", "wk", "wv"))
        if norms:
            q = ref.rms_norm(q, lp["q_norm"], 1e-5)
            k = ref.rms_norm(k, lp["k_norm"], 1e-5)
        if rotates:
            q, k = (ref.rotary(a, jnp.arange(s), 1e4) for a in (q, k))
        k, v = (jnp.repeat(a, 2, axis=2) for a in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, s, -1)
        if gates:
            o = o * jax.nn.sigmoid(xn @ lp["wg"])
        want = o @ lp["wo"]
    np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------------------ configuration

@pytest.mark.parametrize("model", ["deepfm", "sdar_moe", "kimi_linear",
                                   "solar_open2", "lfm2_moe",
                                   "glm4_moe_lite"])
def test_the_window_belongs_to_the_stacks_with_windowed_layers(model):
    with pytest.raises(ValueError):
        Config(model=model, attn_window=8)


def test_the_model_is_a_stack_by_its_list():
    assert "afmoe" not in registered_models()
    model = get_model(config())
    assert isinstance(model, kimi_linear.KimiLinear) and model.owns_loss
    assert model.kinds == tuple(zip(TYPES, ("mlp", "moe", "moe")))
    assert model.moe_layers == (1, 2)
    assert model.embed_scale == np.sqrt(32)
    params, state = model.init(jax.random.PRNGKey(0))
    mixer = {"norm1", "norm1_post", "norm2", "norm2_post", "wq", "wk", "wv",
             "wg", "wo", "q_norm", "k_norm"}
    assert set(params["layers"]["0"]) == mixer | {
        "mlp_w_gate", "mlp_w_up", "mlp_w_down"}
    # both kinds hold the same leaves: the kind is the list's, not a leaf's
    assert set(params["layers"]["1"]) == set(params["layers"]["2"]) \
        == mixer | {"router", "w_gate", "w_up", "w_down", "shared_w_gate",
                    "shared_w_up", "shared_w_down"}
    assert not np.any(np.asarray(state[lfm2_moe.SELECT_BIAS]))


# ------------------------------------ both masks' kernels in the whole model

def test_model_by_the_kernel_under_both_masks_takes_the_same_step(
        monkeypatch):
    """The whole model with its windowed and its causal scores by the block
    kernel (interpreted, blocks of 128 over 512 positions, window 200)
    against the XLA path: the same loss and gradients, and the notes say how
    many blocks each mask's grid visits."""
    cfg = config(history_max_len=512, attn_head_dim=64, attn_q_heads=2,
                 attn_kv_heads=1, decoder_layers=2, attn_window=200,
                 layer_types="window_attention,full_attention", batch_size=1,
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
    monkeypatch.setattr(afmoe, "attn_scores_by", lambda *a, **k: "kernel")
    got, got_g = value_and_grad()
    assert model.step_notes["attn_scores"] == "kernel"
    # (the notes count blocks of 512: one)
    assert model.step_notes["attn_score_blocks"] == "1/1"
    assert model.step_notes["attn_window_blocks"] == "1/1"
    assert abs(float(got) - float(want)) < 1e-5
    for name, g in flat(got_g).items():
        assert leaf_gap(g, flat(want_g)[name]) < 1e-4, name
