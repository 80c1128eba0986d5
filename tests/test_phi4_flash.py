"""``--model phi4_flash`` (Mamba-1 selective scans, differential attention
under a window and whole, a gated memory unit and a cross layer reading
other layers' tensors; dense, LayerNorm, tied table) at small widths on the
CPU, from seeded weights, against the plain reference
(``benchmark/reference_phi4_flash.py``). The decoders' shared tests are
``tests/decoder_contract.py``'s, read through ``SPEC`` (loss, every leaf's
gradient and three Adam steps of the stack, float32 and bfloat16; what
``Config`` refuses; the scopes and notes of the compiled step; a fit from
TFRecord shards); the scan runs them in chunks of 4 and segments of 8, so
that states pass between chunks and between segments. This model's own are
here: each kind's layer against the reference's; the cut is the model (a
20-layer reference stack fed the cut's leaves at 14-19); the scan against
the reference's loop over positions, padded and not; the band's allowed
pairs and the kernel's visited blocks; the kernel with values twice as wide
as keys through the Pallas interpreter; the producers' gradients whole; the
five broken programs told apart; the parameter counts at the published
widths. (The cell's own step compiled for a described v5e:
``tests/test_tpu_compile_phi4_flash.py``.)"""

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

from benchmark import reference_phi4_flash as ref  # noqa: E402
from benchmark import roofline_phi4_flash  # noqa: E402
from benchmark.reference_sdar_moe import leaf_gap  # noqa: E402
from decoder_contract import (DecoderContract, HybridStack,  # noqa: E402
                              Spec, highest, off_one)
from deepfm_tpu.models import (get_model, kimi_linear, phi4_flash,  # noqa: E402
                               registered_models, sdar_moe)
from deepfm_tpu.ops import block_attention  # noqa: E402

V, L, B = 60, 24, 2
CUT = "mamba,window_attention,mamba,full_attention,gmu,cross_attention"
#: The cut's own order (published layers 14-19) at small widths.
SMALL = dict(model="phi4_flash", feature_size=V, field_size=1,
             embedding_size=32, history_max_len=L, decoder_layers=6,
             layer_types=CUT, first_layer=14, attn_window=5, mamba_state=4,
             mamba_conv=4, mamba_expand=2, mamba_dt_rank=2,
             dense_mlp_width=48, attn_q_heads=8, attn_kv_heads=4,
             attn_head_dim=8, rms_norm_eps=1e-5, batch_size=B, l2_reg=0.0,
             learning_rate=1e-3, steps_per_loop=1)
SIZES = dict(head_dim=8, eps=1e-5, window=5, first_layer=14)
F32 = jnp.dtype("float32")
XLA = {"scores_by": "xla"}

SPEC = Spec(
    ref=ref, small=SMALL, sizes=SIZES, experts=False,
    # a bias on every key shifts a softmax row's scores alike: no gradient
    zero_gradient=("bk",),
    scopes=frozenset({"embed", "mamba", "mamba_scan", "gmu", "attn",
                      "attn_scores", "mlp", "head", "opt"}),
    no_scopes=frozenset({"fm", "tower", "cross", "bottom", "moe", "kda",
                         "conv"}),
    notes=lambda trainer: {"mamba_scan": "lockstep chunk4/segment8",
                           "attn_scores": "xla",
                           "head_grad":
                           "forward 3 products/chunk, 0.00 GB kept",
                           "mlp_kept": "0/%d" % len(trainer.model.kinds),
                           # the window, full and cross layers
                           "attn_kept": "0/3"},
    refusals=(
        ({"layer_types": "mamba,gmu"}, "layer_types"),
        ({"layer_types": CUT.replace("gmu", "conv")}, "layer_types"),
        ({"layer_types": "gmu,mamba,mamba,full_attention,gmu,"
                         "cross_attention"}, "ahead of every gmu"),
        ({"layer_types": "mamba,cross_attention,mamba,full_attention,gmu,"
                         "cross_attention"}, "ahead of every"),
        ({"first_layer": -1}, "first_layer"),
        ({"attn_window": 0}, "attn_window"),
        ({"mamba_state": 0}, "mamba_state"),
        ({"mamba_dt_rank": 0}, "mamba_dt_rank"),
        ({"attn_q_heads": 6}, "attn_q_heads"),
        ({"attn_kv_heads": 3}, "attn_kv_heads"),
        ({"dense_mlp_width": 0}, "dense_mlp_width"),
        ({"history_max_len": 1}, "history_max_len"),
        ({"moe_experts": 8}, "moe_"),
        ({"dense_layers": 1}, "dense_layers"),
        ({"kda_heads": 2}, "kda_heads"),
        ({"conv_taps": 2}, "conv_taps"),
        ({"mla_latent_dim": 8}, "mla_"),
        ({"task_type": "infer"}, "infer/export"),
        ({"online_mode": True}, "online_mode"),
        ({"mesh_model": 2}, "mesh_model"),
        ({"loss_type": "square_loss"}, "loss_type"),
    ))
config, flat = SPEC.config, SPEC.flat


@pytest.fixture(autouse=True, scope="module")
def small_chunks():
    """Chunks of 4 positions in segments of 8: 24 positions are 3 segments
    of 2 chunks, so states pass between chunks and between segments.
    (Module-scoped: the contract's class-scoped programs are built under
    it.)"""
    was = phi4_flash.MAMBA_CHUNK, phi4_flash.MAMBA_SEGMENT
    phi4_flash.MAMBA_CHUNK, phi4_flash.MAMBA_SEGMENT = 4, 8
    yield
    phi4_flash.MAMBA_CHUNK, phi4_flash.MAMBA_SEGMENT = was


def off_zero(key, tree):
    """``tree`` with every bias (a leaf of zeros) moved off zero."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        x + 0.1 * jax.random.normal(k, x.shape)
        if bool(jnp.all(x == 0.0)) else x for k, x in zip(keys, leaves)])


def moved(tree):
    return off_zero(jax.random.PRNGKey(6), off_one(jax.random.PRNGKey(5),
                                                   tree))


class TestPhi4Flash(DecoderContract):
    spec = SPEC
    test_fit_trains_from_tfrecord_shards = \
        HybridStack.test_fit_trains_from_tfrecord_shards
    fit_from_shards = HybridStack.fit_from_shards

    def _seeded(self, cfg):
        """... and the biases off zero."""
        model, params, state = super()._seeded(cfg)
        return model, off_zero(jax.random.PRNGKey(6), params), state

    def step_metrics_hold(self, metrics):
        assert float(metrics[phi4_flash.DECAY_MIN]) < 0.0
        assert not any(name.startswith("moe_") for name in metrics)

    def test_logits_and_loss_match_the_reference(self, seeded):
        _, params, _ = seeded
        counts = self.logits_and_loss(seeded)
        assert set(counts) == {phi4_flash.DECAY_MIN}
        assert float(counts[phi4_flash.DECAY_MIN]) < 0.0
        assert "head" not in params     # the head is the table

    def test_gradients_of_every_leaf_match_the_reference(self, short):
        got = self.gradients(short)
        # tied: every row of the table has a gradient, a token's or the
        # head's
        assert np.all(np.abs(got["tok_emb"]).sum(axis=1) > 0)


def test_the_model_is_built_and_is_no_ranker():
    model = get_model(config())
    assert isinstance(model, phi4_flash.Phi4Flash) and model.owns_loss
    assert "phi4_flash" not in registered_models()
    assert model.kinds == tuple((m, "mlp") for m in CUT.split(","))
    # the program's words for published layers 14-19 are the reference's
    assert [ref.kind(14 + i) for i in range(6)] == CUT.split(",")
    assert [phi4_flash.lambda_init(l) for l in (0, 14, 19)] == [
        ref.lambda_init(l) for l in (0, 14, 19)]
    assert ref.lambda_init(0) == pytest.approx(0.2)


# ------------------------------------------------------- one layer a kind

def a_layer(mixer):
    model = get_model(config())
    return model, moved(model._init_layer(jax.random.PRNGKey(3), mixer,
                                          "mlp"))


def what_is_read(mixer):
    """What a layer of kind ``mixer`` reads of earlier layers, made up, under
    the program's names and the reference's."""
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    if mixer == "gmu":
        m = jax.random.normal(keys[0], (B, L, 64))
        return {"memory": m}, {"memory": m}
    if mixer == "cross_attention":
        k, v = (jax.random.normal(key, (B, L, 4, 8)) for key in keys[1:])
        return {"shared_k": k, "shared_v": v}, {"shared": (k, v)}
    return {}, {}


@pytest.mark.parametrize("index, mixer", list(enumerate(CUT.split(","))))
def test_a_layer_matches_the_reference(index, mixer):
    """Each of the six held layers' forward, lambda_init by the published
    index 14 + ``index``, and what it leaves for later layers."""
    model, lp = a_layer(mixer)
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    read, ref_read = what_is_read(mixer)
    got, counts, left = jax.jit(functools.partial(
        model._layer, mixer, "mlp", layer=14 + index, **XLA))(x, lp, read)
    with highest():
        want, ref_left = jax.jit(lambda x, lp, r: ref.layer(
            x, lp, SIZES, index, r))(x, lp, ref_read)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert (phi4_flash.DECAY_MIN in counts) == (mixer == "mamba")
    assert set(left) == set(phi4_flash.LEAVES.get(mixer, ()))
    if mixer == "mamba":
        np.testing.assert_allclose(left["memory"], ref_left["memory"],
                                   atol=2e-5)
    if mixer == "full_attention":
        for mine, theirs in zip((left["shared_k"], left["shared_v"]),
                                ref_left["shared"]):
            np.testing.assert_allclose(mine, theirs, atol=2e-5)


def test_lambda_init_is_the_published_layers():
    """The same leaves at another published index give another layer."""
    model, lp = a_layer("full_attention")
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    at = [model._layer("full_attention", "mlp", x, lp, {}, layer=l, **XLA)[0]
          for l in (3, 17)]
    assert float(jnp.max(jnp.abs(at[0] - at[1]))) > 1e-2


def run_stack(model, params, x):
    """The program's layers on the stream x (its ``hidden`` without the
    lookup)."""
    left = {}
    for i, kind in enumerate(model.kinds):
        x, _, left = model._run_layer(i, kind, x, params["layers"][str(i)],
                                      left, XLA)
    return x


def test_the_cut_is_the_model():
    """A 20-layer stack in the reference (kinds and lambda_init by its own
    published indices 0-19), and the program's cut fed layers 14-19's leaves
    and layer 14's input: the same last stream."""
    kinds = [ref.kind(l) for l in range(20)]
    whole = get_model(config(decoder_layers=20, first_layer=0,
                             layer_types=",".join(kinds)))
    params = moved(whole.init(jax.random.PRNGKey(2))[0])
    names = {k: jnp.asarray(v) for k, v in flat(params).items()}
    x0 = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, L, 32))
    sizes = {**SIZES, "first_layer": 0}
    with highest():
        at_14 = jax.jit(lambda x, p: ref.stack(x, p, sizes, 0, 14))(x0, names)
        want = jax.jit(lambda x, p: ref.stack(x, p, sizes))(x0, names)
    cut = get_model(config())
    assert [m for m, _ in cut.kinds] == kinds[14:]
    held = {"layers": {str(i): params["layers"][str(14 + i)]
                       for i in range(6)}}
    got = jax.jit(lambda x, p: run_stack(cut, p, x))(at_14, held)
    np.testing.assert_allclose(got, want, atol=5e-5)
    # taken from the held index 0-5, lambda_init gives another stream
    wrong = get_model(config(first_layer=0))
    off = jax.jit(lambda x, p: run_stack(wrong, p, x))(at_14, held)
    assert float(jnp.max(jnp.abs(off - want))) > 1e-3


# ------------------------------------------------------------- the scan

def scan_inputs(length, width=12, n=4, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(keys[0], (B, length, width)),
            jnp.exp(jax.random.uniform(keys[1], (B, length, width),
                                       minval=-5.0, maxval=0.0)),
            -jnp.exp(jax.random.normal(keys[2], (width, n))),
            jax.random.normal(keys[3], (B, length, n)),
            jax.random.normal(keys[4], (B, length, n)),
            jax.random.normal(keys[5], (width,)))


def ref_scan(x, delta, a, b, c, skip):
    return jax.vmap(ref.recurrence, in_axes=(0, 0, None, 0, 0))(
        x, delta, a, b, c) + skip * x


@pytest.mark.parametrize("length, chunk, segment", [
    (24, 4, 8), (24, 8, 24), (21, 4, 8), (7, 64, 1024), (24, 1, 2)])
def test_the_scan_matches_a_loop_over_positions(length, chunk, segment):
    """Output and every input's gradient, whole segments and a padded one,
    one chunk and many; the count is the most negative whole-chunk
    log-decay."""
    args = scan_inputs(length)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, length, 12))

    def mine(*a):
        return phi4_flash.selective_scan(*a, chunk=chunk, segment=segment)

    got, low = jax.jit(mine)(*args)
    want = jax.jit(ref_scan)(*args)
    np.testing.assert_allclose(got, want, atol=2e-5)
    grads = [jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * w),
                              argnums=tuple(range(6))))(*args)
             for f in (lambda *a: mine(*a)[0], ref_scan)]
    for g, wnt in zip(*grads):
        assert leaf_gap(np.asarray(g), np.asarray(wnt)) < 1e-5
    x, delta, a = args[:3]
    pad = -length % chunk
    sums = jnp.pad(delta, ((0, 0), (0, pad), (0, 0))).reshape(
        B, -1, chunk, 12).sum(axis=2)
    np.testing.assert_allclose(
        low, jnp.min(sums[..., None] * a), rtol=1e-5)


@pytest.mark.parametrize("batch, length, width", [(2, 128, 2048),
                                                  (1, 64, 1024)])
def test_the_scan_kernels_match_a_loop_over_positions(batch, length, width):
    """``ops/pallas_selective_scan`` through the Pallas interpreter: two time
    blocks of 64 and two channel blocks of 1,024 (the state passes between
    the time blocks, forward and backward), and one of each: the output and
    every input's gradient, through ``selective_scan(by="kernel")``."""
    from deepfm_tpu.ops import pallas_selective_scan as pss
    assert pss.supported(width, length, "tpu")
    assert not pss.supported(width, length, "cpu")
    assert not pss.supported(width + 128, length, "tpu")
    assert not pss.supported(width, length + 8, "tpu")
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    args = (jax.random.normal(keys[0], (batch, length, width)),
            jnp.exp(jax.random.uniform(keys[1], (batch, length, width),
                                       minval=-5.0, maxval=-1.0)),
            -jnp.exp(jax.random.normal(keys[2], (width, 4))),
            jax.random.normal(keys[3], (batch, length, 4)),
            jax.random.normal(keys[4], (batch, length, 4)),
            jax.random.normal(keys[5], (width,)))
    w = jax.random.normal(keys[6], (batch, length, width))

    def mine(*a):
        return phi4_flash.selective_scan(*a, by="kernel", interpret=True)

    got, low = jax.jit(mine)(*args)
    np.testing.assert_allclose(got, jax.jit(ref_scan)(*args), atol=2e-5)
    grads = [jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * w),
                              argnums=tuple(range(6))))(*args)
             for f in (lambda *a: mine(*a)[0], ref_scan)]
    for g, wnt in zip(*grads):
        assert leaf_gap(np.asarray(g), np.asarray(wnt)) < 1e-5
    sums = args[1].reshape(batch, -1, 64, width).sum(axis=2)
    np.testing.assert_allclose(low, jnp.min(sums[..., None] * args[2]),
                               rtol=1e-5)


def test_scan_by_reads_the_backend_the_shapes_and_the_mesh():
    assert phi4_flash.scan_by(5120, 8192, backend="tpu") == "kernel"
    assert phi4_flash.scan_by(5120, 8192, backend="cpu") == "xla"
    assert phi4_flash.scan_by(5120, 8192, backend="tpu",
                              one_device=False) == "xla"
    assert phi4_flash.scan_by(64, 24, backend="tpu") == "xla"
    assert phi4_flash.scan_note("kernel") == "kernel steps64"


def unchunked(monkeypatch):
    """The scan's state not carried across a chunk: every chunk of 4 a
    sequence of its own."""
    whole = phi4_flash.selective_scan

    def broken(x, delta, a, b, c, skip, **kw):
        bsz, length, _ = x.shape
        parts = [v.reshape(bsz * length // 4, 4, v.shape[-1])
                 for v in (x, delta, b, c)]
        y, low = whole(parts[0], parts[1], a, parts[2], parts[3], skip, **kw)
        return y.reshape(x.shape), low
    monkeypatch.setattr(phi4_flash, "selective_scan", broken)


# ------------------------------------------------------------- the masks

def test_the_window_reads_itself_and_the_positions_before():
    allowed = np.asarray(phi4_flash.window(5)(jnp.arange(12), jnp.arange(12)))
    for q in range(12):
        assert list(np.nonzero(allowed[q])[0]) == list(
            range(max(0, q - 4), q + 1))
    flags = {**SMALL, "history_max_len": 12}
    pairs = roofline_phi4_flash.allowed_pairs(flags)
    assert pairs["window_attention"] == int(allowed.sum())
    assert pairs["full_attention"] == pairs["cross_attention"] == 12 * 13 // 2
    # the cell's: 512 * 513 / 2 + (8192 - 512) * 512
    cell = roofline_phi4_flash.allowed_pairs(
        {**SMALL, "history_max_len": 8192, "attn_window": 512})
    assert cell["window_attention"] == 4063488
    assert cell["full_attention"] == 33558528


def test_the_kernel_visits_the_band_and_the_triangle():
    """Blocks of 512 at 8,192 positions, a head: the diagonal and the one
    below it under the window of 512 (16 + 15), the lower triangle under
    the causal mask."""
    assert sdar_moe.attn_notes("kernel", phi4_flash.window(512), 8192, 2) \
        == {"attn_scores": "kernel", "attn_score_blocks": "31/256"}
    assert sdar_moe.attn_notes("kernel", kimi_linear.causal, 8192, 2)[
        "attn_score_blocks"] == "136/256"
    assert block_attention.supported("tpu", 8192, 64, 512)


@pytest.mark.parametrize("mask", ["window", "causal"])
def test_the_kernel_takes_values_twice_as_wide_as_keys(monkeypatch, mask):
    """``masked_scores`` with the pair's values (16 wide) under keys of 8...
    at the kernel's widths: keys of 64, values of 128, 256 positions, blocks
    of 128, through the Pallas interpreter, against ``_scores_xla``: output
    and the gradients of q, k, v."""
    monkeypatch.setattr(sdar_moe, "_scores_kernel", functools.partial(
        sdar_moe._scores_kernel, interpret=True, kernel_block=128))
    which = phi4_flash.window(100) if mask == "window" else kimi_linear.causal
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(keys[1], (1, 256, 2, 64))
    v = jax.random.normal(keys[2], (1, 256, 2, 128))
    w = jax.random.normal(keys[3], (1, 256, 4 * 128))

    def value_and_grads(scores_by):
        def loss(q, k, v):
            out = sdar_moe.masked_scores(q, k, v, mask=which, cdt=F32,
                                         scores_by=scores_by)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    got, want = value_and_grads("kernel"), value_and_grads("xla")
    assert got[0].shape == (1, 256, 4 * 128)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)


# ------------------------------------------- the producers' gradients whole

def drop_cotangent(monkeypatch, names):
    """What layers leave under ``names`` handed on without its cotangent."""
    whole = phi4_flash.Phi4Flash._run_layer

    def broken(self, i, kind, x, lp, left, *rest):
        return whole(self, i, kind, x, lp, {
            k: jax.lax.stop_gradient(v) if k in names else v
            for k, v in left.items()}, *rest)
    monkeypatch.setattr(phi4_flash.Phi4Flash, "_run_layer", broken)


def windowed_as_causal(monkeypatch):
    monkeypatch.setattr(phi4_flash, "window", lambda width: kimi_linear.causal)


def lambda_from_the_held_index(monkeypatch):
    whole = phi4_flash.lambda_init
    monkeypatch.setattr(phi4_flash, "lambda_init",
                        lambda layer: whole(layer - 14))


FAULTS = {
    "window-as-causal": windowed_as_causal,
    "lambda-from-held-index": lambda_from_the_held_index,
    "shared-kv-cotangent-dropped": functools.partial(
        drop_cotangent, names=("shared_k", "shared_v")),
    "memory-cotangent-dropped": functools.partial(
        drop_cotangent, names=("memory",)),
    "state-not-carried": unchunked,
}
#: fault -> the leaves of the cut whose gradient it has to move
MOVES = {
    "window-as-causal": ("layers.1.wq",),
    "lambda-from-held-index": ("layers.1.wv", "layers.5.wq"),
    "shared-kv-cotangent-dropped": ("layers.3.wk", "layers.3.wv"),
    "memory-cotangent-dropped": ("layers.2.mamba_w_x", "layers.2.mamba_d"),
    "state-not-carried": ("layers.0.mamba_a_log",),
}


def loss_gradients(model, params, state, tokens):
    def loss(p):
        per_seq, _ = model.per_example_loss(
            p, state, {"hist_ids": tokens}, train=True, rng=None)
        return jnp.mean(per_seq)
    return flat(jax.jit(jax.grad(loss))(params))


@pytest.fixture(scope="module")
def sound():
    """(config, tokens, params, state, the sound program's gradients)."""
    cfg = config()
    tokens = jnp.asarray(SPEC.sequences(B, 1))
    model = get_model(cfg)
    params = moved(model.init(jax.random.PRNGKey(0))[0])
    state = model.init_counts()
    return cfg, tokens, params, state, loss_gradients(model, params, state,
                                                      tokens)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_broken_program_is_told_apart(monkeypatch, sound, fault):
    """The five faults of ISSUE 44 at small widths: each moves the gradient
    of the leaves it should by far more than the contract's tolerance (a
    producer's gradient without a consumer's cotangent is not whole), where
    the sound program's are the reference's (the contract's test)."""
    cfg, tokens, params, state, grads = sound
    FAULTS[fault](monkeypatch)
    broken = loss_gradients(get_model(cfg), params, state, tokens)
    for name in MOVES[fault]:
        assert leaf_gap(broken[name], grads[name]) > 100 * SPEC.grad_tol, name


# --------------------------------------------------- the published widths

def test_parameter_counts_at_the_published_widths():
    """From the model's own leaves (shapes only), by kind of layer."""
    from benchmark import harness

    flags = harness.load_json(
        "configs", "phi-4-mini-flash-reasoning.json")["flags"]
    from deepfm_tpu.config import Config

    model = get_model(Config(**flags))
    shapes, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    by_layer = [count(shapes["layers"][str(i)]) for i in range(6)]
    assert by_layer == [119895040, 98322304, 119895040, 98322304, 104867840,
                        91766144]
    # (the table's padding rows, if any, are no parameters of the model)
    table = flags["feature_size"] * flags["embedding_size"]
    assert table == 64020480
    assert count({k: v for k, v in shapes.items()
                  if k.startswith("final_norm")}) == 5120
    assert sum(by_layer) + table + 5120 == 697094272
    assert roofline_phi4_flash.param_count(flags)["all"] == 697094272
