"""``--model sdar_moe`` (block-diffusion MoE decoder) at small widths on the
CPU, from seeded weights, against the plain reference
(``benchmark/reference_sdar_moe.py``). The decoders' shared tests are
``tests/decoder_contract.py``'s, read through ``SPEC`` (logits, loss, every
leaf's gradient and three Adam steps in float32, on one device and on two
data replicas, with bfloat16 compute required to miss the same tolerance;
pairs over a small buffer; what ``Config`` refuses; the scopes in the
compiled step; the row kernels), this model's loss being over the positions
its own noise masked. This model's own are here: the noise against the
objective's statement of it; the mask against a hand-written example; the
router against a hand-computed top-k; the share test (the 8 shares' partial
results of an attention block and of an expert layer add up to the uncut
reference's); the block-masked attention kernel; where the kernels are
taken; and the launcher's train -> eval on a tiny file."""

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

from benchmark import reference_sdar_moe as ref  # noqa: E402
from decoder_contract import (DecoderContract, RowKernels,  # noqa: E402
                              SmallBuffer, Spec, force_row_kernels,
                              trainer_on)
from deepfm_tpu.data import example_codec, tfrecord  # noqa: E402
from deepfm_tpu.models import get_model, registered_models, sdar_moe  # noqa: E402
from deepfm_tpu.ops import pallas_grouped_dot  # noqa: E402

V, L, B = 50, 8, 4
SMALL = dict(model="sdar_moe", feature_size=V, field_size=1,
             embedding_size=32, history_max_len=L, decoder_layers=2,
             attn_q_heads=4, attn_kv_heads=2, attn_head_dim=8,
             moe_experts=8, moe_top_k=2, moe_expert_width=16,
             moe_experts_held=4, moe_first_expert=2,
             moe_pair_capacity=B * 2 * L * 2, diffusion_block=4,
             batch_size=B, l2_reg=0.0, learning_rate=1e-3, steps_per_loop=1)
SIZES = dict(head_dim=8, top_k=2, first_expert=2, eps=1e-6, theta=1e6,
             block=4)

SPEC = Spec(
    ref=ref, small=SMALL, sizes=SIZES,
    scopes=frozenset({"embed", "attn", "moe", "head", "opt"}),
    reserved_rows=1, draws_noise=True, grad_tol=1e-5, logits_atol=1e-5,
    small_buffer=8,
    row_kernels=dict(flags=dict(embedding_size=128),
                     passes={"one-pass": sdar_moe.PASS_ROWS},
                     moved=2 * B * 2 * L * 2),
    refusals=(
        ({"tasks": "ctr,cvr"}, "tasks"),
        ({"embedding_update": "sparse"}, "embedding_update=sparse"),
        ({"task_type": "infer"}, "infer/export"),
        ({"task_type": "export"}, "infer/export"),
        ({"servable_model_dir": "/tmp/x"}, "servable_model_dir"),
        ({"batch_norm": True}, "batch_norm"),
        ({"loss_type": "square_loss"}, "loss_type"),
        ({"embedding_buckets": "64,64"}, "embedding_buckets"),
        ({"history_max_len": 6}, "multiple of diffusion_block"),
        ({"history_max_len": 0}, "multiple of diffusion_block"),
        ({"decoder_layers": 0}, "decoder_layers"),
        ({"attn_q_heads": 3}, "attn_q_heads"),
        ({"moe_top_k": 9}, "moe_top_k"),
        ({"moe_first_expert": 6}, "moe_experts_held"),
        ({"moe_pair_capacity": 0}, "moe_pair_capacity"),
        ({"diffusion_t_min": 0.0}, "diffusion_t_min"),
        ({"model": "deepfm", "history_max_len": 0},
         "belong to --model sdar_moe"),
    ))
config, sequences = SPEC.config, SPEC.sequences


def draw(key, tokens):
    """(noisy tokens, t a block) as the program's step of ``key`` draws."""
    return sdar_moe.draw_noise(key, jnp.asarray(tokens), block=4, t_min=1e-3,
                               mask_id=V - 1)


class TestSdarMoE(DecoderContract, SmallBuffer, RowKernels):
    spec = SPEC

    def reference_loss(self, params, tokens, state, rng):
        """Over the positions the program's own draw of ``rng`` masked."""
        noisy, t = draw(rng, tokens)
        return ref.forward_loss(params, noisy, tokens, t, SIZES)

    def reference_inputs(self, tokens, base, step, n_dev):
        """A replica draws from the step's key folded with its index."""
        key = jax.random.fold_in(base, step)
        per = B // n_dev
        noise = [draw(jax.random.fold_in(key, s) if n_dev > 1 else key,
                      tokens[s * per:(s + 1) * per]) for s in range(n_dev)]
        return (np.concatenate([n for n, _ in noise]), tokens,
                np.concatenate([t for _, t in noise]))

    def test_logits_and_loss_match_the_reference(self, seeded):
        counts = self.logits_and_loss(seeded)
        tokens = sequences(B, 0)
        noisy, _ = draw(self.key(7), tokens)
        assert int(counts["masked_positions"]) == int(
            jnp.sum(noisy != tokens))

    def test_pairs_over_a_small_buffer_are_counted_not_lost(self,
                                                            monkeypatch):
        lp = uncut_layer(experts=4)
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 32))
        kw = dict(top_k=2, first_expert=0, eps=1e-6,
                  cdt=jnp.dtype("float32"))
        whole, all_counts = sdar_moe.expert_layer(lp, x, capacity=32, **kw)
        assert int(all_counts["moe_pairs_held"]) == 32      # all 4 are held
        assert int(all_counts["moe_pairs_over_buffer"]) == 0
        cut, counts = sdar_moe.expert_layer(lp, x, capacity=20, **kw)
        with monkeypatch.context() as patched:
            patched.setattr(sdar_moe, "PASS_ROWS", 16)
            for capacity in (32, 20):   # two passes of 16 and of 10: the same
                twice, two = sdar_moe.expert_layer(lp, x, capacity=capacity,
                                                   **kw)
                assert int(two["moe_pairs_over_buffer"]) == 32 - capacity
                np.testing.assert_allclose(
                    twice, whole if capacity == 32 else cut, atol=1e-5)
        assert int(counts["moe_pairs_held"]) == 32
        assert int(counts["moe_layer_pairs_max"]) == 32     # one layer's
        assert int(counts["moe_pairs_over_buffer"]) == 12
        assert int(counts["moe_expert_load_max"]) == int(
            all_counts["moe_expert_load_max"]) >= 8
        assert np.isfinite(np.asarray(cut)).all()
        assert not np.allclose(cut, whole)
        # and a trainer's state keeps the run's total
        super().test_pairs_over_a_small_buffer_are_counted_not_lost()

    def test_compiled_step_carries_each_blocks_scope(self):
        """Of two steps a dispatch (``multi_step``: the other decoders'
        shared program is ``train_step``'s), so a program of its own."""
        scopes = set(trainer_on(1, config(steps_per_loop=2))
                     .step_op_scopes().values())
        assert SPEC.scopes <= scopes and not SPEC.no_scopes & scopes

    def test_a_cpu_step_makes_its_scores_with_xla(self, seeded):
        """On this backend the model's step takes the chunked XLA path and
        says so (what ``train.log_sync`` carries while tracing is on)."""
        model, params, _ = seeded
        jax.eval_shape(lambda p: model.hidden(
            p, jnp.zeros((B, 2 * L), jnp.int32)), params)
        assert model.step_notes == {
            "attn_scores": "xla", "moe_rows": "xla", "moe_products": "xla",
            "attn_kept": "0/%d" % model.cfg.decoder_layers,
            "moe_rows_moved": "{moe_pairs_held}/%d" % (
                model.cfg.decoder_layers * model.cfg.moe_pair_capacity)}


def _drawn(seed, kind):
    """(noisy, tokens, t) at the cell's shape a step (2 x 4,096, block 4):
    the program's draw, or a draw with one fault in it."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 18991, (2, 4096))
    if kind == "sound":
        noisy, t = sdar_moe.draw_noise(
            jax.random.PRNGKey(seed), jnp.asarray(tokens), block=4,
            t_min=1e-3, mask_id=18991)
        return np.asarray(noisy), tokens, np.asarray(t)
    t = rng.uniform(0.5 if kind == "t from 0.5" else 1e-3, 1.0, (2, 1024))
    p = {"t squared": t * t, "one less t": 1.0 - t,
         "another block's t": np.roll(t, 1, axis=1)}.get(kind, t)
    masked = rng.uniform(size=(2, 4096)) < np.repeat(p, 4, axis=1)
    mask_id = 0 if kind == "another token" else 18991
    return np.where(masked, mask_id, tokens), tokens, t


@pytest.mark.parametrize("kind", [
    "sound", "t squared", "one less t", "another block's t", "t from 0.5",
    "another token"])
def test_the_reference_tells_a_sound_draw_of_the_noise_from_a_wrong_one(
        kind):
    """``noise_z`` holds the step's noise to the objective's statement of it
    and takes nothing of the program's: the program's own draw passes the
    cell's limit (5) on every seed, a draw with one fault fails it."""
    z = [ref.noise_z(*_drawn(seed, kind), block=4, t_min=1e-3,
                     mask_id=18991) for seed in range(8)]
    assert max(z) < 4.0 if kind == "sound" else min(z) > 5.0, z


def uncut_layer(seed=0, d=32, hd=8, n_q=16, n_kv=4, experts=16, f=16):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def w(*shape):
        return 0.3 * jax.random.normal(next(keys), shape)
    return {"norm1": 1 + w(d), "norm2": 1 + w(d), "q_norm": 1 + w(hd),
            "k_norm": 1 + w(hd), "wq": w(d, n_q * hd), "wk": w(d, n_kv * hd),
            "wv": w(d, n_kv * hd), "wo": w(n_q * hd, d),
            "router": w(d, experts), "w_gate": w(experts, d, f),
            "w_up": w(experts, d, f), "w_down": w(experts, f, d)}


def test_the_model_is_built_with_one_table_leaf():
    assert "sdar_moe" not in registered_models()    # the rankers' zoo
    model = get_model(config())
    assert model.embedding_param_names() == ("tok_emb",)
    assert model.uses_history and model.owns_loss
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert params["tok_emb"].shape == (model.padded_vocab, 32)
    assert params["head"].shape == (32, V)
    assert params["layers"]["w_gate"].shape == (2, 4, 32, 16)
    assert set(state) == set(sdar_moe.COUNT_NAMES)


def test_mask_matches_a_hand_written_example():
    n = np.array([[1, 1, 1, 1, 0, 0, 0, 0],      # noisy queries, block 0
                  [0, 0, 0, 0, 1, 1, 1, 1]], bool)   # and block 1
    none, first = np.zeros(8, bool), np.array([1] * 4 + [0] * 4, bool)
    every = np.ones(8, bool)
    rows = ([np.concatenate([n[0], none])] * 4     # noisy 0: own noisy block
            + [np.concatenate([n[1], first])] * 4  # noisy 1: + clean block 0
            + [np.concatenate([none, first])] * 4  # clean 0: clean block 0
            + [np.concatenate([none, every])] * 4)  # clean 1: clean 0 and 1
    want = np.stack(rows)
    assert want.sum() == 4 * 4 * 2 * 3            # b*b*nb*(nb+1)
    np.testing.assert_array_equal(ref.block_diffusion_mask(8, 4), want)
    index = jnp.arange(16)
    np.testing.assert_array_equal(sdar_moe.allowed(index, index, 8, 4), want)


def test_router_matches_a_hand_computed_top_k():
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]])
    router = jnp.asarray([[0.0, 1.0, 2.0, -1.0], [1.0, 0.0, -1.0, 0.5]])
    # token 0: logits (0, 1, 2, -1): experts 2 and 1, e^2 and e^1 renormalised
    # token 1: logits (2, 0, -2, 1): experts 0 and 3
    experts, weights, moved = sdar_moe.route(x, router, 2)
    assert int(moved) == 0       # (no bias)
    np.testing.assert_array_equal(experts, [[2, 1], [0, 3]])
    e = np.exp
    np.testing.assert_allclose(
        weights, [[e(2) / (e(2) + e(1)), e(1) / (e(2) + e(1))]] * 2,
        rtol=1e-6)
    dense = ref.router_weights(x, router, {"top_k": 2})
    np.testing.assert_allclose(dense[0], [0, weights[0, 1], weights[0, 0], 0],
                               rtol=1e-6)
    np.testing.assert_allclose(dense[1], [weights[1, 0], 0, 0, weights[1, 1]],
                               rtol=1e-6)


def test_eight_shares_add_up_to_the_uncut_layer():
    """8 chips share the layer as the benchmark's deployment does: each holds
    2 of 16 query heads, the key/value head they read (a key/value head lives
    on 2 chips) and 2 of 16 experts. The shares' partial results of the
    attention block and of the expert layer, on the same input, add up to the
    uncut reference's."""
    hd, length, block = 8, 8, 4
    lp = uncut_layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2 * length, 32))
    pos = jnp.arange(2 * length) % length
    sizes = dict(head_dim=hd, top_k=2, first_expert=0, eps=1e-6, theta=1e6)
    mask = jnp.asarray(ref.block_diffusion_mask(length, block))
    with jax.default_matmul_precision("highest"):
        want_attn = ref.attention(ref.rms_norm(x, lp["norm1"], 1e-6), lp,
                                  sizes, mask, pos)
        want_moe = ref.moe(ref.rms_norm(x, lp["norm2"], 1e-6), lp, sizes)
    # (a share's program is every share's: compiled once, run on each)
    attn = jax.jit(lambda share: sdar_moe.attention(
        share, x, pos, mask=sdar_moe.block_diffusion(length, block),
        head_dim=hd, eps=1e-6, theta=1e6, cdt=jnp.dtype("float32")))
    moe = jax.jit(lambda share, first: sdar_moe.expert_layer(
        share, x, top_k=2, first_expert=first, capacity=64, eps=1e-6,
        cdt=jnp.dtype("float32")))
    got_attn, got_moe, held = 0.0, 0.0, 0
    for r in range(8):
        q = slice(2 * r * hd, (2 * r + 2) * hd)
        kv = slice((r // 2) * hd, (r // 2 + 1) * hd)
        share = {**lp, "wq": lp["wq"][:, q], "wk": lp["wk"][:, kv],
                 "wv": lp["wv"][:, kv], "wo": lp["wo"][q],
                 **{n: lp[n][2 * r:2 * r + 2]
                    for n in ("w_gate", "w_up", "w_down")}}
        got_attn += attn(share)
        part, counts = moe(share, 2 * r)
        got_moe += part
        held += int(counts["moe_pairs_held"])
        assert int(counts["moe_pairs_over_buffer"]) == 0
    assert held == x.shape[0] * x.shape[1] * 2     # every pair, once
    np.testing.assert_allclose(got_attn, want_attn, atol=2e-5)
    np.testing.assert_allclose(got_moe, want_moe, atol=2e-5)


def test_grouped_product_kernels_are_charged_to_the_expert_layer():
    """XLA's TPU backend compiles ``ragged_dot`` to kernels it names itself
    and strips of where they were traced; the model declares their scope and
    the step's text carries it, for this model only."""
    from deepfm_tpu.utils import profiling

    text = "\n".join([
        '  %ragged-dot-none.3 = bf16[20480,768]{1,0} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        '  %ragged-dot-metadata = (s32[17]{0}) custom-call(%gs), '
        'metadata={op_name="ragged-dot-metadata"}',
        '  %fusion.9 = f32[16384,2048]{1,0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(step)/jvp()/while/body/moe/scatter-add"}',
        '  %fusion.7 = f32[2,4,1024]{2,1,0} fusion(%q), kind=kLoop, '
        'metadata={op_name="jit(step)/transpose(jvp(attn))/reduce_max"}',
        '  %copy.4 = f32[2]{0} copy(%x)'])
    bare = {"ragged-dot-none.3": "", "ragged-dot-metadata": "",
            "fusion.9": "moe", "fusion.7": "attn", "copy.4": ""}
    assert profiling.hlo_op_scopes(text) == bare
    assert profiling.scope_kernels(text, ()) == text
    named = profiling.scope_kernels(text, sdar_moe.SdarMoE.kernel_scopes)
    assert 'op_name="moe/ragged-dot-none"' in named
    assert profiling.hlo_op_scopes(named) == {
        **bare, "ragged-dot-none.3": "moe", "ragged-dot-metadata": "moe"}
    # every line but the kernels' is as it was
    assert [a == b for a, b in zip(text.splitlines(), named.splitlines())
            ] == [False, False, True, True, True]


def _qkv(dtype, length=256, group=4, head_dim=128, batch=1):
    """Rotated, normalised q/k/v as ``attention`` hands them on: unit-RMS
    rows, rounded to the compute precision."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    s = 2 * length
    q = jax.random.normal(keys[0], (batch, s, 1, group, head_dim))
    k = jax.random.normal(keys[1], (batch, s, 1, head_dim))
    v = jax.random.normal(keys[2], (batch, s, 1, head_dim))
    w = jax.random.normal(keys[3], (batch, s, group * head_dim))
    return q, k.astype(dtype), v.astype(dtype), w


@pytest.mark.parametrize("kernel_block", [128, 256])
@pytest.mark.parametrize("dtype, tol", [("float32", 1e-4),
                                        ("bfloat16", 2e-2)])
def test_kernel_scores_match_the_chunked_xla_path(dtype, tol, kernel_block):
    """The kernels (forward, dq, dk/dv) through the Pallas interpreter against
    ``_scores_xla`` on the same q/k/v: L = 256, block 4, 4 query heads on 1
    key/value head, head_dim 128; output and the gradients of q, k, v, within
    1e-4 in float32 and within bfloat16's rounding of an operand (2^-8,
    through three products) under bfloat16."""
    length, block = 256, 4
    cdt = jnp.dtype(dtype)
    q, k, v, w = _qkv(cdt, length)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def by_xla(q, k, v):
        return sdar_moe._scores_xla(
            sdar_moe._operand(q, cdt), k, v,
            mask=sdar_moe.block_diffusion(length, block), cdt=cdt)

    def by_kernel(q, k, v):
        return sdar_moe._scores_kernel(
            sdar_moe._operand(q * scale, cdt), k, v,
            mask=sdar_moe.block_diffusion(length, block), interpret=True,
            kernel_block=kernel_block)

    def value_and_grads(f):
        def loss(q, k, v):
            out = f(q, k, v).astype(jnp.float32)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    names = ("out", "dq", "dk", "dv")
    for name, got, want in zip(names, value_and_grads(by_kernel),
                               value_and_grads(by_xla)):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all(), name
        gap = np.max(np.abs(got - want)) / np.max(np.abs(want))
        assert gap < tol, (name, gap)


@pytest.mark.parametrize("length", [8, 1024])
def test_kernel_mask_is_allowed_entry_for_entry(length):
    from deepfm_tpu.ops import block_attention
    s, block = 2 * length, 4
    mask = block_attention.PairMask(
        s, functools.partial(sdar_moe.allowed_pairs, length=length,
                             block=block), ("block_diffusion", length, block))
    index = jnp.arange(s)
    want = np.asarray(sdar_moe.allowed(index, index, length, block))
    np.testing.assert_array_equal(mask[:, :], want)
    np.testing.assert_array_equal(mask[4:s // 2, s // 4:], want[4:s // 2,
                                                                s // 4:])
    np.testing.assert_array_equal(want, ref.block_diffusion_mask(length,
                                                                 block))


@pytest.mark.parametrize("length, kernel_block, visited, total", [
    (4096, 512, 80, 256), (4096, 1024, 24, 64), (256, 128, None, 16)])
def test_forward_grid_visits_the_blocks_allowed_leaves_something_in(
        length, kernel_block, visited, total):
    """The kernel's own block table against a count made from ``allowed``
    with NumPy, a stripe of query blocks at a time (computed, not traced)."""
    from deepfm_tpu.ops import block_attention
    s, block = 2 * length, 4
    mask = sdar_moe.block_diffusion(length, block)
    kernel = sdar_moe.attn_kernel(s, mask, 4, True, kernel_block)
    index = np.arange(s)
    count = 0
    for start in range(0, s, kernel_block):
        stripe = sdar_moe.allowed_pairs(
            index[start:start + kernel_block, None], index[None, :], length,
            block)
        count += int(stripe.reshape(kernel_block, s // kernel_block,
                                    kernel_block).any(axis=(0, 2)).sum())
    assert block_attention.visited_blocks(kernel, s, kernel_block) == (
        count, total)
    if visited is not None:
        assert count == visited
    # one kernel a shape: the step is traced more than once a run
    assert sdar_moe.attn_kernel(s, sdar_moe.block_diffusion(length, block),
                                4, True, kernel_block) is kernel


@pytest.mark.parametrize("backend, seq, head_dim, one_device, says", [
    ("tpu", 8192, 128, True, "kernel"), ("tpu", 1024, 256, True, "kernel"),
    ("cpu", 8192, 128, True, "xla"), ("gpu", 8192, 128, True, "xla"),
    ("tpu", 8192, 64, True, "kernel"),      # (half a line, as it is: PR 40)
    ("tpu", 8192, 32, True, "xla"), ("tpu", 8192 + 256, 128, True, "xla"),
    ("tpu", 16, 8, True, "xla"), ("tpu", 8192, 128, False, "xla")])
def test_the_kernel_is_taken_where_backend_shape_and_mesh_allow(
        backend, seq, head_dim, one_device, says):
    assert sdar_moe.attn_scores_by(seq, head_dim, one_device=one_device,
                                   backend=backend) == says
    assert sdar_moe.ATTN_BLOCK == 512


@pytest.mark.parametrize("backend, width, positions, capacity, one_device, "
                         "says", [
    ("tpu", 2048, 16384, 32768, True, "kernel"),    # the SDAR cell's layer
    ("tpu", 2304, 16384, 16384, True, "kernel"),    # the Kimi-Linear cell's
    ("cpu", 2048, 16384, 32768, True, "xla"),
    ("tpu", 2048, 16384, 32768, False, "xla"),      # across data replicas
    ("tpu", 64, 16384, 32768, True, "xla"),         # no whole line a row
    ("tpu", 2048, 16380, 32768, True, "xla"),       # positions in no tiles
    ("tpu", 2048, 16384, 100, True, "xla"),         # nor a pass's rows
    ("tpu", 2048, 16384, 32760, True, "kernel")])   # (made up to 2 x 16,384)
def test_the_row_kernels_are_taken_where_backend_shape_and_mesh_allow(
        backend, width, positions, capacity, one_device, says):
    assert sdar_moe.moe_rows_by(width, positions, capacity,
                                one_device=one_device,
                                backend=backend) == says
    assert sdar_moe.pass_rows(32768) == (2, 16384)
    assert sdar_moe.pass_rows(16384) == (1, 16384)


@pytest.mark.parametrize("backend, width, hidden, capacity, one_device, "
                         "says", [
    ("tpu", 2048, 768, 32768, True, "kernel"),      # the SDAR cell's layer
    ("tpu", 2048, 1792, 32768, True, "kernel"),     # the LFM2 cell's
    ("tpu", 2304, 1024, 16384, True, "kernel"),     # the Kimi-Linear cell's
    ("tpu", 2048, 1536, 16384, True, "kernel"),     # the GLM-4.7-Flash cell's
    ("tpu", 4096, 1280, 3280, True, "kernel"),      # Solar-Open2's (3,328)
    ("cpu", 2048, 768, 32768, True, "xla"),
    ("gpu", 2048, 768, 32768, True, "xla"),
    ("tpu", 2048, 768, 32768, False, "xla"),        # across data replicas
    ("tpu", 2048, 96, 32768, True, "xla"),          # experts of no whole line
    ("tpu", 64, 768, 32768, True, "xla"),           # nor the model's width
    ("tpu", 2048, 768, 100, True, "xla"),           # a pass under one tile
    ("tpu", 2048, 768, 32760, True, "kernel")])     # (made up to 2 x 16,384)
def test_the_product_kernels_are_taken_where_backend_shape_and_mesh_allow(
        backend, width, hidden, capacity, one_device, says):
    assert sdar_moe.moe_products_by(width, hidden, capacity,
                                    one_device=one_device,
                                    backend=backend) == says
    # a pass of a tile or more is whole tiles of the kernels' too
    assert sdar_moe.PRODUCT_TILE_ROWS % pallas_grouped_dot.TILE_ROWS == 0


@pytest.mark.parametrize("capacity,passes,rows", [
    (8, 1, 8), (255, 1, 255),           # under one tile: held row for row
    (256, 1, 256), (257, 1, 512),
    (3280, 1, 3328), (3328, 1, 3328),   # ISSUE 37's buffer and its tile's
    (4992, 1, 5120), (20480, 1, 20480),
    (20481, 2, 10496), (40000, 2, 20224)])
def test_a_pass_of_a_tile_or_more_is_whole_tiles_of_the_grouped_product(
        capacity, passes, rows):
    """``jax.lax.ragged_dot`` on a TPU costs 5.7 x over a buffer that is no
    multiple of 128 rows and 1.5 x over one that is none of 256 (PERF.md,
    PR 37): the buffer holds at least what was asked, in whole tiles."""
    assert sdar_moe.pass_rows(capacity) == (passes, rows)
    assert passes * rows >= capacity and rows <= sdar_moe.PASS_ROWS
    assert rows < sdar_moe.PRODUCT_TILE_ROWS \
        or rows % sdar_moe.PRODUCT_TILE_ROWS == 0
    notes = sdar_moe.moe_notes("xla", capacity, 3)
    assert notes["moe_rows_moved"] == "{moe_pairs_held}/%d" % (
        3 * passes * rows)


def test_the_step_notes_say_how_the_expert_layers_rows_move():
    notes = sdar_moe.moe_notes("kernel", 32768, 6)
    assert notes == {"moe_rows": "kernel", "moe_products": "xla",
                     "moe_rows_moved": "{moe_pairs_held}/196608"}
    # the trainer fills the count in where it writes the notes
    assert notes["moe_rows_moved"].format(moe_pairs_held=101_000,
                                          other=3) == "101000/196608"
    assert sdar_moe.moe_notes("xla", 20, 1)["moe_rows_moved"].endswith("/20")


@pytest.mark.parametrize("products_by, width, hidden, says", [
    ("xla", 2048, 768, "xla"), ("xla", 0, 0, "xla"),
    ("kernel", 2048, 768, "kernel rows256 dw768/2048"),
    ("kernel", 4096, 1280, "kernel rows256 dw640/2048")])
def test_the_step_notes_say_what_multiplies_the_expert_layers_rows(
        products_by, width, hidden, says):
    """``moe_products`` beside ``moe_rows``: ``moe_products_by``'s word and,
    of the kernels, their tiles; ``moe_rows_moved`` is then the share of
    the buffer the products visit too."""
    notes = sdar_moe.moe_notes("kernel", 32768, 6, products_by, width,
                               hidden)
    assert notes == {"moe_rows": "kernel", "moe_products": says,
                     "moe_rows_moved": "{moe_pairs_held}/196608"}


@pytest.mark.parametrize("capacity, pass_most, first, case", [
    (128, 20480, 0, "one pass, spare rows"),
    (128, 64, 0, "two passes, the second part spare"),
    (192, 64, 0, "three passes, the last with no valid row"),
    (32, 20480, 0, "pairs over the buffer"),
    (64, 20480, 20, "no expert held gets a pair")])
def test_expert_layer_by_the_row_kernels_matches_the_xla_rows(
        monkeypatch, capacity, pass_most, first, case):
    """``expert_layer`` with the rows taken and added by the kernels (forced
    on, through the interpreter) against ``jnp.take`` / ``.at[].add``: the
    output, the counts, and the gradient of every leaf and of the input."""
    force_row_kernels(monkeypatch)
    monkeypatch.setattr(sdar_moe, "PASS_ROWS", pass_most)
    d, f, experts, held, top_k = 128, 32, 16, 4, 4
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    lp = {"norm2": 1 + 0.1 * jax.random.normal(next(keys), (d,)),
          "router": 0.5 * jax.random.normal(next(keys), (d, experts)),
          "w_gate": 0.2 * jax.random.normal(next(keys), (held, d, f)),
          "w_up": 0.2 * jax.random.normal(next(keys), (held, d, f)),
          "w_down": 0.2 * jax.random.normal(next(keys), (held, f, d))}
    x = jax.random.normal(next(keys), (2, 32, d))
    w = jax.random.normal(next(keys), x.shape)

    def loss(lp, x, rows_by):
        y, counts = sdar_moe.expert_layer(
            lp, x, top_k=top_k, first_expert=first, capacity=capacity,
            eps=1e-6, cdt=jnp.dtype("float32"), rows_by=rows_by)
        return jnp.sum(y * w), (y, counts)

    (_, (want, counts)), want_g = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(lp, x, "xla")
    (_, (got, got_counts)), got_g = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(lp, x, "kernel")
    assert {k: int(v) for k, v in got_counts.items()} == {
        k: int(v) for k, v in counts.items()}
    held_pairs = int(counts["moe_pairs_held"])
    if "no expert" in case:
        assert held_pairs == 0
    elif "over" in case:
        assert int(counts["moe_pairs_over_buffer"]) > 0
    else:       # the buffer has spare rows, and a whole pass of them in (3)
        assert 0 < held_pairs < capacity - ("three" in case) * pass_most
    np.testing.assert_allclose(got, want, atol=1e-5)
    for name in lp:
        np.testing.assert_allclose(got_g[0][name], want_g[0][name],
                                   atol=1e-4, err_msg=name)
    np.testing.assert_allclose(got_g[1], want_g[1], atol=1e-4)


@pytest.mark.parametrize("rows_by", ["xla", "kernel"])
@pytest.mark.parametrize("capacity, pass_most, first, case", [
    (128, 20480, 0, "one pass, spare rows"),
    (128, 64, 0, "two passes, the second part spare"),
    (192, 64, 0, "three passes, the last with no valid row"),
    (32, 20480, 0, "pairs over the buffer"),
    (64, 20480, 20, "no expert held gets a pair")])
def test_expert_layer_by_the_product_kernels_matches_ragged_dot(
        monkeypatch, capacity, pass_most, first, case, rows_by):
    """``expert_layer`` with the grouped products made by the kernels of
    ``ops/pallas_grouped_dot`` (forced on, through the interpreter, which
    leaves NaNs in every row the kernels do not write: the rows past a
    pass's prefix) against ``jax.lax.ragged_dot`` over the whole buffer,
    under either way of moving the rows: the output, the counts, and the
    gradient of every leaf and of the input, all finite."""
    force_row_kernels(monkeypatch)
    monkeypatch.setattr(pallas_grouped_dot, "grouped_dot", functools.partial(
        pallas_grouped_dot.grouped_dot, tile=16, interpret=True))
    monkeypatch.setattr(sdar_moe, "PASS_ROWS", pass_most)
    d, f, experts, held, top_k = 128, 128, 16, 4, 4
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    lp = {"norm2": 1 + 0.1 * jax.random.normal(next(keys), (d,)),
          "router": 0.5 * jax.random.normal(next(keys), (d, experts)),
          "w_gate": 0.2 * jax.random.normal(next(keys), (held, d, f)),
          "w_up": 0.2 * jax.random.normal(next(keys), (held, d, f)),
          "w_down": 0.2 * jax.random.normal(next(keys), (held, f, d))}
    x = jax.random.normal(next(keys), (2, 32, d))
    w = jax.random.normal(next(keys), x.shape)

    def loss(lp, x, products_by):
        y, counts = sdar_moe.expert_layer(
            lp, x, top_k=top_k, first_expert=first, capacity=capacity,
            eps=1e-6, cdt=jnp.dtype("float32"), rows_by=rows_by,
            products_by=products_by)
        return jnp.sum(y * w), (y, counts)

    (_, (want, counts)), want_g = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(lp, x, "xla")
    (_, (got, got_counts)), got_g = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(lp, x, "kernel")
    assert {k: int(v) for k, v in got_counts.items()} == {
        k: int(v) for k, v in counts.items()}
    if "no expert" in case:
        assert int(counts["moe_pairs_held"]) == 0
    elif "over" not in case:    # spare rows, which the interpreter poisons
        assert 0 < int(counts["moe_pairs_held"]) < capacity
    np.testing.assert_allclose(got, want, atol=1e-4)
    for name in lp:
        np.testing.assert_allclose(got_g[0][name], want_g[0][name],
                                   atol=2e-4, err_msg=name)
    np.testing.assert_allclose(got_g[1], want_g[1], atol=2e-4)


def test_attention_by_the_kernel_matches_attention_by_xla(monkeypatch):
    """The whole block (projections, QK-norm, rotary, scale folded into q,
    ``wo``) with the kernel forced on through the interpreter against the
    XLA path, output and every weight's gradient, float32."""
    monkeypatch.setattr(sdar_moe, "_scores_kernel", functools.partial(
        sdar_moe._scores_kernel, interpret=True, kernel_block=128))
    d, hd, length, block = 64, 128, 128, 4
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    lp = {"norm1": jnp.ones((d,)), "q_norm": jnp.ones((hd,)),
          "k_norm": jnp.ones((hd,)),
          "wq": jax.random.normal(next(keys), (d, 2 * hd)) * 0.2,
          "wk": jax.random.normal(next(keys), (d, hd)) * 0.2,
          "wv": jax.random.normal(next(keys), (d, hd)) * 0.2,
          "wo": jax.random.normal(next(keys), (2 * hd, d)) * 0.1}
    x = jax.random.normal(next(keys), (2, 2 * length, d))
    w = jax.random.normal(next(keys), x.shape)
    pos = jnp.arange(2 * length) % length

    def loss(lp, scores_by):
        out = sdar_moe.attention(
            lp, x, pos, mask=sdar_moe.block_diffusion(length, block),
            head_dim=hd, eps=1e-6, theta=1e6, cdt=jnp.dtype("float32"),
            scores_by=scores_by)
        return jnp.sum(out * w), out

    by = jax.jit(jax.value_and_grad(loss, has_aux=True), static_argnums=1)
    (_, want), want_g = by(lp, "xla")
    (_, got), got_g = by(lp, "kernel")
    np.testing.assert_allclose(got, want, atol=1e-4)
    for name in lp:
        np.testing.assert_allclose(got_g[name], want_g[name], atol=2e-4,
                                   err_msg=name)


def test_launcher_trains_and_evaluates(tmp_path, capsys):
    from deepfm_tpu import launch

    data = tmp_path / "data"
    os.makedirs(data)
    for prefix, n, seed in (("tr", 64, 1), ("va", 24, 2)):
        with tfrecord.TFRecordWriter(
                str(data / f"{prefix}-00000.tfrecords")) as w:
            for row in sequences(n, seed):
                w.write(example_codec.encode_ctr_example(
                    0.0, np.zeros(1), np.ones(1), hist_ids=row))
    argv = ["--data_dir", str(data), "--val_data_dir", str(data),
            "--model_dir", str(tmp_path / "ckpt"), "--num_epochs", "2",
            "--compute_dtype", "float32", "--steps_per_loop", "4",
            "--mesh_data", "2"]
    for key, value in SMALL.items():
        if "--" + key not in argv:
            argv += ["--" + key, str(value)]
    assert launch.main(argv + ["--task_type", "train"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["task"] == "train" and line["steps"] == 2 * (64 // B)
    assert np.isfinite(line["loss"]) and "saved_model" not in line
    assert launch.main(argv + ["--task_type", "eval"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["task"] == "eval" and 0 < line["loss"] < 20
    with pytest.raises(ValueError, match="infer/export"):
        launch.main(argv + ["--task_type", "infer"])
