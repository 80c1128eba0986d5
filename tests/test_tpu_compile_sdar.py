"""What only the TPU's compiler can say of the decoders' kernels in an
SDAR-shaped step, asked of a *described* v5e (no chip attached, nothing
runs; the fixtures are ``conftest.py``'s): the masked scores are the
block-masked attention kernels' where a head is a whole or half a lane line
wide (``ops/block_attention.py``; PR 32, PR 40), the expert layers' rows
move by the row kernels (``ops/pallas_moe_rows``; PR 34) and are multiplied
by the grouped-product kernels (``ops/pallas_grouped_dot``; PR 52), each
charged to its scope by the step's own text, in a step cut to compile in seconds; and
the attention kernels alone at ``sdar-30b-a3b.train-sequences``'s shapes.
(The other families' are ``tests/test_tpu_compile_rankers.py``,
``_kimi_linear.py``, ``_solar_open2.py`` and ``_lfm2.py``.)
"""

import collections

import jax

from benchmark import harness
from decoder_contract import attention_kernel_calls
from deepfm_tpu.utils import profiling

# The SDAR cell's attention widths (4 query heads of 128 on one key/value
# head) with the depth, the width, the experts, the vocabulary and the
# length cut so that the step compiles in seconds; 2L = 1,024 positions are
# two of the kernel's blocks.
SDAR_FLAGS = dict(
    model="sdar_moe", feature_size=512, field_size=1, embedding_size=256,
    history_max_len=512, decoder_layers=2, attn_q_heads=4, attn_kv_heads=1,
    attn_head_dim=128, moe_experts=8, moe_top_k=2, moe_expert_width=128,
    moe_experts_held=4, moe_first_expert=0, moe_pair_capacity=4096,
    diffusion_block=4, batch_size=1, l2_reg=0.0, learning_rate=1e-5,
    compute_dtype="bfloat16", steps_per_loop=1)


def test_decoder_step_makes_its_masked_scores_in_the_attention_kernels(
        step_for_v5e):
    """On a TPU at head_dim 128 the step's masked scores are the three
    kernels JAX's flash attention is made of (forward; dq; dk and dv), each
    charged to ``attn`` by the step's own text, though each prints over three
    lines (``profiling.whole_instructions``), and the model says so. The
    scanned layers keep the forward kernel's output and log-sum-exp (the
    chip's memory described to ``sdar_moe.kept_by``), so the scan's backward
    body holds no forward kernel: one call in the step, the parent's two."""
    tr, compiled, text = step_for_v5e(SDAR_FLAGS)
    scopes = profiling.hlo_op_scopes(text)
    assert tr.model.step_notes == {
        "attn_scores": "kernel", "attn_score_blocks": "3/4",
        "attn_kept": "2/2 layers 0.00 GB",
        "head_grad": "forward 3 products/chunk, 0.00 GB kept",
        "moe_rows": "kernel", "moe_products": "kernel rows256 dw128/256",
        "moe_rows_moved": "{moe_pairs_held}/8192"}
    # the expert layer's rows move by the row kernels (rows of two lines,
    # 1,024 positions, one pass of 4,096 rows), forward and backward, all
    # charged to ``moe``
    rows = {name: scope for name, scope in scopes.items()
            if name.startswith(("moe_take_rows", "moe_add_rows"))}
    assert {name.split(".")[0] for name in rows} == {
        "moe_take_rows", "moe_add_rows"} and len(rows) == 5, rows
    assert set(rows.values()) == {"moe"}, rows
    # and its grouped products are the kernels that stop at the valid prefix
    # (``ops/pallas_grouped_dot``; PR 52): three forward, the three again in
    # the pass's recomputation, three rows' gradients and three matrices',
    # charged to ``moe`` by their own op_name; no ``ragged-dot`` is left
    products = {name: scope for name, scope in scopes.items()
                if name.startswith("moe_grouped_dot")}
    by_kernel = collections.Counter(
        name.split(".")[0] for name in products)
    assert by_kernel == {"moe_grouped_dot": 6, "moe_grouped_dot_da": 3,
                         "moe_grouped_dot_dw": 3}, products
    assert set(products.values()) == {"moe"}, products
    assert not any(name.startswith("ragged-dot") for name in scopes)
    kernels = {name: scope for name, scope in scopes.items()
               if name.startswith("splash_mqa_")}
    assert {name.split(".")[0] for name in kernels} == {
        "splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
        "splash_mqa_dkv_no_residuals"}, kernels
    assert set(kernels.values()) == {"attn"}, kernels
    assert attention_kernel_calls(kernels) == (1,) * 3
    # the raw text loses them: their op_name is on a continuation line
    raw = profiling.hlo_op_scopes(compiled.as_text())
    assert {raw[name] for name in kernels} == {""}
    # and every other instruction is charged as it was
    def others(by_op):
        return {n: s for n, s in by_op.items()
                if n not in kernels and not n.startswith("pallas_call")}
    assert others(scopes) == others(raw)


def test_sdar_step_at_the_cells_shapes_keeps_the_kernels_results_and_fits(
        step_for_v5e):
    """``sdar-30b-a3b.train-sequences``'s own step (every width, 6 scanned
    layers, 2 x 2 x 4,096 positions) compiled for a described v5e: every
    layer keeps its forward kernel's output and log-sum-exp (6 x 17 MB, the
    scan's stacked outputs), the forward kernel is called once in the step
    (the scan's forward body; the parent's step holds it in the backward
    body too), and arguments and temporaries together are under the 15.5 GB
    the other cells' tests allow a step (6.578 + 7.584 = 14.16 GB; the
    parent's 14.33: what the backward body no longer makes again outweighs
    what is kept)."""
    tr, compiled, text = step_for_v5e(
        harness.load_json("configs", "sdar-30b-a3b.json")["flags"])
    notes = tr.model.step_notes
    assert (notes["attn_scores"], notes["attn_score_blocks"]) == (
        "kernel", "80/256")
    assert notes["attn_kept"] == "6/6 layers 0.10 GB"
    by_op = profiling.hlo_op_scopes(text)
    assert attention_kernel_calls(by_op) == (1,) * 3
    memory = compiled.memory_analysis()
    assert 6.5e9 < memory.argument_size_in_bytes < 6.7e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 15.5e9


def test_decoder_step_at_head_dim_32_keeps_the_xla_scores(step_for_v5e):
    """A head a quarter of a lane line wide keeps XLA's chunks
    (``block_attention.supported``: whole and half lines)."""
    tr, _, text = step_for_v5e({**SDAR_FLAGS, "attn_head_dim": 32})
    assert tr.model.step_notes["attn_scores"] == "xla"
    assert "attn_score_blocks" not in tr.model.step_notes
    assert "splash_mqa" not in text


def test_decoder_step_at_head_dim_64_takes_the_kernels(step_for_v5e):
    """Since PR 40 a head half a lane line wide takes the kernels, as it is
    (Mosaic compiles them at 64 lanes)."""
    tr, _, text = step_for_v5e({**SDAR_FLAGS, "attn_head_dim": 64})
    notes = tr.model.step_notes
    assert (notes["attn_scores"], notes["attn_score_blocks"]) == (
        "kernel", "3/4")
    assert "bf16[4,1024,64]" in text and "bf16[4,1024,128]" not in text
    for name in ("splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
                 "splash_mqa_dkv_no_residuals"):
        assert f"%{name}" in text, name


def test_attention_kernels_compile_at_the_cells_shapes(v5e,
                                                       no_compile_cache):
    """Forward and backward at q [2, 8192, 1, 4, 128] bfloat16 under the
    block-diffusion mask of 4,096 tokens: Mosaic takes the three kernels at
    blocks of 512 (VMEM, tiling), and nothing [S, S] is made outside them."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from deepfm_tpu.models import sdar_moe

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=SingleDeviceSharding(v5e))

    def loss(q, k, v):
        return jnp.sum(sdar_moe._scores_kernel(
            q, k, v, mask=sdar_moe.block_diffusion(4096, 4)).astype(
                jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec(2, 8192, 1, 4, 128), spec(2, 8192, 1, 128),
        spec(2, 8192, 1, 128)).compile()
    text = compiled.as_text()
    for name in ("splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
                 "splash_mqa_dkv_no_residuals"):
        assert f"%{name}" in text, name
    # one float32 [2, 4, 8192, 8192] score matrix would be 2.1 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


def test_grouped_product_kernels_compile_at_the_cells_pass_shape(
        v5e, no_compile_cache):
    """``ops/pallas_grouped_dot`` at the SDAR cell's pass (16,384 rows of
    2,048 by 16 experts of 768, bfloat16 operands), the up product and the
    down product, forward and both gradients: Mosaic takes a group's whole
    matrix and a float32 block of its gradient in VMEM at tiles of 256 rows,
    and nothing of the buffer's size is made outside the kernels."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from deepfm_tpu.ops import pallas_grouped_dot

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=SingleDeviceSharding(v5e))

    def loss(a, w, ends):
        return jnp.sum(pallas_grouped_dot.grouped_dot(a, w, ends))

    for k, n in ((2048, 768), (768, 2048)):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            spec((16384, k)), spec((16, k, n)),
            spec((16,), jnp.int32)).compile()
        text = compiled.as_text()
        for name in ("moe_grouped_dot_da", "moe_grouped_dot_dw"):
            assert f"%{name}" in text, name
        # (the cotangent of the sum, float32 [16384, n], and the gradients)
        assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2 ** 20
