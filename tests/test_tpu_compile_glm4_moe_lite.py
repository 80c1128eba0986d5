"""``glm-4.7-flash.train-sequences-8k-ep8``'s own step, every width, compiled
for a *described* v5e (no chip attached, nothing runs; the fixtures are
``conftest.py``'s): ISSUE 48's memory rule as a standing test; and the
attention kernels alone at the cell's shapes, heads of 256 with one key/value
head a query head.
"""

import re

import jax

from benchmark import harness
from decoder_contract import attention_kernel_calls
from deepfm_tpu.utils import profiling


def test_glm4_step_at_the_cells_shapes_takes_the_kernel_at_256(step_for_v5e):
    """The cell's own step (every width, layers 0-4 and the module, 2 x
    8,192 tokens) compiled for a described v5e: every block's causal scores
    by the block kernel at heads of 256 (no ``[..., 1024, 8192]`` float32
    score tensor, which the XLA path would hold), the expert blocks' rows by
    the row kernels, ops charged to each of the model's scopes (``mtp`` and
    ``mtp_head`` among them, the second head pass's products under
    ``mtp_head`` and not ``head``), and arguments and temporaries together
    under the issue's 15.5 GB **with every block keeping its forward
    kernel's output and log-sum-exp** (the chip's memory described to
    ``sdar_moe.kept_by``: six of 42 MB, the module's block's among them,
    placed first: six calls of the forward kernel, the parent's twelve)
    **and the five shared experts their first products** (5 x 201 MB, as on
    the parent's tree: the dense layer's 1.34 GB have no room since PR 49's
    head; 8.404 + 5.614 = 14.02 GB, the parent's 13.79)."""
    tr, compiled, text = step_for_v5e(
        harness.load_json("configs", "glm-4.7-flash.json")["flags"])
    notes = tr.model.step_notes
    assert (notes["attn_scores"], notes["attn_score_blocks"],
            notes["moe_rows"]) == ("kernel", "136/256", "kernel")
    # five expert blocks of one pass of 16,384 rows
    assert notes["moe_rows_moved"] == "{moe_pairs_held}/%d" % (5 * 16384)
    # multiplied by the kernels that stop at the valid prefix (PR 52)
    assert notes["moe_products"] == "kernel rows256 dw1536/2048"
    assert notes["attn_kept"] == "6/6 layers 0.25 GB"
    assert notes["mlp_kept"] == "5/6 layers 1.01 GB"
    by_op = profiling.hlo_op_scopes(text)
    assert attention_kernel_calls(by_op) == (6,) * 3
    assert {scope for name, scope in by_op.items()
            if name.startswith("moe_grouped_dot")} == {"moe"}
    assert not any(name.startswith("ragged-dot") for name in by_op)
    assert {"embed", "attn", "attn_scores", "mlp", "moe", "mtp", "head",
            "mtp_head", "opt"} <= set(by_op.values())
    assert {scope for name, scope in by_op.items()
            if name.startswith("splash_mqa")} == {"attn_scores"}
    assert {scope for name, scope in by_op.items() if name.startswith(
        ("moe_take_rows", "moe_add_rows"))} == {"moe"}
    assert not re.search(r"f32\[[\d,]*1024,8192\]", text)
    memory = compiled.memory_analysis()
    print("glm4 step memory:", notes, memory.argument_size_in_bytes,
          memory.temp_size_in_bytes)
    # 12 bytes a parameter resident: weight and Adam's two moments
    assert 8.4e9 < memory.argument_size_in_bytes < 8.5e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 15.5e9


def test_attention_kernels_compile_at_256_lanes_at_the_cells_shapes(
        v5e, no_compile_cache):
    """Forward and backward at q [2, 8192, 5, 1, 256] bfloat16 under the
    causal mask, one key/value head a query head: Mosaic takes the three
    kernels at two lane lines a row, and nothing [S, S] is made outside
    them."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from deepfm_tpu.models import kimi_linear, sdar_moe

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=SingleDeviceSharding(v5e))

    def loss(q, k, v):
        return jnp.sum(sdar_moe._scores_kernel(
            q, k, v, mask=kimi_linear.causal).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec(2, 8192, 5, 1, 256), spec(2, 8192, 5, 256),
        spec(2, 8192, 5, 256)).compile()
    text = compiled.as_text()
    for name in ("splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
                 "splash_mqa_dkv_no_residuals"):
        assert f"%{name}" in text, name
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
