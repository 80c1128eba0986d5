"""``lfm2-8b-a1b.train-sequences-8k-ep4``'s own step, every width, compiled
for a *described* v5e (no chip attached, nothing runs; the fixtures are
``conftest.py``'s): ISSUE 40's Step 0 as a standing test; and the attention
kernels alone at the cell's shapes, heads half a lane line wide.
"""

import re

import jax

from benchmark import harness
from decoder_contract import attention_kernel_calls, products_in_scope
from deepfm_tpu.utils import profiling


def test_lfm2_step_at_the_cells_shapes_takes_the_kernels_at_64_lanes(
        step_for_v5e):
    """``lfm2-8b-a1b.train-sequences-8k-ep4``'s own step (every width, 5
    layers, 2 x 8,192 tokens; ISSUE 40's Step 0 as a standing test) compiled
    for a described v5e: the full layer's causal scores by the block kernel
    at heads of 64 (no ``[..., 1024, 8192]`` float32 score tensor, which the
    XLA path would hold: 2.1 GB), the expert layers' rows by the row
    kernels, ops charged to each of the model's scopes (``conv``,
    ``conv_taps`` and ``attn_scores`` among them), and arguments and
    temporaries together under the issue's 15.5 GB (measured here: 6.094 +
    4.254 GB; with the kernel refused 6.094 + 8.941) **with the one dense
    MLP keeping its first products** (the chip's memory described to
    ``sdar_moe.kept_by``: 0.94 GB; no layer has a shared expert): nine
    products under ``mlp``, none made again; **and the full layer its
    forward kernel's output and log-sum-exp** (67 + 2 MB, placed first): one
    call of the forward kernel, where the parent's step holds two (6.094 +
    5.138 GB since PR 54)."""
    tr, compiled, text = step_for_v5e(
        harness.load_json("configs", "lfm2-8b-a1b.json")["flags"])
    notes = tr.model.step_notes
    assert (notes["attn_scores"], notes["attn_score_blocks"],
            notes["moe_rows"], notes["conv_taps_by"]) == (
                "kernel", "136/256", "kernel", "xla")
    assert notes["attn_kept"] == "1/1 layers 0.07 GB"
    assert notes["mlp_kept"] == "1/1 layers 0.94 GB"
    assert notes["moe_products"] == "kernel rows256 dw1792/2048"
    assert products_in_scope(text, "mlp") == (9, 0)
    by_op = profiling.hlo_op_scopes(text)
    assert {"embed", "conv", "conv_taps", "attn", "attn_scores", "mlp",
            "moe", "head", "opt"} <= set(by_op.values())
    assert {scope for name, scope in by_op.items()
            if name.startswith("splash_mqa")} == {"attn_scores"}
    assert attention_kernel_calls(by_op) == (1,) * 3
    assert {scope for name, scope in by_op.items() if name.startswith(
        ("moe_take_rows", "moe_add_rows"))} == {"moe"}
    assert not re.search(r"f32\[[\d,]*1024,8192\]", text)
    memory = compiled.memory_analysis()
    assert 6.0e9 < memory.argument_size_in_bytes < 6.2e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 15.5e9


def test_attention_kernels_compile_at_64_lanes_at_the_cells_shapes(
        v5e, no_compile_cache):
    """Forward and backward at q [2, 8192, 8, 4, 64] bfloat16 under the
    causal mask: Mosaic takes the three kernels with the half line padded,
    and nothing [S, S] is made outside them."""
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
        spec(2, 8192, 8, 4, 64), spec(2, 8192, 8, 64),
        spec(2, 8192, 8, 64)).compile()
    text = compiled.as_text()
    for name in ("splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
                 "splash_mqa_dkv_no_residuals"):
        assert f"%{name}" in text, name
    # one float32 [2, 32, 8192, 8192] score matrix would be 17 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
