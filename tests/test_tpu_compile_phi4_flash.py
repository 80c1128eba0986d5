"""``phi-4-mini-flash-reasoning.train-traces-8k-b1``'s own step, every
width, compiled for a *described* v5e (no chip attached, nothing runs; the
fixtures are ``conftest.py``'s): ISSUE 44's standing test; and the attention
kernel alone at the cell's shapes, values twice as wide as keys, under the
window's band and the causal mask.
"""

import re

import jax
import pytest

from benchmark import harness
from decoder_contract import attention_kernel_calls, products_in_scope
from deepfm_tpu.utils import profiling


def test_phi4_flash_step_at_the_cells_shapes_fits_and_takes_the_kernels(
        step_for_v5e):
    """The cell's own step (every width, published layers 14-19 whole, one
    sequence of 8,192 tokens) compiled for a described v5e: both masks'
    scores by the block kernel (31 of 256 blocks of 512 under the window,
    136 under the causal mask; no ``[..., 1024, 8192]`` float32 score
    tensor), the scans by their two kernels, ops charged to each of the
    model's scopes (``mamba``, ``mamba_scan`` and ``gmu`` among them), no
    whole-sequence scan state
    (``[8192, 16, 5120]`` float32 would be 2.7 GB), and arguments and
    temporaries together under the issue's 15.5 GB **with the three
    attention layers keeping their forward kernel's output and log-sum-exp**
    (the chip's memory described to ``sdar_moe.kept_by``: three of 85 MB,
    placed first: the forward kernel is called three times, the parent's
    six) **and five of the six layers their MLP's first product** (five of
    671 MB in what is left; 6/6 layers 4.03 GB until PR 54: the rule's room
    is 3.87 GB at 225 KiB a position, the kernels' 0.26 GB leave 3.61, five
    products take 3.36; 8.366 + 6.190 = 14.56 GB, the parent's 14.94, and
    3.787 GB of temporaries where nothing is kept): the scope ``mlp`` holds
    six products a layer (the first and the down product forward, four
    backward) and the first layer, which keeps nothing, the first product a
    third time, made again."""
    tr, compiled, text = step_for_v5e(
        harness.load_json("configs",
                          "phi-4-mini-flash-reasoning.json")["flags"])
    notes = tr.model.step_notes
    assert (notes["attn_scores"], notes["attn_window_blocks"],
            notes["attn_score_blocks"]) == ("kernel", "31/256", "136/256")
    assert notes["mamba_scan"] == "kernel steps64"
    assert notes["attn_kept"] == "3/3 layers 0.26 GB"
    assert notes["mlp_kept"] == "5/6 layers 3.36 GB"
    assert products_in_scope(text, "mlp") == (6 * 6 + 1, 1)
    by_op = profiling.hlo_op_scopes(text)
    assert attention_kernel_calls(by_op) == (3,) * 3
    assert {"embed", "mamba", "mamba_scan", "gmu", "attn", "attn_scores",
            "mlp", "head", "opt"} <= set(by_op.values())
    assert not {"moe", "kda", "conv", "cross"} & set(by_op.values())
    assert {scope for name, scope in by_op.items()
            if name.startswith("splash_mqa")} == {"attn_scores"}
    assert {scope for name, scope in by_op.items()
            if name.startswith("mamba_scan_")} == {"mamba_scan"}
    assert {name.split(".")[0] for name in by_op
            if name.startswith("mamba_scan_")} == {"mamba_scan_fwd",
                                                   "mamba_scan_bwd"}
    assert not re.search(r"f32\[[\d,]*1024,8192\]", text)
    assert not re.search(r"f32\[[\d,]*8192,[\d,]*16,5120\]", text)
    memory = compiled.memory_analysis()
    # float32 weights and Adam's two moments: 12 bytes a parameter
    assert 8.3e9 < memory.argument_size_in_bytes < 8.5e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 15.5e9


@pytest.mark.parametrize("mask", ["window", "causal"])
def test_attention_kernels_compile_with_values_twice_as_wide_as_keys(
        v5e, no_compile_cache, mask):
    """Forward and backward at q [1, 8192, 20, 2, 64], k [1, 8192, 20, 64]
    and v [1, 8192, 20, 128] bfloat16: Mosaic takes the three kernels with
    the pair's values 128 wide over 64-wide keys, and nothing [S, S] is made
    outside them."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from deepfm_tpu.models import kimi_linear, phi4_flash, sdar_moe

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=SingleDeviceSharding(v5e))

    which = phi4_flash.window(512) if mask == "window" else kimi_linear.causal

    def loss(q, k, v):
        return jnp.sum(sdar_moe._scores_kernel(
            q, k, v, mask=which).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec(1, 8192, 20, 2, 64), spec(1, 8192, 20, 64),
        spec(1, 8192, 20, 128)).compile()
    text = compiled.as_text()
    for name in ("splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
                 "splash_mqa_dkv_no_residuals"):
        assert f"%{name}" in text, name
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_scan_kernels_compile_at_the_cells_shapes(v5e, no_compile_cache):
    """Forward and backward at [1, 8192, 5120] x 16 float32: Mosaic takes
    both kernels (the per-state scalars from SMEM blocks, the states' history
    of a time block in VMEM), and nothing [T, N, C] is made outside them."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from deepfm_tpu.ops import pallas_selective_scan as pss

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=SingleDeviceSharding(v5e))

    def loss(x, d, a, b, c):
        return jnp.sum(pss.selective_scan(x, d, a, b, c) ** 2)

    assert pss.supported(5120, 8192, "tpu")
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        spec(1, 8192, 5120), spec(1, 8192, 5120), spec(5120, 16),
        spec(1, 8192, 16), spec(1, 8192, 16)).compile()
    text = compiled.as_text()
    assert "mamba_scan_fwd" in text and "mamba_scan_bwd" in text
    # the lanes' partial sums of dB and dC are 2 x 335 MB; the whole
    # sequence's states would be 2.7 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
