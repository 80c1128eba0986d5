"""``solar-open2-250b.train-sequences-8k-b1``'s own step, every width,
compiled for a *described* v5e (no chip attached, nothing runs; the fixtures
are ``conftest.py``'s): ISSUE 37's Step 0 as a standing test.
"""

import re

import numpy as np

import jax

from benchmark import harness
from decoder_contract import attention_kernel_calls, products_in_scope
from deepfm_tpu.utils import profiling
from test_tpu_compile_kimi_linear import assert_scan_by_the_kernels


def test_solar_open2_step_at_the_cells_shapes_fits_beside_its_state(
        step_for_v5e):
    """``solar-open2-250b.train-sequences-8k-b1``'s own step (every width,
    4 layers, 8,192 tokens; ISSUE 37's Step 0 as a standing test) compiled
    for a described v5e: the full layer's causal scores by the block kernel
    and the expert layers' rows by the row kernels, ops charged to each of
    the model's scopes (``attn_scores`` among them), no float32 copy of an
    array as large as a parameter, and the 10.09 GB of weights and moments
    with the step's temporaries under the chip's 16 GB (measured here:
    10.090 + 5.191 GB) **with the four shared experts keeping their first
    products** (the chip's memory described to ``sdar_moe.kept_by``: 4 x 84
    MB): nine products a shared expert under ``mlp``, none made again;
    **and the full layer its forward kernel's output and log-sum-exp** (17
    MB, placed first): one call of the forward kernel, the parent's two
    (10.090 + 4.058 GB since PR 54, the parent's 4.009)."""
    tr, compiled, text = step_for_v5e(
        harness.load_json("configs", "solar-open2-250b.json")["flags"])
    notes = tr.model.step_notes
    assert (notes["attn_scores"], notes["attn_score_blocks"],
            notes["moe_rows"]) == ("kernel", "136/256", "kernel")
    assert notes["attn_kept"] == "1/1 layers 0.02 GB"
    assert notes["mlp_kept"] == "4/4 layers 0.34 GB"
    assert notes["moe_products"] == "kernel rows256 dw640/2048"
    assert products_in_scope(text, "mlp") == (4 * 9, 0)
    by_op = profiling.hlo_op_scopes(text)
    assert {"embed", "attn", "attn_scores", "kda", "kda_scan", "mlp", "moe",
            "head", "opt"} <= set(by_op.values())
    assert {scope for name, scope in by_op.items()
            if name.startswith("splash_mqa")} == {"attn_scores"}
    assert attention_kernel_calls(by_op) == (1,) * 3
    assert {scope for name, scope in by_op.items() if name.startswith(
        ("moe_take_rows", "moe_add_rows"))} == {"moe"}
    assert_scan_by_the_kernels(tr, text, by_op, kda_layers=3)
    # a float32 array of a large parameter's shape is never copied
    shapes, _ = jax.eval_shape(tr.model.init, jax.random.PRNGKey(0))
    large = {",".join(map(str, x.shape)) for x in jax.tree.leaves(shapes)
             if np.prod(x.shape) >= 2 ** 20}
    copied = re.findall(r"= f32\[([\d,]+)\][^ ]* copy\(", text)
    assert len(large) == 9 and not large & set(copied)
    memory = compiled.memory_analysis()
    assert 10.0e9 < memory.argument_size_in_bytes < 10.2e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 15.5e9
