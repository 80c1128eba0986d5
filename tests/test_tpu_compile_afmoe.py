"""``trinity-mini.train-sequences-16k-ep8``'s own step, every width, compiled
for a *described* v5e (no chip attached, nothing runs; the fixtures are
``conftest.py``'s): ISSUE 53's memory rule as a standing test.
"""

import re

from benchmark import harness
from decoder_contract import attention_kernel_calls
from deepfm_tpu.utils import profiling


def test_afmoe_step_at_the_cells_shapes_runs_both_masks_by_the_kernel(
        step_for_v5e):
    """The cell's own step (every width, published layers 1-5, one sequence
    of 16,384 tokens) compiled for a described v5e: the windowed layers' and
    the full layer's scores by the block kernel under their own tables (no
    ``[..., 1024, 16384]`` float32 score tensor, which the XLA path would
    hold), each under its own scope; the expert layers' rows and products by
    their kernels; ops charged to each of the model's scopes; and arguments
    and temporaries together under the chip's memory **with every layer
    keeping its forward kernel's output and log-sum-exp** (the chip's memory
    described to ``sdar_moe.kept_by``: five of 136 MB, placed first), so the
    forward kernel is called once a layer, five times and not the parent's
    ten, **and the four shared experts their first products** (4 x 134 MB;
    5/5 layers 1.34 GB until PR 54: the dense layer's 805 MB are what the
    kernels' 0.68 GB took the room of; with them the step compiles to 15.87
    GB, without to 8.466 + 6.597 = 15.06, the parent's 15.32)."""
    tr, compiled, text = step_for_v5e(
        harness.load_json("configs", "trinity-mini.json")["flags"])
    notes = tr.model.step_notes
    memory = compiled.memory_analysis()
    print("afmoe step memory:", notes, memory.argument_size_in_bytes,
          memory.temp_size_in_bytes)
    # blocks of 512: the causal half 528 of 1,024, the band of 2,048 150
    assert (notes["attn_scores"], notes["attn_score_blocks"],
            notes["attn_window_blocks"]) == ("kernel", "528/1024", "150/1024")
    assert notes["attn_kept"] == "5/5 layers 0.68 GB"
    assert notes["mlp_kept"] == "4/5 layers 0.54 GB"
    assert notes["moe_rows"] == "kernel"
    # four expert layers of two passes of 16,384 rows
    assert notes["moe_rows_moved"] == "{moe_pairs_held}/%d" % (4 * 32768)
    assert notes["moe_products"].startswith("kernel rows256")
    by_op = profiling.hlo_op_scopes(text)
    assert {"embed", "attn", "attn_scores", "attn_scores_window", "mlp",
            "moe", "head", "opt"} <= set(by_op.values())
    assert {scope for name, scope in by_op.items()
            if name.startswith("splash_mqa")} == {"attn_scores",
                                                  "attn_scores_window"}
    assert attention_kernel_calls(by_op) == (5,) * 3
    assert {scope for name, scope in by_op.items()
            if name.startswith("moe_grouped_dot")} == {"moe"}
    assert not re.search(r"f32\[[\d,]*1024,16384\]", text)
    # 12 bytes a parameter resident: weight and Adam's two moments
    assert 8.4e9 < memory.argument_size_in_bytes < 8.6e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 15.5e9
