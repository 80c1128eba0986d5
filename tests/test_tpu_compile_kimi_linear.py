"""``kimi-linear-48b-a3b.train-sequences-8k``'s own step, every width,
compiled for a *described* v5e (no chip attached, nothing runs; the fixtures
are ``conftest.py``'s): that it fits the chip and names its layers, as a
standing test; and the expert layers' row kernels alone at the two widths
the decoder cells move (2,304 is this cell's).
"""

import re

import pytest

import jax

from benchmark import harness
from decoder_contract import products_in_scope
from deepfm_tpu.utils import profiling
from test_tpu_compile_shared_attention import PARENT, opcode_counts


def test_hybrid_decoder_step_at_the_cells_shapes_fits_and_names_its_layers(
        step_for_v5e):
    """``kimi-linear-48b-a3b.train-sequences-8k``'s own step (every width,
    5 layers, 2 x 8,192 tokens) compiled for a described v5e: ops charged to
    each of the model's scopes, the delta-rule scan's own among them, and
    arguments and temporaries together under the chip's memory (the issue's
    fallback to one sequence a step starts at 15.5 GB) **with the dense
    MLP and the four shared experts keeping their first products** (the
    chip's memory described to ``sdar_moe.kept_by``: 1.21 + 4 x 0.134
    GB): the scope ``mlp`` holds nine products a SwiGLU (gate, up and down
    forward, six backward) and none made again; one that keeps nothing
    holds gate and up a third time. Its latent attention's scores are
    XLA's: no block kernel is in the step, ``attn_kept`` is 0/1, and the
    step is the one PR 53's tree compiled, instruction for instruction by
    opcode (``step_opcodes_shared_attention.json``, counted there)."""
    tr, compiled, text = step_for_v5e(
        harness.load_json("configs", "kimi-linear-48b-a3b.json")["flags"])
    scopes = set(profiling.hlo_op_scopes(text).values())
    assert {"embed", "kda", "kda_scan", "attn", "mlp", "moe", "head",
            "opt"} <= scopes
    assert tr.model.step_notes["mlp_kept"] == "5/5 layers 1.74 GB"
    assert tr.model.step_notes["attn_kept"] == "0/1"
    assert "splash_mqa" not in text
    assert opcode_counts(text) == PARENT["kimi-linear-48b-a3b"]["opcodes"]
    assert products_in_scope(text, "mlp") == (5 * 9, 0)
    memory = compiled.memory_analysis()
    assert 7.8e9 < memory.argument_size_in_bytes < 8.0e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 15.5e9
    # its expert layers' rows (2,304 wide: 18 lines) move by the row kernels
    assert tr.model.step_notes["moe_rows"] == "kernel"
    by_op = profiling.hlo_op_scopes(text)
    assert {scope for name, scope in by_op.items() if name.startswith(
        ("moe_take_rows", "moe_add_rows"))} == {"moe"}
    # and are multiplied by the kernels that stop at the valid prefix (PR 52)
    assert tr.model.step_notes["moe_products"] \
        == "kernel rows256 dw1024/2304"
    assert {scope for name, scope in by_op.items()
            if name.startswith("moe_grouped_dot")} == {"moe"}
    assert not any(name.startswith("ragged-dot") for name in by_op)
    assert_scan_by_the_kernels(tr, text, by_op, kda_layers=4)


def assert_scan_by_the_kernels(tr, text, by_op, kda_layers):
    """The compiled step's delta-rule scan is two kernel calls a KDA layer
    under the scope ``kda_scan`` (the forward that keeps the entering states
    and the inverses, once: the layer's recomputation reads what it kept;
    and the backward), and nothing of the XLA form's pairwise-decay tensors
    (``[..., 4, 16, 16, 128]``) is left."""
    assert tr.model.step_notes["kda_scan"] == "kernel chunk64"
    calls = {name: scope for name, scope in by_op.items()
             if name.startswith("kda_scan_")}
    assert set(calls.values()) == {"kda_scan"}
    assert sorted(name.split(".")[0] for name in calls) == sorted(
        ["kda_scan_fwd_keep", "kda_scan_bwd"] * kda_layers)
    assert not re.search(r"\[[\d,]*4,16,16,128\]", text)


@pytest.mark.parametrize("width", [2048, 2304])
def test_row_kernels_compile_at_the_cells_shapes(v5e, no_compile_cache,
                                                 width):
    """``ops/pallas_moe_rows`` at a pass of 16,384 rows over 16,384 positions
    of the two decoder cells' widths, forward and backward: Mosaic takes the
    one-row strided copies (a ``[T/8, W/128, 8, 1, 128]`` view) and XLA makes
    that view and its way back without a copy of the array: nothing
    ``[16384, W]`` is made beside the results."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from deepfm_tpu.ops import pallas_moe_rows as pmr

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=SingleDeviceSharding(v5e))

    rows = 16384

    def loss(x, y, carry, tok, ends):
        xs, through = pmr.gather(jax.lax.stop_gradient(x), x, tok, ends,
                                 jnp.bfloat16)
        out = pmr.combine(carry, xs.astype(jnp.float32) * y, y[:, 0], tok,
                          ends)
        return jnp.sum(out) + jnp.sum(through)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec((rows, width), jnp.float32), spec((rows, width), jnp.float32),
        spec((rows, width), jnp.float32), spec((rows,), jnp.int32),
        spec((16,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    for name in ("moe_take_rows", "moe_add_rows"):
        assert name in text, name
    # (one copy of an array: this function's own carry, an argument it may
    # not spoil)
    copies = [line for line in text.splitlines() if " copy(" in line
              and f"[{rows},{width}]" in line.split(" copy(")[0]]
    assert len(copies) == 1 and " copy(%carry" in copies[0], copies
    assert " transpose(" not in text
    # xs and its product, the rows' cotangents: a few arrays, no more
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * rows * width * 4
