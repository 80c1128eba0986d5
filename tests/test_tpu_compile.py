"""What only the TPU's compiler can say of the train step, asked of a
*described* v5e (``jax.experimental.topologies``: no chip attached, nothing
runs; the ``on-chip-measurement`` guide, section 2): the row-local table
update (``Trainer._update_rows``; PERF.md §6, PR 28) writes its rows into
the tables **in place inside the step's loop** — no copy of a table, no
sweep — which XLA:CPU's text cannot show (it reports no aliasing); and
where a row is whole 128-lane lines it writes them by the ``embed_put_rows``
kernel (one DMA a row; PERF.md §6, PR 30), which only Mosaic can compile.

One file, and the topology is described inside a fixture: only one process
may hold libtpu, and every xdist worker imports every test file.
"""

import pytest

import jax

from deepfm_tpu.config import Config
from deepfm_tpu.parallel import mesh as mesh_lib
from deepfm_tpu.train import Trainer
from deepfm_tpu.utils import profiling

# MLPerf DLRM-DCNv2's row (K=128, one 128-lane line) and field split, with
# the heights, batch and towers cut so that the compile takes seconds. The
# table stays taller than VMEM (205 MB against 128 MiB): one that fits is
# prefetched there whole by XLA around the ``embed_put_rows`` kernel
# (``slice-start`` / ``copy-start`` of the table), which no benchmark cell's
# table can be.
FLAGS = dict(
    model="dlrm_dcnv2", feature_size=400_000, field_size=39,
    numeric_fields=13, embedding_size=128, bottom_layers="64,128",
    cross_layers=1, cross_rank=64, deep_layers="128,64",
    dropout="1,1", optimizer="Adagrad", learning_rate=0.004, l2_reg=0.0,
    compute_dtype="bfloat16", batch_size=512, steps_per_loop=2)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture()
def no_compile_cache():
    """A described device cannot read an executable back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled(device, monkeypatch, **over):
    # the trainer picks its kernels by backend: trace what a TPU host would
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(**{**FLAGS, **over})
    tr = Trainer(cfg, mesh_info=mesh_lib.build_mesh(cfg, devices=[device]))
    return tr, tr.step_compiled(device=device)


def _row_writes(tr, compiled):
    """The step's table-tall instructions: for a row-local step, the table's
    and its accumulator's row writes, inside the loop, in place, and nothing
    else as tall as a table — no fill, no sweep, no copy."""
    ops = profiling.hlo_table_ops(compiled.as_text(), tr.model.padded_vocab)
    for op in ops:
        assert op["loop_body"] and op["scope"] == "embed", op
    # the step's temporaries are the batch's, not a table's
    table_bytes = tr.model.padded_vocab * tr.cfg.embedding_size * 4
    assert compiled.memory_analysis().temp_size_in_bytes < table_bytes / 2
    return ops


def test_row_local_step_writes_rows_by_dma_in_place_inside_the_loop(
        v5e, no_compile_cache, monkeypatch):
    """K=128, a row one 128-lane line: one ``embed_put_rows`` kernel
    (``ops/pallas_put_rows.py``) writes the table's and the accumulator's
    rows, each array aliased to its result (operands: ids, two of new rows,
    the two tables). An alias XLA did not honour would show as a ``copy``
    of the table here."""
    tr, compiled = _compiled(v5e, monkeypatch)
    assert tr._row_local_eligible() and tr.row_writeback == "dma"
    (op,) = _row_writes(tr, compiled)
    assert op["opcode"] == "custom-call", op
    assert op["name"].startswith("embed_put_rows"), op
    assert op["results"] == ["f32[400000,128]"] * 2, op
    assert op["primitive"] == "pallas_call" and op["in_place"] == [3, 4], op


def test_row_local_step_at_k32_keeps_the_scatter(v5e, no_compile_cache,
                                                 monkeypatch):
    """K=32: ids run along the lanes, a row is not one line, and the
    write-back stays XLA's scatter, in place inside the loop as before."""
    tr, compiled = _compiled(v5e, monkeypatch, embedding_size=32,
                             bottom_layers="64,32", feature_size=1_600_000)
    assert tr._row_local_eligible() and tr.row_writeback == "scatter"
    ops = _row_writes(tr, compiled)
    assert len(ops) == 2, ops
    for op in ops:
        assert op["primitive"] == "scatter" and op["in_place"] == [0], op
    assert "embed_put_rows" not in compiled.as_text()


def test_row_local_deepfm_at_k128_writes_each_table_its_own_way(
        v5e, no_compile_cache, monkeypatch):
    """DeepFM under Adagrad at K=128 has two tables: ``fm_v`` ``[V,128]``
    goes by DMA, the first-order ``fm_w`` ``[V]`` by the scatter; the choice
    is each array's shape's. (``fm_w`` is 1.6 MB here and XLA prefetches it
    into VMEM, so only the ``[V,128]`` arrays are held to "nothing else".)"""
    tr, compiled = _compiled(
        v5e, monkeypatch, model="deepfm", numeric_fields=0, bottom_layers="",
        cross_layers=0, cross_rank=0)
    assert tr._row_local_eligible() and tr.row_writeback == "dma+scatter"
    ops = profiling.hlo_table_ops(compiled.as_text(), tr.model.padded_vocab)
    wide = [o for o in ops if "f32[400000,128]" in o["results"]]
    assert [(o["primitive"], o["in_place"]) for o in wide] == [
        ("pallas_call", [3, 4])], wide
    narrow = [o["primitive"] for o in ops if o["in_place"] and o not in wide]
    assert narrow == ["scatter", "scatter"], ops


DEEPFM_K32 = dict(model="deepfm", numeric_fields=0, bottom_layers="",
                  cross_layers=0, cross_rank=0, embedding_size=32,
                  feature_size=1_600_000, l2_reg=1e-4)


DLRM_K32 = dict(embedding_size=32, bottom_layers="64,32",
                feature_size=1_600_000)


@pytest.mark.parametrize("over", [{}, DEEPFM_K32, DLRM_K32],
                         ids=["dlrm_dcnv2-k128", "deepfm-k32-l2",
                              "dlrm_dcnv2-k32"])
def test_the_table_shaped_step_still_sweeps(v5e, no_compile_cache,
                                            monkeypatch, over):
    """The same model under Adam, and DeepFM at the DeepFM cells' row with
    L2: fill, scatter-add, sweep — the lines TUNING §5 item 6 describes.
    The scatter-adds take a trip of the batch's distinct rows
    (``Trainer._table_grads``), never its positions, and update the loop's
    carry where it lies: a copy of the 2.16 GB gradient a trip would cost
    more than the scatter saves. A narrow table's views are read by the
    batch's rows (``ops.embedding.take_planned``), and no cast of the whole
    table rides into that loop: with one narrow table and bfloat16 compute
    XLA's bfloat16 propagation put a ``convert bf16[V, 32]`` there until
    the rows travelled as raw words (PERF.md §6, PR 42)."""
    tr, compiled = _compiled(v5e, monkeypatch, optimizer="Adam", **over)
    assert not tr._row_local_eligible() and tr.embed_grad == "rows"
    ops = profiling.hlo_table_ops(compiled.as_text(), tr.model.padded_vocab)
    primitives = [o["primitive"] for o in ops if o["loop_body"]]
    assert "scatter-add" in primitives and "broadcast_in_dim" in primitives
    assert any(o["scope"] == "opt" and len(o["in_place"]) >= 3 for o in ops)
    scatters = [o for o in ops if o["primitive"] == "scatter-add"]
    assert len(scatters) == len(tr.model.embedding_param_names()), ops
    for op in scatters:
        assert op["in_place"] == [0] and op["scope"] == "embed", op
    assert "copy" not in {o["opcode"] for o in ops
                          if any("," in r for r in o["results"])}, ops
    assert "convert" not in {o["opcode"] for o in ops}, ops
    assert tr.embed_lookup == ("positions" if not over else "rows")


# --- the block-masked attention kernel (ops/block_attention.py; PR 32) ------

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
        v5e, no_compile_cache, monkeypatch):
    """On a TPU at head_dim 128 the step's masked scores are the three
    kernels JAX's flash attention is made of (forward; dq; dk and dv), each
    charged to ``attn`` by the step's own text, though each prints over three
    lines (``profiling.whole_instructions``), and the model says so."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(**SDAR_FLAGS)
    tr = Trainer(cfg, mesh_info=mesh_lib.build_mesh(cfg, devices=[v5e]))
    scopes = profiling.hlo_op_scopes(tr.step_hlo_text(device=v5e))
    assert tr.model.step_notes == {
        "attn_scores": "kernel", "attn_score_blocks": "3/4",
        "moe_rows": "kernel", "moe_rows_moved": "{moe_pairs_held}/8192"}
    # the expert layer's rows move by the row kernels (rows of two lines,
    # 1,024 positions, one pass of 4,096 rows), forward and backward, all
    # charged to ``moe``
    rows = {name: scope for name, scope in scopes.items()
            if name.startswith(("moe_take_rows", "moe_add_rows"))}
    assert {name.split(".")[0] for name in rows} == {
        "moe_take_rows", "moe_add_rows"} and len(rows) == 5, rows
    assert set(rows.values()) == {"moe"}, rows
    kernels = {name: scope for name, scope in scopes.items()
               if name.startswith("splash_mqa_")}
    assert {name.split(".")[0] for name in kernels} == {
        "splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
        "splash_mqa_dkv_no_residuals"}, kernels
    assert set(kernels.values()) == {"attn"}, kernels
    # the raw text loses them: their op_name is on a continuation line
    raw = profiling.hlo_op_scopes(tr.step_compiled(device=v5e).as_text())
    assert {raw[name] for name in kernels} == {""}
    # and every other instruction is charged as it was (the grouped
    # products' kernels get their scope from the model, as before)
    def others(by_op):
        return {n: s for n, s in by_op.items()
                if n not in kernels and not n.startswith(
                    ("ragged-dot", "pallas_call"))}    # (the kernels' parts)
    assert others(scopes) == others(raw)


def test_decoder_step_at_head_dim_32_keeps_the_xla_scores(
        v5e, no_compile_cache, monkeypatch):
    """A head a quarter of a lane line wide keeps XLA's chunks
    (``block_attention.supported``: whole and half lines)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(**{**SDAR_FLAGS, "attn_head_dim": 32})
    tr = Trainer(cfg, mesh_info=mesh_lib.build_mesh(cfg, devices=[v5e]))
    text = tr.step_hlo_text(device=v5e)
    assert tr.model.step_notes["attn_scores"] == "xla"
    assert "attn_score_blocks" not in tr.model.step_notes
    assert "splash_mqa" not in text


def test_decoder_step_at_head_dim_64_takes_the_kernels(
        v5e, no_compile_cache, monkeypatch):
    """Since PR 40 a head half a lane line wide takes the kernels, as it is
    (Mosaic compiles them at 64 lanes)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(**{**SDAR_FLAGS, "attn_head_dim": 64})
    tr = Trainer(cfg, mesh_info=mesh_lib.build_mesh(cfg, devices=[v5e]))
    text = tr.step_hlo_text(device=v5e)
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


def test_hybrid_decoder_step_at_the_cells_shapes_fits_and_names_its_layers(
        v5e, no_compile_cache, monkeypatch):
    """``kimi-linear-48b-a3b.train-sequences-8k``'s own step (every width,
    5 layers, 2 x 8,192 tokens) compiled for a described v5e: ops charged to
    each of the model's scopes, the delta-rule scan's own among them, and
    arguments and temporaries together under the chip's memory (the issue's
    fallback to one sequence a step starts at 15.5 GB)."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        flags = json.load(f)["flags"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(**flags)
    tr = Trainer(cfg, mesh_info=mesh_lib.build_mesh(cfg, devices=[v5e]))
    compiled = tr.step_compiled(device=v5e)
    text = profiling.scope_kernels(
        profiling.whole_instructions(compiled.as_text()),
        tr.model.kernel_scopes)
    scopes = set(profiling.hlo_op_scopes(text).values())
    assert {"embed", "kda", "kda_scan", "attn", "mlp", "moe", "head",
            "opt"} <= scopes
    memory = compiled.memory_analysis()
    assert 7.8e9 < memory.argument_size_in_bytes < 8.0e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 15.5e9
    # its expert layers' rows (2,304 wide: 18 lines) move by the row kernels
    assert tr.model.step_notes["moe_rows"] == "kernel"
    by_op = profiling.hlo_op_scopes(text)
    assert {scope for name, scope in by_op.items() if name.startswith(
        ("moe_take_rows", "moe_add_rows"))} == {"moe"}


def test_solar_open2_step_at_the_cells_shapes_fits_beside_its_state(
        v5e, no_compile_cache, monkeypatch):
    """``solar-open2-250b.train-sequences-8k-b1``'s own step (every width,
    4 layers, 8,192 tokens; ISSUE 37's Step 0 as a standing test) compiled
    for a described v5e: the full layer's causal scores by the block kernel
    and the expert layers' rows by the row kernels, ops charged to each of
    the model's scopes (``attn_scores`` among them), no float32 copy of an
    array as large as a parameter, and the 10.09 GB of weights and moments
    with the step's temporaries under the chip's 16 GB (measured here:
    10.090 + 5.191 GB)."""
    import json
    import os
    import re

    import numpy as np

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "solar-open2-250b.json")) as f:
        flags = json.load(f)["flags"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(**flags)
    tr = Trainer(cfg, mesh_info=mesh_lib.build_mesh(cfg, devices=[v5e]))
    compiled = tr.step_compiled(device=v5e)
    notes = tr.model.step_notes
    assert (notes["attn_scores"], notes["attn_score_blocks"],
            notes["moe_rows"]) == ("kernel", "136/256", "kernel")
    text = profiling.scope_kernels(
        profiling.whole_instructions(compiled.as_text()),
        tr.model.kernel_scopes)
    by_op = profiling.hlo_op_scopes(text)
    assert {"embed", "attn", "attn_scores", "kda", "kda_scan", "mlp", "moe",
            "head", "opt"} <= set(by_op.values())
    assert {scope for name, scope in by_op.items()
            if name.startswith("splash_mqa")} == {"attn_scores"}
    assert {scope for name, scope in by_op.items() if name.startswith(
        ("moe_take_rows", "moe_add_rows"))} == {"moe"}
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


def test_lfm2_step_at_the_cells_shapes_takes_the_kernels_at_64_lanes(
        v5e, no_compile_cache, monkeypatch):
    """``lfm2-8b-a1b.train-sequences-8k-ep4``'s own step (every width, 5
    layers, 2 x 8,192 tokens; ISSUE 40's Step 0 as a standing test) compiled
    for a described v5e: the full layer's causal scores by the block kernel
    at heads of 64 (no ``[..., 1024, 8192]`` float32 score tensor, which the
    XLA path would hold: 2.1 GB), the expert layers' rows by the row
    kernels, ops charged to each of the model's scopes (``conv``,
    ``conv_taps`` and ``attn_scores`` among them), and arguments and
    temporaries together under the issue's 15.5 GB (measured here: 6.094 +
    4.254 GB; with the kernel refused 6.094 + 8.941)."""
    import json
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        flags = json.load(f)["flags"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(**flags)
    tr = Trainer(cfg, mesh_info=mesh_lib.build_mesh(cfg, devices=[v5e]))
    compiled = tr.step_compiled(device=v5e)
    notes = tr.model.step_notes
    assert (notes["attn_scores"], notes["attn_score_blocks"],
            notes["moe_rows"], notes["conv_taps_by"]) == (
                "kernel", "136/256", "kernel", "xla")
    text = profiling.scope_kernels(
        profiling.whole_instructions(compiled.as_text()),
        tr.model.kernel_scopes)
    by_op = profiling.hlo_op_scopes(text)
    assert {"embed", "conv", "conv_taps", "attn", "attn_scores", "mlp",
            "moe", "head", "opt"} <= set(by_op.values())
    assert {scope for name, scope in by_op.items()
            if name.startswith("splash_mqa")} == {"attn_scores"}
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
