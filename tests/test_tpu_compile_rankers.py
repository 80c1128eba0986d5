"""What only the TPU's compiler can say of the rankers' train step, asked of
a *described* v5e (no chip attached, nothing runs; the fixtures are
``conftest.py``'s): the row-local table update (``Trainer._update_rows``;
PERF.md §6, PR 28) writes its rows into the tables **in place inside the
step's loop** — no copy of a table, no sweep — which XLA:CPU's text cannot
show (it reports no aliasing); where a row is whole 128-lane lines it
writes them by the ``embed_put_rows`` kernel (one DMA a row; PERF.md §6,
PR 30), which only Mosaic can compile; and the table-shaped step beside it
still sweeps. (The same steps as XLA:CPU compiles them:
``tests/test_row_local_update.py``.)
"""

import pytest

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


DEEPFM = dict(model="deepfm", numeric_fields=0, bottom_layers="",
              cross_layers=0, cross_rank=0)
DLRM_K32 = dict(embedding_size=32, bottom_layers="64,32",
                feature_size=1_600_000)
DEEPFM_K32 = dict(DEEPFM, embedding_size=32, feature_size=1_600_000,
                  l2_reg=1e-4)


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
        step_for_v5e):
    """K=128, a row one 128-lane line: one ``embed_put_rows`` kernel
    (``ops/pallas_put_rows.py``) writes the table's and the accumulator's
    rows, each array aliased to its result (operands: ids, two of new rows,
    the two tables). An alias XLA did not honour would show as a ``copy``
    of the table here."""
    tr, compiled, _ = step_for_v5e(FLAGS)
    assert tr._row_local_eligible() and tr.row_writeback == "dma"
    (op,) = _row_writes(tr, compiled)
    assert op["opcode"] == "custom-call", op
    assert op["name"].startswith("embed_put_rows"), op
    assert op["results"] == ["f32[400000,128]"] * 2, op
    assert op["primitive"] == "pallas_call" and op["in_place"] == [3, 4], op


def test_row_local_step_at_k32_keeps_the_scatter(step_for_v5e):
    """K=32: ids run along the lanes, a row is not one line, and the
    write-back stays XLA's scatter, in place inside the loop as before."""
    tr, compiled, _ = step_for_v5e({**FLAGS, **DLRM_K32})
    assert tr._row_local_eligible() and tr.row_writeback == "scatter"
    ops = _row_writes(tr, compiled)
    assert len(ops) == 2, ops
    for op in ops:
        assert op["primitive"] == "scatter" and op["in_place"] == [0], op
    assert "embed_put_rows" not in compiled.as_text()


def test_row_local_deepfm_at_k128_writes_each_table_its_own_way(
        step_for_v5e):
    """DeepFM under Adagrad at K=128 has two tables: ``fm_v`` ``[V,128]``
    goes by DMA, the first-order ``fm_w`` ``[V]`` by the scatter; the choice
    is each array's shape's. (``fm_w`` is 1.6 MB here and XLA prefetches it
    into VMEM, so only the ``[V,128]`` arrays are held to "nothing else".)"""
    tr, compiled, _ = step_for_v5e({**FLAGS, **DEEPFM})
    assert tr._row_local_eligible() and tr.row_writeback == "dma+scatter"
    ops = profiling.hlo_table_ops(compiled.as_text(), tr.model.padded_vocab)
    wide = [o for o in ops if "f32[400000,128]" in o["results"]]
    assert [(o["primitive"], o["in_place"]) for o in wide] == [
        ("pallas_call", [3, 4])], wide
    narrow = [o["primitive"] for o in ops if o["in_place"] and o not in wide]
    assert narrow == ["scatter", "scatter"], ops


@pytest.mark.parametrize("over", [{}, DEEPFM_K32, DLRM_K32],
                         ids=["dlrm_dcnv2-k128", "deepfm-k32-l2",
                              "dlrm_dcnv2-k32"])
def test_the_table_shaped_step_still_sweeps(step_for_v5e, over):
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
    tr, compiled, _ = step_for_v5e({**FLAGS, "optimizer": "Adam", **over})
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
