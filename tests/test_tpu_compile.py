"""What only the TPU's compiler can say of the train step, asked of a
*described* v5e (``jax.experimental.topologies``: no chip attached, nothing
runs; the ``on-chip-measurement`` guide, section 2): the row-local table
update (``Trainer._update_rows``; PERF.md §6, PR 28) writes its rows into
the tables **in place inside the step's loop** — no copy of a table, no
sweep — which XLA:CPU's text cannot show (it reports no aliasing).

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
# the heights, batch and towers cut so that the compile takes seconds.
FLAGS = dict(
    model="dlrm_dcnv2", feature_size=200_000, field_size=39,
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


def test_row_local_step_writes_rows_in_place_inside_the_loop(
        v5e, no_compile_cache, monkeypatch):
    tr, compiled = _compiled(v5e, monkeypatch)
    assert tr._row_local_eligible()
    ops = profiling.hlo_table_ops(compiled.as_text(), tr.model.padded_vocab)
    # the table and its accumulator, one row write each, and nothing else
    # as tall as the table: no fill, no sweep, no copy
    assert len(ops) == 2, ops
    for op in ops:
        assert op["loop_body"] and op["scope"] == "embed", op
        assert op["primitive"] == "scatter" and op["in_place"] == [0], op
    # the step's temporaries are the batch's, not a table's
    table_bytes = tr.model.padded_vocab * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < table_bytes / 2


def test_the_table_shaped_step_still_sweeps(v5e, no_compile_cache,
                                            monkeypatch):
    """The same model under Adam: fill, scatter-add in place on the fill,
    sweep — the three lines TUNING §5 item 6 describes."""
    tr, compiled = _compiled(v5e, monkeypatch, optimizer="Adam")
    assert not tr._row_local_eligible()
    ops = profiling.hlo_table_ops(compiled.as_text(), tr.model.padded_vocab)
    primitives = [o["primitive"] for o in ops if o["loop_body"]]
    assert "scatter-add" in primitives and "broadcast_in_dim" in primitives
    assert any(o["scope"] == "opt" and len(o["in_place"]) >= 3 for o in ops)
