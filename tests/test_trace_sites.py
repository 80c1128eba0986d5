"""The instrumentation sites of the train path: the named scopes that name
the step's phases in the compiled program, and the spans of the input
thread, the staging ring, the dispatch loop and the collector (TUNING §17).
"""

import faulthandler
import gc
import os
import re
import threading
import time

import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.data import libsvm, pipeline
from deepfm_tpu.obs import trace as trace_lib
from deepfm_tpu.train import Trainer
from deepfm_tpu.utils import profiling

SCOPES = ("embed", "fm", "tower", "loss", "l2", "opt")
K = 2


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace_lib.reset()
    yield
    trace_lib.reset()


def _cfg(**over):
    base = dict(
        feature_size=200, field_size=4, embedding_size=4, deep_layers="8",
        dropout="0.5", batch_size=32, compute_dtype="float32", l2_reg=1e-4,
        learning_rate=0.01, log_steps=2, seed=7, scale_lr_by_world=False,
        mesh_data=1, mesh_model=1, steps_per_loop=K)
    base.update(over)
    return Config(**base)


def _batches(n, bs=32, fields=4, vocab=200):
    rng = np.random.default_rng(3)
    return [{
        "label": rng.integers(0, 2, (bs, 1)).astype(np.float32),
        "feat_ids": rng.integers(0, vocab, (bs, fields)).astype(np.int32),
        "feat_vals": rng.standard_normal((bs, fields)).astype(np.float32),
    } for _ in range(n)]


# ---------------------------------------------------------------------------
# (a) named scopes reach the lowered step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("update,kernels", [
    ("dense", "auto"), ("sparse", "auto"), ("sparse", "off")],
    ids=["dense", "sparse-fused", "sparse-plan"])
def test_compiled_multi_step_names_every_phase(update, kernels):
    tr = Trainer(_cfg(embedding_update=update, embedding_kernels=kernels))
    lowered = tr.multi_step.lower(tr.init_state(),
                                  tr.put_superbatch(_batches(K)))
    # The scan's body is lowered as a function of its own, so the whole
    # path of an op is only put together in the compiled program's text.
    ops = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    assert {profiling.innermost_scope(o) for o in ops} >= set(SCOPES)
    # The backward pass keeps the name: the gather's transpose is the
    # scatter-add into the row-shaped gradient of the sparse plan. The dense
    # step (``Trainer._grad_by_rows``) and the fused sparse step
    # differentiate the gathered views, and scatter their cotangents
    # themselves, under ``embed``.
    backward = {"transpose(jvp(tower))", "transpose(jvp(fm))"}
    if kernels != "auto":
        backward.add("transpose(jvp(embed))")
    for name in backward:
        assert any(f"/{name}/" in o for o in ops), name
    # What the benchmark's reader is handed: instruction -> scope.
    by_op = tr.step_op_scopes()
    assert set(by_op.values()) == set(SCOPES) | {""}
    assert all(re.fullmatch(r"[\w.\-]+", name) for name in by_op)


def test_a_step_compiled_once_reads_as_an_untouched_trainers(compiled_once):
    """``conftest.py``'s ``compiled_once``: the trainer answers with the one
    executable, and its text and scopes, by the trainer's own methods, are
    those of a trainer that compiles for every question."""
    held, alone = Trainer(_cfg()), Trainer(_cfg())
    compiled = compiled_once(held)
    assert held.step_compiled() is compiled
    # (the text's tables of source frames name the caller: not instructions)
    def instructions(text):
        return [re.sub(r" stack_frame_id=\d+", "", line)
                for line in text.splitlines() if " = " in line]
    assert instructions(held.step_hlo_text()) == instructions(
        alone.step_hlo_text())
    assert held.step_op_scopes() == alone.step_op_scopes()


def test_innermost_scope():
    f = profiling.innermost_scope
    assert f("jit(multi)/jit(main)/while/body/transpose(jvp(embed))/"
             "scatter-add") == "embed"
    assert f("jit(multi)/while/body/opt/l2/reduce_sum") == "l2"
    assert f("jit(multi)/while/body/jvp(tower)/tower/dot_general") == "tower"
    assert f("jit(multi)/while/body/add") == ""
    assert f("jit(multi)/while/body/jvp(opt)") == ""      # the primitive
    assert f("jit(embedding)/fm_extra/mul") == ""


# ---------------------------------------------------------------------------
# (b) the in-process input thread
# ---------------------------------------------------------------------------

def _pipeline(tmp_path, epochs=2):
    files = libsvm.generate_synthetic_ctr(
        str(tmp_path), num_files=2, examples_per_file=96, feature_size=200,
        field_size=4, prefix="tr", seed=1)
    return pipeline.CtrPipeline(
        files, field_size=4, batch_size=16, num_epochs=epochs,
        shuffle_buffer=64, seed=5, prefetch_batches=0)


def test_pooled_pipeline_emits_input_spans(tmp_path):
    trace_lib.configure("full", export_env=False)
    n = sum(n_ex for _, _, n_ex in _pipeline(tmp_path).iter_superbatches(K))
    assert n == 2 * 192
    events = trace_lib._tracer.events()
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    epochs = by["input.epoch"]
    assert [e["args"]["epoch"] for e in epochs] == [0, 1]
    assert all(e["ph"] == "i" and e["args"]["files"] == 2 for e in epochs)
    for name in ("input.pool_fill", "input.pool_drain", "input.emit",
                 "input.read", "input.frame"):
        assert by[name] and all(e["ph"] == "X" for e in by[name]), name
    # Every record is filled once, drained at least once, emitted once.
    for epoch in (0, 1):
        filled = sum(e["args"]["records"] for e in by["input.pool_fill"]
                     if e["args"]["epoch"] == epoch)
        assert filled == 192
    assert sum(e["args"]["records"] for e in by["input.emit"]) == n
    assert all(e["args"]["records"] > 0 for e in by["input.pool_drain"])


def test_pooled_pipeline_is_silent_when_off(tmp_path, monkeypatch):
    built = []
    monkeypatch.setattr(trace_lib, "_Span",
                        lambda *a, **k: built.append(a) or pytest.fail())
    list(_pipeline(tmp_path).iter_superbatches(K))
    assert trace_lib._tracer.events() == [] and not built


def test_pipeline_reads_no_environment_knob(tmp_path, monkeypatch):
    """The input thread's pace is the data's: the two ``DEEPFM_TPU_*``
    variables that once slept a given number of ns per record in the drain
    and in the staging transfer are read by nothing. At 1 s a record,
    honouring either would take this test minutes."""
    def emission():
        t0 = time.monotonic()
        out = [(k, n, {name: a.tobytes() for name, a in rows.items()})
               for rows, k, n in _pipeline(tmp_path).iter_superbatches(K)]
        return out, time.monotonic() - t0

    for name in [n for n in os.environ if n.startswith("DEEPFM_TPU_")]:
        monkeypatch.delenv(name)
    plain, _ = emission()
    for leg in ("HOST", "TRANSFER"):
        monkeypatch.setenv(f"DEEPFM_TPU_SYNTH_{leg}_NS_PER_RECORD",
                           "1000000000")
    knobbed, seconds = emission()
    assert knobbed == plain and sum(n for _, n, _ in plain) == 2 * 192
    assert seconds < 60.0
    tr = Trainer(_cfg())
    t0 = time.monotonic()
    _, out = tr.fit(tr.init_state(), _batches(K * 2))
    assert out["steps"] == K * 2 and time.monotonic() - t0 < 60.0


# ---------------------------------------------------------------------------
# (c) the collector
# ---------------------------------------------------------------------------

def test_host_gc_span_and_hook_lifetime():
    before = len(gc.callbacks)
    trace_lib.configure("full", export_env=False)
    assert len(gc.callbacks) == before + 1
    trace_lib.configure("ring", export_env=False)     # not installed twice
    assert len(gc.callbacks) == before + 1
    gc.collect()
    ev = [e for e in trace_lib._tracer.events() if e["name"] == "host.gc"]
    assert ev and ev[-1]["ph"] == "X"
    assert ev[-1]["args"]["generation"] == 2
    assert ev[-1]["args"]["collected"] >= 0
    trace_lib.reset()
    assert len(gc.callbacks) == before
    trace_lib.configure("off", export_env=False)
    assert len(gc.callbacks) == before


@pytest.mark.parametrize("mode", ["full", "ring"])
def test_a_collection_inside_the_tracer_does_not_hang_it(mode, tmp_path):
    """With a threshold of 1 the collector fires wherever the tracer
    allocates, under its own lock too (``events()`` copies the buffer
    there): the ``host.gc`` hook must never wait for that lock."""
    trace_lib.configure(mode, capacity=64, export_env=False)
    done = []

    def work():
        for i in range(300):
            with trace_lib.span("w", i=i):
                trace_lib.instant("p", held=[[i]])
            trace_lib._tracer.events()
        trace_lib.export(str(tmp_path / "t.json"))
        done.append(len(trace_lib._tracer.events()))

    old = gc.get_threshold()
    gc.set_threshold(1)
    try:
        t = threading.Thread(target=work, daemon=True)
        t.start()
        t.join(30)
    finally:
        gc.set_threshold(*old)
    if t.is_alive():
        faulthandler.dump_traceback(all_threads=True)
        pytest.fail("the tracer hangs when a collection starts inside it")
    names = {e["name"] for e in trace_lib._tracer.events()}
    assert done and {"w", "p", "host.gc"} <= names
    if mode == "ring":
        assert done[0] <= 2 * 64 and trace_lib.dropped() > 0


# ---------------------------------------------------------------------------
# (d) one superbatch, one seq
# ---------------------------------------------------------------------------

def test_fit_spans_share_seq():
    trace_lib.configure("full", export_env=False)
    tr = Trainer(_cfg())
    n_dispatch = 4
    tr.fit(tr.init_state(), _batches(K * n_dispatch))
    events = [e for e in trace_lib._tracer.events() if e["ph"] == "X"]
    seqs = {name: [e["args"]["seq"] for e in events if e["name"] == name]
            for name in ("stage.input_wait", "stage.wait", "stage.transfer",
                         "train.dispatch")}
    want = list(range(1, n_dispatch + 1))
    assert seqs["stage.transfer"] == want
    assert seqs["train.dispatch"] == want
    # Batches arrive one by one here: K waits per superbatch, then the one
    # that finds the source at its end.
    assert seqs["stage.input_wait"] == sorted(want * K) + [n_dispatch + 1]
    # Transfer j fences on dispatch j - staging_buffers.
    assert seqs["stage.wait"] == want[tr.cfg.staging_buffers:]
    transfers = [e for e in events if e["name"] == "stage.transfer"]
    per_dispatch = K * 32 * (4 * 4 + 4 * 4 + 4)
    assert all(e["args"]["bytes"] == per_dispatch
               and e["args"]["records"] == K * 32 for e in transfers)
    syncs = [e["args"]["step"] for e in events if e["name"] == "train.log_sync"]
    assert syncs == [K * i for i in want]


def test_staging_is_timed_by_its_spans_alone():
    """``stage.wait`` and ``stage.transfer`` are the staging ring's only
    clock: each carries exactly the attributes the benchmark's readers and
    ``trace_report.py --stalls`` key on, and ``fit``'s result repeats
    neither interval (``examples_per_sec``, the operator's line, stays)."""
    trace_lib.configure("full", export_env=False)
    tr = Trainer(_cfg())
    _, out = tr.fit(tr.init_state(), _batches(K * 4))
    events = [e for e in trace_lib._tracer.events() if e["ph"] == "X"]
    transfers = [e for e in events if e["name"] == "stage.transfer"]
    waits = [e for e in events if e["name"] == "stage.wait"]
    assert len(transfers) == 4 and len(waits) == 4 - tr.cfg.staging_buffers
    assert {frozenset(e["args"]) for e in transfers} == {
        frozenset(("seq", "records", "bytes"))}
    assert {frozenset(e["args"]) for e in waits} == {frozenset(("seq",))}
    assert all(e["dur"] >= 0 for e in transfers + waits)
    assert not [k for k in out if k.startswith("staging_")]
    assert out["examples_per_sec"] > 0 and out["steps"] == K * 4


# ---------------------------------------------------------------------------
# (e) the row-local table update's counters ride on train.log_sync
# ---------------------------------------------------------------------------

def _report():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import trace_report
    return trace_report


@pytest.mark.parametrize("eligible", [True, False],
                         ids=["adagrad-no-l2", "adam-l2"])
def test_log_sync_carries_the_row_counters(eligible, tmp_path, capsys):
    """Adagrad without L2 updates the tables on the batch's distinct rows
    and says how many and in how many trips, on the span that reads the loss
    back (no sync of its own); Adam with L2 sweeps every row and builds its
    table gradient from the same distinct rows: the same counts, and
    ``embed_grad`` in place of the write-back."""
    trace_lib.configure("full", export_env=False)
    over = dict(optimizer="Adagrad", l2_reg=0.0) if eligible else {}
    tr = Trainer(_cfg(**over))
    assert tr._row_local_eligible() == eligible
    n_dispatch = 3
    batches = _batches(K * n_dispatch)
    tr.fit(tr.init_state(), batches)
    syncs = [e["args"] for e in trace_lib._tracer.events()
             if e["name"] == "train.log_sync"]
    assert [a["step"] for a in syncs] == [K * i for i in (1, 2, 3)]
    path = str(tmp_path / "trace.json")
    trace_lib.export(path)
    report = _report()
    events, _ = report._load(path)
    # the last scanned step's, like the loss
    want = [len(np.unique(batches[K * i - 1]["feat_ids"])) for i in (1, 2, 3)]
    assert [a["embed_distinct_rows"] for a in syncs] == want
    assert [a["embed_row_trips"] for a in syncs] == [1, 1, 1]
    counts = {
        "steps": 3, "distinct_rows_mean": sum(want) / 3,
        "distinct_rows_max": max(want), "row_trips_mean": 1.0,
        "row_trips_max": 1, "one_trip_share": 1.0}
    # either step read its [V] and [V,4] tables' views by the batch's rows
    assert [a["embed_lookup"] for a in syncs] == ["rows"] * 3
    assert report.table_lookup(events) == tr.embed_lookup == "rows"
    if not eligible:
        assert all(set(a) == {"step", "embed_distinct_rows",
                              "embed_row_trips", "embed_grad",
                              "embed_grad_by_table", "embed_lookup"}
                   and a["embed_grad"] == "rows"
                   and a["embed_grad_by_table"] == "" for a in syncs)
        assert report.row_updates(events) == {**counts, "writeback": "?"}
        assert report.table_gradient(events) == "rows"
        assert report.tables_summed_as_tables(events) == ""
        assert report.main([path]) == 0
        assert ("dense-gradient step, table gradient from rows, views looked "
                "up by rows over 3 logged "
                "steps: embed_distinct_rows mean %.0f max %d, embed_row_trips "
                "mean 1.00 max 1, one trip in 100%% of them, every row swept "
                "after it" % (sum(want) / 3, max(want))
                ) in capsys.readouterr().out
        return
    # XLA:CPU, and a [V,4] row anyway: the write-back is the scatter
    assert [a["embed_row_writeback"] for a in syncs] == ["scatter"] * 3
    assert report.row_updates(events) == {**counts, "writeback": "scatter"}
    assert report.table_gradient(events) is None
    assert report.main([path]) == 0
    assert ("row-local table update, views looked up by rows over 3 logged "
            "steps: embed_distinct_rows "
            "mean %.0f max %d, embed_row_trips mean 1.00 max 1, one trip in "
            "100%% of them, rows written back by scatter"
            % (sum(want) / 3, max(want))) in capsys.readouterr().out


# a model whose tables are all wide: MLPerf's DLRM-DCNv2 holds ``fm_v`` alone
_NO_ONE_WORD_ROW_TABLE = dict(
    model="dlrm_dcnv2", numeric_fields=1, bottom_layers="8,4",
    cross_layers=1, cross_rank=2)


@pytest.mark.parametrize("replicas,over,by_table", [
    (2, {}, "fm_w"), (4, {}, "fm_w"), (2, _NO_ONE_WORD_ROW_TABLE, "")],
    ids=["2", "4", "2-no-one-word-row-table"])
def test_log_sync_says_when_data_replicas_exchange_their_rows(
        replicas, over, by_table, tmp_path, capsys):
    """Under data replicas the dense-gradient step gathers the replicas'
    rows in place of all-reducing the tables, says so in ``embed_grad`` and
    adds ``embed_exchanged_rows`` (all replicas' rows, what each chip
    scattered) to the fullest replica's two counts; the report's line
    carries its mean. ``embed_grad_by_table`` names the table whose row is
    one word, which crosses as a table all the same, and is empty where the
    model has none; the line says which."""
    trace_lib.configure("full", export_env=False)
    tr = Trainer(_cfg(mesh_data=replicas, **over))
    batches = _batches(K * 3)
    tr.fit(tr.init_state(), batches)
    assert tr.embed_grad == "rows, exchanged over data"
    assert tr.embed_grad_by_table == by_table
    syncs = [e["args"] for e in trace_lib._tracer.events()
             if e["name"] == "train.log_sync"]
    per_replica = [[len(np.unique(tr.model.lookup_ids(shard)))
                    for shard in np.split(
                        batches[K * i - 1]["feat_ids"], replicas)]
                   for i in (1, 2, 3)]
    assert all(set(a) == {"step", "embed_distinct_rows", "embed_row_trips",
                          "embed_exchanged_rows", "embed_grad",
                          "embed_grad_by_table", "embed_lookup"}
               and a["embed_grad"] == tr.embed_grad
               and a["embed_lookup"] == "rows"
               and a["embed_grad_by_table"] == by_table for a in syncs)
    assert [a["embed_distinct_rows"] for a in syncs] == [
        max(d) for d in per_replica]
    assert [a["embed_exchanged_rows"] for a in syncs] == [
        sum(d) for d in per_replica]
    path = str(tmp_path / "trace.json")
    trace_lib.export(path)
    report = _report()
    events, _ = report._load(path)
    mean = sum(sum(d) for d in per_replica) / 3
    assert report.row_updates(events)["exchanged_rows_mean"] == mean
    assert report.table_gradient(events) == tr.embed_grad
    assert report.tables_summed_as_tables(events) == by_table
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert ("dense-gradient step, table gradient from rows, exchanged over "
            "data, views looked up by rows over 3 logged steps: "
            "embed_distinct_rows mean") in out
    assert ("every row swept after it (the fullest replica's; every chip "
            "scattered all replicas' rows, embed_exchanged_rows mean %.0f a "
            "step, and %s)" % (mean, (
                "of the tables only fm_w crossed the interconnect, "
                "all-reduced" if by_table
                else "no table crossed the interconnect"))) in out


def test_log_sync_says_dma_where_the_kernel_writes_the_rows(monkeypatch):
    """``embed_row_writeback`` is the compiled step's choice: with the
    ``embed_put_rows`` kernel taken (forced on here through the interpreter;
    on a TPU it is at K=128) every ``train.log_sync`` says ``dma``, and the
    report's line with it."""
    import functools
    from deepfm_tpu.ops import pallas_put_rows as ppr
    monkeypatch.setattr(ppr, "supported", lambda t: t.ndim == 2)
    monkeypatch.setattr(ppr, "put_rows_many", functools.partial(
        ppr.put_rows_many, interpret=True))
    trace_lib.configure("full", export_env=False)
    tr = Trainer(_cfg(optimizer="Adagrad", l2_reg=0.0, embedding_size=128))
    tr.fit(tr.init_state(), _batches(K * 2))
    # deepfm: fm_v [V,128] by the kernel, the first-order fm_w [V] not
    assert tr.row_writeback == "dma+scatter"
    # and a row of whole lines is gathered a position, the [V] one by rows
    assert tr.embed_lookup == "fm_w:rows,fm_v:positions"
    events = trace_lib._tracer.events()
    syncs = [e["args"] for e in events if e["name"] == "train.log_sync"]
    assert [a["embed_row_writeback"] for a in syncs] == ["dma+scatter"] * 2
    assert [a["embed_lookup"] for a in syncs] == [tr.embed_lookup] * 2
    assert _report().row_updates(
        [dict(e, ph="X") for e in events])["writeback"] == "dma+scatter"


def test_report_reads_a_trace_that_predates_the_writeback_attribute():
    events = [{"name": "train.log_sync", "ph": "X", "args": {
        "step": 2, "embed_distinct_rows": 90, "embed_row_trips": 1}}]
    assert _report().row_updates(events)["writeback"] == "?"


@pytest.mark.parametrize("args,says", [
    ({"step": 2}, None),                    # predates the note: no line
    ({"step": 2, "embed_grad": "positions"},
     "dense-gradient step: table gradient from positions"),
    ({"step": 2, "embed_grad": "positions",
      "embed_grad_by_table": "fm_w,fm_v"},
     "of every position of the batch), summed over data as tables: "
     "fm_w,fm_v"),
], ids=["predates-the-note", "positions", "positions-on-replicas"])
def test_report_says_how_a_dense_step_made_its_table_gradient(
        args, says, tmp_path, capsys):
    """A trace from before ``embed_grad`` existed reads as it did; a step
    that leaves its tables to AD (a history model, hashed tables) gets the
    line that says so."""
    import json
    report = _report()
    events = [{"name": "train.log_sync", "ph": "X", "ts": 0, "dur": 5,
               "pid": 1, "tid": 1, "args": args}]
    assert report.table_gradient(events) == args.get("embed_grad")
    assert report.row_updates(events) is None
    path = str(tmp_path / "trace.json")
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "table gradient" in out if says else "table gradient" not in out
    if says:
        assert says in out


_COUNTS = {"step": 2, "embed_distinct_rows": 90, "embed_row_trips": 1}


@pytest.mark.parametrize("args,says,lookup", [
    ({**_COUNTS, "embed_grad": "rows", "embed_grad_by_table": ""},
     "dense-gradient step, table gradient from rows over 1 logged steps",
     None),                                 # predates the note: as it read
    ({**_COUNTS, "embed_grad": "rows", "embed_grad_by_table": "",
      "embed_lookup": "rows"},
     "table gradient from rows, views looked up by rows over 1 logged",
     "rows"),
    ({**_COUNTS, "embed_grad": "rows, exchanged over data",
      "embed_grad_by_table": "fm_w", "embed_exchanged_rows": 300,
      "embed_lookup": "fm_w:rows,fm_v:positions"},
     "table gradient from rows, exchanged over data, views looked up by "
     "fm_w:rows,fm_v:positions over 1 logged", "fm_w:rows,fm_v:positions"),
    ({**_COUNTS, "embed_row_writeback": "dma",
      "embed_lookup": "positions"},
     "row-local table update, views looked up by positions over 1 logged",
     "positions"),
], ids=["predates-the-note", "rows", "per-table-on-replicas",
        "row-local-positions"])
def test_report_says_how_a_step_read_its_tables_views(args, says, lookup,
                                                      tmp_path, capsys):
    """``embed_lookup`` rides on the span beside ``embed_grad`` (and beside
    the write-back in a row-local step); the rows' line names it, and a
    trace from before it existed reads as it did."""
    import json
    report = _report()
    events = [{"name": "train.log_sync", "ph": "X", "ts": 0, "dur": 5,
               "pid": 1, "tid": 1, "args": args}]
    assert report.table_lookup(events) == lookup
    path = str(tmp_path / "trace.json")
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert says in out
    assert ("views looked up by" in out) == (lookup is not None)


def _sdar_fit(n_steps=4):
    """A tiny ``--model sdar_moe`` fit of ``n_steps`` one-step dispatches,
    traced; the model and the events."""
    length, vocab, batch = 8, 50, 2
    cfg = Config(model="sdar_moe", feature_size=vocab, field_size=1,
                 embedding_size=16, history_max_len=length, decoder_layers=1,
                 attn_q_heads=2, attn_kv_heads=1, attn_head_dim=8,
                 moe_experts=4, moe_top_k=2, moe_expert_width=8,
                 moe_experts_held=2, moe_first_expert=0,
                 moe_pair_capacity=batch * 2 * length * 2, diffusion_block=4,
                 batch_size=batch, l2_reg=0.0, learning_rate=1e-3,
                 steps_per_loop=1, log_steps=2, compute_dtype="float32",
                 mesh_data=1, mesh_model=1)
    rng = np.random.default_rng(5)
    batches = [{"feat_ids": np.zeros((batch, 1), np.int32),
                "feat_vals": np.ones((batch, 1), np.float32),
                "label": np.zeros((batch, 1), np.float32),
                "hist_ids": rng.integers(0, vocab - 1, (batch, length)
                                         ).astype(np.int32),
                "hist_mask": np.ones((batch, length), np.float32)}
               for _ in range(n_steps)]
    trace_lib.configure("full", export_env=False)
    tr = Trainer(cfg)
    tr.fit(tr.init_state(), batches)
    return tr, trace_lib._tracer.events()


def test_log_sync_says_what_makes_the_attention_scores(tmp_path, capsys):
    """The block-diffusion decoder says what its compiled step's masked
    scores are made of on the span that reads the loss back: on this backend
    the chunked XLA path, and the report's line with it."""
    tr, events = _sdar_fit()
    assert tr.model.step_notes == {
        "attn_scores": "xla", "moe_rows": "xla", "moe_products": "xla",
        "attn_kept": "0/1",
        "head_grad": "forward 3 products/chunk, 0.00 GB kept",
        "moe_rows_moved": "{moe_pairs_held}/64"}
    syncs = [e["args"] for e in events if e["name"] == "train.log_sync"]
    assert [a["attn_scores"] for a in syncs] == ["xla"] * 2
    assert all("attn_score_blocks" not in a for a in syncs)
    # how the expert layer's rows moved, the step's held pairs filled in
    assert [a["moe_rows"] for a in syncs] == ["xla"] * 2
    assert [a["moe_products"] for a in syncs] == ["xla"] * 2
    assert [a["moe_rows_moved"] for a in syncs] == [
        "%d/64" % a["moe_pairs_held"] for a in syncs]
    assert all(0 < a["moe_pairs_held"] <= 64 for a in syncs)
    path = str(tmp_path / "trace.json")
    trace_lib.export(path)
    report = _report()
    loaded, _ = report._load(path)
    assert report.attention_scores(loaded) == {"steps": 2, "scores": "xla",
                                               "kept": "0/1"}
    assert report.row_updates(loaded) is None
    moved = report.expert_rows(loaded)
    assert moved == {"steps": 2, "rows": "xla", "buffer": 64,
                     "products": "xla",
                     "held": sum(a["moe_pairs_held"] for a in syncs) / 2}
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert ("block-masked attention over 2 logged steps: scores by xla, "
            "every score computed; the forward kernel's output and "
            "log-sum-exp kept for the backward pass in 0/1\n") in out
    assert ("expert layers' rows over 2 logged steps: moved by xla, %.0f of "
            "64 buffer rows a step held a pair" % moved["held"]) in out
    assert "multiplied by xla" in out


def test_report_prints_the_kernels_block_count(tmp_path, capsys):
    """What a TPU's trace carries: ``attn_scores`` = ``kernel`` and the
    forward grid's visited / total blocks a head."""
    events = [{"name": "train.log_sync", "ph": "X", "ts": 10.0 * i,
               "dur": 1.0, "pid": 1, "tid": 1,
               "args": {"step": i, "attn_scores": "kernel",
                        "attn_score_blocks": "80/256",
                        "attn_kept": "6/6 layers 0.10 GB"}}
              for i in (1, 2, 3)]
    report = _report()
    assert report.attention_scores(events) == {
        "steps": 3, "scores": "kernel", "visited": 80, "total": 256,
        "kept": "6/6 layers 0.10 GB"}
    # a ranker's trace, or one that predates the attribute, has no line
    assert report.attention_scores(
        [{"name": "train.log_sync", "ph": "X", "args": {"step": 2}}]) is None
    assert report.expert_rows(events) is None
    # what a TPU's trace says of the expert layers' rows
    for e, held in zip(events, (97_000, 101_000, 102_000)):
        e["args"].update(moe_rows="kernel",
                         moe_rows_moved="%d/196608" % held)
    assert report.expert_rows(events) == {
        "steps": 3, "rows": "kernel", "held": 100_000.0, "buffer": 196608}
    # and, since PR 52, of what multiplies them
    for e in events:
        e["args"]["moe_products"] = "kernel rows256 dw768/2048"
    assert report.expert_rows(events)["products"] \
        == "kernel rows256 dw768/2048"
    path = tmp_path / "trace.json"
    path.write_text(__import__("json").dumps({"traceEvents": events}))
    assert report.main([str(path)]) == 0
    assert ("block-masked attention over 3 logged steps: scores by kernel, "
            "80 of 256 blocks of the score matrix visited a head (31.2%); "
            "the forward kernel's output and log-sum-exp kept for the "
            "backward pass in 6/6 layers 0.10 GB\n"
            ) in capsys.readouterr().out
    assert report.main([str(path), "--json"]) == 0
    assert '"attention_scores"' in capsys.readouterr().out


def test_a_model_note_on_the_kernel_names_the_forward_grids_blocks():
    """With the kernel taken (a TPU backend; nothing is compiled here) the
    model's note carries the block count of the kernel's own table."""
    import jax
    from deepfm_tpu.models import get_model
    cfg = Config(model="sdar_moe", feature_size=50, field_size=1,
                 embedding_size=16, history_max_len=512, decoder_layers=1,
                 attn_q_heads=2, attn_kv_heads=1, attn_head_dim=128,
                 moe_experts=4, moe_top_k=2, moe_expert_width=8,
                 moe_experts_held=2, moe_first_expert=0,
                 moe_pair_capacity=64, diffusion_block=4, batch_size=1,
                 l2_reg=0.0, steps_per_loop=1, compute_dtype="float32")
    model = get_model(cfg)
    notes = model._attn_notes("kernel", 1024, 512)
    # S = 1,024 in blocks of 512: [noisy ; clean] x [noisy ; clean], every
    # quarter holds something but clean queries on noisy keys
    assert notes == {"attn_scores": "kernel", "attn_score_blocks": "3/4"}
    assert jax.default_backend() == "cpu"


def test_whole_instructions_puts_a_kernels_note_back_on_its_line():
    """A Pallas call with a profiler note prints over three lines, its
    ``op_name`` on the last; the step's text joins them, so the kernel keeps
    its scope, and text without such a note is unchanged."""
    text = "\n".join([
        "%body (p: f32[2]) -> f32[2] {",
        '  %splash_mqa_fwd.2 = (f32[2,512,128]{2,1,0}, bf16[2,4,8192,128]'
        '{3,2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_'
        'call", frontend_attributes={kernel_metadata={',
        '"xprof_metadata":"{\\"block_q\\": 512}"',
        '}}, metadata={op_name="jit(step)/while/body/checkpoint/attn/vmap('
        'jit(_splash_attention))/pallas_call"}, backend_config={"x":[]}',
        '  %fusion.7 = f32[2,4,1024]{2,1,0} fusion(%q), kind=kLoop, '
        'metadata={op_name="jit(step)/transpose(jvp(attn))/reduce_max"}',
        "}"])
    assert profiling.hlo_op_scopes(text)["splash_mqa_fwd.2"] == ""
    whole = profiling.whole_instructions(text)
    assert len(whole.splitlines()) == 4
    assert profiling.hlo_op_scopes(whole) == {"splash_mqa_fwd.2": "attn",
                                              "fusion.7": "attn"}
    assert profiling.whole_instructions(whole) == whole
    plain = "\n".join(text.splitlines()[:1] + text.splitlines()[4:])
    assert profiling.whole_instructions(plain) == plain


def test_log_sync_reads_no_counter_when_tracing_is_off():
    """The counters cost two scalar reads a log line: not paid untraced."""
    tr = Trainer(_cfg(optimizer="Adagrad", l2_reg=0.0))
    _, out = tr.fit(tr.init_state(), _batches(K * 2))
    assert out["steps"] == K * 2 and not trace_lib._tracer.events()


def test_log_sync_says_how_the_delta_rule_scan_is_computed(tmp_path, capsys):
    """The hybrid linear-attention decoder says on the span that reads the
    loss back what its compiled step's scan and latent attention are made
    of, with the step's most negative chunk log-decay (a float among the
    counts), and the report prints its line."""
    length, vocab, batch = 12, 50, 2
    cfg = Config(model="kimi_linear", feature_size=vocab, field_size=1,
                 embedding_size=16, history_max_len=length, decoder_layers=2,
                 attn_every=2, dense_layers=1, kda_heads=2, kda_head_dim=8,
                 attn_q_heads=2, attn_kv_heads=2, attn_head_dim=8,
                 mla_latent_dim=8, mla_rope_dim=4, dense_mlp_width=16,
                 moe_experts=4, moe_top_k=2, moe_expert_width=8,
                 moe_shared_width=8, moe_experts_held=2, moe_first_expert=0,
                 moe_pair_capacity=batch * length * 2, moe_route_scale=2.446,
                 batch_size=batch, l2_reg=0.0, learning_rate=1e-3,
                 steps_per_loop=1, log_steps=2, compute_dtype="float32",
                 mesh_data=1, mesh_model=1)
    rng = np.random.default_rng(5)
    batches = [{"feat_ids": np.zeros((batch, 1), np.int32),
                "feat_vals": np.ones((batch, 1), np.float32),
                "label": np.zeros((batch, 1), np.float32),
                "hist_ids": rng.integers(0, vocab, (batch, length)
                                         ).astype(np.int32),
                "hist_mask": np.ones((batch, length), np.float32)}
               for _ in range(4)]
    trace_lib.configure("full", export_env=False)
    tr = Trainer(cfg)
    tr.fit(tr.init_state(), batches)
    syncs = [e["args"] for e in trace_lib._tracer.events()
             if e["name"] == "train.log_sync"]
    assert [(a["kda_scan"], a["mla_scores"]) for a in syncs] == [
        ("chunk64/sub16", "xla")] * 2
    # the dense MLP's and the shared expert's first products: off a TPU
    # no layer keeps them
    assert [a["mlp_kept"] for a in syncs] == ["0/2"] * 2
    lows = [a["kda_chunk_log_decay_min"] for a in syncs]
    assert all(isinstance(x, float) and x < 0 for x in lows)
    assert all(isinstance(a["moe_pairs_held"], int) for a in syncs)
    path = str(tmp_path / "trace.json")
    trace_lib.export(path)
    report = _report()
    loaded, _ = report._load(path)
    assert report.delta_rule_scan(loaded) == {
        "steps": 2, "scan": "chunk64/sub16", "mla_scores": "xla",
        "log_decay_min": pytest.approx(min(lows))}
    assert report.attention_scores(loaded) is None
    assert report.kept_products(loaded) == {"steps": 2, "kept": "0/2"}
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert ("delta-rule scan over 2 logged steps: chunk64/sub16, latent "
            "attention's scores by xla, most negative chunk log-decay "
            ) in out
    assert ("dense SwiGLUs over 2 logged steps: first products kept for the "
            "backward pass in 0/2") in out
    # a trace that predates the count, and one of another model
    old = [{"name": "train.log_sync", "ph": "X",
            "args": {"step": 2, "kda_scan": "chunk64/sub16"}}]
    assert report.delta_rule_scan(old) == {
        "steps": 1, "scan": "chunk64/sub16", "mla_scores": "?",
        "log_decay_min": None}
    assert report.delta_rule_scan(
        [{"name": "train.log_sync", "ph": "X", "args": {"step": 2}}]) is None
    assert report.kept_products(old) is None


@pytest.mark.parametrize("model, seq, backend, note", [
    ("kimi_linear", 128, "tpu", "kernel chunk64"),
    ("solar_open2", 128, "tpu", "kernel chunk64"),
    ("kimi_linear", 100, "tpu", "chunk64/sub16"),
    ("solar_open2", 128, "cpu", "chunk64/sub16")])
def test_the_scans_note_follows_the_backend_and_the_length(
        monkeypatch, model, seq, backend, note):
    """``kda_scan`` among ``step_notes`` is ``kimi_linear.kda_scan_by``'s
    word: the kernels on a TPU at whole chunks and heads of 128-lane lines,
    one device; the XLA form off a TPU, at a ragged length and across data
    replicas."""
    import jax
    import jax.numpy as jnp

    from deepfm_tpu.models import get_model
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    own = {"kimi_linear": dict(dense_layers=1, mla_latent_dim=8,
                               mla_rope_dim=4, dense_mlp_width=16),
           "solar_open2": {}}[model]
    cfg = Config(model=model, feature_size=50, field_size=1,
                 embedding_size=256, history_max_len=seq, decoder_layers=2,
                 attn_every=2, kda_heads=2, kda_head_dim=128,
                 attn_q_heads=2, attn_kv_heads=2, attn_head_dim=8,
                 moe_experts=4, moe_top_k=2, moe_expert_width=8,
                 moe_shared_width=8, moe_experts_held=2, moe_first_expert=0,
                 moe_pair_capacity=2 * seq * 2, batch_size=2,
                 compute_dtype="float32", mesh_data=1, mesh_model=1, **own)
    built = get_model(cfg)
    ids = jnp.zeros((2, seq), jnp.int32)
    assert built._paths(ids, one_device=True)["scan_by"] == (
        "kernel" if note.startswith("kernel") else "xla")
    assert built.step_notes["kda_scan"] == note
    assert built._paths(ids, one_device=False)["scan_by"] == "xla"
    assert built.step_notes["kda_scan"] == "chunk64/sub16"


def test_log_sync_says_the_causal_score_path_and_the_write_strength(
        tmp_path, capsys):
    """``--model solar_open2`` says on the span that reads the loss back
    what makes its full layer's causal scores (``attn_scores``: the site
    ``sdar_moe`` and this model share, ``sdar_moe.attn_notes``) and how
    often the delta rule's write strength passed 1
    (``kda_beta_over_one``, a whole number among the counts), and the report
    prints both on their lines."""
    length, vocab, batch = 12, 50, 2
    cfg = Config(model="solar_open2", feature_size=vocab, field_size=1,
                 embedding_size=16, history_max_len=length, decoder_layers=2,
                 attn_every=2, kda_heads=2, kda_head_dim=8, attn_q_heads=4,
                 attn_kv_heads=2, attn_head_dim=8, moe_experts=4,
                 moe_top_k=2, moe_expert_width=8, moe_shared_width=8,
                 moe_experts_held=2, moe_first_expert=0,
                 moe_pair_capacity=batch * length * 2, batch_size=batch,
                 l2_reg=0.0, learning_rate=1e-3, steps_per_loop=1,
                 log_steps=2, compute_dtype="float32", mesh_data=1,
                 mesh_model=1)
    rng = np.random.default_rng(5)
    batches = [{"feat_ids": np.zeros((batch, 1), np.int32),
                "feat_vals": np.ones((batch, 1), np.float32),
                "label": np.zeros((batch, 1), np.float32),
                "hist_ids": rng.integers(0, vocab, (batch, length)
                                         ).astype(np.int32),
                "hist_mask": np.ones((batch, length), np.float32)}
               for _ in range(4)]
    trace_lib.configure("full", export_env=False)
    tr = Trainer(cfg)
    tr.fit(tr.init_state(), batches)
    syncs = [e["args"] for e in trace_lib._tracer.events()
             if e["name"] == "train.log_sync"]
    assert [(a["kda_scan"], a["attn_scores"]) for a in syncs] == [
        ("chunk64/sub16", "xla")] * 2
    assert [a["mlp_kept"] for a in syncs] == ["0/2"] * 2   # shared experts
    assert all("mla_scores" not in a and "attn_score_blocks" not in a
               for a in syncs)
    over = [a["kda_beta_over_one"] for a in syncs]
    # one KDA layer of 2 heads over 2 x 12 positions: some pass 1, not all
    assert all(isinstance(x, int) and 0 < x < batch * length * 2
               for x in over)
    assert all(a["kda_chunk_log_decay_min"] < 0 for a in syncs)
    path = str(tmp_path / "trace.json")
    trace_lib.export(path)
    report = _report()
    loaded, _ = report._load(path)
    assert report.delta_rule_scan(loaded) == {
        "steps": 2, "scan": "chunk64/sub16", "mla_scores": "?",
        "log_decay_min": pytest.approx(min(
            a["kda_chunk_log_decay_min"] for a in syncs)),
        "beta_over_one": pytest.approx(sum(over) / 2)}
    assert report.attention_scores(loaded) == {"steps": 2, "scores": "xla",
                                               "kept": "0/1"}
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert ("delta-rule scan over 2 logged steps: chunk64/sub16, most "
            "negative chunk log-decay ") in out
    assert ("write strength over 1 at %.0f positions x heads a step"
            % (sum(over) / 2)) in out
    assert ("block-masked attention over 2 logged steps: scores by xla, "
            "every score computed; the forward kernel's output and "
            "log-sum-exp kept for the backward pass in 0/1\n") in out
    # on the kernel the note names the causal half's blocks
    notes = tr.model.step_notes
    assert notes["attn_scores"] == "xla" and "kda_scan" in notes


def test_log_sync_says_the_convolution_the_scores_and_the_bias(tmp_path,
                                                               capsys):
    """``--model lfm2_moe`` says on the span that reads the loss back what
    computes its short convolution's passes (``conv_taps_by``), what makes
    its full layer's causal scores (``attn_scores``, the site it shares with
    ``sdar_moe`` and ``solar_open2``) and how many selections its routers'
    bias changed (``moe_bias_moved_picks``, a whole number among the
    counts; never the bias itself), and the report prints each on its
    line."""
    from deepfm_tpu.models import lfm2_moe
    length, vocab, batch = 12, 50, 2
    cfg = Config(model="lfm2_moe", feature_size=vocab, field_size=1,
                 embedding_size=16, history_max_len=length, decoder_layers=3,
                 layer_types="conv,full_attention,conv", dense_layers=1,
                 dense_mlp_width=16, attn_q_heads=4, attn_kv_heads=2,
                 attn_head_dim=8, moe_experts=4, moe_top_k=2,
                 moe_expert_width=8, moe_experts_held=2, moe_first_expert=0,
                 moe_pair_capacity=batch * length * 2, batch_size=batch,
                 l2_reg=0.0, learning_rate=1e-3, steps_per_loop=1,
                 log_steps=2, compute_dtype="float32", mesh_data=1,
                 mesh_model=1)
    rng = np.random.default_rng(5)
    batches = [{"feat_ids": np.zeros((batch, 1), np.int32),
                "feat_vals": np.ones((batch, 1), np.float32),
                "label": np.zeros((batch, 1), np.float32),
                "hist_ids": rng.integers(0, vocab, (batch, length)
                                         ).astype(np.int32),
                "hist_mask": np.ones((batch, length), np.float32)}
               for _ in range(4)]
    trace_lib.configure("full", export_env=False)
    tr = Trainer(cfg)
    state = tr.init_state()
    state = state.replace(model_state={
        **state.model_state, lfm2_moe.SELECT_BIAS: 0.2 * np.asarray(
            rng.standard_normal((2, 4)), np.float32)})
    tr.fit(state, batches)
    syncs = [e["args"] for e in trace_lib._tracer.events()
             if e["name"] == "train.log_sync"]
    assert [(a["conv_taps_by"], a["attn_scores"]) for a in syncs] == [
        ("xla", "xla")] * 2
    # one dense layer, no shared expert beside the two expert layers
    assert [a["mlp_kept"] for a in syncs] == ["0/1"] * 2
    assert all(k not in a for a in syncs for k in (
        "kda_scan", "mla_scores", lfm2_moe.SELECT_BIAS))
    picks = [a["moe_bias_moved_picks"] for a in syncs]
    assert all(isinstance(x, int) and 0 < x <= 2 * batch * length
               for x in picks)
    path = str(tmp_path / "trace.json")
    trace_lib.export(path)
    report = _report()
    loaded, _ = report._load(path)
    assert report.short_convolution(loaded) == {"steps": 2, "taps_by": "xla"}
    assert report.delta_rule_scan(loaded) is None
    assert report.attention_scores(loaded) == {"steps": 2, "scores": "xla",
                                               "kept": "0/1"}
    assert report.expert_rows(loaded)["bias_moved_picks"] == sum(picks) / 2
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert ("gated short convolution over 2 logged steps: taps and gates by "
            "xla") in out
    assert ("the selection bias moved %.0f picks a step"
            % (sum(picks) / 2)) in out
    # what a TPU's trace says of heads of 64 on the kernel
    events = [{"name": "train.log_sync", "ph": "X", "ts": 1.0, "dur": 1.0,
               "pid": 1, "tid": 1,
               "args": {"step": 1, "attn_scores": "kernel",
                        "attn_score_blocks": "136/256"}}]
    assert report.attention_scores(events) == {
        "steps": 1, "scores": "kernel", "visited": 136, "total": 256}
    assert report.short_convolution(events) is None
    trace = tmp_path / "tpu.json"
    trace.write_text(__import__("json").dumps({"traceEvents": events}))
    assert report.main([str(trace)]) == 0
    assert ("136 of 256 blocks of the score matrix visited a head "
            "(53.1%)") in capsys.readouterr().out


def test_log_sync_says_the_selective_scan_and_both_masks_blocks(tmp_path,
                                                                capsys):
    """``--model phi4_flash`` says on the span that reads the loss back the
    form of its selective scan (``mamba_scan``), the step's most negative
    whole-chunk log-decay (``mamba_chunk_log_decay_min``, a float among the
    counts) and what makes its masked scores (``attn_scores``, the site it
    shares with the other decoders; on a TPU the visited blocks of both
    masks), no ``moe_*`` count, and the report prints its line."""
    length, vocab, batch = 12, 50, 2
    cfg = Config(model="phi4_flash", feature_size=vocab, field_size=1,
                 embedding_size=16, history_max_len=length, decoder_layers=6,
                 layer_types="mamba,window_attention,mamba,full_attention,"
                             "gmu,cross_attention", first_layer=14,
                 attn_window=4, mamba_state=4, mamba_dt_rank=2,
                 dense_mlp_width=16, attn_q_heads=4, attn_kv_heads=2,
                 attn_head_dim=8, rms_norm_eps=1e-5, batch_size=batch,
                 l2_reg=0.0, learning_rate=1e-3, steps_per_loop=1,
                 log_steps=2, compute_dtype="float32", mesh_data=1,
                 mesh_model=1)
    rng = np.random.default_rng(5)
    batches = [{"feat_ids": np.zeros((batch, 1), np.int32),
                "feat_vals": np.ones((batch, 1), np.float32),
                "label": np.zeros((batch, 1), np.float32),
                "hist_ids": rng.integers(0, vocab, (batch, length)
                                         ).astype(np.int32),
                "hist_mask": np.ones((batch, length), np.float32)}
               for _ in range(4)]
    trace_lib.configure("full", export_env=False)
    tr = Trainer(cfg)
    tr.fit(tr.init_state(), batches)
    syncs = [e["args"] for e in trace_lib._tracer.events()
             if e["name"] == "train.log_sync"]
    assert [(a["mamba_scan"], a["attn_scores"]) for a in syncs] == [
        ("lockstep chunk32/segment512", "xla")] * 2
    assert [a["mlp_kept"] for a in syncs] == ["0/6"] * 2
    lows = [a["mamba_chunk_log_decay_min"] for a in syncs]
    assert all(isinstance(x, float) and x < 0 for x in lows)
    assert not any(k.startswith(("moe_", "kda_", "conv_"))
                   for a in syncs for k in a)
    path = str(tmp_path / "trace.json")
    trace_lib.export(path)
    report = _report()
    loaded, _ = report._load(path)
    assert report.selective_scan(loaded) == {
        "steps": 2, "scan": "lockstep chunk32/segment512",
        "log_decay_min": min(lows)}
    assert report.delta_rule_scan(loaded) is None
    assert report.expert_rows(loaded) is None
    assert report.main([path]) == 0
    assert ("selective scan over 2 logged steps: lockstep "
            "chunk32/segment512, most negative chunk log-decay") \
        in capsys.readouterr().out
    # what a TPU's trace says of both masks on the kernel
    events = [{"name": "train.log_sync", "ph": "X", "ts": 1.0, "dur": 1.0,
               "pid": 1, "tid": 1,
               "args": {"step": 1, "attn_scores": "kernel",
                        "attn_score_blocks": "136/256",
                        "attn_window_blocks": "31/256",
                        "mamba_scan": "kernel steps64",
                        "mamba_chunk_log_decay_min": -38.5,
                        "mlp_kept": "6/6 layers 4.03 GB"}}]
    assert report.selective_scan(events) == {
        "steps": 1, "scan": "kernel steps64",
        "log_decay_min": -38.5, "window_blocks": "31/256"}
    assert report.kept_products(events) == {
        "steps": 1, "kept": "6/6 layers 4.03 GB"}
    trace = tmp_path / "tpu.json"
    trace.write_text(__import__("json").dumps({"traceEvents": events}))
    assert report.main([str(trace)]) == 0
    out = capsys.readouterr().out
    assert "31/256 blocks of the score matrix visited a head under the " \
        "window" in out
    assert ("dense SwiGLUs over 1 logged steps: first products kept for the "
            "backward pass in 6/6 layers 4.03 GB") in out


def test_log_sync_says_both_losses_and_the_latent_attentions_path(tmp_path,
                                                                  capsys):
    """``--model glm4_moe_lite`` says on the span that reads the loss back
    both parts of its loss (``xent``, the main model's, and ``mtp_xent``,
    its multi-token-prediction module's: floats among the counts), what
    makes its latent attention's masked scores (``attn_scores``, the site it
    shares with the other decoders; on a TPU the visited blocks at heads of
    256), the picks its selection bias moved and how its expert blocks' rows
    moved (the module's block among them); the report prints its line."""
    length, vocab, batch = 12, 50, 2
    cfg = Config(model="glm4_moe_lite", feature_size=vocab, field_size=1,
                 embedding_size=16, history_max_len=length, decoder_layers=2,
                 dense_layers=1, dense_mlp_width=16, attn_q_heads=2,
                 attn_kv_heads=2, mla_q_rank=6, mla_latent_dim=8,
                 mla_nope_dim=4, mla_rope_dim=4, mla_value_dim=8,
                 rms_norm_eps=1e-5, moe_experts=8, moe_top_k=2,
                 moe_expert_width=8, moe_shared_width=8, moe_route_scale=1.8,
                 moe_experts_held=4, moe_first_expert=0,
                 moe_pair_capacity=2 * batch * length, mtp_depth=1,
                 batch_size=batch, l2_reg=0.0, learning_rate=1e-3,
                 steps_per_loop=1, log_steps=2, compute_dtype="float32",
                 mesh_data=1, mesh_model=1)
    rng = np.random.default_rng(5)
    batches = [{"feat_ids": np.zeros((batch, 1), np.int32),
                "feat_vals": np.ones((batch, 1), np.float32),
                "label": np.zeros((batch, 1), np.float32),
                "hist_ids": rng.integers(0, vocab, (batch, length)
                                         ).astype(np.int32),
                "hist_mask": np.ones((batch, length), np.float32)}
               for _ in range(4)]
    trace_lib.configure("full", export_env=False)
    tr = Trainer(cfg)
    tr.fit(tr.init_state(), batches)
    syncs = [e["args"] for e in trace_lib._tracer.events()
             if e["name"] == "train.log_sync"]
    assert len(syncs) == 2
    for a in syncs:
        assert isinstance(a["xent"], float) and isinstance(a["mtp_xent"],
                                                           float)
        assert 0 < a["xent"] != a["mtp_xent"] > 0
        assert (a["attn_scores"], a["moe_rows"]) == ("xla", "xla")
        # the expert layer and the module's block, one pass each
        assert a["moe_rows_moved"].endswith("/%d" % (2 * 2 * batch * length))
        assert a["mlp_kept"] == "0/3" and a["moe_bias_moved_picks"] == 0
        assert "moe_select_bias" not in a
    path = str(tmp_path / "trace.json")
    trace_lib.export(path)
    report = _report()
    loaded, _ = report._load(path)
    assert report.multi_token_prediction(loaded) == {
        "steps": 2, "xent": syncs[-1]["xent"],
        "mtp_xent": syncs[-1]["mtp_xent"]}
    assert report.selective_scan(loaded) is None
    assert report.main([path]) == 0
    assert "multi-token prediction over 2 logged steps: the main loss" \
        in capsys.readouterr().out
    # another model's trace has no such line
    assert report.multi_token_prediction(
        [{"name": "train.log_sync", "ph": "X", "args": {"step": 1}}]) is None
