"""``train_moe_roofline`` (``benchmark/readers/roofline_moe.py``), the one
expert-layer share of the five cells with experts, and ``train_step_mfu``
(``readers/train_step_mfu.py``), the whole step's share in all nine: each
against its arithmetic on a rehearsed context, a case a cell, and the
property the three name-matched shares lacked: the reading is the same
whatever the layer's products are called. Nothing here measures a speed.
"""

import importlib
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, xplane  # noqa: E402
from benchmark.readers import roofline_moe, train_step_mfu  # noqa: E402
from benchmark.readers import scope_device_ms as sdm  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
ENTRY = next(m for m in BENCH["per_layer"] if m["name"] == "train_moe_roofline")
EXPERT_CELLS = ENTRY["workloads"]
PEAKS = harness.peaks_for("TPU v5 lite")
STEPS, PAIRS = 10, 81920.0

#: The expert layer of a compiled step as the rehearsal has it: the products
#: under three names a program could give them (XLA's own for
#: ``jax.lax.ragged_dot``, a Pallas kernel's, a plain fusion), the same
#: results and the same own time each; beside them the router's fusion in
#: ``moe`` and one op of another scope.
PRODUCT_NAMES = ("ragged-dot-none.3", "moe_grouped_dot.3", "fusion.12")
OWN_S = {"product": 0.9, "router": 0.3, "attn": 5.0}


def step_text(product: str) -> str:
    return "\n".join([
        "ENTRY %main.9 () -> f32[] {",
        f"  %{product} = bf16[20480,768]{{1,0:T(8,128)(2,1)}} "
        "custom-call(%a, %b), custom_call_target=\"x\"",
        "  %fusion.7 = f32[16384,128]{1,0:T(8,128)} fusion(%h), kind=kLoop",
        "  %fusion.9 = (bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)}, f32[]) "
        "fusion(%q), kind=kOutput",
        "}"])


def rehearse(monkeypatch, cell, product=PRODUCT_NAMES[0], scope="moe",
             trace=True, pairs=PAIRS):
    """A context of ``cell`` whose newest trace holds ``step_text(product)``'s
    three ops, the first two under ``scope``."""
    flags = harness.load_cell(cell).config["flags"]
    text = step_text(product)
    op_scopes = sdm.keyed_scopes(text, {product: scope, "fusion.7": scope,
                                        "fusion.9": "attn"})
    assert len(op_scopes) == 3
    ops = {sdm.op_key(line): OWN_S[what] for line, what in zip(
        text.splitlines()[1:4], ("product", "router", "attn"))}
    monkeypatch.setattr(sdm, "_reduced", {})
    monkeypatch.setattr(sdm, "newest_trace", lambda name: "a.xplane.pb")
    monkeypatch.setattr(sdm, "own_seconds", lambda path, w: (dict(ops), 0.0))
    monkeypatch.setattr(sdm, "program_op_scopes", lambda ctx: op_scopes)
    counters = {"steps_in_window": STEPS}
    if pairs:
        counters["moe_pairs_held_per_step"] = pairs
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell, config={"flags": flags}),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        counters=counters, window=(0, 12 * 10 ** 9),
        trace={"devices": 1, "busy_s": 11.0, "window_s": 12.0}
        if trace else None)


# ----------------------------------------------------- train_moe_roofline

def test_the_entry_is_the_expert_cells_one_share():
    assert ENTRY == {
        "name": "train_moe_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "expert layer",
        "moves": "train_examples_per_s_per_chip", "workloads": EXPERT_CELLS}
    # the cells that time scope `moe`, and no other
    assert EXPERT_CELLS == next(m for m in BENCH["per_layer"] if m["name"]
                                == "train_moe_device_ms")["workloads"]
    assert len(EXPERT_CELLS) == 5
    assert not [m["name"] for m in BENCH["per_layer"]
                if "moe_matmul" in m["name"]]
    assert harness.load_json("metrics", "train_moe_roofline.json") == {
        "reader": "roofline_moe"}
    # no reader finds device work by an op's name
    readers = os.path.join(harness.BENCH_DIR, "readers")
    for name in os.listdir(readers):
        if name.endswith(".py"):
            with open(os.path.join(readers, name)) as f:
                assert "ragged-dot" not in f.read(), name


@pytest.mark.parametrize("cell", EXPERT_CELLS)
def test_the_share_is_the_pairs_products_over_the_scopes_time(monkeypatch,
                                                              cell):
    ctx = rehearse(monkeypatch, cell)
    flags = ctx.cell.config["flags"]
    counts = importlib.import_module(f"benchmark.roofline_{flags['model']}")
    moe_s = (OWN_S["product"] + OWN_S["router"]) / STEPS
    want = 100 * 3 * counts.forward_flops(flags, PAIRS)["experts"] \
        / PEAKS["bf16_flops_per_s"] / moe_s
    got = roofline_moe.read(ctx)
    assert got == pytest.approx(want, rel=1e-12) and 0 < got < 100
    # three products a pair, forward and twice that backward, at the
    # model's own width and the experts' own: nothing else of the flags
    assert want == pytest.approx(
        100 * 3 * 2.0 * PAIRS * 3 * int(flags["embedding_size"])
        * int(flags["moe_expert_width"]) / 197e12 / moe_s)
    # and the scope metric beside it reads the time it divided by
    assert sdm.read(ctx, ["moe"]) == pytest.approx(1e3 * moe_s)


@pytest.mark.parametrize("cell", EXPERT_CELLS)
def test_the_share_does_not_read_the_products_name(monkeypatch, cell):
    got = [roofline_moe.read(rehearse(monkeypatch, cell, product=name))
           for name in PRODUCT_NAMES]
    assert got[0] is not None and got[0] == got[1] == got[2]


@pytest.mark.parametrize("cell", EXPERT_CELLS)
def test_nothing_to_read_is_none(monkeypatch, cell):
    assert roofline_moe.read(rehearse(monkeypatch, cell, pairs=0)) is None
    assert roofline_moe.read(rehearse(monkeypatch, cell, trace=False)) is None
    # a map that knows the ops and has no `moe` in it (a program from
    # before the scope)
    assert roofline_moe.read(rehearse(monkeypatch, cell, scope="mlp")) is None
    # a map of another program
    ctx = rehearse(monkeypatch, cell)
    monkeypatch.setattr(sdm, "program_op_scopes", lambda ctx: {})
    assert roofline_moe.read(ctx) is None
    # no trace file in the work directory
    ctx = rehearse(monkeypatch, cell)
    monkeypatch.setattr(sdm, "newest_trace", lambda name: None)
    assert roofline_moe.read(ctx) is None


def test_the_share_rides_the_scope_metrics_one_reduction(monkeypatch):
    """No compilation and no pass over the trace of its own: after the
    cell's scope metrics have read a trace, the share costs neither."""
    ctx = rehearse(monkeypatch, EXPERT_CELLS[0])
    calls = {"map": 0, "trace": 0}
    own, scopes = sdm.own_seconds, sdm.program_op_scopes

    def counted(kind, fn):
        def call(*args):
            calls[kind] += 1
            return fn(*args)
        return call
    monkeypatch.setattr(sdm, "own_seconds", counted("trace", own))
    monkeypatch.setattr(sdm, "program_op_scopes", counted("map", scopes))
    assert sdm.read(ctx, ["moe"]) and roofline_moe.read(ctx)
    assert sdm.read(ctx, ["attn"]) and roofline_moe.read(ctx)
    assert calls == {"map": 1, "trace": 1}


# --------------------------------------------------------- train_step_mfu

def test_the_whole_steps_share_is_listed_in_every_cell():
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "train_step_mfu")
    assert entry == {
        "name": "train_step_mfu", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "train step",
        "moves": "train_examples_per_s_per_chip", "workloads": CELLS}
    assert harness.load_json("metrics", "train_step_mfu.json") == {
        "reader": "train_step_mfu"}
    # beside every share of a roofline there is the whole step's
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"]:
            assert set(m["workloads"]) <= set(entry["workloads"]), m["name"]
            assert m["moves"] == entry["moves"]


@pytest.mark.parametrize("cell", CELLS)
def test_mfu_is_the_steps_flops_over_all_of_the_window(monkeypatch, cell):
    ctx = rehearse(monkeypatch, cell)
    loaded = harness.load_cell(cell)
    ctx.devices = ctx.devices * loaded.chips
    flags = ctx.cell.config["flags"]
    model = flags["model"]
    counts = importlib.import_module(
        "benchmark." + ("roofline" if model == "deepfm"
                        else f"roofline_{model}"))
    if cell in EXPERT_CELLS:
        least = counts.train_step_least_seconds(flags, PAIRS, PEAKS)
    elif model == "phi4_flash":
        least = counts.train_step_least_seconds(flags, PEAKS)
    else:
        least = counts.train_step_least_seconds(flags, loaded.chips, PEAKS)
    want = 100 * least["flops"] * STEPS / (197e12 * 12.0)
    got = train_step_mfu.read(ctx)
    assert got == pytest.approx(want, rel=1e-12) and 0 < got < 100
    # over all of the window: never above the busy time's share of a
    # FLOP-bound step, which `train_step_roofline.*` reads
    assert got <= 100 * least["seconds"] / (11.0 / STEPS)
    # nothing to read: no trace, no steps, and, where the count wants the
    # routed pairs, a driver that counted none
    assert train_step_mfu.read(rehearse(monkeypatch, cell, trace=False)) \
        is None
    ctx.counters["steps_in_window"] = 0
    assert train_step_mfu.read(ctx) is None
    bare = rehearse(monkeypatch, cell, pairs=0)
    bare.devices = bare.devices * loaded.chips
    assert (train_step_mfu.read(bare) is None) == (cell in EXPERT_CELLS)


# --------------------------------------------------------- idle-gap names

@pytest.mark.parametrize("name", ["stage.input_wait", "host.gc",
                                  "train.log_sync"])
def test_an_idle_gap_is_named_for_the_programs_span_over_it(name):
    assert name in xplane.GAP_SPANS
    spans = [{"name": "stage.wait", "ts": 0.0, "dur": 1.0},        # us
             {"name": name, "ts": 2.5, "dur": 8.0},
             {"name": "input.pool_fill", "ts": 0.0, "dur": 100.0}]
    # the gap is the span's alone, and most of a gap that `stage.wait`
    # only touches; a span no gap is named for names none
    assert xplane.name_gap((3000.0, 9000.0), spans) == name
    assert xplane.name_gap((500.0, 9000.0), spans) == name
    assert xplane.name_gap((20000.0, 30000.0), spans) == "host.other"
