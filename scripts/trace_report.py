#!/usr/bin/env python
"""Summarize a Chrome trace JSON produced by ``deepfm_tpu.obs.trace``.

Input: one ``trace-<pid>.json`` (per-process export) or a ``merge()``d
file. Complete ("X") spans are aggregated per name with wall total, SELF
time (total minus time spent in nested spans on the same thread —
containment reconstructed per (pid, tid) from ts/dur), and nearest-rank
p50/p99 of span duration. Async ("b"/"e") spans — cross-thread waits —
pair by id and aggregate the same way (self == total: they have no
nesting). Ring-buffer drops recorded at export time are surfaced, never
hidden: a wrapped ring means the totals undercount.

``--stalls MS`` answers "what was the host doing when the trainer fell
behind". Its clock is the END of each ``stage.wait`` span: there the staging
thread sees a dispatch complete on the device (it fences on dispatch j-2
before transfer j), so the ends are one dispatch apart while the device sets
the pace, and further apart when it was starved. (``train.dispatch`` starts
will not do: the fit thread reads the loss back every ``log_steps`` steps
and enqueues in bunches.) Every interval longer than MS is printed with the
time each of the spans that can hold a dispatch back covered of it
(``STALL_SPANS``: ``host.stall`` — the tracer's pulse thread woke that late:
the whole process ran no Python, whatever the other spans say their threads
were doing; ``stage.input_wait`` — the staging thread waiting for the
input thread; ``input.pool_fill`` / ``input.pool_drain`` / ``input.emit`` —
the input thread reading+framing, permuting+decoding, slicing; ``host.gc`` —
a collection, which stops every thread; ``train.log_sync`` — the loss read
back at the log cadence; ``stage.transfer`` — the host->device copy; and
``stage.wait`` itself, the part the device was simply busy; and
``compile.backend`` — a program compiled or fetched in the middle of the run,
named by its ``fun_name`` under the stall). Under each interval a line a
``host.stall`` that overlaps it: its ``cause`` (``host_cpu``, ``gil``,
``memory``, ``io``, ``frozen``: ``obs.trace.stall_cause``), ``late_ms`` and
what the operating system counted meanwhile (``runq_ms``, ``cpu_ms``,
``steal_ms``, ``busiest_thread``). Where the trace holds the pulse's spans
the report has a "host pulse" line: the stalls by cause (count and total
ms) and the median ``late_ms_max`` of the quiet seconds (``host.pulse``
spans in which no beat stalled): this host's baseline. A trace that
predates the pulse reports as before. TUNING §17 lists every span.

Where the trace holds the process's start-up record (``obs.startup``: the
``setup.*`` phases and JAX's ``compile.*`` timings up to the first dispatch,
kept whether or not tracing was on when they ran), the report has a
"start-up" section: the launcher's start-up line, each phase with its
inclusive and self time (imports nest by containment), and every compiled
function with its tracing, lowering and backend seconds and whether the
persistent cache had it. Cold against warm is ``cache=miss`` against ``hit``
and the ``backend`` column; a kernel's cost in every warm start is its
function's ``trace`` + ``lower``, which no cache saves.

Where the train step updates its tables on the rows the batch touched
(Adagrad without L2: ``Trainer._row_local_eligible``), each
``train.log_sync`` carries the last scanned step's ``embed_distinct_rows``
and ``embed_row_trips``; the report prints their mean and maximum and the
share of those steps that took one trip (a capacity that most steps
overflow by a little pays a second trip for it), and how the compiled step
writes its rows back (``embed_row_writeback``: ``dma``, one asynchronous
copy a row, or ``scatter``, XLA's; TUNING §5). A dense-gradient step (Adam,
L2, data replicas: every row swept every step) says on the same span how it
made its table-shaped gradient (``embed_grad``): ``rows``, from the batch's
distinct rows, each the sum of its positions' cotangents
(``Trainer._grad_by_rows``), with the same two counts and the same line in
the report; ``rows, exchanged over data``, the same under data replicas,
where a trip gathers every replica's (row id, sum) pairs and every chip
scatter-adds them all in place of an all-reduce of the tables — the two
counts are then the fullest replica's, and ``embed_exchanged_rows`` (all
replicas' rows together: what each chip scattered, whose mean the line
adds) says which side of the break-even a workload is on (TUNING §4);
or ``positions``, AD's scatter-add of every position (history models,
hashed tables, row shards, accumulation), all-reduced as tables under data
replicas, with a line that says so. ``embed_grad_by_table`` names the
tables whose gradient crossed the interconnect as a table ("" where none
did): under the exchange those whose row is one word (``fm_w``: one scatter
of the replica's own rows beside the trips, then the table's all-reduce,
cheaper than its slots in every chip's trips), with the tables left to AD
every one; the line names them. A step that differentiates its tables'
views (that one, and the row-local update) also says how it read them
(``embed_lookup``): ``rows``, each distinct row of the batch gathered from
its table once and the positions copied from those (a table whose row is
narrower than a lane line: ``Trainer._looked_up_by_rows``), ``positions``,
one table row gathered a position, or per table where a model's tables
differ (``fm_w:rows,fm_v:positions``); the line names it.

Where the model says what makes its attention's masked scores
(``--model sdar_moe``, ``--model solar_open2``, ``--model lfm2_moe``), each
``train.log_sync`` carries ``attn_scores`` (``kernel``: one Pallas call that
visits only the blocks of the score matrix the model's mask,
block-diffusion or causal, leaves something in; ``xla``: every score of
every query chunk) and, of the kernel, ``attn_score_blocks`` (visited / all,
a head), and ``attn_kept``: how many attention layers keep the forward
kernel's output and log-sum-exp for the backward pass, which so runs that
kernel once a layer and not twice, and the bytes kept (``5/5 layers 0.68
GB``; ``0/5`` on the XLA path and where the device's memory holds none:
``sdar_moe.kept_by``); the report prints them on its "block-masked
attention" line (TUNING §5).

The decoders say how their expert layers' rows go to and from their
positions: ``moe_rows`` (``kernel``: one copy a row over the pairs really
held, ``ops/pallas_moe_rows``; ``xla``: ``take`` and scatter-add over every
row of the buffer), ``moe_products`` (``kernel <tiling>``: the grouped
products over the held rows' tiles only, ``ops/pallas_grouped_dot``;
``xla``: ``jax.lax.ragged_dot`` over every row) and ``moe_rows_moved`` (the
step's held pairs over the buffers' rows: the share of the buffer both
kernels touch); the report prints them on its "expert layers' rows" line,
and where the router has a selection bias (``lfm2_moe``) the mean of
``moe_bias_moved_picks``, the (position, layer) selections of a step the
bias changed.

Where the model mixes by a gated short convolution (``--model lfm2_moe``),
each ``train.log_sync`` carries ``conv_taps_by`` (what computes the mixer's
elementwise passes: ``xla``); the report prints its "gated short
convolution" line.

Where the model scans a delta-rule recurrence (``--model kimi_linear``,
``--model solar_open2``), each ``train.log_sync`` carries ``kda_scan`` (what
computes the compiled step's scan, and its chunk length: ``kernel chunk64``,
the Pallas kernels of ``ops/pallas_kda_scan.py``, on one TPU at whole
chunks and 128-lane heads; ``chunk64/sub16``, XLA's chunked form, anywhere
else: ``kimi_linear.kda_scan_by``),
Kimi-Linear's ``mla_scores`` (``xla`` / ``kernel``), the step's
``kda_chunk_log_decay_min``, the most negative cumulative log-decay a chunk
held, and, where the write strength reaches 2 (``solar_open2``),
``kda_beta_over_one``, the positions x heads of the step whose strength
passed 1; the report prints one "delta-rule scan" line with the first
count's minimum and the second's mean over the trace (TUNING §17).

Where the model scans a selective recurrence (``--model phi4_flash``), each
``train.log_sync`` carries ``mamba_scan`` (the form of the compiled step:
``kernel steps64`` on a TPU, ``lockstep chunk32/segment512`` elsewhere), the step's
``mamba_chunk_log_decay_min``, the most negative whole-chunk log-decay, and
beside ``attn_scores`` / ``attn_score_blocks`` (the causal layers')
``attn_window_blocks``, the blocks the kernel visits under the window; the
report prints one "selective scan" line.

Where the model's loss has parts (``--model glm4_moe_lite``: the main
model's next-token loss and its multi-token-prediction module's), each
``train.log_sync`` carries each part (``xent``, ``mtp_xent``) beside the
decoders' other keys (``attn_scores`` / ``attn_score_blocks``: its latent
attention at heads of 256; ``moe_rows``, ``moe_bias_moved_picks``,
``mlp_kept``); the report prints one "multi-token prediction" line.

Where the model has dense SwiGLUs (a dense MLP or a shared expert:
``--model kimi_linear``, ``solar_open2``, ``lfm2_moe``, ``phi4_flash``,
``glm4_moe_lite``), each
``train.log_sync`` carries ``mlp_kept``: how many of those layers keep their
float32 first products for the backward pass and the bytes kept
(``6/6 layers 4.03 GB``; ``0/5`` where the device's memory holds none, off a
TPU or where the device says nothing of its memory:
``sdar_moe.kept_by``); the report prints one "dense SwiGLUs" line
(TUNING §17).

Usage:
    python scripts/trace_report.py TRACE.json [--top 20] [--json]
                                              [--stalls MS]
"""

import argparse
import collections
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepfm_tpu.obs import startup as startup_lib  # noqa: E402  (stdlib-only)
from deepfm_tpu.obs import trace as trace_lib  # noqa: E402  (stdlib-only)


def _pct(sorted_vals, q):
    """Nearest-rank percentile of an ascending list (None when empty)."""
    if not sorted_vals:
        return None
    n = len(sorted_vals)
    return sorted_vals[max(0, -(-q * n // 100) - 1)]


def _load(path):
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):  # bare event-array form is also loadable
        return doc, {}
    return doc.get("traceEvents", []), doc.get("otherData", {})


def _self_times_by_event(events):
    """-> {id(event): self microseconds} for the X events among ``events``.

    Within one thread, spans nest by interval containment (a span's
    children start after it and end before it). Sorting by (ts, -dur)
    visits parents before their children; a stack of open spans then
    attributes each child's duration against its direct parent's self
    time."""
    out = {}
    per_thread = collections.defaultdict(list)
    for ev in events:
        if ev.get("ph") == "X":
            per_thread[(ev.get("pid"), ev.get("tid"))].append(ev)
    for evs in per_thread.values():
        evs.sort(key=lambda e: (float(e["ts"]), -float(e.get("dur", 0.0))))
        stack = []  # [event, end_ts]
        for ev in evs:
            ts, dur = float(ev["ts"]), float(ev.get("dur", 0.0))
            while stack and stack[-1][1] <= ts:
                stack.pop()
            if stack:
                out[id(stack[-1][0])] -= dur  # child time is not parent self
            out[id(ev)] = dur
            stack.append([ev, ts + dur])
    return out


def _self_times(events):
    """-> {name: [self microseconds]} for X events, nesting per (pid, tid)."""
    by_event = _self_times_by_event(events)
    out = collections.defaultdict(list)
    for ev in events:
        if id(ev) in by_event:
            out[ev["name"]].append(by_event[id(ev)])
    return out


def _pair_async(events):
    """-> ({name: [dur]}, unmatched_count) from b/e pairs keyed by id."""
    opens, durs, unmatched = {}, collections.defaultdict(list), 0
    for ev in events:
        ph = ev.get("ph")
        if ph == "b":
            opens[(ev.get("pid"), ev.get("id"))] = ev
        elif ph == "e":
            b = opens.pop((ev.get("pid"), ev.get("id")), None)
            if b is None:
                unmatched += 1
            else:
                durs[b["name"]].append(float(ev["ts"]) - float(b["ts"]))
    return durs, unmatched + len(opens)


def summarize(events):
    """Aggregate rows: one dict per span name, sorted by self time desc."""
    x_self = _self_times(events)
    x_durs = collections.defaultdict(list)
    for ev in events:
        if ev.get("ph") == "X":
            x_durs[ev["name"]].append(float(ev.get("dur", 0.0)))
    async_durs, unmatched = _pair_async(events)
    rows = []
    for name, durs in x_durs.items():
        durs.sort()
        rows.append({
            "name": name, "kind": "span", "count": len(durs),
            "total_ms": sum(durs) / 1e3,
            "self_ms": sum(x_self.get(name, ())) / 1e3,
            "p50_ms": _pct(durs, 50) / 1e3,
            "p99_ms": _pct(durs, 99) / 1e3,
        })
    for name, durs in async_durs.items():
        durs.sort()
        total = sum(durs) / 1e3
        rows.append({
            "name": name, "kind": "async", "count": len(durs),
            "total_ms": total, "self_ms": total,
            "p50_ms": _pct(durs, 50) / 1e3,
            "p99_ms": _pct(durs, 99) / 1e3,
        })
    rows.sort(key=lambda r: -r["self_ms"])
    instants = collections.Counter(
        ev["name"] for ev in events if ev.get("ph") == "i")
    return rows, dict(instants), unmatched


#: Spans that can hold a dispatch back, in the order they are reported.
STALL_SPANS = ("host.stall", "stage.input_wait", "input.pool_fill",
               "input.pool_drain", "input.emit", "host.gc", "train.log_sync",
               "stage.transfer", "stage.wait", "compile.backend")
#: Of a ``host.stall``'s attributes, those printed under a stalled interval.
HOST_STALL_KEYS = ("cause", "late_ms", "runq_ms", "cpu_ms", "steal_ms",
                   "busiest_thread")


def stalls(events, threshold_ms):
    """Intervals between the ends of consecutive ``stage.wait`` spans (one
    process) over ``threshold_ms``: one dict each with the ``seq`` of the
    transfer that waited last, the interval and, per ``STALL_SPANS`` name,
    the milliseconds of the interval that spans of that name cover (summed
    over threads, so two busy threads can cover more than the interval),
    and under ``host_stalls`` the ``HOST_STALL_KEYS`` of each ``host.stall``
    that overlaps it."""
    spans = [e for e in events if e.get("ph") == "X"]
    by_pid = collections.defaultdict(list)    # pid -> [(end, stage.wait)]
    for e in spans:
        if e["name"] == "stage.wait":
            by_pid[e.get("pid")].append((float(e["ts"]) + float(e["dur"]), e))
    out = []
    for pid, waits in by_pid.items():
        waits.sort(key=lambda w: w[0])
        for (a, _), (b, wait) in zip(waits, waits[1:]):
            if b - a <= threshold_ms * 1e3:
                continue
            cover = dict.fromkeys(STALL_SPANS, 0.0)
            compiled, host_stalls = [], []
            for e in spans:
                if e["name"] in cover and e.get("pid") == pid:
                    t0 = float(e["ts"])
                    ov = min(t0 + float(e.get("dur", 0.0)), b) - max(t0, a)
                    if ov > 0:
                        cover[e["name"]] += ov / 1e3
                        if e["name"] == "compile.backend":
                            compiled.append(
                                e.get("args", {}).get("fun_name", "?"))
                        elif e["name"] == "host.stall":
                            args = e.get("args", {})
                            host_stalls.append({k: args[k] for k in
                                                HOST_STALL_KEYS if k in args})
            out.append({"seq": wait.get("args", {}).get("seq"),
                        "at_ms": (a - waits[0][0]) / 1e3,
                        "interval_ms": (b - a) / 1e3, "cover_ms": cover,
                        "compiled": compiled, "host_stalls": host_stalls})
    return out


def host_pulse(events):
    """What the tracer's pulse recorded: ``stalls`` by cause (count and
    total ms of the ``host.stall`` spans), the number of ``host.pulse``
    spans and the median ``late_ms_max`` of the quiet ones (no beat of
    theirs stalled). None for a trace without the pulse."""
    by_cause = {}
    for e in events:
        if e.get("name") == "host.stall" and e.get("ph") == "X":
            row = by_cause.setdefault(e.get("args", {}).get("cause", "?"),
                                      {"count": 0, "total_ms": 0.0})
            row["count"] += 1
            row["total_ms"] += float(e["dur"]) / 1e3
    late = [float(e["args"]["late_ms_max"]) for e in events
            if e.get("name") == "host.pulse" and e.get("ph") == "X"]
    if not by_cause and not late:
        return None
    quiet = sorted(v for v in late if v <= trace_lib.STALL_MS)
    return {"stalls": by_cause, "pulses": len(late),
            "quiet_late_ms_max_median":
                statistics.median(quiet) if quiet else None}


#: ``compile.*`` span -> its column in the start-up section's table.
_COMPILE_COLUMN = {"compile.trace": "trace_ms", "compile.lower": "lower_ms",
                   "compile.backend": "backend_ms",
                   "compile.cache_fetch": "fetch_ms"}


def start_up(events):
    """The start-up record of each process that has one: ``pid``, the
    launcher's ``line`` (``obs.startup.log_line`` over the same phases),
    ``phases`` (``setup.*``: name, ``module`` of an import, inclusive and
    self milliseconds, by start) and ``compiles`` (one row a ``fun_name``:
    how many times, ``trace_ms`` / ``lower_ms`` / ``backend_ms`` /
    ``fetch_ms`` and the cache's ``hit`` / ``miss`` counts, largest first),
    of the ``compile.*`` spans up to the end of ``setup.first_dispatch``.
    A trace without ``setup.process_start`` (an older one) has none."""
    out = []
    origins = {e.get("pid"): float(e["ts"]) for e in events
               if e.get("name") == "setup.process_start"}
    for pid, origin_us in origins.items():
        mine = [e for e in events if e.get("pid") == pid
                and e.get("ph") == "X"
                and e["name"].startswith(("setup.", "compile."))]
        ends = [float(e["ts"]) + float(e["dur"]) for e in mine
                if e["name"] == "setup.first_dispatch"]
        end_us = ends[-1] if ends else float("inf")
        mine = [e for e in mine if float(e["ts"]) < end_us]
        held = [(e["name"], int(float(e["ts"]) * 1e3),
                 int((float(e["ts"]) + float(e["dur"])) * 1e3),
                 e.get("tid"), e.get("args", {})) for e in mine]
        self_us = _self_times_by_event(mine)
        phases = [{"name": e["name"],
                   "module": e.get("args", {}).get("module"),
                   "at_ms": (float(e["ts"]) - origin_us) / 1e3,
                   "inclusive_ms": float(e["dur"]) / 1e3,
                   "self_ms": self_us[id(e)] / 1e3}
                  for e in sorted(mine, key=lambda e: float(e["ts"]))
                  if e["name"].startswith("setup.")]
        rows = {}
        for e in sorted(mine, key=lambda e: float(e["ts"])):
            if not e["name"].startswith("compile."):
                continue
            args = e.get("args", {})
            if e["name"] == "compile.cache_fetch":
                # nested in the ``compile.backend`` that covers it
                fun = next((b.get("args", {}).get("fun_name", "?")
                            for b in mine if b["name"] == "compile.backend"
                            and b.get("tid") == e.get("tid")
                            and float(b["ts"]) <= float(e["ts"])
                            and float(e["ts"]) + float(e["dur"])
                            <= float(b["ts"]) + float(b["dur"]) + 1.0), "?")
            else:
                fun = args.get("fun_name", "?")
            # JAX names the traced function ``f`` and its program ``jit(f)``
            if fun.startswith("jit(") and fun.endswith(")"):
                fun = fun[4:-1]
            row = rows.setdefault(fun, {
                "fun_name": fun, "count": 0, "trace_ms": 0.0,
                "lower_ms": 0.0, "backend_ms": 0.0, "fetch_ms": 0.0,
                "hit": 0, "miss": 0})
            row[_COMPILE_COLUMN[e["name"]]] += float(e["dur"]) / 1e3
            if e["name"] == "compile.backend":
                row["count"] += 1
                if args.get("cache") in ("hit", "miss"):
                    row[args["cache"]] += 1
        compiles = sorted(rows.values(), key=lambda r: -(
            r["trace_ms"] + r["lower_ms"] + r["backend_ms"]))
        out.append({"pid": pid,
                    "line": startup_lib.log_line(held, int(origin_us * 1e3)),
                    "phases": phases, "compiles": compiles})
    return out


def _log_syncs(events, attribute):
    """The attributes of the ``train.log_sync`` spans that carry
    ``attribute``."""
    return [e["args"] for e in events
            if e.get("name") == "train.log_sync" and e.get("ph") == "X"
            and attribute in e.get("args", {})]


def row_updates(events):
    """The distinct-row counters off the ``train.log_sync`` spans that carry
    them (a row-local step's, or a dense-gradient step's that builds its
    table gradient from those rows: ``table_gradient``): ``steps`` read,
    mean and max of ``embed_distinct_rows`` and ``embed_row_trips``,
    ``one_trip_share`` of those steps and the row-local step's
    ``writeback`` (``embed_row_writeback``; "?" where the trace predates it
    or the step writes no rows back) and, where data replicas exchanged
    their rows, ``exchanged_rows_mean`` (``embed_exchanged_rows``: what
    every chip scattered); None when no span has them (the step scatters
    every position, or the trace predates the counters)."""
    seen = _log_syncs(events, "embed_distinct_rows")
    if not seen:
        return None
    rows = [a["embed_distinct_rows"] for a in seen]
    trips = [a["embed_row_trips"] for a in seen]
    out = {"steps": len(seen),
           "distinct_rows_mean": sum(rows) / len(rows),
           "distinct_rows_max": max(rows),
           "row_trips_mean": sum(trips) / len(trips),
           "row_trips_max": max(trips),
           "one_trip_share": sum(t == 1 for t in trips) / len(trips),
           "writeback": seen[-1].get("embed_row_writeback", "?")}
    exchanged = [a["embed_exchanged_rows"] for a in seen
                 if "embed_exchanged_rows" in a]
    if exchanged:
        out["exchanged_rows_mean"] = sum(exchanged) / len(exchanged)
    return out


def _last_said(events, attribute):
    """``attribute`` of the last ``train.log_sync`` that carries it; None
    where none does."""
    seen = _log_syncs(events, attribute)
    return seen[-1][attribute] if seen else None


def table_gradient(events):
    """How the dense-gradient step made its table-shaped gradient
    (``embed_grad``: "rows" / "rows, exchanged over data" / "positions");
    None in a row-local step's trace, a sparse-update one's, or one that
    predates the note."""
    return _last_said(events, "embed_grad")


def table_lookup(events):
    """How a step that differentiates its tables' views read them
    (``embed_lookup``: "rows" / "positions" / per table); None in a trace
    of any other step, or one that predates the note."""
    return _last_said(events, "embed_lookup")


def tables_summed_as_tables(events):
    """Which tables' gradient the dense-gradient step summed over its data
    replicas as a table (``embed_grad_by_table``: names joined by ","; ""
    where none was — one device, or every table's rows exchanged); None
    where ``table_gradient`` is, or in a trace that predates the note."""
    return _last_said(events, "embed_grad_by_table")


def attention_scores(events):
    """What makes the attention's masked scores, off the ``train.log_sync``
    spans that say so: ``steps`` read, ``scores`` (``attn_scores``:
    ``kernel`` / ``xla``, the compiled step's choice) and, of the kernel,
    ``visited`` and ``total`` blocks of the score matrix a head
    (``attn_score_blocks``), and ``kept`` where the spans say it
    (``attn_kept``: layers keeping the forward kernel's results of layers
    that could, and the bytes); None when no span has them (another model,
    or a trace that predates them)."""
    seen = _log_syncs(events, "attn_scores")
    if not seen:
        return None
    out = {"steps": len(seen), "scores": seen[-1]["attn_scores"]}
    if "attn_kept" in seen[-1]:
        out["kept"] = seen[-1]["attn_kept"]
    if "attn_score_blocks" in seen[-1]:
        visited, total = seen[-1]["attn_score_blocks"].split("/")
        out.update(visited=int(visited), total=int(total))
    return out


def short_convolution(events):
    """What computes the gated short convolution's elementwise passes, off
    the ``train.log_sync`` spans that say so (``conv_taps_by``): ``steps``
    read and ``taps_by``; None when no span says (another model)."""
    seen = _log_syncs(events, "conv_taps_by")
    if not seen:
        return None
    return {"steps": len(seen), "taps_by": seen[-1]["conv_taps_by"]}


def delta_rule_scan(events):
    """The delta-rule scan's notes and count off the ``train.log_sync`` spans
    that carry them: ``steps`` read, ``scan`` (``kda_scan``: algorithm and
    chunk length), ``mla_scores`` (``?`` where no span says: another model's
    full layer, or an older trace), ``log_decay_min`` (the least
    ``kda_chunk_log_decay_min``; None in a trace that predates the count)
    and, where the spans carry ``kda_beta_over_one``, ``beta_over_one`` (its
    mean a step); None when no span says ``kda_scan`` (another model, or an
    older trace)."""
    seen = _log_syncs(events, "kda_scan")
    if not seen:
        return None
    lows = [a["kda_chunk_log_decay_min"] for a in seen
            if "kda_chunk_log_decay_min" in a]
    out = {"steps": len(seen), "scan": seen[-1]["kda_scan"],
           "mla_scores": seen[-1].get("mla_scores", "?"),
           "log_decay_min": min(lows) if lows else None}
    over = [a["kda_beta_over_one"] for a in seen if "kda_beta_over_one" in a]
    if over:
        out["beta_over_one"] = sum(over) / len(over)
    return out


def selective_scan(events):
    """The selective scan's note and count off the ``train.log_sync`` spans
    that carry them: ``steps`` read, ``scan`` (``mamba_scan``: form, chunk
    and segment), ``log_decay_min`` (the least
    ``mamba_chunk_log_decay_min``) and, of the attention kernel under the
    window, ``window_blocks`` (``attn_window_blocks``; absent where XLA
    makes the scores); None when no span says ``mamba_scan`` (another
    model)."""
    seen = _log_syncs(events, "mamba_scan")
    if not seen:
        return None
    lows = [a["mamba_chunk_log_decay_min"] for a in seen
            if "mamba_chunk_log_decay_min" in a]
    out = {"steps": len(seen), "scan": seen[-1]["mamba_scan"],
           "log_decay_min": min(lows) if lows else None}
    if "attn_window_blocks" in seen[-1]:
        out["window_blocks"] = seen[-1]["attn_window_blocks"]
    return out


def kept_products(events):
    """What the dense SwiGLUs keep for the backward pass, off the
    ``train.log_sync`` spans that say so: ``steps`` read and ``kept``
    (``mlp_kept``: layers keeping of layers that could, and the bytes);
    None when no span says (another model, or an older trace)."""
    seen = _log_syncs(events, "mlp_kept")
    if not seen:
        return None
    return {"steps": len(seen), "kept": seen[-1]["mlp_kept"]}


def multi_token_prediction(events):
    """The two losses of a model with a multi-token-prediction module, off
    the ``train.log_sync`` spans that carry ``mtp_xent``: ``steps`` read and
    the last span's ``xent`` (the main model's) and ``mtp_xent`` (the
    module's); None when no span has it (another model)."""
    seen = _log_syncs(events, "mtp_xent")
    if not seen:
        return None
    return {"steps": len(seen), "xent": seen[-1].get("xent"),
            "mtp_xent": seen[-1]["mtp_xent"]}


def expert_rows(events):
    """How the expert layers' rows moved, off the ``train.log_sync`` spans
    that say so: ``steps`` read, ``rows`` (``moe_rows``: ``kernel``, one copy
    a row over the pairs held, or ``xla``, every row of the buffer) and the
    mean ``held`` of ``buffer`` rows a step (``moe_rows_moved``), where the
    spans say it ``products`` (``moe_products``: ``kernel <tiling>``, the
    grouped products over the held rows' tiles only, or ``xla``,
    ``ragged_dot`` over every row of the buffer) and, where
    the spans carry ``moe_bias_moved_picks`` (a router with a selection
    bias), ``bias_moved_picks``, its mean a step; None when no span has them
    (another model, or a trace that predates them)."""
    seen = _log_syncs(events, "moe_rows")
    if not seen:
        return None
    moved = [a["moe_rows_moved"].split("/") for a in seen]
    out = {"steps": len(seen), "rows": seen[-1]["moe_rows"],
           "held": sum(int(h) for h, _ in moved) / len(moved),
           "buffer": int(moved[-1][1])}
    if "moe_products" in seen[-1]:
        out["products"] = seen[-1]["moe_products"]
    picks = [a["moe_bias_moved_picks"] for a in seen
             if "moe_bias_moved_picks" in a]
    if picks:
        out["bias_moved_picks"] = sum(picks) / len(picks)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="trace-<pid>.json or a merged trace file")
    ap.add_argument("--top", type=int, default=20,
                    help="rows to print, by self time (default 20)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output instead of the table")
    ap.add_argument("--stalls", type=float, default=None, metavar="MS",
                    help="also list dispatch intervals longer than MS with "
                         "the spans that cover them")
    args = ap.parse_args(argv)

    events, other = _load(args.trace)
    rows, instants, unmatched = summarize(events)
    dropped = int(other.get("dropped_spans", 0))
    slow = stalls(events, args.stalls) if args.stalls is not None else None
    touched = row_updates(events)
    attn = attention_scores(events)
    scan = delta_rule_scan(events)
    conv = short_convolution(events)
    selective = selective_scan(events)
    mtp = multi_token_prediction(events)
    kept = kept_products(events)
    moved = expert_rows(events)
    boots = start_up(events)
    pulse = host_pulse(events)

    if args.json:
        doc = {
            "spans": rows[:args.top], "instants": instants,
            "unmatched_async": unmatched, "dropped_spans": dropped,
            "events": len(events), "other": other}
        if slow is not None:
            doc["stalls"] = slow
        if touched is not None:
            doc["row_updates"] = touched
        if attn is not None:
            doc["attention_scores"] = attn
        if scan is not None:
            doc["delta_rule_scan"] = scan
        if conv is not None:
            doc["short_convolution"] = conv
        if selective is not None:
            doc["selective_scan"] = selective
        if mtp is not None:
            doc["multi_token_prediction"] = mtp
        if kept is not None:
            doc["kept_products"] = kept
        if moved is not None:
            doc["expert_rows"] = moved
        if boots:
            doc["start_up"] = boots
        if pulse is not None:
            doc["host_pulse"] = pulse
        print(json.dumps(doc, indent=2))
        return 0

    print(f"{len(events)} events"
          + (f" from pids {other['pids']}" if "pids" in other else "")
          + (f"; {dropped} spans DROPPED to ring wraparound"
             if dropped else ""))
    if unmatched:
        print(f"{unmatched} async begin/end events unpaired "
              "(in flight at export, or partner lost to the ring)")
    header = (f"{'span':<24}{'kind':<7}{'count':>7}{'total_ms':>11}"
              f"{'self_ms':>10}{'p50_ms':>9}{'p99_ms':>9}")
    print(header)
    print("-" * len(header))
    for r in rows[:args.top]:
        print(f"{r['name']:<24}{r['kind']:<7}{r['count']:>7}"
              f"{r['total_ms']:>11.2f}{r['self_ms']:>10.2f}"
              f"{r['p50_ms']:>9.3f}{r['p99_ms']:>9.3f}")
    for name, n in sorted(instants.items()):
        print(f"instant {name}: {n}")
    grad, whole = table_gradient(events), tables_summed_as_tables(events)
    if touched is not None:
        by_rows = grad is not None      # "rows", exchanged or not
        lookup = table_lookup(events)
        print("%s%s over %d logged steps: "
              "embed_distinct_rows mean %.0f max %d, embed_row_trips mean "
              "%.2f max %d, one trip in %.0f%% of them, %s" % (
                  f"dense-gradient step, table gradient from {grad}"
                  if by_rows else "row-local table update",
                  f", views looked up by {lookup}" if lookup else "",
                  touched["steps"], touched["distinct_rows_mean"],
                  touched["distinct_rows_max"], touched["row_trips_mean"],
                  touched["row_trips_max"],
                  100 * touched["one_trip_share"],
                  "every row swept after it" if by_rows
                  else "rows written back by " + touched["writeback"])
              + (" (the fullest replica's; every chip scattered all "
                 "replicas' rows, embed_exchanged_rows mean %.0f a step, and "
                 "%s)" % (touched["exchanged_rows_mean"],
                          f"of the tables only {whole} crossed the "
                          "interconnect, all-reduced" if whole
                          else "no table crossed the interconnect")
                 if "exchanged_rows_mean" in touched else ""))
    elif grad == "positions":
        print("dense-gradient step: table gradient from positions (AD's "
              "scatter-add of every position of the batch)"
              + (f", summed over data as tables: {whole}" if whole else ""))
    if attn is not None:
        print("block-masked attention over %d logged steps: scores by %s"
              % (attn["steps"], attn["scores"])
              + (", %d of %d blocks of the score matrix visited a head "
                 "(%.1f%%)" % (attn["visited"], attn["total"],
                               100 * attn["visited"] / attn["total"])
                 if "visited" in attn else ", every score computed")
              + ("; the forward kernel's output and log-sum-exp kept for "
                 "the backward pass in %s" % attn["kept"]
                 if "kept" in attn else ""))
    if conv is not None:
        print("gated short convolution over %d logged steps: taps and gates "
              "by %s" % (conv["steps"], conv["taps_by"]))
    if scan is not None:
        low = scan["log_decay_min"]
        print("delta-rule scan over %d logged steps: %s" % (
            scan["steps"], scan["scan"])
              + (", latent attention's scores by %s" % scan["mla_scores"]
                 if scan["mla_scores"] != "?" else "")
              + ", most negative chunk log-decay %s" % (
                  "not in this trace" if low is None else "%.4g" % low)
              + (", write strength over 1 at %.0f positions x heads a step"
                 % scan["beta_over_one"] if "beta_over_one" in scan else ""))
    if selective is not None:
        low = selective["log_decay_min"]
        print("selective scan over %d logged steps: %s" % (
            selective["steps"], selective["scan"])
              + ", most negative chunk log-decay %s" % (
                  "not in this trace" if low is None else "%.4g" % low)
              + (", %s blocks of the score matrix visited a head under the "
                 "window" % selective["window_blocks"]
                 if "window_blocks" in selective else ""))
    if kept is not None:
        print("dense SwiGLUs over %d logged steps: first products kept for "
              "the backward pass in %s" % (kept["steps"], kept["kept"]))
    if mtp is not None:
        print("multi-token prediction over %d logged steps: the main loss "
              "%s, the module's %.5g at the last" % (
                  mtp["steps"], "not in this trace" if mtp["xent"] is None
                  else "%.5g" % mtp["xent"], mtp["mtp_xent"]))
    if moved is not None:
        print("expert layers' rows over %d logged steps: moved by %s, %.0f "
              "of %d buffer rows a step held a pair (%.1f%%)"
              % (moved["steps"], moved["rows"], moved["held"],
                 moved["buffer"], 100 * moved["held"] / moved["buffer"])
              + (", multiplied by %s" % moved["products"]
                 if "products" in moved else "")
              + (", the selection bias moved %.0f picks a step"
                 % moved["bias_moved_picks"]
                 if "bias_moved_picks" in moved else ""))
    for boot in boots:
        print(f"start-up of pid {boot['pid']}: {boot['line']}")
        print(f"  {'phase':<46}{'at_s':>9}{'incl_s':>9}{'self_s':>9}")
        for ph in boot["phases"]:
            label = ph["name"] + (f" {ph['module']}" if ph["module"] else "")
            print(f"  {label:<46}{ph['at_ms'] / 1e3:>9.2f}"
                  f"{ph['inclusive_ms'] / 1e3:>9.2f}"
                  f"{ph['self_ms'] / 1e3:>9.2f}")
        print(f"  {'compiled before the first dispatch ended':<46}"
              f"{'n':>4}{'trace_s':>9}{'lower_s':>9}{'backend_s':>10}"
              f"{'fetch_s':>9}  cache")
        for r in boot["compiles"][:args.top]:
            cache = "/".join(f"{r[k]} {k}" for k in ("hit", "miss") if r[k])
            print(f"  {r['fun_name'][:46]:<46}{r['count']:>4}"
                  f"{r['trace_ms'] / 1e3:>9.2f}{r['lower_ms'] / 1e3:>9.2f}"
                  f"{r['backend_ms'] / 1e3:>10.2f}"
                  f"{r['fetch_ms'] / 1e3:>9.2f}  {cache or '-'}")
    for st in slow or ():
        cover = ", ".join(f"{k} {v:.1f}" for k, v in st["cover_ms"].items()
                          if v > 0)
        print(f"stall before transfer seq={st['seq']} at "
              f"{st['at_ms'] / 1e3:.2f} s: {st['interval_ms']:.1f} ms; "
              f"covered (ms): {cover or 'by no known span'}"
              + (f"; compiled: {', '.join(st['compiled'])}"
                 if st["compiled"] else ""))
        for under in st["host_stalls"]:
            print("  host.stall: " + ", ".join(
                f"{k} {v:.1f}" if isinstance(v, float) else f"{k} {v}"
                for k, v in under.items()))
    if slow is not None:
        print(f"{len(slow)} dispatch intervals over {args.stalls:g} ms")
    if pulse is not None:
        by_cause = "; ".join(
            f"{cause} {row['count']}, {row['total_ms']:.1f} ms"
            for cause, row in sorted(pulse["stalls"].items()))
        quiet = pulse["quiet_late_ms_max_median"]
        print(f"host pulse: {sum(r['count'] for r in pulse['stalls'].values())}"
              f" stalls ({by_cause or 'none'}) in {pulse['pulses']} s of "
              "pulses; a quiet second's late_ms_max, median: "
              + ("no quiet second" if quiet is None else f"{quiet:.2f} ms"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
