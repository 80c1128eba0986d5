"""Training driver for ``--model dlrm_dcnv2``: ``drivers/train.py``'s run
with this model's reference.

The window, the clock, the feed, the probe's place (right after the first
dispatch) and the rate are ``drivers/train``'s own, by import: one
``Trainer.fit`` over the normal file pipeline, whose first dispatches are
set-up and which then runs the measured window. What differs is what
``drivers/train`` ties to DeepFM under Adam: the reference
(``reference_dlrm_dcnv2``), what the probe reads beside the parameters
(Adagrad's sum of squares in place of Adam's first moment), and the settings
(``_program_dlrm_dcnv2``).

Compared outside the window, on the first dispatch's steps: the last step's
log-loss, Adagrad's sum of squares and the parameters' change by the worst
leaf — table rows the batches touched, a sample of rows they did not, every
dense leaf — and the untouched sample's change, which the mathematics holds
to exactly 0 (no L2 term: a row without a gradient does not move).
"""

from __future__ import annotations

import os
import time
from typing import Dict, Optional

import numpy as np

from benchmark import harness, reference_dlrm_dcnv2, traffic, xplane
from benchmark.drivers import _program, _program_dlrm_dcnv2
from benchmark.drivers.train import (UNTOUCHED_SAMPLE, Clock, Feed,
                                     window_rate)

#: Followed rows are padded up to a multiple of this, so that seeds whose
#: batches touch about as many rows share one compiled probe and one compiled
#: reference step. (``drivers/train`` pads to the most a dispatch could
#: touch; at K=128 that is 1.3 GB for each of two probed trees.)
ROWS_QUANTUM = 131072


def make_probe(trainer, table_names, rows_dev):
    """A jitted read of what the check compares, enqueued right after the
    first dispatch (the next one donates the state away): table leaves and
    their Adagrad accumulators at ``rows_dev``, every other leaf whole."""
    import jax
    import jax.numpy as jnp

    def read(params, acc, rows):
        def pick(tree):
            flat, _ = jax.tree_util.tree_flatten_with_path(tree)
            return {_program.leaf_name(p): (
                jnp.take(x, rows, axis=0)
                if _program.leaf_name(p) in table_names else jnp.copy(x))
                for p, x in flat}
        return pick(params), pick(acc)

    jitted = jax.jit(read)

    def probe(state):
        return jitted(state.params,
                      _program_dlrm_dcnv2.accumulator(state.opt_state),
                      rows_dev())
    return probe


def run(cell: harness.Cell, acquire, seed: int, seconds: float,
        trace: bool, work: str, t_start: Optional[float] = None) -> dict:
    t0 = t_start if t_start is not None else time.time()
    t_perf0 = time.perf_counter() - (time.time() - t0)
    if trace:
        seconds = min(seconds, harness.MAX_TRACE_SECONDS)
    layout = traffic.FieldLayout.from_config(cell.config)
    flags = dict(cell.config["flags"])
    if layout.feature_size != flags["feature_size"] \
            or layout.field_size != flags["field_size"] \
            or layout.numeric_fields != flags["numeric_fields"]:
        raise ValueError("the configuration's fields and flags disagree")
    # First of all, and before any other thread imports the package: a
    # program that does not know this model fails here, within a second.
    cfg = _program.make_config(flags)
    writer = traffic.ShardWriter(os.path.join(work, "shards"), layout, seed,
                                 cell.traffic)
    harness.say(t0, f"shards being written ({writer.examples} examples)")
    wait_for_tasks = _program.import_tasks_beside()
    try:
        devices = acquire()
    except BaseException:
        writer.files()
        raise
    import jax.numpy as jnp
    import jax

    harness.say(t0, f"JAX up on {len(devices)} {devices[0].device_kind}")
    tasks = wait_for_tasks()
    harness.say(t0, "deepfm_tpu.train.tasks imported")
    trainer = _program.build_trainer(cfg, devices)
    settings = _program_dlrm_dcnv2.reference_settings(trainer, cell.config)
    harness.say(t0, "trainer built")
    compiles = harness.CompileCounter()
    spans = harness.Spans(trace)
    state, _ = _program.seeded_state(trainer, seed, cell.config)
    specs = _program.leaf_specs(trainer)
    wkw = _program.weight_kwargs(cell.config, trainer)
    tables = {n for n, s in specs.items() if s and s[0] == wkw["padded_vocab"]}
    jax.block_until_ready(state.params)
    harness.say(t0, "seeded state on the device")

    files = writer.files()
    harness.say(t0, f"{len(files)} shards written")
    pipeline = tasks.make_pipeline(cfg, files,
                                   epochs=int(cell.traffic["max_epochs"]))
    feed = Feed(pipeline)
    n_num = layout.numeric_fields
    followed: Dict[str, np.ndarray] = {}

    def rows_dev():
        """Rows the reference will hold: those the first dispatch looks up
        (the categorical fields' ids; a numeric field's slot row is never
        read) and a seeded sample of the others, padded to a multiple of
        ``ROWS_QUANTUM`` with the last row."""
        touched = np.unique(feed.first["feat_ids"][..., n_num:])
        extra = np.random.default_rng([seed, 0x726F]).integers(
            0, layout.feature_size, UNTOUCHED_SAMPLE)
        rows = np.union1d(touched, extra)
        followed["n_real"] = len(rows)
        followed["touched"] = np.isin(rows, touched, assume_unique=True)
        pad = np.full(-len(rows) % ROWS_QUANTUM, rows[-1], rows.dtype)
        followed["rows"] = np.concatenate([rows, pad]).astype(np.int32)
        return jnp.asarray(followed["rows"])

    device_trace = harness.DeviceTrace(os.path.join(work, "trace")) \
        if trace else None
    clock = Clock(seconds, feed, make_probe(trainer, tables, rows_dev),
                  compiles, device_trace)
    harness.say(t0, "fit starts")
    try:
        state, fit_out = trainer.fit(state, feed, hooks=[clock])
    finally:
        clock.finish()
        pipeline.close()
    if clock.t_close is None:
        raise RuntimeError(
            f"the data ran out after {clock.n} dispatches, before the "
            f"{seconds:.0f} s window closed: raise max_epochs")
    setup_s = clock.t_open - t_perf0
    final_loss = float(fit_out["loss"])
    xplane_path = device_trace.stop() if trace else None
    device = harness.device_report(devices)
    del state
    harness.say(t0, "window closed")

    # ---- the window's numbers -------------------------------------------
    intervals = np.diff(clock.stamps)
    per_dispatch = clock.steps_per_dispatch * cfg.batch_size / len(devices)
    window_s = clock.stamps[-1] - clock.stamps[0]
    steps = len(intervals) * clock.steps_per_dispatch
    rate, median_s = window_rate(clock.stamps, per_dispatch)
    print(f"window: {len(intervals)} dispatches completed in {window_s:.3f} s;"
          f" interval min/median/max {intervals.min():.4f}/{median_s:.4f}/"
          f"{intervals.max():.4f} s; by the median interval "
          f"{per_dispatch / median_s:.1f} ex/s/chip; set-up {setup_s:.2f} s",
          flush=True)
    end_to_end = {"train_examples_per_s_per_chip": rate, "setup_s": setup_s}
    if not trace and len(intervals) < 10:
        raise RuntimeError(f"only {len(intervals)} dispatches completed in "
                           "the window: too few to close it within a tenth "
                           "of its length")

    # ---- correctness: the reference follows the first dispatch ----------
    correct = check_first_dispatch(
        cell, settings, specs, wkw, tables, seed, n_num, feed.first,
        followed, clock, np.isfinite(final_loss), t0)

    ctx = None
    if trace:
        window_ns = (clock.open_wall_ns, clock.close_wall_ns)
        events = harness.spans_in(spans.events(os.path.join(work, "spans.json")),
                                  *window_ns)
        reduced = xplane.reduce(xplane_path, window_ns=window_ns,
                                spans=events)
        ctx = harness.Context(
            cell=cell, devices=devices, spans=events, trace=reduced,
            window=window_ns,
            counters={"memory_peak_bytes": device["memory_peak_bytes"],
                      "compiles_in_window": compiles.count,
                      "steps_in_window": steps,
                      "dispatch_interval_median_ms": 1e3 * median_s,
                      "input_wait_ns": feed.wait_s * 1e9,
                      "input_records": feed.records})
    if compiles.count:
        print(f"check compiles_in_window: {compiles.count} (limit 0) NOT OK",
              flush=True)
        correct = False
    return harness.result_line(cell, correct=correct, attempted=steps,
                               failed=0 if np.isfinite(final_loss) else steps,
                               end_to_end=end_to_end, ctx=ctx, device=device)


def check_first_dispatch(cell, settings, specs, wkw, tables, seed, n_num,
                         batches, followed, clock, loss_finite, t0) -> bool:
    """Program vs reference over the first dispatch's steps."""
    import jax.numpy as jnp

    from benchmark import weights

    rows, touched = followed["rows"], followed["touched"]
    n_real = followed["n_real"]
    got_params, got_acc = ({n: np.asarray(v) for n, v in tree.items()}
                           for tree in clock.probed)
    got_xent = float(clock.first_xent)
    params0 = {}
    for name, shape in specs.items():
        params0[name] = np.asarray(weights.leaf_values(
            weights.leaf_salt(seed, name), shape,
            rows=jnp.asarray(rows, jnp.uint32) if name in tables else None,
            xp=jnp, **wkw))
    follower = reference_dlrm_dcnv2.Follower(params0, rows, **settings)
    xent = float("nan")
    for step in range(batches["label"].shape[0]):
        xent = follower.step(batches["feat_ids"][step][:, n_num:],
                             batches["feat_vals"][step][:, :n_num],
                             batches["label"][step])
    harness.say(t0, f"reference followed {follower.count} steps on "
                    f"{n_real} rows ({int(touched.sum())} touched)")

    gaps = reference_dlrm_dcnv2.dispatch_gaps(
        got_params, got_acc, got_xent, follower, xent, params0, tables,
        n_real, touched)
    print(f"check leaves: accumulator worst {gaps['accumulator_leaf']}, "
          f"parameter change worst {gaps['param_change_leaf']}; program xent "
          f"{got_xent:.6f} reference {xent:.6f}", flush=True)
    limits = cell.traffic["limits"]
    ok = [harness.report_check(name, gaps[name], limits[name])
          for name in ("xent_gap", "accumulator_gap", "param_change_gap",
                       "untouched_rows_moved")]
    if not loss_finite:
        print("check final loss: not finite NOT OK", flush=True)
    return all(ok) and bool(loss_finite)
