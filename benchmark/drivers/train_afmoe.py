"""Training driver for ``--model afmoe``: ``drivers/train_sdar_moe``'s run
with this model's seeding, reference and counters.

Everything that names no model's module is imported: the feed, the clock,
the probes, the strided sample and the gaps from ``train_sdar_moe``
(``StepFeed``, ``StepClock``, ``make_probe``, ``sampled``, ``step_gaps``),
the routed leaves' names from ``train_solar_open2`` (``ROUTED``), the hook
that keeps the selection bias from ``train_lfm2_moe`` (``StepCounts``).
Written again, as in the other decoder drivers and for their reason (PERF.md
section 7 row 18: the fold is a ``benchmark`` PR's), is what names a model's
reference and program by module: the seeded state (``_program_afmoe``: the
router's plan at one expert a class and layer, the selection bias in the
model state, the table at 3 / sqrt(d)), the run's order and the check
(``reference_afmoe``).

The check is the GLM-4.7-Flash cell's without its module's three numbers:
the first ``CHECK_STEPS`` steps' loss (``xent_gap``), Adam's first moment
after the first step over all leaves and over the leaves no routing reaches,
the parameters' change, the untied table's untouched rows, the selection
bias bit-equal after the first steps and after the run, and no pair over the
buffer. The first layer is dense: the fullest expert's load is against the
mean over ``(decoder_layers - dense_layers) * moe_experts_held`` shares.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from benchmark import harness, reference_afmoe, traffic_sequences, xplane
from benchmark.reference_sdar_moe import worst_leaf_gap
from benchmark.drivers import _program, _program_afmoe
from benchmark.drivers._program_afmoe import SELECT_BIAS
from benchmark.drivers._program_sdar_moe import leaf_specs
from benchmark.drivers.train import WARMUP_DISPATCHES, window_rate
from benchmark.drivers.train_lfm2_moe import BIAS_MOVED, StepCounts
from benchmark.drivers.train_sdar_moe import (CHECK_STEPS, EXTRA_WARMUP,
                                              TABLE, StepClock, StepFeed,
                                              make_probe, sampled, step_gaps)
from benchmark.drivers.train_solar_open2 import ROUTED

#: The check's numbers, in the order they are said.
CHECKS = ("xent_gap", "first_moment_gap", "first_moment_gap_unrouted",
          "param_change_gap", "untouched_rows_moved", "bias_moved",
          "pairs_over_buffer")


def run(cell: harness.Cell, acquire, seed: int, seconds: float,
        trace: bool, work: str, t_start: Optional[float] = None) -> dict:
    t0 = t_start if t_start is not None else time.time()
    t_perf0 = time.perf_counter() - (time.time() - t0)
    if trace:
        seconds = min(seconds, harness.MAX_TRACE_SECONDS)
    flags = dict(cell.config["flags"])
    tr = cell.traffic
    if (flags["history_max_len"], flags["batch_size"]) != (
            tr["sequence_length"], tr["sequences_per_step"]) \
            or flags["feature_size"] != cell.config["vocabulary_rows"]:
        raise ValueError("the traffic's sizes and the configuration's flags "
                         "disagree")
    # First of all, and before any other thread imports the package: a
    # program that does not know this model fails here, within a second.
    cfg = _program.make_config(flags)
    writer = traffic_sequences.ShardWriter(
        os.path.join(work, "shards"), tr["sequence_length"],
        cell.config["vocabulary_rows"], seed, tr)
    harness.say(t0, f"shards being written ({writer.examples} sequences)")
    wait_for_tasks = _program.import_tasks_beside()
    try:
        devices = acquire()
    except BaseException:
        writer.files()
        raise
    import jax

    harness.say(t0, f"JAX up on {len(devices)} {devices[0].device_kind}")
    tasks = wait_for_tasks()
    harness.say(t0, "deepfm_tpu.train.tasks imported")
    trainer = _program.build_trainer(cfg, devices)
    settings = _program_afmoe.reference_settings(trainer)
    harness.say(t0, "trainer built")
    compiles = harness.CompileCounter()
    spans = harness.Spans(trace)
    state, _ = _program_afmoe.seeded_state(trainer, seed, cell.config)
    jax.block_until_ready(state.params)
    harness.say(t0, "seeded state on the device")

    files = writer.files()
    harness.say(t0, f"{len(files)} shards written")
    pipeline = tasks.make_pipeline(cfg, files,
                                   epochs=int(tr["max_epochs"]))
    feed = StepFeed(pipeline)
    device_trace = harness.DeviceTrace(os.path.join(work, "trace")) \
        if trace else None
    clock = StepClock(seconds, feed, make_probe(trainer), compiles,
                      device_trace)
    counts = StepCounts()
    harness.say(t0, "fit starts")
    try:
        state, fit_out = trainer.fit(state, feed, hooks=[clock, counts])
    finally:
        clock.finish()
        pipeline.close()
    if clock.t_close is None:
        raise RuntimeError(
            f"the data ran out after {clock.seen} dispatches, before the "
            f"{seconds:.0f} s window closed: raise max_epochs")
    setup_s = clock.t_open - t_perf0
    final_loss = float(fit_out["loss"])
    xplane_path = device_trace.stop() if trace else None
    device = harness.device_report(devices)
    over_buffer = int(state.model_state["moe_pairs_over_buffer"])
    biases = [np.asarray(counts.bias_after_check),
              np.asarray(state.model_state[SELECT_BIAS])]
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
          f"{per_dispatch / median_s:.2f} sequences/s/chip; set-up "
          f"{setup_s:.2f} s", flush=True)
    end_to_end = {"train_examples_per_s_per_chip": rate, "setup_s": setup_s}
    if not trace and len(intervals) < 10:
        raise RuntimeError(f"only {len(intervals)} dispatches completed in "
                           "the window: too few to close it within a tenth "
                           "of its length")
    seen = counts.read(EXTRA_WARMUP + WARMUP_DISPATCHES, len(intervals))
    whole = counts.read(0, len(counts.dispatches))
    shares = (cfg.decoder_layers - cfg.dense_layers) * cfg.moe_experts_held
    load = {"moe_pairs_held_per_step": float(seen["moe_pairs_held"].mean()),
            "moe_expert_load_max_over_mean": float(np.mean(
                seen["moe_expert_load_max"] * shares
                / seen["moe_pairs_held"])),
            # how near the buffer came to running over, in the whole run
            "moe_layer_pairs_max_over_buffer": float(
                whole["moe_layer_pairs_max"].max() / cfg.moe_pair_capacity),
            # (position, layer) selections a step that the bias changed
            BIAS_MOVED: float(seen[BIAS_MOVED].mean())}
    print("counts (the window's dispatches, each one's last step): "
          + ", ".join(f"{k} {v:.6g}" for k, v in load.items())
          + f"; pairs over the buffer in the whole run {over_buffer}",
          flush=True)

    # ---- correctness: the reference follows the first steps -------------
    correct = check_first_steps(
        cell, trainer, settings, seed, feed.first, clock, biases,
        over_buffer, np.isfinite(final_loss), t0)

    ctx = None
    if trace:
        window_ns = (clock.open_wall_ns, clock.close_wall_ns)
        events = harness.spans_in(
            spans.events(os.path.join(work, "spans.json")), *window_ns)
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
                      "input_records": feed.records,
                      "moe_pairs_over_buffer": over_buffer, **load})
    if compiles.count:
        print(f"check compiles_in_window: {compiles.count} (limit 0) NOT OK",
              flush=True)
        correct = False
    return harness.result_line(cell, correct=correct, attempted=steps,
                               failed=0 if np.isfinite(final_loss) else steps,
                               end_to_end=end_to_end, ctx=ctx, device=device)


def check_first_steps(cell, trainer, settings, seed, batches, clock, biases,
                      over_buffer, loss_finite, t0) -> bool:
    """Program vs reference over the run's first ``CHECK_STEPS`` steps;
    ``biases`` is the program's selection bias after them and after the
    run."""
    import jax.numpy as jnp

    from benchmark import weights

    got_mu, got_params = clock.first_mu.result(), clock.last_params.result()
    got_xents = [float(x) for x in clock.xents]
    specs = leaf_specs(trainer)
    wkw = _program_afmoe.weight_kwargs(cell.config, trainer)
    rows = int(trainer.cfg.feature_size)
    salts = {name: weights.leaf_salt(seed, name) for name in specs}
    params0 = {name: np.asarray(_program_afmoe.seeded_leaf(
        salts, name, shape, wkw, xp=jnp)) for name, shape in specs.items()}
    bias0 = _program_afmoe.seeded_bias(
        weights.leaf_salt(seed, SELECT_BIAS),
        _program_afmoe.bias_shape(trainer.cfg))
    # The reference's table is the vocabulary's rows; the program's padding
    # rows beyond them are compared with the untouched rows below.
    follower = reference_afmoe.Follower(
        {**params0, TABLE: params0[TABLE][:rows]}, bias0, settings["sizes"],
        settings["learning_rate"])
    tokens = batches["hist_ids"]
    want_xents, want_mu = [], None
    for step in range(CHECK_STEPS):
        want_xents.append(follower.step(tokens[step]))
        if want_mu is None:     # after the first step: 0.1 of its gradient
            want_mu = {n: np.array(sampled(n, v))
                       for n, v in follower.mu.items()}
        harness.say(t0, f"reference step {step + 1}: loss "
                        f"{want_xents[-1]:.6f}, program {got_xents[step]:.6f}")

    touched = np.zeros(params0[TABLE].shape[0], bool)
    touched[np.unique(tokens)] = True
    gaps = step_gaps(got_params, got_mu, got_xents, follower.params, want_mu,
                     want_xents, params0, touched)
    unrouted = [n for n in got_mu
                if n != TABLE and n.rsplit(".", 1)[-1] not in ROUTED]
    gaps["first_moment_gap_unrouted"], unrouted_leaf = worst_leaf_gap(
        {n: got_mu[n] for n in unrouted}, {n: want_mu[n] for n in unrouted})
    print(f"check leaves: first moment worst {gaps['first_moment_leaf']} "
          f"(of the leaves no routing reaches {unrouted_leaf}), "
          f"parameter change worst {gaps['param_change_leaf']}; "
          f"{int(touched.sum())} of {len(touched)} table rows touched",
          flush=True)
    gaps["bias_moved"] = sum(int(np.count_nonzero(
        b.view(np.uint32) != bias0.view(np.uint32))) for b in biases)
    gaps["pairs_over_buffer"] = over_buffer
    limits = cell.traffic["limits"]
    ok = [harness.report_check(name, gaps[name], limits[name])
          for name in CHECKS]
    if not loss_finite:
        print("check final loss: not finite NOT OK", flush=True)
    return all(ok) and bool(loss_finite)
