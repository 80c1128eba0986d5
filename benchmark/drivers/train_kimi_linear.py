"""Training driver for ``--model kimi_linear``: ``drivers/train_sdar_moe``'s
run with this model's seeding, reference and counters.

The feed, the clock, the probes, the strided sample and the gaps are
``train_sdar_moe``'s, by import (``StepFeed``, ``StepClock``, ``Counts``,
``make_probe``, ``sampled``, ``step_gaps``): one ``Trainer.fit`` over the
normal file pipeline, one step a dispatch, an example one sequence, the
run's first ``CHECK_STEPS`` dispatches set-up and followed by the reference
after the window. Written again is what names a model's reference and
program by module: the seeded state (``_program_kimi_linear``), the run's
order and the check (``reference_kimi_linear``: no noise is drawn here, so
the check has no ``noise_z`` and no count of masked positions; a KDA
layer's two decay vectors are judged as one leaf, ``pooled``), and the
counts of this model (``kda_chunk_log_decay_min`` beside the ``moe_*``
ones).
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from benchmark import (harness, reference_kimi_linear, traffic_sequences,
                       xplane)
from benchmark.drivers import _program, _program_kimi_linear
from benchmark.drivers._program_sdar_moe import leaf_specs
from benchmark.drivers.train import WARMUP_DISPATCHES, window_rate
from benchmark.drivers.train_sdar_moe import (CHECK_STEPS, EXTRA_WARMUP,
                                              TABLE, Counts, StepClock,
                                              StepFeed, make_probe, sampled,
                                              step_gaps)

DECAY_MIN = "kda_chunk_log_decay_min"


class StepCounts(Counts):
    """``Counts`` that also keeps the delta-rule scan's count."""

    def __call__(self, state, m) -> None:
        super().__call__(state, m)
        if DECAY_MIN in m:
            self.dispatches[-1][DECAY_MIN] = m[DECAY_MIN]


def pooled(tree: dict) -> dict:
    """``tree`` ({leaf name: array}) with each KDA layer's two decay vectors
    (``kda_a_log``, one element a held head, and ``kda_dt_bias``) as the one
    flat leaf ``layers.<i>.kda_decay``. A leaf of two elements cannot be
    judged by a relative norm under Adam, whose first steps move every
    element by about the learning rate whatever its gradient: one element
    whose gradient is near zero (a sum over every position and channel of a
    head that cancels) goes the other way by a rounding, and the leaf's gap
    reads 0.58 where its 71 other readings were at most 0.065 (my chip
    runs, PR 33). The gate's parameters are judged together."""
    out = dict(tree)
    for name in tree:
        if name.endswith(".kda_a_log"):
            layer = name.rsplit(".", 1)[0]
            out[layer + ".kda_decay"] = np.concatenate([
                np.asarray(out.pop(n)).reshape(-1)
                for n in (name, layer + ".kda_dt_bias")])
    return out


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
    settings = _program_kimi_linear.reference_settings(trainer)
    harness.say(t0, "trainer built")
    compiles = harness.CompileCounter()
    spans = harness.Spans(trace)
    state, _ = _program_kimi_linear.seeded_state(trainer, seed, cell.config)
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
            # the most negative cumulative log-decay a chunk held, whole run
            DECAY_MIN: float(whole[DECAY_MIN].min())}
    print("counts (the window's dispatches, each one's last step): "
          + ", ".join(f"{k} {v:.6g}" for k, v in load.items())
          + f"; pairs over the buffer in the whole run {over_buffer}",
          flush=True)

    # ---- correctness: the reference follows the first steps -------------
    correct = check_first_steps(
        cell, trainer, settings, seed, feed.first, clock, over_buffer,
        np.isfinite(final_loss), t0)

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


def check_first_steps(cell, trainer, settings, seed, batches, clock,
                      over_buffer, loss_finite, t0) -> bool:
    """Program vs reference over the run's first ``CHECK_STEPS`` steps."""
    import jax.numpy as jnp

    from benchmark import weights

    got_mu, got_params = clock.first_mu.result(), clock.last_params.result()
    got_xents = [float(x) for x in clock.xents]
    specs = leaf_specs(trainer)
    wkw = _program_kimi_linear.weight_kwargs(cell.config, trainer)
    rows = int(trainer.cfg.feature_size)
    salts = {name: weights.leaf_salt(seed, name) for name in specs}
    params0 = {name: np.asarray(_program_kimi_linear.seeded_leaf(
        salts, name, shape, wkw, xp=jnp)) for name, shape in specs.items()}
    # The reference's table is the vocabulary's rows; the program's padding
    # rows beyond them are compared with the untouched rows below.
    follower = reference_kimi_linear.Follower(
        {**params0, TABLE: params0[TABLE][:rows]}, settings["sizes"],
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
    gaps = step_gaps(*(pooled(t) for t in (got_params, got_mu)), got_xents,
                     pooled(follower.params), pooled(want_mu), want_xents,
                     pooled(params0), touched)
    print(f"check leaves: first moment worst {gaps['first_moment_leaf']}, "
          f"parameter change worst {gaps['param_change_leaf']}; "
          f"{int(touched.sum())} of {len(touched)} table rows touched",
          flush=True)
    gaps["pairs_over_buffer"] = over_buffer
    limits = cell.traffic["limits"]
    ok = [harness.report_check(name, gaps[name], limits[name])
          for name in ("xent_gap", "first_moment_gap", "param_change_gap",
                       "untouched_rows_moved", "pairs_over_buffer")]
    if not loss_finite:
        print("check final loss: not finite NOT OK", flush=True)
    return all(ok) and bool(loss_finite)
