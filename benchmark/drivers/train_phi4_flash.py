"""Training driver for ``--model phi4_flash``: ``drivers/train_sdar_moe``'s
run with this model's seeding, reference and counters.

Everything that names no model's module is imported: the feed, the clock,
the probes, the strided sample and the gaps from ``train_sdar_moe``
(``StepFeed``, ``StepClock``, ``Counts``, ``make_probe``, ``sampled``,
``step_gaps``). Written again, as in the other decoder drivers and for their
reason (PERF.md section 7 row 18: the fold is a ``benchmark`` PR's), is what
names a model's reference and program by module: the seeded state
(``_program_phi4_flash``), the run's order and the check
(``reference_phi4_flash``).

What this model changes in the run and the check: there are no experts, so
no pair is counted and nothing is judged apart from the routed leaves
(``first_moment_gap`` is over every judged leaf); the table is tied, so
every row of it has a gradient every step and ``untouched_rows_moved`` has
nothing to say (as the LFM2 cell); the keys' biases (``UNJUDGED``) are left
out of the leaves' gaps: a bias on every key of a softmax row shifts the
row's scores alike and moves nothing, so its gradient is zero by the
mathematics and what the program and the reference hold of it is each
one's rounding. The parameters' change is judged with each mixer's vectors
pooled into one leaf (``pooled``), as ``train_kimi_linear.pooled`` pools a
KDA layer's decay vectors and for its reason, and Adam's first moment with
an attention layer's vectors pooled (a layer's lambda vectors are one
scalar's), every other leaf by itself. The counts line says the scans' most negative whole-chunk
log-decay (``mamba_chunk_log_decay_min``).
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from benchmark import harness, reference_phi4_flash, traffic_sequences, xplane
from benchmark.reference_sdar_moe import leaf_gap, worst_leaf_gap
from benchmark.drivers import _program, _program_phi4_flash
from benchmark.drivers._program_phi4_flash import DECAY_MIN, leaf_of
from benchmark.drivers._program_sdar_moe import leaf_specs
from benchmark.drivers.train import window_rate
from benchmark.drivers.train_sdar_moe import (CHECK_STEPS, TABLE, Counts,
                                              StepClock, StepFeed,
                                              make_probe, sampled, step_gaps)

#: Leaves whose gradient is zero by the mathematics (the module's docstring).
UNJUDGED = ("bk",)


class StepCounts(Counts):
    """``Counts`` that keeps the scans' count (``mamba_*``)."""

    def __call__(self, state, m) -> None:
        self.dispatches.append({k: v for k, v in m.items()
                                if k.startswith("mamba_")})


#: A mixer's vectors whose change over the first steps is judged together,
#: by the pooled leaf's name.
POOLS = {"attn_vectors": ("bq", "bv", "bo", "lambda_q1", "lambda_k1",
                          "lambda_q2", "lambda_k2", "sub_norm"),
         "mamba_vectors": ("mamba_conv_b", "mamba_dt_bias", "mamba_d")}


def table_change_gap(got_params, want_params, params0, rows) -> float:
    """The token table's change gap over its ``rows`` real rows."""
    start = np.asarray(params0[TABLE][:rows], np.float64)
    return leaf_gap(np.asarray(got_params[TABLE][:rows], np.float64) - start,
                    np.asarray(want_params[TABLE][:rows], np.float64) - start)


def judged(tree: dict) -> dict:
    return {n: v for n, v in tree.items() if leaf_of(n) not in UNJUDGED}


def pooled(tree: dict, pools=tuple(POOLS)) -> dict:
    """``tree`` ({leaf name: array}) with each mixer's vectors (``pools`` of
    ``POOLS``) as one flat leaf, ``layers.<i>.attn_vectors`` /
    ``layers.<i>.mamba_vectors``. Two kinds of leaf cannot be judged alone by
    a relative norm. A layer's four lambda vectors are one scalar's: their
    gradients are ``dL/dlambda`` times each other, a sum over every position
    and head that may cancel, so the leaf's first moment read 0.0007 to 0.06
    on 24 sound seeds and 0.42 on a 25th (chip, PR 44), and under Adam, where
    that scalar passes near zero in the second or third step, 64 elements go
    the other way together by a rounding (their change read 0.60 to 4.2).
    The step's bias moves by less than float32 holds of it: its gradient is
    near 1e-9, under Adam's epsilon, so a step moves it by 1e-5 g / 1e-8,
    about 8e-7, on values of 2 to 7 whose last bit is 2.4 to 4.8e-7 (its
    change read 0.25 to 3.35 where its first moment read 0.005 to 0.009).
    The parameters' change is judged on both pools, the first moment on the
    attention's alone (``ATTN_POOL``): the step's bias's gradient is
    resolved, its update is not."""
    out = dict(tree)
    for pool in pools:
        leaves = POOLS[pool]
        for name in tree:
            if leaf_of(name) == leaves[0]:
                layer = name.rsplit(".", 1)[0]
                out[f"{layer}.{pool}"] = np.concatenate([
                    np.asarray(out.pop(f"{layer}.{n}")).reshape(-1)
                    for n in leaves if f"{layer}.{n}" in out])
    return out


ATTN_POOL = ("attn_vectors",)


def pooled_moment_gap(got_mu: dict, want_mu: dict, rows: int):
    """(gap, leaf) of Adam's first moment after the first step, worst leaf
    with the attention layers' vectors pooled (the token table over its
    ``rows`` real rows)."""
    def cut(tree):
        return pooled({n: np.asarray(v[:rows] if n == TABLE else v,
                                     np.float64) for n, v in tree.items()},
                      ATTN_POOL)
    return worst_leaf_gap(cut(got_mu), cut(want_mu))


def pooled_change_gap(got_params: dict, want_params: dict, params0: dict):
    """(gap, leaf) of the parameters' change over the first steps, worst
    pooled leaf but the token table, which ``step_gaps`` judges (on what the
    probe sampled: ``got_params``)."""
    def change(tree, is_sampled):
        return pooled({n: np.asarray(v if is_sampled else sampled(n, v),
                                     np.float64)
                       - np.asarray(sampled(n, params0[n]), np.float64)
                       for n, v in tree.items() if n != TABLE})
    return worst_leaf_gap(change(got_params, True),
                          change({n: want_params[n] for n in got_params},
                                 False))


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
    settings = _program_phi4_flash.reference_settings(trainer)
    harness.say(t0, "trainer built")
    compiles = harness.CompileCounter()
    spans = harness.Spans(trace)
    state, _ = _program_phi4_flash.seeded_state(trainer, seed, cell.config)
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
    whole = counts.read(0, len(counts.dispatches))
    # the most negative whole-chunk log-decay a scan held, whole run
    load = {DECAY_MIN: float(whole[DECAY_MIN].min())}
    print("counts (the whole run's dispatches, each one's last step): "
          + ", ".join(f"{k} {v:.6g}" for k, v in load.items()), flush=True)

    # ---- correctness: the reference follows the first steps -------------
    correct = check_first_steps(
        cell, trainer, settings, seed, feed.first, clock,
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
                      "input_records": feed.records, **load})
    if compiles.count:
        print(f"check compiles_in_window: {compiles.count} (limit 0) NOT OK",
              flush=True)
        correct = False
    return harness.result_line(cell, correct=correct, attempted=steps,
                               failed=0 if np.isfinite(final_loss) else steps,
                               end_to_end=end_to_end, ctx=ctx, device=device)


def check_first_steps(cell, trainer, settings, seed, batches, clock,
                      loss_finite, t0) -> bool:
    """Program vs reference over the run's first ``CHECK_STEPS`` steps."""
    import jax.numpy as jnp

    from benchmark import weights

    got_mu, got_params = clock.first_mu.result(), clock.last_params.result()
    got_xents = [float(x) for x in clock.xents]
    specs = leaf_specs(trainer)
    wkw = _program.weight_kwargs(cell.config, trainer)
    rows = int(trainer.cfg.feature_size)
    salts = {name: weights.leaf_salt(seed, name) for name in specs}
    params0 = {name: np.asarray(_program_phi4_flash.seeded_leaf(
        salts, name, shape, wkw, xp=jnp)) for name, shape in specs.items()}
    # The reference's table is the vocabulary's rows (the program's padding
    # rows beyond them, if any, never move).
    follower = reference_phi4_flash.Follower(
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

    # tied: every row of the vocabulary is touched, a token's or the head's
    touched = np.zeros(params0[TABLE].shape[0], bool)
    touched[:rows] = True
    gaps = step_gaps(judged(got_params), judged(got_mu), got_xents,
                     judged(follower.params), judged(want_mu), want_xents,
                     judged(params0), touched)
    unpooled = (gaps["first_moment_gap"], gaps["first_moment_leaf"],
                gaps["param_change_gap"], gaps["param_change_leaf"])
    gaps["first_moment_gap"], gaps["first_moment_leaf"] = pooled_moment_gap(
        judged(got_mu), judged(want_mu), rows)
    # the table's change as ``step_gaps`` read it, every other leaf's with
    # the mixers' vectors pooled
    table_gap = table_change_gap(got_params, follower.params, params0, rows)
    gaps["param_change_gap"], gaps["param_change_leaf"] = max(
        pooled_change_gap(judged(got_params), judged(follower.params),
                          params0), (table_gap, TABLE))
    print(f"check leaves: first moment worst {gaps['first_moment_leaf']} "
          f"(attention's vectors pooled; leaf by leaf {unpooled[0]:.4g} "
          f"{unpooled[1]}), parameter change worst "
          f"{gaps['param_change_leaf']} (the mixers' vectors pooled; leaf by "
          f"leaf {unpooled[2]:.4g} {unpooled[3]}); "
          f"{len(np.unique(tokens))} of {rows} table rows named by a token",
          flush=True)
    limits = cell.traffic["limits"]
    ok = [harness.report_check(name, gaps[name], limits[name])
          for name in ("xent_gap", "first_moment_gap", "param_change_gap")]
    if not loss_finite:
        print("check final loss: not finite NOT OK", flush=True)
    return all(ok) and bool(loss_finite)
