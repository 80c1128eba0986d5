"""Training driver for ``--model sdar_moe``: ``drivers/train.py``'s run with
this model's traffic, reference and counters.

The window, the clock, the feed and the rate are ``drivers/train``'s own, by
import: one ``Trainer.fit`` over the normal file pipeline, whose first
dispatches are set-up and which then runs the measured window. An example is
one sequence, a dispatch one step. What differs: the shards
(``traffic_sequences``: the tokens ride the record's history list), the
seeded state (``_program_sdar_moe``: gains near one, the model's counts
beside the parameters), what the probes read (the parameters and Adam's
first moment are 4.4 GB here, beside a step that needs the rest of the chip:
every leaf is read on a strided sample of its elements, the token table
whole, and pulled to the host by a thread of its own, so that the fit never
waits for it) and the reference (``reference_sdar_moe``), which follows the
first ``CHECK_STEPS`` steps after the window, on a device the trainer's
state has left, one layer at a time, at the timed sizes.

Compared outside the window, on the run's first ``CHECK_STEPS`` steps (all
of them set-up): each step's loss; by the worst leaf, Adam's first moment
after the first step (0.1 of the gradient as Adam got it) and the
parameters' change over all of the steps (by the second step Adam's second
moment and its decays decide the size of every element's move), each as a
2-norm of the difference over the reference's own; the rows of the token
table no batch touched, which Adam leaves exactly where they were (zero
moments: a zero update); the noise the steps drew, against the objective's
own statement of it (``reference_sdar_moe.noise_z``, which takes nothing of
the program's but the arrays) and the program's own count of masked
positions; and the pairs the expert layer's buffer could not hold, over the
whole run, which have to be none.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import numpy as np

from benchmark import harness, reference_sdar_moe, traffic_sequences, xplane
from benchmark.drivers import _program, _program_sdar_moe
from benchmark.drivers.train import (WARMUP_DISPATCHES, Clock, Feed,
                                     window_rate)

#: Steps the reference follows: the run's first dispatches, one step each
#: and every one of them set-up.
CHECK_STEPS = 3
#: Dispatches ahead of the base clock's own warm-up, so that the last probe
#: (after dispatch ``CHECK_STEPS``) has run a dispatch before the window.
EXTRA_WARMUP = max(CHECK_STEPS + 1 - WARMUP_DISPATCHES, 0)
#: Elements of a leaf the probe reads (about; the token table is read whole).
SAMPLE = 1 << 20
TABLE = "tok_emb"


def sample_stride(n: int) -> int:
    """An odd stride that takes about ``SAMPLE`` of ``n`` elements."""
    return max(1, n // SAMPLE) | 1


def sampled(name: str, x):
    """What is compared of leaf ``name``: the table whole, any other leaf on
    a stride over its elements (every layer, head and expert is in it)."""
    if name == TABLE:
        return x
    flat = x.reshape(-1)
    return flat[::sample_stride(flat.shape[0])]


def make_probe(trainer):
    """``probe(state, params=False)``: a jitted read of what the check
    compares of Adam's first moment (or of the parameters), enqueued right
    after a dispatch (the next one donates the state away) and pulled to the
    host by a thread, so that the fit goes on and nothing of it stays on
    the device; the result is a future."""
    import jax
    import jax.numpy as jnp
    import optax

    def pick(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {_program.leaf_name(p): jnp.copy(sampled(
            _program.leaf_name(p), x)) for p, x in flat}

    read = jax.jit(pick)    # the two trees are one shape: one compilation
    puller = ThreadPoolExecutor(1, thread_name_prefix="bench-probe")

    def probe(state, params=False):
        tree = state.params if params else optax.tree_utils.tree_get(
            state.opt_state, "mu")
        return puller.submit(jax.tree.map, np.asarray, read(tree))
    return probe


class StepFeed(Feed):
    """``drivers/train``'s feed for a trainer that dispatches one step at a
    time (``steps_per_loop`` 1: a step here is two thirds of a second, and a
    dispatch of several would leave the window too few completions to close
    on): ``fit`` then iterates its source batch by batch, and this is that
    iteration, timed like the superbatches'; ``first`` keeps the first
    ``CHECK_STEPS`` batches, stacked."""

    def __iter__(self):
        def keep_first(it):
            kept = []
            for batch in it:
                if len(kept) < CHECK_STEPS:
                    kept.append({key: np.array(v)
                                 for key, v in batch.items()})
                    if len(kept) == CHECK_STEPS:
                        self.first = {key: np.stack([b[key] for b in kept])
                                      for key in kept[0]}
                yield batch

        return self._timed(keep_first(iter(self.pipeline)),
                           lambda batch: batch["label"].shape[0])


class StepClock(Clock):
    """``drivers/train``'s clock, which here keeps the loss of each of the
    first ``CHECK_STEPS`` dispatches and probes after the first of them
    (Adam's first moment) and after the last (the parameters). The last
    probe runs on the device after dispatch ``CHECK_STEPS``, so the base
    clock, whose window opens on its ``WARMUP_DISPATCHES``-th completion,
    is handed the dispatches from ``1 + EXTRA_WARMUP`` on: the window opens
    a whole dispatch after the probe and holds nothing of the check."""

    def __init__(self, seconds, feed, probe, compiles, device_trace):
        super().__init__(seconds, feed, lambda state: None, compiles,
                         device_trace)
        self.read = probe
        self.seen = 0
        self.xents: List[object] = []
        self.first_mu = self.last_params = None

    def __call__(self, state, m) -> None:
        self.seen += 1
        if self.seen <= CHECK_STEPS:
            self.xents.append(m["xent"])
        if self.seen == 1:
            self.first_mu = self.read(state)
        if self.seen == CHECK_STEPS:
            self.last_params = self.read(state, params=True)
        if self.seen > EXTRA_WARMUP:
            super().__call__(state, m)


class Counts:
    """A ``fit`` hook: keeps each dispatch's counts (the last step's; device
    scalars of the step's own output, read after the window)."""

    def __init__(self):
        self.dispatches: List[Dict[str, object]] = []

    def __call__(self, state, m) -> None:
        self.dispatches.append({k: v for k, v in m.items()
                                if k.startswith(("moe_", "masked_"))})

    def read(self, first: int, n: int) -> Dict[str, np.ndarray]:
        """{count: its values over dispatches ``first .. first + n - 1``}."""
        rows = self.dispatches[first:first + n]
        return {k: np.asarray([float(r[k]) for r in rows])
                for k in (rows[0] if rows else {})}


def run(cell: harness.Cell, acquire, seed: int, seconds: float,
        trace: bool, work: str, t_start: Optional[float] = None) -> dict:
    t0 = t_start if t_start is not None else time.time()
    t_perf0 = time.perf_counter() - (time.time() - t0)
    if trace:
        seconds = min(seconds, harness.MAX_TRACE_SECONDS)
    flags = dict(cell.config["flags"])
    tr = cell.traffic
    if (flags["history_max_len"], flags["batch_size"],
            flags["diffusion_block"], flags["diffusion_t_min"]) != (
            tr["sequence_length"], tr["sequences_per_step"],
            tr["block_length"], tr["t_min"]) \
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
    settings = _program_sdar_moe.reference_settings(trainer)
    harness.say(t0, "trainer built")
    compiles = harness.CompileCounter()
    spans = harness.Spans(trace)
    state, base_rng = _program_sdar_moe.seeded_state(trainer, seed,
                                                     cell.config)
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
    counts = Counts()
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
    shares = cfg.decoder_layers * cfg.moe_experts_held
    load = {"moe_pairs_held_per_step": float(seen["moe_pairs_held"].mean()),
            "moe_expert_load_max_over_mean": float(np.mean(
                seen["moe_expert_load_max"] * shares
                / seen["moe_pairs_held"])),
            "masked_positions_per_step": float(
                seen["masked_positions"].mean()),
            # how near the buffer came to running over, in the whole run
            "moe_layer_pairs_max_over_buffer": float(
                counts.read(0, len(counts.dispatches))[
                    "moe_layer_pairs_max"].max() / cfg.moe_pair_capacity)}
    print("counts (the window's dispatches, each one's last step): "
          + ", ".join(f"{k} {v:.6g}" for k, v in load.items())
          + f"; pairs over the buffer in the whole run {over_buffer}",
          flush=True)

    # ---- correctness: the reference follows the first steps -------------
    correct = check_first_steps(
        cell, trainer, settings, seed, base_rng, feed.first, clock, counts,
        over_buffer, np.isfinite(final_loss), t0)

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
                      "input_records": feed.records,
                      "moe_pairs_over_buffer": over_buffer, **load})
    if compiles.count:
        print(f"check compiles_in_window: {compiles.count} (limit 0) NOT OK",
              flush=True)
        correct = False
    return harness.result_line(cell, correct=correct, attempted=steps,
                               failed=0 if np.isfinite(final_loss) else steps,
                               end_to_end=end_to_end, ctx=ctx, device=device)


def check_first_steps(cell, trainer, settings, seed, base_rng, batches,
                      clock, counts, over_buffer, loss_finite, t0) -> bool:
    """Program vs reference over the run's first ``CHECK_STEPS`` steps."""
    import jax.numpy as jnp

    from benchmark import weights

    got_mu, got_params = clock.first_mu.result(), clock.last_params.result()
    got_xents = [float(x) for x in clock.xents]
    specs = _program_sdar_moe.leaf_specs(trainer)
    wkw = _program_sdar_moe.weight_kwargs(cell.config, trainer)
    cfg = trainer.cfg
    rows = int(cfg.feature_size)
    salts = {name: weights.leaf_salt(seed, name) for name in specs}
    params0 = {name: np.asarray(_program_sdar_moe.seeded_leaf(
        salts, name, shape, wkw, xp=jnp)) for name, shape in specs.items()}
    # The reference's table is the vocabulary's rows; the program's padding
    # rows beyond them are compared with the untouched rows below.
    follower = reference_sdar_moe.Follower(
        {**params0, TABLE: params0[TABLE][:rows]}, settings["sizes"],
        settings["learning_rate"])
    tokens = batches["hist_ids"]
    want_xents, noise, want_mu = [], [], None
    for step in range(CHECK_STEPS):
        noisy, t = _program_sdar_moe.step_noise(trainer, base_rng, step,
                                                tokens[step])
        noise.append((noisy, t))
        want_xents.append(follower.step(noisy, tokens[step], t))
        if want_mu is None:     # after the first step: 0.1 of its gradient
            want_mu = {n: np.array(sampled(n, v))
                       for n, v in follower.mu.items()}
        harness.say(t0, f"reference step {step + 1}: loss "
                        f"{want_xents[-1]:.6f}, program {got_xents[step]:.6f}")

    touched = np.zeros(params0[TABLE].shape[0], bool)
    touched[np.unique(tokens)] = True
    touched[trainer.model.mask_id] = True
    gaps = step_gaps(got_params, got_mu, got_xents, follower.params, want_mu,
                     want_xents, params0, touched)
    print(f"check leaves: first moment worst {gaps['first_moment_leaf']}, "
          f"parameter change worst {gaps['param_change_leaf']}; "
          f"{int(touched.sum())} of {len(touched)} table rows touched",
          flush=True)
    # The noise, against the objective's statement of it and nothing of the
    # program's: every block's masked share with its own t, t uniform.
    noisy = np.stack([n for n, _ in noise])
    gaps["noise_z"] = reference_sdar_moe.noise_z(
        noisy, tokens, np.stack([t for _, t in noise]),
        cfg.diffusion_block, cfg.diffusion_t_min, trainer.model.mask_id)
    said = counts.read(0, CHECK_STEPS)["masked_positions"]
    gaps["masked_count_gap"] = float(np.abs(
        said - (noisy != tokens).sum(axis=(1, 2))).sum())
    gaps["pairs_over_buffer"] = over_buffer
    limits = cell.traffic["limits"]
    ok = [harness.report_check(name, gaps[name], limits[name])
          for name in ("xent_gap", "first_moment_gap", "param_change_gap",
                       "untouched_rows_moved", "noise_z", "masked_count_gap",
                       "pairs_over_buffer")]
    if not loss_finite:
        print("check final loss: not finite NOT OK", flush=True)
    return all(ok) and bool(loss_finite)


def step_gaps(got_params: dict, got_mu: dict, got_xents: List[float],
              want_params: dict, want_mu: dict, want_xents: List[float],
              params0: dict, touched: np.ndarray) -> dict:
    """The numbers the cell is judged by, for the first steps: the largest
    gap in a step's loss; by the worst leaf the gaps in Adam's first moment
    after the first step (``want_mu``: what the probe samples of it) and in
    the parameters' change over all the steps (``want_params``: whole), on
    what the probe sampled (the table: its touched rows); and how many
    elements of the table's other rows the program moved or gave a
    moment."""
    rows = want_params[TABLE].shape[0]

    def cut(tree, minus=None):
        out = {}
        for name, x in tree.items():
            x = np.asarray(x, np.float64)
            if minus is not None:
                x = x - minus[name][: x.shape[0]] if name == TABLE \
                    else x - sampled(name, minus[name])
            out[name] = x[:rows][touched[:rows]] if name == TABLE else x
        return out

    mus, want_mus = cut(got_mu), cut(want_mu)
    moves = cut(got_params, params0)
    want_moves = cut({n: v if n == TABLE else sampled(n, v)
                      for n, v in want_params.items()}, params0)
    for name in sorted(mus):
        print(f"leaf {name}: first moment gap "
              f"{reference_sdar_moe.leaf_gap(mus[name], want_mus[name]):.4g}"
              f", parameter change gap "
              f"{reference_sdar_moe.leaf_gap(moves[name], want_moves[name]):.4g}",
              flush=True)
    m_gap, m_leaf = reference_sdar_moe.worst_leaf_gap(mus, want_mus)
    d_gap, d_leaf = reference_sdar_moe.worst_leaf_gap(moves, want_moves)
    still = ~touched
    moved = int(np.count_nonzero(got_mu[TABLE][still])) + int(
        np.count_nonzero(np.asarray(got_params[TABLE])[still]
                         != params0[TABLE][still]))
    return {"xent_gap": max(abs(float(g) - float(w))
                            for g, w in zip(got_xents, want_xents)),
            "first_moment_gap": m_gap, "first_moment_leaf": m_leaf,
            "param_change_gap": d_gap, "param_change_leaf": d_leaf,
            "untouched_rows_moved": moved}
