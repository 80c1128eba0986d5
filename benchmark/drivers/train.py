"""Training driver: the trainer as users run it, fed from files.

Set-up writes the seed's shards, builds the ``Trainer`` and a seeded state,
and starts ONE ``Trainer.fit`` over the normal file pipeline. Its first
dispatches are set-up (compilation, the correctness probe, warm-up); the same
call then runs the measured window, so the window drives the very object the
check looked at. A ``fit`` hook hands each dispatch's loss to a watcher thread,
which stamps when it became ready on the device, so the device queue never
drains for the sake of the clock. The window opens and closes on such a stamp,
and the rate is all the examples completed between the two over the time
between them.

After the window the plain reference follows the first dispatch's steps on
the rows those batches touched (plus a sample of rows they did not) and the
two are compared; nothing of that is timed.
"""

from __future__ import annotations

import os
import queue
import statistics
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark import harness, reference, traffic, xplane
from benchmark.drivers import _program

#: Dispatches before the window opens: compile + probe, then two to settle.
WARMUP_DISPATCHES = 3
#: Table rows the batches do not touch that the reference follows as well
#: (only the L2 term moves them).
UNTOUCHED_SAMPLE = 65536


class Feed:
    """The batch iterable handed to ``fit``: the program's pipeline, with
    ``next()`` timed on the thread that calls it, the first superbatch kept
    for the reference, and an end when the window has closed."""

    def __init__(self, pipeline):
        self.pipeline = pipeline
        self.health = getattr(pipeline, "health", None)
        self.stop = threading.Event()
        self.timing = threading.Event()
        self.first: Optional[Dict[str, np.ndarray]] = None
        self.wait_s = 0.0
        self.records = 0

    def _timed(self, it, n_examples):
        try:
            while not self.stop.is_set():
                t0 = time.perf_counter()
                try:
                    item = next(it)
                except StopIteration:
                    return
                if self.timing.is_set():
                    self.wait_s += time.perf_counter() - t0
                    self.records += n_examples(item)
                yield item
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()

    def iter_superbatches(self, k: int):
        def keep_first(it):
            for rows, m, n_ex in it:
                if self.first is None and m == k:
                    self.first = {key: np.array(v).reshape(
                        (k, v.shape[0] // k) + v.shape[1:])
                        for key, v in rows.items()}
                yield rows, m, n_ex

        return self._timed(keep_first(self.pipeline.iter_superbatches(k)),
                           lambda item: item[2])

    def __iter__(self):
        raise RuntimeError("fit no longer feeds from iter_superbatches: the "
                           "benchmark's feed has to follow it")


class Clock:
    """The window's clock. The ``fit`` hook hands each dispatch's loss to a
    watcher thread, which waits for it on the device and stamps the time it
    became ready: completion times, one per dispatch, that do not depend on
    when the fit thread next looks (``fit`` itself reads the loss back at its
    log cadence, which would make stamps taken on its thread uneven). The
    window opens at the completion of the last warm-up dispatch and closes at
    the first completion ``seconds`` later; the feed then ends."""

    def __init__(self, seconds: float, feed: Feed, probe, compiles,
                 device_trace: Optional[harness.DeviceTrace]):
        self.seconds = seconds
        self.feed = feed
        self.probe = probe
        self.compiles = compiles
        self.device_trace = device_trace
        self.n = 0
        self.first_xent = None
        self.probed = None
        self.stamps: List[float] = []
        self.t_open = self.t_close = None
        self.open_wall_ns = self.close_wall_ns = 0
        self.steps_per_dispatch = 0
        self.error: Optional[BaseException] = None
        self._losses: "queue.Queue" = queue.Queue()
        self._watcher = threading.Thread(target=self._watch, daemon=True,
                                         name="bench-clock")
        self._watcher.start()

    def __call__(self, state, m) -> None:
        """The ``fit`` hook, once per dispatch, after it was enqueued."""
        self.n += 1
        if self.n == 1:
            self.steps_per_dispatch = int(m["steps_done"])
            self.first_xent = m["xent"]
            self.probed = self.probe(state)
        if self.n == 2 and self.device_trace is not None:
            self.device_trace.start()
        self._losses.put(m["loss"])

    def finish(self) -> None:
        self._losses.put(None)
        self._watcher.join()
        if self.error is not None:
            raise self.error

    def _watch(self) -> None:
        import jax

        try:
            done = 0
            while True:
                loss = self._losses.get()
                if loss is None:
                    return
                jax.block_until_ready(loss)
                now, wall = time.perf_counter(), time.time_ns()
                done += 1
                if self.t_open is None:
                    if done == WARMUP_DISPATCHES:
                        self.t_open, self.open_wall_ns = now, wall
                        self.stamps.append(now)
                        self.compiles.open()
                        self.feed.timing.set()
                elif self.t_close is None:
                    self.stamps.append(now)
                    if now >= self.t_open + self.seconds:
                        self.t_close, self.close_wall_ns = now, wall
                        self.compiles.close()
                        self.feed.timing.clear()
                        self.feed.stop.set()
        except BaseException as e:  # surfaced by finish() on the main thread
            self.error = e
            self.feed.stop.set()


def window_rate(stamps: List[float], per_dispatch: float):
    """Examples per second over the whole window (it opens and closes on a
    dispatch's completion, so it holds whole dispatches and every stall
    between them), and the median interval between completions."""
    intervals = np.diff(stamps)
    return (per_dispatch * len(intervals) / (stamps[-1] - stamps[0]),
            float(statistics.median(intervals)))


def make_probe(trainer, table_names, rows_dev):
    """A jitted read of what the check compares, enqueued right after the
    first dispatch (the next one donates the state away): table leaves and
    their first moments at ``rows_dev``, every other leaf whole."""
    import jax
    import jax.numpy as jnp
    import optax

    def read(params, mu, rows):
        def pick(tree):
            flat, _ = jax.tree_util.tree_flatten_with_path(tree)
            return {_program.leaf_name(p): (
                jnp.take(x, rows, axis=0)
                if _program.leaf_name(p) in table_names else jnp.copy(x))
                for p, x in flat}
        return pick(params), pick(mu)

    jitted = jax.jit(read)

    def probe(state):
        mu = optax.tree_utils.tree_get(state.opt_state, "mu")
        return jitted(state.params, mu, rows_dev())
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
            or layout.field_size != flags["field_size"]:
        raise ValueError("the configuration's fields and flags disagree: "
                         f"{layout.feature_size} rows in {layout.field_size} "
                         f"fields vs {flags['feature_size']}/"
                         f"{flags['field_size']}")
    writer = traffic.ShardWriter(os.path.join(work, "shards"), layout, seed,
                                 cell.traffic)
    harness.say(t0, f"shards being written ({writer.examples} examples)")
    wait_for_tasks = _program.import_tasks_beside()
    try:
        devices = acquire()
    except BaseException:
        writer.files()
        raise
    import jax
    import jax.numpy as jnp

    harness.say(t0, f"JAX up on {len(devices)} {devices[0].device_kind}")
    tasks = wait_for_tasks()
    harness.say(t0, "deepfm_tpu.train.tasks imported")
    cfg = _program.make_config(flags)
    trainer = _program.build_trainer(cfg, devices)
    harness.say(t0, "trainer built")
    compiles = harness.CompileCounter()
    spans = harness.Spans(trace)
    state, base_rng = _program.seeded_state(trainer, seed, cell.config)
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
    k = cfg.steps_per_loop
    n_rows = k * cfg.batch_size * cfg.field_size + UNTOUCHED_SAMPLE
    followed: Dict[str, np.ndarray] = {}

    def rows_dev():
        """Rows the reference will hold: those the first dispatch touches
        and a seeded sample of the others, padded to a fixed length."""
        touched = np.unique(feed.first["feat_ids"])
        extra = np.random.default_rng([seed, 0x726F]).integers(
            0, layout.feature_size, UNTOUCHED_SAMPLE)
        rows = np.union1d(touched, extra)
        followed["n_real"] = len(rows)
        followed["touched"] = np.isin(rows, touched, assume_unique=True)
        # Padded with the last row to one length for every seed, so that one
        # compiled probe and one compiled reference step serve them all.
        pad = np.full(n_rows - len(rows), rows[-1], rows.dtype)
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
        cell, trainer, specs, wkw, tables, seed, base_rng, feed.first,
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


def check_first_dispatch(cell, trainer, specs, wkw, tables, seed, base_rng,
                         batches, followed, clock, loss_finite, t0) -> bool:
    """Program vs reference over the first dispatch's steps: the last
    step's log-loss, the first moments (the gradients as Adam got them) and
    the parameters' change, the last two by the worst leaf."""
    import jax.numpy as jnp

    from benchmark import weights

    rows, touched = followed["rows"], followed["touched"]
    n_real = followed["n_real"]
    got_params, got_mu = ({n: np.asarray(v) for n, v in tree.items()}
                          for tree in clock.probed)
    got_xent = float(clock.first_xent)
    settings = _program.reference_settings(trainer)
    params0 = {}
    for name, shape in specs.items():
        params0[name] = np.asarray(weights.leaf_values(
            weights.leaf_salt(seed, name), shape,
            rows=jnp.asarray(rows, jnp.uint32) if name in tables else None,
            xp=jnp, **wkw))
    follower = reference.Follower(params0, rows, **settings)
    cfg = trainer.cfg
    n_shards = trainer.mesh_info.data_size
    xent = float("nan")
    for step in range(batches["label"].shape[0]):
        masks = _program.dropout_masks(
            base_rng, step, n_shards=n_shards,
            local_batch=cfg.batch_size // n_shards,
            widths=cfg.deep_layer_sizes, keep=settings["keep"]) \
            if any(kp < 1.0 for kp in settings["keep"]) else None
        xent = follower.step(batches["feat_ids"][step],
                             batches["feat_vals"][step],
                             batches["label"][step], masks)
    harness.say(t0, f"reference followed {follower.count} steps on "
                    f"{n_real} rows")

    gaps = reference.dispatch_gaps(got_params, got_mu, got_xent, follower,
                                   xent, params0, tables, n_real, touched)
    print(f"check leaves: first moment worst {gaps['first_moment_leaf']}, "
          f"parameter change worst {gaps['param_change_leaf']}; program xent "
          f"{got_xent:.6f} reference {xent:.6f}", flush=True)
    limits = cell.traffic["limits"]
    ok = [harness.report_check(name, gaps[name], limits[name])
          for name in ("xent_gap", "first_moment_gap", "param_change_gap")]
    if not loss_finite:
        print("check final loss: not finite NOT OK", flush=True)
    return all(ok) and bool(loss_finite)
