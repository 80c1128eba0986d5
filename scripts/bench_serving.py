#!/usr/bin/env python
"""Serving scale-out sweep: QPS/p50/p99 per replica count and in-flight
depth -> ``SERVING_r0N.json``. With ``--flood``, the overload sweep
instead: open-loop Zipf flood past saturation -> ``FLOOD_r0N.json``.
With ``--fastpath``, the fast-path A/B flood (result cache + in-flight
coalescing off vs on over identical traffic) -> ``SERVING_r0N.json``.

The measurement half of ROADMAP item 1's serving receipt (the correctness
half is ``scripts/serving_drill.py``, re-run here so the committed report
carries BOTH):

  1. **Sweep.** ``bench.serving_series`` over replicas {1, 2, 4} x
     in-flight depth {1, 2} against ONE pre-exported artifact pair, same
     closed-loop synthetic load for every point. ``inflight=1`` on one
     replica is the PR 7-style strict flush-then-refill engine — the
     within-report baseline the pipelined points are read against.
  2. **Drill gates.** The 2-replica pipelined drill re-asserts the PR 12
     serving gates (zero dropped/failed/overloaded across >= 3 staggered
     swaps, blackout <= 100 ms PER replica); its report is embedded.
  3. **Acceptance.** The headline point (1 replica, pipelined depth 2)
     must beat the SERVING_r01 baseline: p99 below 236 ms at >= 185 QPS.
  4. **Scaling honesty.** On a host with fewer cores than replicas, the
     replica axis time-slices the same core(s), so the report REFUSES a
     scaling-efficiency claim (``scaling_efficiency: null`` + reason, the
     SCALING_r01.json rule) while still publishing the
     measured per-point QPS/p99 curve.

Run on CPU:  JAX_PLATFORMS=cpu python scripts/bench_serving.py
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
import serving_drill

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REPLICA_COUNTS = (1, 2, 4)
INFLIGHT_DEPTHS = (1, 2)
# SERVING_r01.json — the pre-pipelining engine on this host: the sweep's
# headline point must beat its p99 at equal-or-better QPS.
BASELINE_P99_MS = 236.0
BASELINE_QPS = 185.0


def say(msg):
    print(f"[bench_serving] {msg}", flush=True)


def _next_report_path(prefix="SERVING"):
    n = 1
    while os.path.exists(
            os.path.join(_REPO_ROOT, f"{prefix}_r{n:02d}.json")):
        n += 1
    return os.path.join(_REPO_ROOT, f"{prefix}_r{n:02d}.json")


def run_sweep(report_path=None, run_secs=3.0, verbose=True):
    global say
    if not verbose:
        say = lambda msg: None  # noqa: E731
    t_start = time.time()
    workdir = tempfile.mkdtemp(prefix="bench_serving_sweep_")
    try:
        say("exporting artifacts once for the whole sweep")
        bench.export_serving_artifacts(workdir)
        series = []
        for replicas in REPLICA_COUNTS:
            for inflight in INFLIGHT_DEPTHS:
                say(f"point replicas={replicas} inflight={inflight}")
                point = bench.serving_series(
                    replicas=replicas, inflight=inflight,
                    run_secs=run_secs, artifact_dir=workdir)
                say(f"  p50={point['serving_p50_ms']:.2f}ms "
                    f"p99={point['serving_p99_ms']:.2f}ms "
                    f"qps={point['serving_qps']}")
                series.append(point)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    say("re-asserting drill gates (2 replicas, pipelined)")
    drill = serving_drill.run_drill(
        report_path=os.path.join(tempfile.mkdtemp(prefix="bench_drill_"),
                                 "drill.json"),
        verbose=verbose, replicas=2)

    headline = next(p for p in series
                    if p["replicas"] == 1 and p["serve_inflight"] == 2)
    pr7_style = next(p for p in series
                     if p["replicas"] == 1 and p["serve_inflight"] == 1)
    assert headline["serving_p99_ms"] < BASELINE_P99_MS, (
        f"headline p99 {headline['serving_p99_ms']:.1f}ms not below the "
        f"SERVING_r01 baseline {BASELINE_P99_MS}ms")
    assert headline["serving_qps"] >= BASELINE_QPS, (
        f"headline QPS {headline['serving_qps']} below the SERVING_r01 "
        f"baseline {BASELINE_QPS}")
    for point in series:
        assert point["serving_failed"] == 0, point

    host_cpus = os.cpu_count() or 1
    if host_cpus < max(REPLICA_COUNTS):
        scaling_efficiency = None
        scaling_reason = (
            f"refused: {max(REPLICA_COUNTS)} replicas time-slice "
            f"{host_cpus} host core(s), so aggregate QPS measures "
            "scheduler interleaving, not replica scaling; the per-point "
            "curve is published for latency/correctness reading only "
            "(scaling-efficiency refusal rule)")
    else:
        base_qps = next(p["serving_qps"] for p in series
                        if p["replicas"] == 1 and p["serve_inflight"] == 2)
        top = max((p for p in series if p["serve_inflight"] == 2),
                  key=lambda p: p["replicas"])
        scaling_efficiency = round(
            top["serving_qps"] / (top["replicas"] * base_qps), 3)
        scaling_reason = "aggregate QPS at max replicas over replicas x " \
                         "single-replica QPS (pipelined points)"

    report = {
        "bench": "serving_scaleout",
        "ok": True,
        "baseline": {"source": "SERVING_r01.json",
                     "serving_p99_ms": BASELINE_P99_MS,
                     "serving_qps": BASELINE_QPS},
        "headline": headline,
        "pr7_style_point": pr7_style,
        "series": series,
        "drill": drill,
        "replica_counts": list(REPLICA_COUNTS),
        "inflight_depths": list(INFLIGHT_DEPTHS),
        "host_cpu_count": host_cpus,
        "scaling_efficiency": scaling_efficiency,
        "scaling_efficiency_reason": scaling_reason,
        "load_kind": "synthetic-closed-loop",
        "device_kind": series[0]["device_kind"],
        "elapsed_s": round(time.time() - t_start, 1),
    }
    path = report_path or _next_report_path()
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    say(f"PASS -> {path}")
    return report


FLOOD_MULTS = (0.5, 1.0, 2.0, 4.0)


def run_flood(report_path=None, run_secs=2.5, users=1_000_000,
              verbose=True):
    """Overload sweep -> ``FLOOD_r0N.json``: the p99-vs-offered-QPS and
    goodput curves from half saturation to 4x past it, over a >= 1M-user
    Zipf population with per-user history continuity, plus the drilled
    degradation-ladder run (``production_drill.run_overload_drill``)
    embedded so the committed report carries BOTH the curve and the
    bit-replayable chaos receipt.

    Gates: every point closes the accounting identity (offered ==
    completed + sheds + overloads + timeouts + failed — zero hangs, zero
    silent drops); at 4x saturation the fleet must SHED (admission
    refusals > 0) while still completing in-SLO work (goodput > 0) —
    degrading, not collapsing; the embedded drill must show the ladder
    engaging under the injected ``executor_slow`` and fully recovering.
    """
    global say
    if not verbose:
        say = lambda msg: None  # noqa: E731
    import production_drill

    t_start = time.time()
    workdir = tempfile.mkdtemp(prefix="bench_flood_")
    try:
        say("exporting artifacts once for the whole flood sweep")
        bench.export_serving_artifacts(workdir)
        say(f"flood sweep at {FLOOD_MULTS} x measured saturation, "
            f"{users} Zipf users")
        flood = bench.overload_series(
            run_secs=run_secs, mults=FLOOD_MULTS, users=users,
            artifact_dir=workdir)
        for p in flood["points"]:
            say(f"  {p['offered_mult']}x offered={p['offered_qps_target']} "
                f"goodput={p['goodput_qps']} p99={p['p99_ms']}ms "
                f"sheds={p['sheds']} overloads={p['overloads']} "
                f"timeouts={p['timeouts']}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    say("overload drill (degradation ladder under executor_slow chaos)")
    drill_dir = tempfile.mkdtemp(prefix="bench_flood_drill_")
    try:
        drill = production_drill.run_overload_drill(
            drill_dir, verbose=verbose)
    finally:
        shutil.rmtree(drill_dir, ignore_errors=True)

    for p in flood["points"]:
        assert p["accounting_ok"], (
            f"accounting identity broken at {p['offered_mult']}x: {p}")
    top = max(flood["points"], key=lambda p: p["offered_mult"])
    assert top["sheds"] + top["overloads"] > 0, (
        f"no load shedding at {top['offered_mult']}x saturation: {top}")
    assert top["goodput_qps"] > 0 and top["completed"] > 0, (
        f"fleet collapsed at {top['offered_mult']}x saturation: {top}")
    assert drill["ladder_engaged"], drill
    assert drill["recovered"], drill

    report = {
        "bench": "serving_flood",
        "ok": True,
        "flood": flood,
        "overload_drill": drill,
        "offered_mults": list(FLOOD_MULTS),
        "host_cpu_count": os.cpu_count() or 1,
        "load_kind": flood["load_kind"],
        "device_kind": flood["device_kind"],
        "elapsed_s": round(time.time() - t_start, 1),
    }
    path = report_path or _next_report_path("FLOOD")
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    say(f"PASS -> {path}")
    return report


def run_fastpath(report_path=None, run_secs=2.5, users=1_000_000,
                 repeat_p=0.5, cache_rows=4096, verbose=True):
    """Serving fast-path A/B flood -> ``SERVING_r0N.json``: the same
    open-loop Zipf flood (0.5/1/2/4x measured saturation, per-user
    byte-identical repeats at ``repeat_p``) served twice over ONE artifact
    and ONE measured saturation — result cache + coalescing OFF vs ON —
    so the p99/goodput deltas are attributable to the fast path alone.

    Gates: the accounting identity (now offered == completed + coalesced +
    sheds + overloads + timeouts + failed) closes at EVERY point of BOTH
    arms; the ON arm sees real cache traffic (hits > 0 at every point);
    and the headline — p99 at 2x saturation — improves by >= 25% with the
    fast path on.
    """
    global say
    if not verbose:
        say = lambda msg: None  # noqa: E731
    t_start = time.time()
    say(f"fast-path A/B flood at {FLOOD_MULTS} x saturation, "
        f"{users} Zipf users, repeat_p={repeat_p}")
    fast = bench.serving_fastpath_series(
        run_secs=run_secs, mults=FLOOD_MULTS, users=users,
        repeat_p=repeat_p, cache_rows=cache_rows)
    for c in fast["comparison"]:
        say(f"  {c['offered_mult']}x p99 off={c['p99_ms_off']}ms "
            f"on={c['p99_ms_on']}ms ({c['p99_improvement_pct']}%) "
            f"hit_rate={c['cache_hit_rate_on']} "
            f"coalesce_rate={c['coalesce_rate_on']}")

    for arm in ("off", "on"):
        for p in fast[arm]["points"]:
            assert p["accounting_ok"], (
                f"accounting identity broken ({arm} arm, "
                f"{p['offered_mult']}x): {p}")
    for p in fast["on"]["points"]:
        assert p["cache_hits"] > 0, (
            f"no cache hits at {p['offered_mult']}x with the fast path "
            f"on: {p}")
    headline = next(c for c in fast["comparison"]
                    if c["offered_mult"] == 2.0)
    assert headline["p99_improvement_pct"] is not None and \
        headline["p99_improvement_pct"] >= 25.0, (
        f"p99 at 2x saturation improved only "
        f"{headline['p99_improvement_pct']}% with the fast path on "
        f"(need >= 25%): {headline}")

    report = {
        "bench": "serving_fastpath",
        "ok": True,
        "headline": headline,
        "fastpath": fast,
        "offered_mults": list(FLOOD_MULTS),
        "host_cpu_count": os.cpu_count() or 1,
        "load_kind": fast["off"]["load_kind"],
        "device_kind": fast["off"]["device_kind"],
        "elapsed_s": round(time.time() - t_start, 1),
    }
    path = report_path or _next_report_path()
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    say(f"PASS -> {path}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--report", default=None,
                    help="report path (default: SERVING_r0N.json or "
                         "FLOOD_r0N.json with --flood, next free N)")
    ap.add_argument("--run_secs", type=float, default=3.0,
                    help="load duration per sweep point")
    ap.add_argument("--flood", action="store_true",
                    help="run the overload flood sweep -> FLOOD_r0N.json "
                         "instead of the scale-out sweep")
    ap.add_argument("--fastpath", action="store_true",
                    help="run the fast-path A/B flood (cache+coalescing "
                         "off vs on) -> SERVING_r0N.json")
    ap.add_argument("--users", type=int, default=1_000_000,
                    help="Zipf user-population size for --flood/--fastpath")
    ap.add_argument("--repeat_p", type=float, default=0.5,
                    help="per-user byte-identical repeat probability for "
                         "--fastpath")
    args = ap.parse_args()
    if args.fastpath:
        run_fastpath(args.report, run_secs=args.run_secs, users=args.users,
                     repeat_p=args.repeat_p)
    elif args.flood:
        run_flood(args.report, run_secs=args.run_secs, users=args.users)
    else:
        run_sweep(args.report, run_secs=args.run_secs)


if __name__ == "__main__":
    main()
