#!/usr/bin/env python3
"""One run of one cell that also reports where set-up went: the six candidate
metrics of ``benchmark/candidates/setup_metrics.json`` (``setup_import_s``,
``setup_build_s``, ``setup_trace_lower_s``, ``setup_backend_compile_s``,
``setup_warmup_s``, ``setup_uncovered_s``), read from the program's start-up
record (``deepfm_tpu.obs.startup``) by ``benchmark/readers/startup_phase_s``.

    python3 scripts/setup_phases.py --workload <cell> --seed <n> [--seconds 12]
                                    [--export TRACE.json]

``BENCHMARK.json`` does not list them yet (its tests refuse a per-layer metric
that moves ``setup_s``; ROADMAP C), so the run goes through the harness's
overrides seam with the contract plus the candidates. It is a traced run (the
per-layer metrics are a traced run's), on the chip only, and prints the
start-up record's line, then the benchmark's own last line with the six added.
``--export`` also writes the program's spans, start-up record included, for
``scripts/trace_report.py``.
"""

import time

_T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def contract_with_candidates() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "candidates",
                           "setup_metrics.json")) as f:
        bench["per_layer"] += json.load(f)["per_layer_moving_setup_s"]
    return bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--export", default=None, metavar="TRACE.json")
    args = ap.parse_args(argv)

    from benchmark import harness
    from deepfm_tpu.obs import startup, trace

    try:
        line = harness.run(args.workload, args.seed, args.seconds, True,
                           overrides={"benchmark": contract_with_candidates()},
                           extra={"t_start": _T_START})
    except harness.NoChip as e:
        print(f"setup_phases: {e}; no result", file=sys.stderr)
        return 3
    if args.export:
        trace.export(args.export)
    print(startup.log_line(), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
