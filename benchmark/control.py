#!/usr/bin/env python3
"""The control of every cell's comparison: the program itself with its
compute precision one step below the configuration's (``--compute_dtype
float8_e4m3fn`` where the configuration says bfloat16), driven through the
same harness at the cell's own size. Each of its runs has to come out as
``"correct": false``; the limits in the cells' files were set between these
readings and those of sound runs (PERF.md has both).

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13

prints each run's ``check`` lines and result line, and exits 0 only if every
run was judged not correct. No benchmark run calls this;
``tests/benchmark_suite`` keeps it as a test at a small size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

#: The precision below each one a configuration may state.
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}


def lower_precision(cell: harness.Cell) -> dict:
    """The override of flags that puts the cell one precision lower."""
    return {"compute_dtype": LOWER[cell.config["flags"]["compute_dtype"]]}


def run(workload: str, seed: int, seconds: float, *,
        overrides: Optional[dict] = None, require_chip: bool = True) -> dict:
    cell = harness.load_cell(workload, overrides)
    merged = dict(overrides or {})
    merged["flags"] = {**merged.get("flags", {}), **lower_precision(cell)}
    return harness.run(workload, seed, seconds, False, overrides=merged,
                       require_chip=require_chip,
                       extra={"t_start": time.time()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    judged_sound = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            line = run(args.workload, seed, args.seconds)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"control_seed": seed, **line}), flush=True)
        judged_sound += bool(line["correct"])
    return 1 if judged_sound else 0


if __name__ == "__main__":
    sys.exit(main())
