"""Local worker fan-out: spawn ``worker_per_host`` training processes on this
host, each with its own JAX process id — the analog of the reference's MPI
launch (``mpirun -np 4`` via ``processes_per_host=4``,
``2-hvd-gpu/deepfm-sagemaker-hvd-gpu.ipynb:87-92``).

More than one worker per host is for CPU clusters (``JAX_PLATFORMS=cpu``: the
local test cluster, where the workers share the virtual devices). On an
accelerator host it is refused: the chips of one host belong to ONE process
(``--mesh_data/--mesh_model`` spread the work over them). Pinning one chip per
worker with ``TPU_VISIBLE_DEVICES`` — what this module used to do — was tried
on a four-chip v5e host (PERF.md, PR 21): the workers collide on libtpu's
multi-process lock, three abort at backend start-up, and none of them exits
until the distributed shutdown times out minutes later. With
``--worker_per_host 1`` this is the per-host launcher of a multi-host job.

Usage (one command per host):

    JAX_PLATFORMS=cpu python -m deepfm_tpu.fanout --worker_per_host 4 \
        --num_hosts 2 --host_index 0 --coordinator_address host0:12355 \
        --task_type train --data_dir ... <any launch.py flags>

Spawns ``worker_per_host`` copies of ``python -m deepfm_tpu.launch`` with:
  * ``process_id``   = host_index * worker_per_host + local_worker
  * ``num_processes`` = num_hosts * worker_per_host
  * ``dist_mode=1`` rendezvous on the coordinator (defaults to a local port
    for single-host runs)

The parent streams children's output and exits nonzero if any child fails.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import threading
from typing import List, Optional


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _pump(stream, sink, prefix: str) -> None:
    for line in iter(stream.readline, ""):
        sink.write(f"[{prefix}] {line}")
        sink.flush()
    stream.close()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        "deepfm_tpu.fanout",
        description="spawn worker_per_host launch.py processes on this host")
    ap.add_argument("--worker_per_host", type=int, required=True)
    ap.add_argument("--num_hosts", type=int, default=1)
    ap.add_argument("--host_index", type=int, default=0)
    ap.add_argument("--coordinator_address", default="",
                    help="host:port all workers rendezvous on "
                         "(default: localhost:<free port>; required for "
                         "num_hosts > 1)")
    args, passthrough = ap.parse_known_args(argv)

    n = args.worker_per_host
    if n < 1:
        raise SystemExit("--worker_per_host must be >= 1")
    if n > 1 and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise SystemExit(
            f"fanout: --worker_per_host {n} needs JAX_PLATFORMS=cpu. On an "
            "accelerator host several workers cannot form one device "
            "topology (one chip per worker was tried on four v5e chips: the "
            "workers collide on libtpu's multi-process lock and hang); run "
            "ONE process over all the host's chips instead: python -m "
            "deepfm_tpu.launch --mesh_data N [--mesh_model M].")
    if args.num_hosts > 1 and not args.coordinator_address:
        raise SystemExit(
            "--coordinator_address is required for num_hosts > 1 "
            "(every host must rendezvous on host 0's address)")
    coord = args.coordinator_address or f"localhost:{_free_port()}"
    world = args.num_hosts * n

    procs = []
    pumps = []
    for local in range(n):
        pid = args.host_index * n + local
        cmd = [
            sys.executable, "-m", "deepfm_tpu.launch",
            *passthrough,
            "--dist_mode", "1",
            "--num_processes", str(world),
            "--process_id", str(pid),
            "--coordinator_address", coord,
            "--worker_per_host", str(n),
        ]
        p = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        procs.append(p)
        t = threading.Thread(
            target=_pump, args=(p.stdout, sys.stdout, f"worker {pid}"),
            name=f"fanout-pump-{pid}", daemon=True)
        t.start()
        pumps.append(t)

    # Watch all children; one failure terminates the siblings (they would
    # otherwise block forever inside collectives waiting for the dead rank).
    import time

    rc = 0
    remaining = set(range(len(procs)))
    while remaining:
        for i in sorted(remaining):
            r = procs[i].poll()
            if r is None:
                continue
            remaining.discard(i)
            if r != 0:
                gpid = args.host_index * n + i
                print(f"fanout: worker {gpid} exited rc={r}", file=sys.stderr)
                rc = rc or r
        if rc and remaining:
            print(f"fanout: terminating {len(remaining)} remaining worker(s)",
                  file=sys.stderr)
            for i in remaining:
                procs[i].terminate()
            for i in remaining:
                try:
                    procs[i].wait(timeout=15)
                except subprocess.TimeoutExpired:
                    procs[i].kill()
            remaining.clear()
        if remaining:
            time.sleep(0.2)
    for t in pumps:
        t.join(timeout=5)
    return rc


if __name__ == "__main__":
    sys.exit(main())
