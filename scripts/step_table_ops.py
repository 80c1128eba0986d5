#!/usr/bin/env python
"""List the table passes of a benchmark cell's compiled train step.

Compiles the cell's K-step dispatch (``Trainer.multi_step``, the program its
``fit`` runs) for a *described* TPU — ``jax.experimental.topologies``: the
chip's compiler is installed wherever libtpu is, no chip is attached and
nothing runs — and prints every instruction outside fused computations whose
result is as tall as the embedding table: ``*`` where it sits in a ``while``
body (that is where the scanned steps live), its name, opcode, results,
operands (``*`` after a table-shaped one), the operands the backend updates
in place, the ``jax.named_scope`` the trainer gave it and the primitive it
came from. Each line is at least one pass over a table in HBM, except an
``R`` line: a scatter, or the ``embed_put_rows`` kernel (one DMA a row,
``ops/pallas_put_rows.py``), that writes rows into a table in place costs
its rows. A step whose only lines are ``R`` updates the tables on the rows the
batch touched (``Trainer._row_local_eligible``); ``docs/TUNING.md`` §5 says
how to count the others. The four-chip DeepFM step lists, beside the wide
table's ``R`` line (the trips' scatter of the replicas' exchanged rows), the
one table that crosses the interconnect as a table: a ``fusion f32[16881344]``
scatter-add of the replica's own rows into a fill fused with it, outside the
trips' ``while``, and an ``all-reduce`` whose tuple holds that
``f32[16881344]`` (one read and one write of a 67.5 MB vector, 1/32 of a
pass: not a pass over the 2.16 GB table); no ``[16881344,32]`` collective
(``PERF.md`` §6, PR 41). Last, what the model says its traced step is made
of (``step_notes``) and the step's ``memory_analysis()``. A described device
says nothing of its memory, so what the program reads from the device
(``sdar_moe.device_memory_bytes``: how many layers keep their SwiGLU's
first products) is described here too, ``BYTES_LIMIT`` by device kind. It
takes ~20 s (a decoder cell's step one to two minutes) and says nothing
about time: times are the chip's
(``benchmark/run.py --trace 1``; its ``breakdown`` names the same ops).

Usage (from the repo root; keep ``JAX_PLATFORMS=cpu``):
    python scripts/step_table_ops.py [--workload deepfm-criteo.train-files]
    python scripts/step_table_ops.py --workload deepfm-criteo-host4.train-files
        [--topology v5e:2x2] [--chips 1|4] [--hlo_out step.hlo.txt]
"""

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


#: ``memory_stats()["bytes_limit"]`` by ``device_kind`` (my chip run, PR 47).
BYTES_LIMIT = {"TPU v5 lite": 16_909_336_064}


def compile_step(workload: str, topology: str, chips: int) -> tuple:
    """(the cell's compiled dispatch, table height, the model's notes)."""
    import jax
    from jax.experimental import topologies

    from benchmark import harness
    from benchmark.drivers import _program
    from deepfm_tpu.models import sdar_moe

    # A described device cannot read an executable back from the cache.
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    # The trainer picks its kernels by backend: trace what a TPU host would.
    jax.default_backend = lambda: "tpu"
    cell = harness.load_cell(workload)
    devices = list(topo.devices)[:chips or cell.chips]
    limit = BYTES_LIMIT[devices[0].device_kind]
    sdar_moe.device_memory_bytes = lambda: limit
    trainer = _program.build_trainer(
        _program.make_config(dict(cell.config["flags"])), devices)
    return (trainer.step_compiled(device=devices[0]),
            int(trainer.model.padded_vocab),
            dict(getattr(trainer.model, "step_notes", {})))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="deepfm-criteo.train-files",
                    help="a train cell of BENCHMARK.json")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--chips", type=int, choices=(1, 4),
                    help="default: the cell's own")
    ap.add_argument("--hlo_out", help="also write the whole text here")
    args = ap.parse_args(argv)

    from deepfm_tpu.utils import profiling

    compiled, rows, notes = compile_step(args.workload, args.topology,
                                         args.chips)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(text)
    ops = profiling.hlo_table_ops(text, rows)
    row_writes = [o for o in ops if o["in_place"] and (
        o["primitive"].startswith("scatter")
        or o["name"].startswith("embed_put_rows"))]
    print(f"{args.workload} compiled for {args.topology}: "
          f"{len(ops)} instructions make an array {rows} rows tall "
          f"({sum(o['loop_body'] for o in ops)} in a loop body); "
          f"{len(row_writes)} of them write rows into a table in place "
          f"(R: the rows' cost), {len(ops) - len(row_writes)} pass over one")
    for o in ops:
        operands = ", ".join(n + "*" * (n in o["tables"])
                             for n in o["operands"])
        in_place = ",".join(str(i) for i in o["in_place"]) or "-"
        print("%s%s %-30s %-10s %s <- (%s) in_place=%s scope=%s %s" % (
            "*" if o["loop_body"] else " ", "R" if o in row_writes else " ",
            o["name"], o["opcode"], " ".join(o["results"]), operands,
            in_place, o["scope"] or "-", o["primitive"]))
    if notes:
        print("step_notes: " + ", ".join(
            f"{k} {v}" for k, v in sorted(notes.items())))
    print("memory_analysis: arguments %.3f GB, outputs %.3f GB (aliased "
          "%.3f), temporaries %.3f GB" % tuple(
              getattr(memory, f"{k}_size_in_bytes") / 1e9
              for k in ("argument", "output", "alias", "temp")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
