"""What one DeepFM train step needs of the chip, whatever the algorithm.

Counted from the mathematics, per chip and per step, for a local batch of
``B`` examples of ``F`` fields, embeddings of ``K`` and a tower of widths
``d_0 = F*K, d_1, ..., d_L, 1``:

* FLOPs: a matrix product ``[B, a] x [a, b]`` is ``2*B*a*b``; forward plus the
  two backward products is three times that, over the tower's layers. The FM
  part (first order, the sum and the squares over fields) is ``6*B*F*K``
  forward, three times that with its backward.
* Bytes: the tables are touched only at the rows of the batch, at most
  ``B*F`` of them: each row (``K + 1`` floats: ``fm_v`` and ``fm_w``) and its
  two Adam moments are read once and written once. The dense parameters and
  their moments likewise, and the batch is read once. A sweep of the whole
  table is the algorithm's choice, not the mathematics', and is not counted;
  that is why today's share is tiny, and why no refactor makes it stale.

The least time is the larger of FLOPs over the peak rate and bytes over the
peak bandwidth.
"""

from __future__ import annotations


def train_step_counts(flags: dict, chips: int) -> dict:
    b = int(flags["batch_size"]) // int(chips)
    f, k = int(flags["field_size"]), int(flags["embedding_size"])
    dims = [f * k] + [int(x) for x in str(flags["deep_layers"]).split(",")
                      if x] + [1]
    tower = sum(a * c for a, c in zip(dims[:-1], dims[1:]))
    dense_params = tower + sum(dims[1:]) + 1
    flops = 3 * (2 * b * tower + 6 * b * f * k)
    table_bytes = b * f * (k + 1) * 4 * 3 * 2     # row, m, v; read + write
    dense_bytes = dense_params * 4 * 3 * 2
    batch_bytes = b * (f * 8 + 4)
    return {"flops": float(flops),
            "bytes": float(table_bytes + dense_bytes + batch_bytes)}


def train_step_least_seconds(flags: dict, chips: int, peaks: dict) -> dict:
    c = train_step_counts(flags, chips)
    by_flops = c["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops > by_bytes else "memory", **c}
