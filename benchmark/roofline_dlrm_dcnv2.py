"""What one DLRM-DCNv2 train step needs of the chip, whatever the algorithm.

Counted from the mathematics, per chip and per step, for a local batch of
``B`` examples with ``N`` numeric values and ``C`` categorical fields of
embeddings of ``K``, a bottom MLP ``N -> b_1 .. b_m`` (``b_m = K``), ``L``
low-rank cross layers of rank ``r`` on ``D = (1 + C) * K`` and a top MLP
``D -> t_1 .. t_n -> 1``:

* FLOPs are those of the matrix products, the MXU's work. A product
  ``[B, a] x [a, b]`` is ``2*B*a*b`` forward; backward it is one product of
  that size for the weight's gradient and one for the input's. The first
  bottom layer's input is data and has no gradient, so it is two products,
  every other layer three. A cross layer is two products, ``x V`` and
  ``(x V) U``, of ``D*r`` multiply-adds each. The elementwise work (ReLU,
  the cross layer's ``x0 * (.) + x``, the loss) is not counted.
* Bytes: the table is touched only at the rows of the batch, at most ``B*C``
  of them: each row of ``K`` floats and its Adagrad accumulator are read
  once and written once. The dense parameters and their accumulators
  likewise, and the batch is read once. A sweep of the whole table is the
  algorithm's choice, not the mathematics', and is not counted (as in
  ``roofline.py``).

The least time is the larger of FLOPs over the peak rate and bytes over the
peak bandwidth; the peaks are ``peaks.json``'s, by the device's kind.
"""

from __future__ import annotations


def _widths(flags: dict, key: str) -> list:
    return [int(x) for x in str(flags[key]).split(",") if x.strip()]


def layer_products(flags: dict) -> dict:
    """Multiply-adds per example of each block's matrix products, forward:
    {"bottom": [...], "cross": [...], "top": [...]}, one entry a product."""
    n = int(flags["numeric_fields"])
    k = int(flags["embedding_size"])
    d = (1 + int(flags["field_size"]) - n) * k
    r = int(flags["cross_rank"])
    bottom = [n] + _widths(flags, "bottom_layers")
    top = [d] + _widths(flags, "deep_layers") + [1]
    return {"bottom": [a * b for a, b in zip(bottom[:-1], bottom[1:])],
            "cross": [d * r, r * d] * int(flags["cross_layers"]),
            "top": [a * b for a, b in zip(top[:-1], top[1:])]}


def matmul_flops(flags: dict, chips: int) -> float:
    """FLOPs of one step's matrix products, forward and backward."""
    b = int(flags["batch_size"]) // int(chips)
    p = layer_products(flags)
    every = sum(p["bottom"]) + sum(p["cross"]) + sum(p["top"])
    return float(2 * b * (3 * every - p["bottom"][0]))


def train_step_counts(flags: dict, chips: int) -> dict:
    b = int(flags["batch_size"]) // int(chips)
    f, n = int(flags["field_size"]), int(flags["numeric_fields"])
    k = int(flags["embedding_size"])
    d = (1 + f - n) * k
    p = layer_products(flags)
    biases = (sum(_widths(flags, "bottom_layers"))
              + int(flags["cross_layers"]) * d
              + sum(_widths(flags, "deep_layers")) + 1)
    dense_params = sum(p["bottom"]) + sum(p["cross"]) + sum(p["top"]) + biases
    table_bytes = b * (f - n) * k * 4 * 2 * 2    # row, accumulator; read+write
    dense_bytes = dense_params * 4 * 2 * 2
    batch_bytes = b * (f * 8 + 4)
    return {"flops": matmul_flops(flags, chips),
            "bytes": float(table_bytes + dense_bytes + batch_bytes)}


def train_step_least_seconds(flags: dict, chips: int, peaks: dict) -> dict:
    c = train_step_counts(flags, chips)
    by_flops = c["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops > by_bytes else "memory", **c}
