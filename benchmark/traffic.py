"""Traffic from a seed: Criteo-shaped CTR records and serving arrivals.

One generator for both drivers. Field ``j`` owns a contiguous range of ids
(numeric fields one row each, categorical field ``j`` its share of the
published cardinality); a record has one id per field. A categorical id is
drawn Zipf within its field, rank 1 the most frequent, and the ranks are
spread over the field's range by a fixed multiplicative bijection so that hot
rows are not neighbours in memory. Labels come from a hidden logistic model
over a hash of the ids, so nothing vocabulary-long is ever held on the host.

Imports NumPy only.
"""

from __future__ import annotations

import concurrent.futures
import math
import multiprocessing
import os
import statistics
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import weights

Columns = Dict[str, np.ndarray]
_NORMAL = statistics.NormalDist()


class FieldLayout:
    """Id ranges of the fields of one configuration."""

    def __init__(self, numeric_fields: int, categorical_rows: Sequence[int]):
        self.numeric_fields = int(numeric_fields)
        self.rows = np.asarray(
            [1] * self.numeric_fields + [int(r) for r in categorical_rows],
            np.int64)
        if np.any(self.rows < 1):
            raise ValueError("every field needs at least one row")
        self.offsets = np.concatenate([[0], np.cumsum(self.rows)[:-1]])
        self.field_size = len(self.rows)
        self.feature_size = int(self.rows.sum())
        # rank -> row spread: (rank * a) mod n is a bijection when gcd(a,n)=1.
        self.spread = np.ones(self.field_size, np.int64)
        for j, n in enumerate(self.rows):
            a = max(1, int(n * 0.6180339887))
            while math.gcd(a, int(n)) != 1:
                a += 1
            self.spread[j] = a

    @classmethod
    def from_config(cls, config: dict) -> "FieldLayout":
        return cls(config["numeric_fields"], config["categorical_rows"])


def zipf_ranks(rng: np.random.Generator, n_rows: int, size: int,
               exponent: float) -> np.ndarray:
    """``size`` ranks in ``[0, n_rows)`` with P(rank r) ~ (r+1)^-exponent,
    by inverting the continuous analogue of the bounded Zipf CDF."""
    if n_rows == 1:
        return np.zeros(size, np.int64)
    s = 1.0 - exponent
    lo, hi = 0.5 ** s, (n_rows + 0.5) ** s
    u = rng.random(size)
    r = np.rint((lo + u * (hi - lo)) ** (1.0 / s)).astype(np.int64)
    return np.clip(r, 1, n_rows) - 1


def generate_rows(layout: FieldLayout, n: int, seed: int, params: dict,
                  stream: int = 0) -> Columns:
    """``n`` records: ``feat_ids`` int32 [n, F], ``feat_vals`` float32 [n, F]
    and ``label`` float32 [n]. ``stream`` picks one of the seed's
    independent streams (a shard, say)."""
    rng = np.random.default_rng([int(seed), 0x7261, int(stream)])
    f = layout.field_size
    ids = np.empty((n, f), np.int32)
    vals = np.ones((n, f), np.float32)
    for j in range(f):
        if j < layout.numeric_fields:
            ids[:, j] = layout.offsets[j]
            vals[:, j] = rng.lognormal(
                params["numeric_log_mean"], params["numeric_log_sigma"], n)
        else:
            rank = zipf_ranks(rng, int(layout.rows[j]), n,
                              params["zipf_exponent"])
            row = (rank * layout.spread[j]) % layout.rows[j]
            ids[:, j] = layout.offsets[j] + row
    # Hidden logistic model: a weight per id from a hash, never a table.
    h = weights.mix32(ids.astype(np.uint32) * np.uint32(0x9E3779B1)
                      + np.uint32(params["label_salt"]))
    w = (h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -23) - 1.0
    logit = params["label_bias"] + params["label_scale"] * (
        (w * np.minimum(vals, 4.0)).sum(axis=1) / math.sqrt(f))
    p = 1.0 / (1.0 + np.exp(-logit))
    label = (rng.random(n) < p).astype(np.float32)
    return {"feat_ids": ids, "feat_vals": vals, "label": label}


# ---------------------------------------------------------------------------
# Training shards
# ---------------------------------------------------------------------------
#
# TFRecord files of tf.Example records in the reference converter's schema
# (label, ids, values), byte for byte what the repo's own
# ``example_codec.encode_ctr_example`` + ``TFRecordWriter`` write (the test
# file compares them). Written here without a Python loop over the fields,
# because every run of every train cell writes its seed's shards during
# set-up: the repo's per-record codec costs 60 us of host time a record (half
# a minute for the seed's 524,288 in one process), this way 2.2 s on the
# chip's host over six worker processes (my chip run, PR 24).

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _delimited(field: int, payload: bytes) -> bytes:
    return bytes([(field << 3) | 2]) + _varint(len(payload)) + payload


def packed_varints(ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The packed-varint bytes of every row of ``ids`` [n, F] (non-negative,
    below 2**31), flat, and the [n + 1] offsets of the rows in them."""
    v = np.ascontiguousarray(ids).astype(np.uint32).ravel()
    nbytes = np.ones(v.shape, np.int64)
    for k in range(1, 5):
        nbytes += v >= np.uint32(1 << (7 * k))
    ends = np.cumsum(nbytes)
    starts = ends - nbytes
    out = np.empty(int(ends[-1]), np.uint8)
    for k in range(5):
        m = nbytes > k
        if not m.any():
            break
        more = (nbytes[m] > k + 1).astype(np.uint8) << np.uint8(7)
        out[starts[m] + k] = ((v[m] >> np.uint32(7 * k))
                              & np.uint32(0x7F)).astype(np.uint8) | more
    row_ends = ends.reshape(ids.shape)[:, -1]
    return out, np.concatenate([[0], row_ends])


def _crc32c():
    try:
        import google_crc32c
        return google_crc32c.value
    except ImportError:          # slower, same bytes
        from deepfm_tpu.data import tfrecord
        return tfrecord.crc32c


def _masked(crc: int) -> int:
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


def encode_records(label: np.ndarray, ids: np.ndarray, vals: np.ndarray
                   ) -> List[bytes]:
    """One serialized tf.Example per row."""
    n, f = ids.shape
    payload, off = packed_varints(ids)
    payload = payload.tobytes()
    lab = np.ascontiguousarray(label, "<f4").reshape(n).tobytes()
    val = np.ascontiguousarray(vals, "<f4").tobytes()
    lab_head = _delimited(1, b"label")
    lab_head += bytes([0x12]) + _varint(4 + 2 + 2)       # Feature
    lab_head += bytes([0x12]) + _varint(4 + 2)           # FloatList
    lab_head += bytes([0x0A]) + _varint(4)               # packed value
    lab_head = bytes([0x0A]) + _varint(len(lab_head) + 4) + lab_head
    vb = 4 * f
    fl = 1 + len(_varint(vb)) + vb
    feat = 1 + len(_varint(fl)) + fl
    val_head = _delimited(1, b"values") + bytes([0x12]) + _varint(feat) \
        + bytes([0x12]) + _varint(fl) + bytes([0x0A]) + _varint(vb)
    val_head = bytes([0x0A]) + _varint(len(val_head) + vb) + val_head
    heads = {}

    def head_for(length: int) -> Tuple[bytes, bytes]:
        il = 1 + len(_varint(length)) + length
        feat_i = 1 + len(_varint(il)) + il
        h = _delimited(1, b"ids") + bytes([0x12]) + _varint(feat_i) \
            + bytes([0x1A]) + _varint(il) + bytes([0x0A]) + _varint(length)
        h = bytes([0x0A]) + _varint(len(h) + length) + h
        total = len(lab_head) + 4 + len(h) + length + len(val_head) + vb
        return bytes([0x0A]) + _varint(total), h

    out = []
    for i in range(n):
        a, b = int(off[i]), int(off[i + 1])
        pair = heads.get(b - a)
        if pair is None:
            pair = heads[b - a] = head_for(b - a)
        out.append(b"".join((pair[0], lab_head, lab[4 * i:4 * i + 4],
                             pair[1], payload[a:b], val_head,
                             val[vb * i:vb * i + vb])))
    return out


def write_shard(path: str, label: np.ndarray, ids: np.ndarray,
                vals: np.ndarray) -> str:
    """One TFRecord shard: per record the length, its masked CRC32C, the
    record and the record's masked CRC32C."""
    import struct

    crc = _crc32c()
    frames, len_crc = [], {}
    for rec in encode_records(label, ids, vals):
        n = len(rec)
        head = len_crc.get(n)
        if head is None:
            length = struct.pack("<Q", n)
            head = len_crc[n] = length + struct.pack("<I",
                                                     _masked(crc(length)))
        frames += (head, rec, struct.pack("<I", _masked(crc(rec))))
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(b"".join(frames))
    os.replace(tmp, path)
    return path


def make_shard(path: str, layout: FieldLayout, n: int, seed: int, shard: int,
               params: dict) -> str:
    """Generate and write shard ``shard`` of the seed (a worker process)."""
    cols = generate_rows(layout, n, seed, params, stream=shard)
    return write_shard(path, cols["label"], cols["feat_ids"],
                       cols["feat_vals"])


class ShardWriter:
    """Generates and writes the seed's shards on worker processes (which
    import NumPy and this module, never JAX: the chip belongs to the parent)
    while the parent brings up the device. ``files()`` waits for them."""

    def __init__(self, out_dir: str, layout: FieldLayout, seed: int,
                 params: dict):
        n_shards = int(params["shards"])
        per = int(params["examples_per_shard"])
        self.examples = n_shards * per
        os.makedirs(out_dir, exist_ok=True)
        workers = max(1, min(n_shards, len(os.sched_getaffinity(0)) // 2))
        self._pool = concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn"))
        self._futures = [self._pool.submit(
            make_shard, os.path.join(out_dir, f"tr-{s:05d}.tfrecord"),
            layout, per, seed, s, params) for s in range(n_shards)]

    def files(self) -> List[str]:
        try:
            return [f.result() for f in self._futures]
        finally:
            self._pool.shutdown(wait=True, cancel_futures=True)


# ---------------------------------------------------------------------------
# Serving arrivals
# ---------------------------------------------------------------------------

def slate_sizes(n: int, params: dict) -> np.ndarray:
    """The ``n`` quantiles of the slate-size law: every seed sends the same
    multiset of sizes, in another order."""
    z = np.asarray([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(math.log(params["slate_median_rows"])
                 + params["slate_log_sigma"] * z)
    return np.clip(np.rint(raw), 1, params["slate_max_rows"]).astype(np.int64)


def mean_slate_rows(params: dict) -> float:
    return float(slate_sizes(4096, params).mean())


def arrival_schedule(seconds: float, rows_per_s: float, params: dict,
                     stream: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """(due offsets in seconds, rows) of the requests due in ``seconds``.

    Poisson arrivals at ``rows_per_s / mean slate``: the gaps are the
    quantiles of the exponential law scaled to fill the span exactly, the
    sizes the quantiles of the slate law, both shuffled by the traffic
    file's own ``arrival_seed``. The run's ``--seed`` does not enter: it
    decides which rows are asked for, not when or how many, because the
    order of arrivals decides which flushes overflow, and a tail read from
    890 requests in 105 flushes moved by 10% from one order to the next (my
    chip runs, PR 24). Every seed offers the same work at the same times."""
    n = max(1, int(round(seconds * rows_per_s / mean_slate_rows(params))))
    rng = np.random.default_rng([int(params["arrival_seed"]), 0x6172,
                                 int(stream)])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    due = np.cumsum(rng.permutation(gaps)) - gaps.min() / 2
    return due, rng.permutation(slate_sizes(n, params))
