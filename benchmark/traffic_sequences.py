"""Traffic from a seed for a language-model train cell: packed token
sequences as TFRecord shards.

A record is the repo's CTR ``tf.Example`` (``label``, ``ids``, ``values``:
one field, written because the codec requires them, read by no sequence
model) with the sequence in the optional history pair: ``hist_ids`` the
tokens, ``hist_vals`` all ones (every position is real; documents are packed
without a boundary mask). Byte for byte what
``example_codec.encode_ctr_example(..., hist_ids=...)`` + ``TFRecordWriter``
write (the test file compares them). Token ids are Zipf over rows
``0 .. vocabulary_rows - 2`` of the chip's slice of the vocabulary, rank 1 the
most frequent, the ranks spread over the rows by a fixed multiplicative
bijection; the last row is ``[MASK]`` and never drawn.

Imports NumPy (and ``benchmark.traffic``'s record framing) only.
"""

from __future__ import annotations

import concurrent.futures
import math
import multiprocessing
import os
import struct
from typing import List

import numpy as np

from benchmark import traffic
from benchmark.traffic import _delimited, _masked


def generate_tokens(n: int, length: int, vocabulary_rows: int, seed: int,
                    params: dict, stream: int = 0) -> np.ndarray:
    """``n`` sequences of ``length`` tokens, int32 [n, length], none of them
    the ``[MASK]`` row (the last). ``stream`` picks one of the seed's
    independent streams (a shard, say)."""
    rows = int(vocabulary_rows) - 1
    rng = np.random.default_rng([int(seed), 0x7365, int(stream)])
    rank = traffic.zipf_ranks(rng, rows, n * length, params["zipf_exponent"])
    return tokens_of_ranks(rank, vocabulary_rows).reshape(n, length)


def tokens_of_ranks(rank: np.ndarray, vocabulary_rows: int) -> np.ndarray:
    """The token of each Zipf rank (0 the most frequent): the ranks spread
    over the rows below ``[MASK]`` by a multiplicative bijection."""
    rows = int(vocabulary_rows) - 1
    spread = max(1, int(rows * 0.6180339887))
    while math.gcd(spread, rows) != 1:
        spread += 1
    return ((np.asarray(rank, np.int64) * spread) % rows).astype(np.int32)


def _entry(key: bytes, kind_field: int, packed: bytes) -> bytes:
    """One entry of the Example's feature map: key, then a Feature holding a
    FloatList (field 2) or an Int64List (field 3) with one packed value."""
    feature = _delimited(kind_field, _delimited(1, packed))
    return _delimited(1, _delimited(1, key) + _delimited(2, feature))


def encode_records(tokens: np.ndarray) -> List[bytes]:
    """One serialized tf.Example per row of ``tokens``."""
    n, length = tokens.shape
    payload, off = traffic.packed_varints(tokens)
    payload = payload.tobytes()
    one = np.ones(1, "<f4").tobytes()
    head = (_entry(b"label", 2, np.zeros(1, "<f4").tobytes())
            + _entry(b"ids", 3, b"\x00") + _entry(b"values", 2, one))
    tail = _entry(b"hist_vals", 2, np.ones(length, "<f4").tobytes())
    return [_delimited(1, head + _entry(
        b"hist_ids", 3, payload[int(off[i]):int(off[i + 1])]) + tail)
        for i in range(n)]


def write_shard(path: str, tokens: np.ndarray) -> str:
    """One TFRecord shard: per record the length, its masked CRC32C, the
    record and the record's masked CRC32C."""
    crc = traffic._crc32c()
    frames = []
    for rec in encode_records(tokens):
        length = struct.pack("<Q", len(rec))
        frames += (length, struct.pack("<I", _masked(crc(length))), rec,
                   struct.pack("<I", _masked(crc(rec))))
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(b"".join(frames))
    os.replace(tmp, path)
    return path


def make_shard(path: str, n: int, length: int, vocabulary_rows: int,
               seed: int, shard: int, params: dict) -> str:
    """Generate and write shard ``shard`` of the seed (a worker process)."""
    return write_shard(path, generate_tokens(n, length, vocabulary_rows,
                                             seed, params, stream=shard))


class ShardWriter:
    """Generates and writes the seed's shards on worker processes (which
    import NumPy and this module, never JAX: the chip belongs to the parent)
    while the parent brings up the device. ``files()`` waits for them."""

    def __init__(self, out_dir: str, length: int, vocabulary_rows: int,
                 seed: int, params: dict):
        n_shards = int(params["shards"])
        per = int(params["sequences_per_shard"])
        self.examples = n_shards * per
        os.makedirs(out_dir, exist_ok=True)
        workers = max(1, min(n_shards, len(os.sched_getaffinity(0)) // 2))
        self._pool = concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn"))
        self._futures = [self._pool.submit(
            make_shard, os.path.join(out_dir, f"tr-{s:05d}.tfrecord"), per,
            length, vocabulary_rows, seed, s, params)
            for s in range(n_shards)]

    def files(self) -> List[str]:
        try:
            return [f.result() for f in self._futures]
        finally:
            self._pool.shutdown(wait=True, cancel_futures=True)
