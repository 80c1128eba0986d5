"""Seeded weights that any row of can be made again, anywhere.

The benchmark makes the weights (they are its input, like the traffic): on
the device in one jitted call for the program, and row by row in NumPy for
the reference, which never sees an array the program has held. Both come from
one counter-based hash of (seed, leaf name, element index), in integer
arithmetic that NumPy and XLA agree on bit for bit.

Tables (leaves whose first dimension is the padded vocabulary) are uniform in
``+-embedding_scale``, the scale of a trained CTR model, so that answers
differ from row to row by far more than bfloat16 rounding; rows past
``feature_size`` are the program's padding and stay zero. Matrices are
Glorot-uniform by their shape, vectors uniform in ``+-BIAS_SCALE``.
"""

from __future__ import annotations

import math
import zlib
from typing import Optional, Sequence, Tuple

import numpy as np


#: Biases and other vectors start small and not at zero, so that a bias that
#: is dropped or not trained shows.
BIAS_SCALE = 0.1


def mix32(x, xp=np):
    """murmur3's 32-bit finalizer on uint32 values (wrapping on overflow)."""
    x = x ^ (x >> xp.uint32(16))
    x = x * xp.uint32(0x85EBCA6B)
    x = x ^ (x >> xp.uint32(13))
    x = x * xp.uint32(0xC2B2AE35)
    return x ^ (x >> xp.uint32(16))


def leaf_salt(seed: int, name: str) -> int:
    return (zlib.crc32(name.encode()) ^ (int(seed) * 0x9E3779B1)) & 0xFFFFFFFF


def leaf_limit(shape: Sequence[int], padded_vocab: int,
               embedding_scale: float) -> float:
    if shape and shape[0] == padded_vocab:
        return float(embedding_scale)
    if len(shape) < 2:
        return BIAS_SCALE
    return math.sqrt(6.0 / (int(shape[-2]) + int(shape[-1])))


def leaf_values(salt, shape: Tuple[int, ...], *,
                feature_size: int, padded_vocab: int, embedding_scale: float,
                rows: Optional[np.ndarray] = None, xp=np):
    """The leaf with ``salt`` (``leaf_salt(seed, name)``; may be a traced
    uint32, so that one compiled program serves every seed) of shape
    ``shape`` (float32), or only its ``rows``."""
    if isinstance(salt, int):
        salt = np.uint32(salt)      # jnp would read a large int as int32
    shape = tuple(int(s) for s in shape)
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if n >= 2 ** 32:
        raise ValueError(f"a leaf of {n} elements: its index overflows")
    inner = n // shape[0] if shape else 1
    if rows is None:
        idx = xp.arange(n, dtype=xp.uint32).reshape(shape)
        row_of = (xp.arange(shape[0], dtype=xp.uint32).reshape(
            (-1,) + (1,) * (len(shape) - 1)) if shape else None)
    else:
        rows = xp.asarray(rows).astype(xp.uint32)
        idx = (rows[:, None] * xp.uint32(inner)
               + xp.arange(inner, dtype=xp.uint32)[None, :])
        idx = idx.reshape((rows.shape[0],) + shape[1:])
        row_of = rows.reshape((-1,) + (1,) * (len(shape) - 1))
    with np.errstate(over="ignore"):
        h = mix32(idx * xp.uint32(0x9E3779B1)
                   + xp.asarray(salt).astype(xp.uint32), xp)
    unit = (h >> xp.uint32(8)).astype(xp.float32) * xp.float32(2.0 ** -23) \
        - xp.float32(1.0)
    out = unit * xp.float32(leaf_limit(shape, padded_vocab, embedding_scale))
    if shape and shape[0] == padded_vocab and padded_vocab > feature_size:
        out = xp.where(row_of < xp.uint32(feature_size), out, xp.float32(0))
    return out
