"""LibSVM text <-> TFRecord conversion (component H of the reference).

Reference behavior (``tools/libsvm_to_tfrecord.py:5-37``): each input line
``"label id:val id:val ..."`` becomes one ``Example{label: float,
ids: int64[F], values: float[F]}``. This implementation adds what the
reference's converter lacks: sharded output, field-size validation, a reverse
(TFRecord->LibSVM) path for round-trip testing, and a synthetic-data
generator for tests/benchmarks.
"""

from __future__ import annotations

import os
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from . import example_codec, tfrecord


def parse_libsvm_line(line: str) -> Tuple[float, np.ndarray, np.ndarray]:
    parts = line.strip().split()
    if not parts:
        raise ValueError("empty LibSVM line")
    label = float(parts[0])
    ids = np.empty(len(parts) - 1, dtype=np.int64)
    vals = np.empty(len(parts) - 1, dtype=np.float32)
    for i, tok in enumerate(parts[1:]):
        k, _, v = tok.partition(":")
        ids[i] = int(k)
        vals[i] = float(v)
    return label, ids, vals


def format_libsvm_line(label: float, ids: np.ndarray, vals: np.ndarray) -> str:
    toks = [f"{label:g}"] + [f"{int(i)}:{float(v):g}" for i, v in zip(ids, vals)]
    return " ".join(toks)


def convert_libsvm_file(
    in_path: str,
    out_path: str,
    *,
    field_size: Optional[int] = None,
    num_shards: int = 1,
) -> int:
    """Convert a LibSVM text file to TFRecord file(s). Returns record count.

    With ``num_shards > 1``, writes ``{out_path}-00000-of-0000N`` shards
    round-robin (the layout `ShardedByS3Key` distribution expects).
    """
    if num_shards < 1:
        raise ValueError("num_shards must be >= 1")
    if num_shards == 1:
        writers = [tfrecord.TFRecordWriter(out_path)]
    else:
        writers = [
            tfrecord.TFRecordWriter(f"{out_path}-{s:05d}-of-{num_shards:05d}")
            for s in range(num_shards)
        ]
    n = 0
    try:
        with open(in_path, "r") as f:
            for line in f:
                if not line.strip():
                    continue
                label, ids, vals = parse_libsvm_line(line)
                if field_size is not None and ids.shape[0] != field_size:
                    raise ValueError(
                        f"line {n}: expected {field_size} features, got {ids.shape[0]}")
                writers[n % num_shards].write(
                    example_codec.encode_ctr_example(label, ids, vals))
                n += 1
    finally:
        for w in writers:
            w.close()
    return n


def tfrecord_to_libsvm(in_path: str, out_path: str, field_size: int) -> int:
    """Reverse conversion, for round-trip tests."""
    n = 0
    with open(out_path, "w") as out:
        for rec in tfrecord.iter_records(in_path):
            label, ids, vals = example_codec.decode_ctr_example(rec, field_size)
            out.write(format_libsvm_line(label, ids, vals) + "\n")
            n += 1
    return n


def generate_synthetic_ctr(
    out_dir: str,
    *,
    num_files: int,
    examples_per_file: int,
    feature_size: int,
    field_size: int,
    prefix: str = "tr",
    seed: int = 0,
    hidden_seed: int = 12345,
    num_labels: int = 1,
    history: int = 0,
) -> List[str]:
    """Write synthetic Criteo-shaped TFRecords with a learnable signal.

    Labels follow a logistic model over a hidden random weight vector so AUC
    above 0.5 is achievable — used by integration tests and the drills
    (reference trained on real Criteo; shape/hparams from
    ``deepfm-sagemaker-ps-cpu.ipynb:82-90``). ``hidden_seed`` fixes the
    label-generating model independently of ``seed`` (the example sampler),
    so train/eval/test splits generated with different seeds share the same
    ground-truth mapping.

    With ``num_labels=2`` each Example additionally carries a ``label2``
    (conversion) key generated from a SECOND hidden vector and gated on the
    click (label2 can be 1 only when label is 1 — the ESMM entire-space
    setup), so both tasks are learnable and realistically correlated. With
    the default ``num_labels=1`` no extra rng draws happen and the output
    is byte-identical to previous versions.

    With ``history > 0`` each Example additionally carries a ragged
    click-gated ``hist_ids``/``hist_vals`` pair: the history is sampled from
    the ids of PREVIOUSLY CLICKED examples in the stream (a rolling pool, so
    early records naturally have empty histories), its length is uniform in
    ``[0, history]``, and the click logit gains an affinity term between the
    history and the candidate through the same hidden vector — target
    attention over the history is therefore genuinely learnable. With the
    default ``history=0`` no extra rng draws happen and the output is
    byte-identical.
    """
    if num_labels not in (1, 2):
        raise ValueError(f"num_labels must be 1 or 2, got {num_labels}")
    if history < 0:
        raise ValueError(f"history must be >= 0, got {history}")
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    hidden_w = np.random.default_rng(hidden_seed).normal(
        0, 1.0, size=feature_size).astype(np.float32)
    hidden_w2 = np.random.default_rng(hidden_seed + 1).normal(
        0, 1.0, size=feature_size).astype(np.float32)
    clicked_pool: List[int] = []  # rolling pool of clicked ids (click-gated)
    paths = []
    for fi in range(num_files):
        path = os.path.join(out_dir, f"{prefix}_{fi:04d}.tfrecords")
        paths.append(path)
        with tfrecord.TFRecordWriter(path) as w:
            for _ in range(examples_per_file):
                ids = rng.integers(0, feature_size, size=field_size, dtype=np.int64)
                vals = rng.normal(0, 1, size=field_size).astype(np.float32)
                logit = float(np.dot(hidden_w[ids], vals)) * 0.5
                hist_ids = None
                if history > 0:
                    hist_n = min(int(rng.integers(0, history + 1)),
                                 len(clicked_pool))
                    if hist_n > 0:
                        pick = rng.integers(0, len(clicked_pool), size=hist_n)
                        hist_ids = np.asarray(
                            [clicked_pool[j] for j in pick], np.int64)
                        # history/candidate affinity through the hidden model
                        logit += float(np.mean(hidden_w[hist_ids])) \
                            * float(np.mean(hidden_w[ids])) * 2.0
                label = float(rng.random() < 1.0 / (1.0 + np.exp(-logit)))
                if history > 0 and label > 0:
                    clicked_pool.extend(int(i) for i in ids)
                    if len(clicked_pool) > 4096:
                        del clicked_pool[:-4096]
                label2 = None
                if num_labels == 2:
                    label2 = 0.0
                    if label > 0:
                        logit2 = float(np.dot(hidden_w2[ids], vals)) * 0.5
                        label2 = float(
                            rng.random() < 1.0 / (1.0 + np.exp(-logit2)))
                w.write(example_codec.encode_ctr_example(
                    label, ids, vals, label2=label2, hist_ids=hist_ids))
    return paths
