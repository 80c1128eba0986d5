#!/usr/bin/env python
"""Prove row-sharding rescues the vocab cliff.

A single-chip vocab sweep (July 2026, record deleted) showed embedding
tables are free to ~10M rows x K=32 and then fall off a cliff: V=25M costs
~9.6 GB of params+Adam moments — HBM pressure pushes the step to 56 ms —
and V=50M fails to compile at all. The claimed rescue is the X1 capability
(the gRPC parameter server's replacement): ``--mesh_model=m`` row-shards
the table and both optimizer moments over the 'model' mesh axis, putting
~1/m of the bytes on each chip.

This script is the rescue's executable proof on the virtual 8-device mesh
(real multi-chip hardware is not available in this environment; the mesh,
shardings, and collectives are identical to real chips — only the physical
placement differs): it builds V=25M with ``mesh_model=8``, compiles and
executes one full training step, and measures per-device bytes of
params+optimizer state, asserting every device holds ~total/8.

Usage: python scripts/vocab_shard_proof.py [--vocab 25000000] [--shards 8]
Prints one JSON line with the measured layout.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=25_000_000)
    ap.add_argument("--shards", type=int, default=8)
    args = ap.parse_args()

    from __graft_entry__ import _provision_virtual_devices
    _provision_virtual_devices(args.shards)

    import jax

    from deepfm_tpu.config import Config
    from deepfm_tpu.train import Trainer

    cfg = Config(
        feature_size=args.vocab, field_size=39, embedding_size=32,
        deep_layers="128,64,32", dropout="0.5,0.5,0.5", batch_size=1024,
        learning_rate=5e-4, optimizer="Adam", l2_reg=1e-4,
        compute_dtype="bfloat16", mesh_data=1, mesh_model=args.shards,
        log_steps=0, seed=0)

    t0 = time.perf_counter()
    trainer = Trainer(cfg)
    state = trainer.init_state()
    t_init = time.perf_counter() - t0

    # Per-device resident bytes of params + optimizer state. The embedding
    # table and BOTH Adam moments must be row-sharded (ops/embedding.py +
    # parallel/mesh.py opt_state_pspecs); the dense tower is replicated but
    # is negligible at this scale.
    per_dev = {}
    total = 0
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        seen_dev = set()
        for s in leaf.addressable_shards:
            if s.device.id in seen_dev:
                continue
            seen_dev.add(s.device.id)
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
            total += s.data.nbytes

    rng = np.random.default_rng(0)
    batch = {
        "feat_ids": rng.integers(
            0, cfg.feature_size, (cfg.batch_size, cfg.field_size)
        ).astype(np.int32),
        "feat_vals": rng.normal(
            size=(cfg.batch_size, cfg.field_size)).astype(np.float32),
        "label": (rng.random((cfg.batch_size, 1)) < 0.25).astype(np.float32),
    }
    t0 = time.perf_counter()
    state, m = trainer.train_step(state, trainer.put_batch(batch))
    jax.block_until_ready(m["loss"])
    t_compile_step = time.perf_counter() - t0
    loss = float(m["loss"])
    assert np.isfinite(loss), loss

    t0 = time.perf_counter()
    state, m = trainer.train_step(state, trainer.put_batch(batch))
    jax.block_until_ready(m["loss"])
    t_step = time.perf_counter() - t0

    shard_bytes = sorted(per_dev.values())
    biggest = shard_bytes[-1]
    # Every device must hold ~total/m: allow 5% slack for the replicated
    # dense tower + scalar opt state.
    assert biggest <= (total / args.shards) * 1.05, (
        f"unbalanced: biggest shard {biggest / 1e9:.2f} GB vs "
        f"total/m {total / args.shards / 1e9:.2f} GB")

    print(json.dumps({
        "vocab": args.vocab,
        "mesh_model": args.shards,
        "total_params_opt_gb": round(total / 1e9, 3),
        "per_shard_gb_min": round(shard_bytes[0] / 1e9, 3),
        "per_shard_gb_max": round(biggest / 1e9, 3),
        "per_shard_over_total_ratio": round(biggest / total, 4),
        "init_s": round(t_init, 1),
        "first_step_incl_compile_s": round(t_compile_step, 1),
        "steady_step_s": round(t_step, 2),
        "loss": round(loss, 4),
        "ok": True,
    }))


if __name__ == "__main__":
    main()
