"""Multi-process bootstrap: the L4 cluster-topology layer, TPU-native.

The reference consumed SageMaker's TF_CONFIG/SM_* contract and shipped a
vestigial local bootstrap (`set_dist_env`, 1-ps-cpu/...py:294-339) that
hand-built TF_CONFIG with chief/evaluator role rewriting. On TPU none of that
role machinery exists: every process is symmetric SPMD. This module wraps
``jax.distributed.initialize`` and exposes rank helpers; "chief" semantics
(rank-0-only checkpoint/export, reference 2-hvd-gpu/...py:365-368) map to
``is_chief()``.

dist_mode (Config):
  0 — single process (auto-init if TPU env provides topology)
  1 — local multi-process test cluster: processes rendezvous on
      ``coordinator_address`` with explicit num_processes/process_id
      (the `set_dist_env` analog, for CPU multi-process tests)
  2 — managed cluster (GKE/TPU VM): jax.distributed.initialize() discovers
      topology from the environment
"""

from __future__ import annotations

import jax

from ..config import Config
from ..obs import startup

_INITIALIZED = False
_BACKEND_ASKED = False


def initialize(cfg: Config) -> None:
    """Idempotent jax.distributed bootstrap per cfg.dist_mode."""
    global _INITIALIZED
    if _INITIALIZED or cfg.dist_mode == 0:
        return
    with startup.phase("setup.distributed", dist_mode=cfg.dist_mode):
        if cfg.dist_mode == 1:
            if not cfg.coordinator_address:
                raise ValueError("dist_mode=1 requires coordinator_address")
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator_address,
                num_processes=cfg.num_processes,
                process_id=cfg.process_id,
            )
        elif cfg.dist_mode == 2:
            jax.distributed.initialize()
        else:
            raise ValueError(f"unknown dist_mode {cfg.dist_mode}")
    _INITIALIZED = True
    if cfg.dist_mode == 1 and jax.process_count() != cfg.num_processes:
        # The coordinator only rendezvouses processes; which devices form
        # one topology is the accelerator runtime's say. Workers that each
        # came up as their own 1-process world would otherwise all train as
        # "chief" into the same model_dir.
        raise RuntimeError(
            f"{cfg.num_processes} processes met at {cfg.coordinator_address} "
            f"but the backend came up as {jax.process_count()} process(es) "
            f"with {jax.device_count()} device(s): the workers did not form "
            "one device topology. On one multi-chip host run ONE process "
            "over all its chips (--mesh_data/--mesh_model); pinning one chip "
            "per worker (deepfm_tpu.fanout) does not join them into a mesh.")


def start_backend() -> None:
    """Ask JAX for its devices, as ``setup.backend``: the XLA backend starts
    at the first such question (after ``initialize``, which must come
    before), so the phase holds the start where the program is the first to
    ask — the launcher, before its first log line — and reads 0.0 where a
    caller asked already. Once a process."""
    global _BACKEND_ASKED
    if _BACKEND_ASKED:
        return
    _BACKEND_ASKED = True
    with startup.phase("setup.backend") as ph:
        ph.add(devices=jax.device_count(), platform=jax.default_backend())


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_chief() -> bool:
    """Rank-0 semantics: checkpoint/eval/export only on the chief process
    (reference rank-0-only model_dir, 2-hvd-gpu/...py:365-368)."""
    return jax.process_index() == 0
