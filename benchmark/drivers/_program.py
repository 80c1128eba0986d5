"""The seam between the benchmark and the program under test.

Everything here is about ``deepfm_tpu`` as it is today: how a configuration's
flags become a ``Config``, what its parameter tree is called, how a seeded
``TrainState`` is put on the device, and how the trainer derives its dropout
masks from the state's key. The reference never imports this module's
program side; it is handed names, shapes, rows and masks.
"""

from __future__ import annotations

import importlib
import threading
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import weights


def make_config(flags: dict):
    from deepfm_tpu.config import Config

    return Config(**flags)


def build_trainer(cfg, devices: list):
    from deepfm_tpu.parallel import mesh as mesh_lib
    from deepfm_tpu.train import Trainer

    return Trainer(cfg, mesh_info=mesh_lib.build_mesh(cfg, devices=devices))


def import_tasks_beside():
    """Start importing ``deepfm_tpu.train.tasks`` (whose ``make_pipeline`` is
    how the launcher's train task wires the file pipeline from the flags) on
    a thread of its own, and return a callable that waits for the module.
    The import pulls in Orbax and its cloud clients: seconds that serve
    nothing in a train cell, so they are spent beside JAX's start-up, which
    is device work outside the interpreter lock. Until the wait returns the
    calling thread imports nothing else of ``deepfm_tpu``: two threads that
    enter a package's import cycles can hand each other half-made modules."""
    import deepfm_tpu.utils.compile_cache  # noqa: F401  what acquire() needs

    box: dict = {}

    def load():
        try:
            box["module"] = importlib.import_module("deepfm_tpu.train.tasks")
        except BaseException as e:   # raised again by wait()
            box["error"] = e

    thread = threading.Thread(target=load, name="bench-import", daemon=True)
    thread.start()

    def wait():
        thread.join()
        if "error" in box:
            raise box["error"]
        return box["module"]
    return wait


def leaf_name(path) -> str:
    """``['tower']['layers'][0]['w']`` -> ``tower.layers.0.w``."""
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_specs(trainer) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every parameter the program's model declares."""
    import jax

    shapes, mstate = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    if jax.tree.leaves(mstate):
        raise ValueError("the benchmark's seeded weights cover parameters "
                         "only; this model also carries state")
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return {leaf_name(p): tuple(s.shape) for p, s in flat}


def weight_kwargs(cell_config: dict, trainer) -> dict:
    return {"feature_size": int(trainer.cfg.feature_size),
            "padded_vocab": int(trainer.model.padded_vocab),
            "embedding_scale": float(
                cell_config["assumed"]["embedding_scale"])}


def seeded_state(trainer, seed: int, cell_config: dict):
    """A ``TrainState`` whose parameters are the benchmark's seeded weights,
    made on the device in one jitted call (parameters and the optimizer's
    state over them), placed as the trainer's mesh wants them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepfm_tpu.train.state import TrainState

    shapes, mstate = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [leaf_name(p) for p, _ in flat]
    kw = weight_kwargs(cell_config, trainer)

    def make(salts):
        leaves = [weights.leaf_values(salts[i], tuple(s.shape), xp=jnp, **kw)
                  for i, (_, s) in enumerate(flat)]
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        return params, trainer.tx.init(params)

    mi = trainer.mesh_info
    out = None if mi.mesh is None else mi.sharding(P())
    salts = np.asarray([weights.leaf_salt(seed, n) for n in names], np.uint32)
    params, opt_state = jax.jit(make, out_shardings=out)(salts)
    rng = jax.random.PRNGKey(int(seed) % (2 ** 31))
    # The state is donated to the first dispatch, its key with it: the
    # caller gets the key's words, to make the dropout masks again later.
    return TrainState.create(params, opt_state, mstate, rng), np.asarray(rng)


def dropout_masks(base_rng, step: int, *, n_shards: int, local_batch: int,
                  widths: Sequence[int], keep: Sequence[float]
                  ) -> List[np.ndarray]:
    """The masks ``Trainer._step_impl`` draws at ``step``: the state's key
    folded with the step (and, across a data mesh, with the shard's index),
    split once per hidden layer, one Bernoulli(keep) draw per layer."""
    import jax

    rng = jax.random.fold_in(jax.numpy.asarray(base_rng), step)
    per_layer: List[List[np.ndarray]] = [[] for _ in widths]
    for s in range(n_shards):
        r = jax.random.fold_in(rng, s) if n_shards > 1 else rng
        keys = jax.random.split(r, len(widths))
        for i, width in enumerate(widths):
            per_layer[i].append(np.asarray(jax.random.bernoulli(
                keys[i], keep[i], (local_batch, width))))
    return [np.concatenate(m) for m in per_layer]


def reference_settings(trainer) -> dict:
    """What the reference needs to know of the run, as plain numbers read
    from the configuration the trainer was built with."""
    cfg = trainer.cfg
    lr = cfg.learning_rate
    if cfg.scale_lr_by_world and trainer.mesh_info.data_size > 1:
        lr *= trainer.mesh_info.data_size      # the Horovod recipe's rule
    if cfg.optimizer.lower() != "adam" or cfg.batch_norm \
            or cfg.embedding_update != "dense" or cfg.model != "deepfm":
        raise ValueError("the reference follows DeepFM with dense Adam only")
    return {"n_layers": len(cfg.deep_layer_sizes),
            "keep": list(cfg.dropout_rates), "l2_reg": cfg.l2_reg,
            "learning_rate": lr}
