"""What the seam to the program (``_program``) lacks for ``--model afmoe``:
the seeded state (parameters, and the routers' selection bias in the model
state beside the model's counts) and the settings its reference needs. A
leaf's seeded values are ``_program_kimi_linear.seeded_leaf``'s (matrices
Glorot-uniform, gains 1 + uniform +-0.1, an expert layer's router with the
heavy token classes placed), the selection bias
``_program_lfm2_moe.seeded_bias``'s (uniform in +-``BIAS_LIMIT`` = 0.02,
constant), the router's plan ``_program_solar_open2.router_plan``'s general
rule, all by import, and so are ``make_config``, ``build_trainer``,
``leaf_name``, ``import_tasks_beside`` and ``_program_sdar_moe.leaf_specs``.

**The router's plan.** A token's balanced share of this chip is ``top_k *
held / experts`` = 8 * 16 / 128 = one expert in every layer: the plan's
period is P = 1, LFM2's: every heavy class has one held expert among its 8
prescribed ones in every expert layer.

**The scaled embedding.** The stream's first state is ``sqrt(d) Emb[t]``, so
the table is uniform in +-3 / sqrt(d) (the configuration file's
``embedding_scale``) and the state in +-3, the other decoder cells'. The
router's boost runs along a heavy token's own row of the table as a unit
direction, which no constant moves.

**This model's further gains** (``GAINS``: the two norms on the sublayers'
outputs and the per-head norms of q and k) are seeded as every gain is, 1 +
uniform +-0.1, **the two on the sublayers' outputs times ``POST_GAIN``**. A
norm on a sublayer's output hands the stream a vector of unit RMS whatever
the sublayer made, and what seeded attention makes is nearly one vector for
every position (a diffuse softmax's mean of the values): at a gain of 1 ten
such vectors over five layers stand beside a token's +-3 and every
position's router sees the same offset, so that tokens pick experts
together. Read on the chip at a gain of 1 (PR 53, 6 seeds): a layer's
fullest expert 5.0-7.8 x the mean, the held pairs of a step 52,003-75,942
from seed to seed where a balanced placement holds 65,536, the fullest
layer 0.53-0.80 of its buffer, and the step's time following the pairs
(714.5-727.3 ms: the rate spread by 1.5%, over half the metric's bound). At
0.25 a sublayer's output is a seventh of a token's own row, the stream stays
its token's as in the other decoder cells (PERF.md section 6, PR 31), and
the routers place by token identity.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights
from benchmark.drivers import _program, _program_kimi_linear
from benchmark.drivers._program_lfm2_moe import (SELECT_BIAS, bias_shape,
                                                 seeded_bias)
from benchmark.drivers._program_solar_open2 import router_plan

#: Gains this model has beyond ``_program_kimi_linear.GAINS``.
GAINS = ("norm1_post", "norm2_post", "q_norm", "k_norm")
#: What the seeded gains of the two norms on the sublayers' outputs are
#: scaled by (the module's docstring).
POST_GAIN = 0.25

__all__ = ["SELECT_BIAS", "bias_shape", "seeded_bias", "weight_kwargs",
           "seeded_leaf", "seeded_state", "reference_settings"]


def weight_kwargs(cell_config: dict, trainer) -> dict:
    """``_program.weight_kwargs`` and the router's plan (P = 1)."""
    return {**_program.weight_kwargs(cell_config, trainer),
            "router_plan": router_plan(trainer.cfg, offset=0)}


def seeded_leaf(salts, name: str, shape, kw: dict, xp=np):
    """The seeded values of leaf ``name`` (the module's docstring)."""
    leaf = _program_kimi_linear.leaf_of(name)
    if leaf in GAINS:
        plain = {k: v for k, v in kw.items() if k != "router_plan"}
        gain = 1.0 + weights.leaf_values(
            salts[name], (int(np.prod(shape)),), xp=xp, **plain).reshape(shape)
        return gain * xp.float32(POST_GAIN) if leaf.endswith("_post") \
            else gain
    return _program_kimi_linear.seeded_leaf(salts, name, shape, kw, xp=xp)


def seeded_state(trainer, seed: int, cell_config: dict):
    """A ``TrainState`` of the benchmark's seeded weights, made on the device
    in one jitted call, with the model's own initial counts and the seeded
    selection bias as its model state, and the words of the state's key."""
    import jax
    import jax.numpy as jnp

    from deepfm_tpu.train.state import TrainState

    shapes, _ = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_program.leaf_name(p) for p, _ in flat]
    kw = weight_kwargs(cell_config, trainer)
    shape = bias_shape(trainer.cfg)

    def make(salts, bias_salt):
        by_name = {n: salts[i] for i, n in enumerate(names)}
        leaves = [seeded_leaf(by_name, names[i], s.shape, kw, xp=jnp)
                  for i, (_, s) in enumerate(flat)]
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        mstate = {**trainer.model.init_counts(),
                  SELECT_BIAS: seeded_bias(bias_salt, shape, xp=jnp)}
        return params, trainer.tx.init(params), mstate

    salts = np.asarray([weights.leaf_salt(seed, n) for n in names], np.uint32)
    params, opt_state, mstate = jax.jit(make)(
        salts, np.uint32(weights.leaf_salt(seed, SELECT_BIAS)))
    rng = jax.random.PRNGKey(int(seed) % (2 ** 31))
    return TrainState.create(params, opt_state, mstate, rng), np.asarray(rng)


def reference_settings(trainer) -> dict:
    """What the reference needs to know of the run, as plain numbers read
    from the configuration the trainer was built with."""
    cfg = trainer.cfg
    if cfg.model != "afmoe" or cfg.optimizer.lower() != "adam" \
            or cfg.l2_reg or trainer.mesh_info.data_size > 1:
        raise ValueError("the reference follows the windowed / global "
                         "gated-attention MoE decoder under Adam on one "
                         "chip, without L2")
    return {"learning_rate": cfg.learning_rate,
            "sizes": {"head_dim": cfg.attn_head_dim, "eps": cfg.rms_norm_eps,
                      "theta": cfg.rope_theta, "window": cfg.attn_window,
                      "layer_types": tuple(cfg.layer_type_list),
                      "top_k": cfg.moe_top_k,
                      "route_scale": cfg.moe_route_scale,
                      "first_expert": cfg.moe_first_expert}}
