"""What the seam to the program (``_program``) lacks for ``--model
lfm2_moe``: the seeded state (parameters, and the routers' selection bias in
the model state beside the model's counts) and the settings its reference
needs. A leaf's seeded values are ``_program_kimi_linear.seeded_leaf``'s
(matrices and the convolution's taps Glorot-uniform, gains 1 + uniform
+-0.1, an expert layer's router with the heavy token classes placed), the
per-head gains ``q_norm`` / ``k_norm`` ``_program_sdar_moe.seeded_leaf``'s,
the router's plan ``_program_solar_open2.router_plan``'s general rule, all by
import, and so are ``make_config``, ``build_trainer``, ``leaf_name``,
``import_tasks_beside`` and ``_program_sdar_moe.leaf_specs``.

**The router's plan.** A token's balanced share of this chip is ``top_k *
held / experts`` = 4 * 8 / 32 = one expert in every layer: the plan's period
is P = 1, every heavy class has one held expert among its 4 prescribed ones
in every expert layer (PERF.md section 6, PR 31, PR 33 and PR 37, taken
over and not found again).

**The tied table** (new with this model). The table is uniform in
+-embedding_scale = 3 so that a position's residual stream stays its
token's; it is also the head, so with a final norm's gain of 1 a position's
logit on its own token's row would be about 2048 * 3 / 1.73 = 3,550 and on
any other row 78 in standard deviation: a loss of thousands that says "the
next token is this one". ``FINAL_GAIN`` scales the final norm's seeded gains
(1 + uniform +-0.1) so that the own row's logit is about 7 and the others'
spread 0.15: the first step's loss is near ln 16,384 = 9.7 (the
configuration file's ``assumed.weights`` has the readings).

**The selection bias** is uniform in +-``BIAS_LIMIT``, seeded like a
parameter (its salt is its name's) and constant: nothing moves it.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights
from benchmark.drivers import (_program, _program_kimi_linear,
                               _program_sdar_moe)
from benchmark.drivers._program_solar_open2 import router_plan

#: (``models.lfm2_moe.SELECT_BIAS``, named here as the other drivers name the
#: program's counts: a driver imports the program inside ``run``.)
SELECT_BIAS = "moe_select_bias"
#: The seeded selection bias is uniform in +-this: a fifth of the spread of
#: a random router's sigmoid scores around a token's 4th largest, so that it
#: decides some picks and not most.
BIAS_LIMIT = 0.02
#: What the final norm's seeded gains are scaled by (the module's docstring).
FINAL_GAIN = 2.0 ** -9


def weight_kwargs(cell_config: dict, trainer) -> dict:
    """``_program.weight_kwargs`` and the router's plan (P = 1)."""
    return {**_program.weight_kwargs(cell_config, trainer),
            "router_plan": router_plan(trainer.cfg, offset=0)}


def seeded_leaf(salts, name: str, shape, kw: dict, xp=np):
    """The seeded values of leaf ``name`` (the module's docstring)."""
    leaf = _program_kimi_linear.leaf_of(name)
    if leaf in ("q_norm", "k_norm"):
        return _program_sdar_moe.seeded_leaf(salts, name, shape, kw, xp=xp)
    out = _program_kimi_linear.seeded_leaf(salts, name, shape, kw, xp=xp)
    return out * xp.float32(FINAL_GAIN) if leaf == "final_norm" else out


def seeded_bias(salt, shape, xp=np):
    """The selection bias [expert layers, experts], uniform in
    +-``BIAS_LIMIT``."""
    n = int(np.prod(shape))
    # (as a table of n rows of one: the limit is then ``embedding_scale``,
    # one multiplication, which the device and the host round alike)
    return weights.leaf_values(salt, (n,), xp=xp, feature_size=n,
                               padded_vocab=n,
                               embedding_scale=BIAS_LIMIT).reshape(shape)


def bias_shape(cfg) -> tuple:
    return (cfg.decoder_layers - cfg.dense_layers, cfg.moe_experts)


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
    if cfg.model != "lfm2_moe" or cfg.optimizer.lower() != "adam" \
            or cfg.l2_reg or trainer.mesh_info.data_size > 1:
        raise ValueError("the reference follows the short-convolution / GQA "
                         "MoE decoder under Adam on one chip, without L2")
    return {"learning_rate": cfg.learning_rate,
            "sizes": {"head_dim": cfg.attn_head_dim, "eps": cfg.rms_norm_eps,
                      "theta": cfg.rope_theta, "top_k": cfg.moe_top_k,
                      "route_scale": cfg.moe_route_scale,
                      "first_expert": cfg.moe_first_expert}}
