"""What the seam to the program (``_program``) lacks for ``--model
solar_open2``: the router's plan at this configuration's share of a layer
(the general rule ``_program_kimi_linear.router_plan`` leaves unwritten),
the seeded state with it, and the settings its reference needs. A leaf's
seeded values (gains near one, the KDA decays' rate and step size in their
published ranges, a router with the heavy token classes placed) are
``_program_kimi_linear.seeded_leaf``'s, by import, and so are
``make_config``, ``build_trainer``, ``leaf_name``, ``import_tasks_beside``
and ``_program_sdar_moe.leaf_specs``.

**The router's plan.** The seeding is ``_program_sdar_moe``'s and
``_program_kimi_linear``'s (PERF.md section 6, PR 31 and PR 33, taken over
and not found again): the token table is uniform in +-3, so a position's
residual stream stays its token's and a random router routes by token
identity; the traffic's ``HEAVY_TOKENS`` most frequent tokens (a third of
all positions) are placed as a balanced deployment would place them. A
token's balanced share of this chip is ``top_k * held / experts`` of an
expert a layer: one in ``P = experts / (top_k * held)`` layers (Kimi-Linear:
P = 2; here 320 / 64 = 5). Heavy class c (by rank, 0 the most frequent) has
one held expert among its ``top_k`` prescribed ones in the expert layers l
with ``(c + l + offset) mod P = 0`` and none in the others. With fewer
layers here than P (4 of a period of 5) one residue of c falls on a layer of
another pipeline stage; ``PLAN_OFFSET`` says which.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark import weights
from benchmark.drivers import _program
from benchmark.drivers._program_kimi_linear import seeded_leaf
from benchmark.drivers._program_sdar_moe import HEAVY_TOKENS, ROUTER_BOOST

#: The plan's offset for ``--model solar_open2``: with layers 0-3 here, the
#: classes of rank 0 and 5 (c + l + 1 = 0 mod 5 only at l = 4) have their
#: held expert in the next stage's first layer, and the fullest layer here
#: (l = 3: ranks 1 and 6) is seeded at 1.02 of the balanced T/5 (largest of
#: 20 counted layer samples 1.19), where any other offset seeds a layer with
#: rank 0's 12% of all positions at 1.35 to 1.49 of it: the drift of a run
#: (measured here: up to 1.71 times the seeded load in 45 steps) then stays
#: inside the buffer's 3.125 x T/5. The four layers' total is 0.90 of
#: a balanced deployment's (counted: the configuration file's
#: ``router_placement``).
PLAN_OFFSET = 1


def plan_period(cfg) -> int:
    """P: a heavy class has a held expert here in one of every P layers."""
    share = cfg.moe_top_k * cfg.moe_experts_held
    if cfg.moe_experts % share:
        raise ValueError("the router's plan places one expert a class in "
                         "one of every P layers: top_k * held has to "
                         "divide experts")
    return cfg.moe_experts // share


def router_plan(cfg, offset: int = PLAN_OFFSET) -> Dict[str, np.ndarray]:
    """Which experts the heavy token classes are sent to: ``rows`` [C] (the
    traffic's ``HEAVY_TOKENS`` most frequent tokens, most frequent first) and
    ``boost`` [layers, C, experts], ``ROUTER_BOOST`` on each class's
    ``top_k`` prescribed experts of an expert layer and 0 elsewhere. Class c
    has one held expert among them in the expert layers l with
    ``(c + l + offset) mod P = 0`` and none in the others (``plan_period``).
    At P = 2 and offset 0 it is ``_program_kimi_linear.router_plan``'s, expert
    for expert."""
    from benchmark import traffic_sequences

    held, first = cfg.moe_experts_held, cfg.moe_first_expert
    period = plan_period(cfg)
    absent = np.asarray([e for e in range(cfg.moe_experts)
                         if not first <= e < first + held])
    heavy = min(HEAVY_TOKENS, cfg.feature_size - 1)
    rows = traffic_sequences.tokens_of_ranks(np.arange(heavy),
                                             cfg.feature_size)
    boost = np.zeros((cfg.decoder_layers, heavy, cfg.moe_experts),
                     np.float32)
    for layer in range(cfg.dense_layers, cfg.decoder_layers):
        for c in range(heavy):
            start = c + layer * heavy
            here = int((c + layer + offset) % period == 0)
            away = cfg.moe_top_k - here
            mine = first + (start + np.arange(here)) % held
            theirs = absent[(start * away + np.arange(away)) % len(absent)]
            boost[layer, c, np.concatenate([mine, theirs])] = ROUTER_BOOST
    return {"rows": rows, "boost": boost}


def weight_kwargs(cell_config: dict, trainer) -> dict:
    """``_program.weight_kwargs`` and the router's plan."""
    return {**_program.weight_kwargs(cell_config, trainer),
            "router_plan": router_plan(trainer.cfg)}


def seeded_state(trainer, seed: int, cell_config: dict):
    """A ``TrainState`` of the benchmark's seeded weights, made on the device
    in one jitted call, with the model's own initial state (its counts), and
    the words of the state's key
    (``_program_kimi_linear.seeded_state`` under this module's plan)."""
    import jax
    import jax.numpy as jnp

    from deepfm_tpu.train.state import TrainState

    shapes, _ = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_program.leaf_name(p) for p, _ in flat]
    kw = weight_kwargs(cell_config, trainer)

    def make(salts):
        by_name = {n: salts[i] for i, n in enumerate(names)}
        leaves = [seeded_leaf(by_name, names[i], s.shape, kw, xp=jnp)
                  for i, (_, s) in enumerate(flat)]
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        return params, trainer.tx.init(params), trainer.model.init_counts()

    salts = np.asarray([weights.leaf_salt(seed, n) for n in names], np.uint32)
    params, opt_state, counts = jax.jit(make)(salts)
    rng = jax.random.PRNGKey(int(seed) % (2 ** 31))
    return TrainState.create(params, opt_state, counts, rng), np.asarray(rng)


def reference_settings(trainer) -> dict:
    """What the reference needs to know of the run, as plain numbers read
    from the configuration the trainer was built with."""
    cfg = trainer.cfg
    if cfg.model != "solar_open2" or cfg.optimizer.lower() != "adam" \
            or cfg.l2_reg or trainer.mesh_info.data_size > 1:
        raise ValueError("the reference follows the gated-GQA / KDA MoE "
                         "decoder under Adam on one chip, without L2")
    return {"learning_rate": cfg.learning_rate,
            "sizes": {"kda_head_dim": cfg.kda_head_dim,
                      "head_dim": cfg.attn_head_dim, "eps": cfg.rms_norm_eps,
                      "top_k": cfg.moe_top_k,
                      "route_scale": cfg.moe_route_scale,
                      "first_expert": cfg.moe_first_expert}}
