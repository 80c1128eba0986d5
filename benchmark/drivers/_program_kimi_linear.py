"""What the seam to the program (``_program``) lacks for ``--model
kimi_linear``: seeded weights (gains near one, the KDA decays' rate and
step size in their published ranges, the router placing the heaviest token
classes as a balanced deployment would), the model's state beside them, and
the settings its reference needs. ``make_config``, ``build_trainer``,
``leaf_name`` and ``import_tasks_beside`` serve this model as they are, and
so does ``_program_sdar_moe.leaf_specs``.

**The seeding**, after ``_program_sdar_moe``'s (PERF.md section 6, PR 31:
what random weights do to a router is taken over, not found again). The
token table is uniform in +-3, so a position's residual stream stays its
token's and a random router routes by token identity. Here a token's
balanced share of this chip is ``top_k * held / experts`` = half an expert,
not one: each of the traffic's ``HEAVY_TOKENS`` most frequent tokens (a
third of all positions; there is no ``[MASK]`` row in this model) sends one
of its 8 experts to this chip in every second expert layer and none in the
others, classes of even and odd rank taking turns (``router_plan``), so
that a layer's held pairs total T/2 up to the difference between the even
and the odd classes' traffic. Every other token keeps the random router.

``kda_a_log`` is the log of a rate uniform in [1, 16] and ``kda_dt_bias``
the inverse softplus of a step size log-uniform in [0.001, 0.1]: the
family's convention for a decay gate (the catalog row has no key for it).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark import weights
from benchmark.drivers import _program
from benchmark.drivers._program_sdar_moe import HEAVY_TOKENS, ROUTER_BOOST

#: Leaves that are RMSNorm gains: seeded as 1 + u, u uniform in +-0.1.
GAINS = ("norm1", "norm2", "kda_out_norm", "mla_kv_norm", "final_norm")
TABLE = "tok_emb"
RATE = (1.0, 16.0)          # exp(kda_a_log): uniform
STEP = (1e-3, 1e-1)         # softplus(kda_dt_bias): log-uniform


def leaf_of(name: str) -> str:
    return name.rsplit(".", 1)[-1]


def router_plan(cfg) -> Dict[str, np.ndarray]:
    """Which experts the heavy token classes are sent to: ``rows`` [C] (the
    traffic's ``HEAVY_TOKENS`` most frequent tokens, most frequent first) and
    ``boost`` [layers, C, experts], ``ROUTER_BOOST`` on each class's
    ``top_k`` prescribed experts of an expert layer and 0 elsewhere. Class c
    has one held expert among them in the expert layers l with c + l even
    and none in the others: half an expert a layer, the share
    ``top_k * held / experts`` of a balanced placement (the general rule
    for another share is not written: the plan refuses it)."""
    from benchmark import traffic_sequences

    held, first = cfg.moe_experts_held, cfg.moe_first_expert
    if 2 * cfg.moe_top_k * held != cfg.moe_experts:
        raise ValueError("the router's plan places half an expert a class "
                         "and layer: top_k * held / experts has to be 1/2")
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
            here = (c + layer + 1) % 2
            away = cfg.moe_top_k - here
            mine = first + (start + np.arange(here)) % held
            theirs = absent[(start * away + np.arange(away)) % len(absent)]
            boost[layer, c, np.concatenate([mine, theirs])] = ROUTER_BOOST
    return {"rows": rows, "boost": boost}


def weight_kwargs(cell_config: dict, trainer) -> dict:
    """``_program.weight_kwargs`` and the router's plan."""
    return {**_program.weight_kwargs(cell_config, trainer),
            "router_plan": router_plan(trainer.cfg)}


def seeded_leaf(salts, name: str, shape, kw: dict, xp=np):
    """The seeded values of leaf ``name`` (``salts``: {leaf name: its salt}):
    ``weights.leaf_values`` (the token table uniform in +-embedding_scale,
    matrices and the convolutions' taps Glorot-uniform by their last two
    dimensions), gains 1 + that, the decays' rate and step size in their
    ranges, and an expert layer's router with the heavy token classes placed
    (``router_plan``), each along its own row of the token table."""
    kw = dict(kw)
    plan = kw.pop("router_plan")
    leaf = leaf_of(name)
    if leaf in GAINS or leaf in ("kda_a_log", "kda_dt_bias"):
        # u in (0, 1), from the vector's uniform +-BIAS_SCALE
        unit = weights.leaf_values(salts[name], (int(np.prod(shape)),),
                                   xp=xp, **kw).reshape(shape)
        if leaf in GAINS:
            return 1.0 + unit
        u = unit * xp.float32(0.5 / weights.BIAS_SCALE) + xp.float32(0.5)
        if leaf == "kda_a_log":
            return xp.log(RATE[0] + (RATE[1] - RATE[0]) * u)
        step = xp.exp(xp.float32(np.log(STEP[0]))
                      + xp.float32(np.log(STEP[1] / STEP[0])) * u)
        return step + xp.log(-xp.expm1(-step))
    out = weights.leaf_values(salts[name], tuple(shape), xp=xp, **kw)
    if leaf == "router":
        layer = int(name.split(".")[1])
        rows = weights.leaf_values(
            salts[TABLE], (kw["padded_vocab"], shape[0]), xp=xp,
            rows=plan["rows"], **kw)
        # (as ``_program_sdar_moe.seeded_leaf``: a row's direction by the
        # length a row of uniform values has on average, a class at a time)
        unit = rows * xp.float32(
            1.0 / (kw["embedding_scale"] * np.sqrt(shape[0] / 3.0)))
        boost = xp.asarray(plan["boost"][layer])
        for c in range(len(plan["rows"])):
            out = out + unit[c][:, None] * boost[c][None, :]
    return out


def seeded_state(trainer, seed: int, cell_config: dict):
    """A ``TrainState`` of the benchmark's seeded weights, made on the device
    in one jitted call, with the model's own initial state (its counts), and
    the words of the state's key."""
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
    if cfg.model != "kimi_linear" or cfg.optimizer.lower() != "adam" \
            or cfg.l2_reg or trainer.mesh_info.data_size > 1:
        raise ValueError("the reference follows the hybrid linear-attention "
                         "MoE decoder under Adam on one chip, without L2")
    return {"learning_rate": cfg.learning_rate,
            "sizes": {"kda_head_dim": cfg.kda_head_dim,
                      "head_dim": cfg.attn_head_dim,
                      "rope_dim": cfg.mla_rope_dim, "eps": cfg.rms_norm_eps,
                      "top_k": cfg.moe_top_k,
                      "route_scale": cfg.moe_route_scale,
                      "first_expert": cfg.moe_first_expert}}
