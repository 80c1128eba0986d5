"""What the seam to the program (``_program``) lacks for ``--model
sdar_moe``: seeded weights whose norm gains are near one and whose router
places the heaviest token classes as a balanced deployment would, the
model's state (its counts) beside them, the settings its reference needs,
and the noise a step draws. ``make_config``, ``build_trainer``,
``leaf_name`` and ``import_tasks_beside`` serve this model as they are.

**Why the seeding is what it is** (my chip runs, PR 31). With a token table
of +-0.1 under Glorot projections and gains near 1, attention averages some
thousand values, every position's residual stream is nearly one vector
after two layers, and the router sends every position to the same 8 experts
(fullest expert 16 times the mean). Sharper attention (QK-norm gains of 2
to 3) spreads the load, and makes the step chaotic in bfloat16: the
gradients of a sound run then differ from the float32 reference's by 60 to
125%, and no limit could tell a sound run from a wrong one. At +-3
(``assumed.embedding_scale``) a position's stream stays its token's, and a
random router then routes by token identity: ``[MASK]``, a quarter of all
positions, sends all of them to the same 8 experts, of which this chip
holds 0 to 8 by the seed's luck, so a layer's held pairs swing by a quarter
of the mean, drift as Adam's first steps move the heavy tokens' logits, and
passed a buffer of 2.5 times the mean in one run of 27. A deployment places
its experts so that every rank's load is near the mean; the seeded router
does the same for the token classes that carry the load (``router_plan``):
``[MASK]`` and the traffic's ``HEAVY_TOKENS`` most frequent tokens each
send exactly their share of experts (``top_k * held / experts``, one of 8)
to this chip, by a logit of about ``ROUTER_BOOST * sqrt(width)`` on 8
prescribed experts, a margin no rounding and no 30 steps of Adam cross.
The other tokens (each under 1% of the positions) keep the random router.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from benchmark import weights
from benchmark.drivers import _program

#: Leaves that are RMSNorm gains: seeded as 1 + u, u uniform in +-0.1 (the
#: seeded weights' scale for a vector), so that a gain that is dropped or
#: not trained shows and the block still normalises.
GAINS = ("norm1", "norm2", "q_norm", "k_norm", "final_norm")


def is_gain(name: str) -> bool:
    return name.rsplit(".", 1)[-1] in GAINS


def leaf_specs(trainer) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every parameter the program's model declares."""
    import jax

    shapes, _ = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return {_program.leaf_name(p): tuple(s.shape) for p, s in flat}


#: What the seeded router adds, for a heavy token class, to each of its
#: prescribed experts' columns along the class's own (unit) direction: the
#: class's logits there rise by about ROUTER_BOOST * sqrt(width), 16 at the
#: published width, beside a random router's spread of 1.4.
ROUTER_BOOST = 0.35
#: The traffic's most frequent tokens placed like ``[MASK]``: with these the
#: placed classes hold 60% of the positions, and the largest class left is
#: under 1% of them.
HEAVY_TOKENS = 8
ROUTER, TABLE = "layers.router", "tok_emb"


def router_plan(cfg) -> Dict[str, np.ndarray]:
    """Which experts the heavy token classes are sent to: ``rows`` [C] (the
    ``[MASK]`` row and the traffic's ``HEAVY_TOKENS`` most frequent tokens)
    and ``boost`` [layers, C, experts], ``ROUTER_BOOST`` on each class's
    ``top_k`` prescribed experts of a layer and 0 elsewhere. Of the
    prescribed experts ``top_k * held / experts`` (rounded) are held here,
    as every rank of a balanced placement would see; which ones changes
    from class to class and from layer to layer."""
    from benchmark import traffic_sequences

    held, first = cfg.moe_experts_held, cfg.moe_first_expert
    absent = np.asarray([e for e in range(cfg.moe_experts)
                         if not first <= e < first + held])
    here = min(int(round(cfg.moe_top_k * held / cfg.moe_experts)), held)
    away = cfg.moe_top_k - here
    heavy = min(HEAVY_TOKENS, cfg.feature_size - 1)
    rows = np.concatenate([[cfg.feature_size - 1],
                           traffic_sequences.tokens_of_ranks(
                               np.arange(heavy), cfg.feature_size)])
    boost = np.zeros((cfg.decoder_layers, len(rows), cfg.moe_experts),
                     np.float32)
    for layer in range(cfg.decoder_layers):
        for c in range(len(rows)):
            start = c + layer * len(rows)
            mine = first + (start * here + np.arange(here)) % held
            theirs = absent[(start * away + np.arange(away)) % len(absent)]
            boost[layer, c, np.concatenate([mine, theirs])] = ROUTER_BOOST
    return {"rows": rows, "boost": boost}


def weight_kwargs(cell_config: dict, trainer) -> dict:
    """``_program.weight_kwargs`` and the router's plan."""
    return {**_program.weight_kwargs(cell_config, trainer),
            "router_plan": router_plan(trainer.cfg)}


def seeded_leaf(salts, name: str, shape, kw: dict, xp=np):
    """The seeded values of leaf ``name`` (``salts``: {leaf name: its salt}):
    ``weights.leaf_values`` (the token table uniform in +-embedding_scale
    with its padding rows zero, matrices Glorot-uniform by their last two
    dimensions), gains 1 + that, and the router with the heavy token
    classes placed (``router_plan``): each class's boost along the
    direction of its own row of the token table."""
    kw = dict(kw)
    plan = kw.pop("router_plan")
    if is_gain(name):
        unit = weights.leaf_values(salts[name], (int(np.prod(shape)),),
                                   xp=xp, **kw)
        return 1.0 + unit.reshape(shape)
    out = weights.leaf_values(salts[name], tuple(shape), xp=xp, **kw)
    if name == ROUTER:
        rows = weights.leaf_values(
            salts[TABLE], (kw["padded_vocab"], shape[1]), xp=xp,
            rows=plan["rows"], **kw)
        # A row's direction, by the length a row of uniform values has on
        # average (no reduction: the program's jitted seeding and the
        # check's leaf by leaf then agree to the bit), one class at a time.
        unit = rows * xp.float32(
            1.0 / (kw["embedding_scale"] * np.sqrt(shape[1] / 3.0)))
        boost = xp.asarray(plan["boost"])
        for c in range(len(plan["rows"])):
            out = out + unit[c][None, :, None] * boost[:, c][:, None, :]
    return out


def seeded_state(trainer, seed: int, cell_config: dict):
    """A ``TrainState`` of the benchmark's seeded weights, made on the device
    in one jitted call, with the model's own initial state (its counts), and
    the words of the state's key (the state is donated to the first
    dispatch, its key with it)."""
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
    if cfg.model != "sdar_moe" or cfg.optimizer.lower() != "adam" \
            or cfg.l2_reg or trainer.mesh_info.data_size > 1:
        raise ValueError("the reference follows the block-diffusion MoE "
                         "decoder under Adam on one chip, without L2")
    return {"learning_rate": cfg.learning_rate,
            "sizes": {"head_dim": cfg.attn_head_dim, "top_k": cfg.moe_top_k,
                      "first_expert": cfg.moe_first_expert,
                      "eps": cfg.rms_norm_eps, "theta": cfg.rope_theta,
                      "block": cfg.diffusion_block}}


def step_noise(trainer, base_rng, step: int, tokens: np.ndarray):
    """(noisy tokens, t a block) that ``Trainer._step_impl`` draws at
    ``step`` on one chip: the state's key folded with the step, through the
    program's one function for it."""
    import jax

    from deepfm_tpu.models import sdar_moe

    cfg = trainer.cfg
    key = jax.random.fold_in(jax.numpy.asarray(base_rng), step)
    noisy, t = sdar_moe.draw_noise(
        key, jax.numpy.asarray(tokens, jax.numpy.int32),
        block=cfg.diffusion_block, t_min=cfg.diffusion_t_min,
        mask_id=trainer.model.mask_id)
    return np.asarray(noisy), np.asarray(t)
