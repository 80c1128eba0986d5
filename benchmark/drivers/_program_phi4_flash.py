"""What the seam to the program (``_program``) lacks for ``--model
phi4_flash``: the seeded state and the settings its reference needs.
``make_config``, ``build_trainer``, ``leaf_name``, ``import_tasks_beside``,
``weight_kwargs`` and ``_program_sdar_moe.leaf_specs`` serve this model as
they are.

**The seeding** (the configuration file's ``assumed.weights``). Matrices
and the convolution's taps are Glorot-uniform by their last two dimensions
and the token table uniform in +-embedding_scale = 3, ``weights.py``'s
rules; gains (``GAINS``: the LayerNorms', the 128-wide sub-norm's, the
scan's skip) are 1 + uniform(+-0.1) and every other vector (the norms' and
the projections' biases, the four lambda vectors, the convolution's bias)
uniform in +-0.1, so that a bias or a lambda that is dropped shows. The
scan's decay rates are not drawn: ``mamba_a_log`` is log(1 .. N) over a
channel's states, the family's initial values, which put a chunk's
log-decay where a trained model's lies; ``mamba_dt_bias`` is the inverse
softplus of a step log-uniform in [0.001, 0.1] (as
``_program_kimi_linear``'s ``kda_dt_bias``). The table is also the head, so
the final LayerNorm's gain and bias are scaled by ``FINAL_GAIN`` = 2^-9 as
``_program_lfm2_moe`` scales its final gains, and for its reason: the own
row's logit is then near 9 and the first loss near ln 25,008.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights
from benchmark.drivers import _program

#: Leaves seeded as 1 + u, u uniform in +-0.1.
GAINS = ("norm1", "norm2", "sub_norm", "mamba_d", "final_norm")
TABLE = "tok_emb"
STEP = (1e-3, 1e-1)         # softplus(mamba_dt_bias): log-uniform
#: What the final norm's seeded gain and bias are scaled by.
FINAL_GAIN = 2.0 ** -9
#: (``models.phi4_flash.DECAY_MIN``: a driver imports the program inside
#: ``run``.)
DECAY_MIN = "mamba_chunk_log_decay_min"


def leaf_of(name: str) -> str:
    return name.rsplit(".", 1)[-1]


def seeded_leaf(salts, name: str, shape, kw: dict, xp=np):
    """The seeded values of leaf ``name`` (``salts``: {leaf name: its
    salt}; the module's docstring)."""
    leaf = leaf_of(name)
    if leaf == "mamba_a_log":
        return xp.broadcast_to(xp.log(xp.arange(
            1, shape[1] + 1, dtype=xp.float32)), tuple(shape))
    if leaf in GAINS or leaf == "mamba_dt_bias":
        # u in +-BIAS_SCALE, from the vector's rule
        unit = weights.leaf_values(salts[name], (int(np.prod(shape)),),
                                   xp=xp, **kw).reshape(shape)
        if leaf == "mamba_dt_bias":
            u = unit * xp.float32(0.5 / weights.BIAS_SCALE) + xp.float32(0.5)
            step = xp.exp(xp.float32(np.log(STEP[0]))
                          + xp.float32(np.log(STEP[1] / STEP[0])) * u)
            return step + xp.log(-xp.expm1(-step))
        out = 1.0 + unit
        return out * xp.float32(FINAL_GAIN) if leaf == "final_norm" else out
    out = weights.leaf_values(salts[name], tuple(shape), xp=xp, **kw)
    return out * xp.float32(FINAL_GAIN) if leaf == "final_norm_b" else out


def seeded_state(trainer, seed: int, cell_config: dict):
    """A ``TrainState`` of the benchmark's seeded weights, made on the device
    in one jitted call, with the model's own initial state (its count), and
    the words of the state's key."""
    import jax
    import jax.numpy as jnp

    from deepfm_tpu.train.state import TrainState

    shapes, _ = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_program.leaf_name(p) for p, _ in flat]
    kw = _program.weight_kwargs(cell_config, trainer)

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
    if cfg.model != "phi4_flash" or cfg.optimizer.lower() != "adam" \
            or cfg.l2_reg or trainer.mesh_info.data_size > 1:
        raise ValueError("the reference follows the selective-scan / "
                         "differential-attention decoder under Adam on one "
                         "chip, without L2")
    return {"learning_rate": cfg.learning_rate,
            "sizes": {"head_dim": cfg.attn_head_dim, "eps": cfg.rms_norm_eps,
                      "window": cfg.attn_window,
                      "first_layer": cfg.first_layer}}
