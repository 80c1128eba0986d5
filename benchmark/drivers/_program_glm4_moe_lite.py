"""What the seam to the program (``_program``) lacks for ``--model
glm4_moe_lite``: the seeded state (parameters, and the routers' selection
bias in the model state beside the model's counts) and the settings its
reference needs. A leaf's seeded values are
``_program_kimi_linear.seeded_leaf``'s (matrices Glorot-uniform, gains 1 +
uniform +-0.1, an expert layer's router with the heavy token classes
placed), the selection bias ``_program_lfm2_moe.seeded_bias``'s (uniform in
+-``BIAS_LIMIT`` = 0.02, constant), the router's plan
``_program_kimi_linear.router_plan``'s (a token's balanced share of this
chip is ``top_k * held / experts`` = 4 * 8 / 64 = half an expert a layer:
the plan's period is P = 2, Kimi-Linear's), all by import, and so are
``make_config``, ``build_trainer``, ``leaf_name``, ``import_tasks_beside``
and ``_program_sdar_moe.leaf_specs``.

**The module's leaves** (``mtp.*``) are seeded by the same rules under their
own names: the three norms' gains 1 + uniform +-0.1, ``mtp.w_eh``
Glorot-uniform, ``mtp.block.<leaf>`` as an expert layer's leaf of layer
``decoder_layers`` (the plan's fifth expert block). **The module's router
places the heavy classes by the next token** (``module_router``). A layer's
router is boosted along a heavy token's own row of the token table, which a
layer's stream stays close to; the module's stream is
``[RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)] W_eh``, a projection of two tokens'
rows, so the same boost along the table's row decides nothing there: with
the module's router left to its random draw the heaviest classes (the
most frequent token is 12% of all positions) are free to change experts
as the router's gradient moves the held experts' logits together, and
one sound run of the first 18 held 1.01 of the module's buffer (222 pairs
over it, ``correct: false``: PERF.md section 6, PR 48). So the module's
router is boosted along the direction a heavy next token gives the module's
stream, ``(RMSNorm(Emb(c); enorm)) W_eh[:d]``, by sqrt(2) of a layer's
boost (the stream is half the next token's, half the last layer's output:
the class's logit rises by the same 16).
"""

from __future__ import annotations

import types

import numpy as np

from benchmark import weights
from benchmark.drivers import _program, _program_kimi_linear
from benchmark.drivers._program_lfm2_moe import SELECT_BIAS, seeded_bias

#: Gains this model has beyond ``_program_kimi_linear.GAINS``.
GAINS = ("mla_q_norm", "enorm", "hnorm")
MTP_BLOCK = "mtp.block."


def weight_kwargs(cell_config: dict, trainer) -> dict:
    """``_program.weight_kwargs`` and the router's plan (P = 2) over the
    stack's expert layers and the module's block after them."""
    cfg = trainer.cfg
    blocks = types.SimpleNamespace(
        decoder_layers=cfg.decoder_layers + cfg.mtp_depth,
        **{k: getattr(cfg, k) for k in (
            "dense_layers", "moe_top_k", "moe_experts", "moe_experts_held",
            "moe_first_expert", "feature_size")})
    return {**_program.weight_kwargs(cell_config, trainer),
            "router_plan": _program_kimi_linear.router_plan(blocks),
            "mtp_layer": cfg.decoder_layers}


def module_router(salts, shape, kw: dict, layer: int, xp=np):
    """The module's block's router [d, experts]: its own Glorot draw with
    each heavy class placed as the plan's block ``layer`` says, along the
    (unit) direction that class as the *next* token gives the module's
    stream: ``RMSNorm(Emb(c); enorm) W_eh[:d]`` (the module's docstring)."""
    kw = dict(kw)
    plan = kw.pop("router_plan")
    d = shape[0]
    out = weights.leaf_values(salts[MTP_BLOCK + "router"], tuple(shape),
                              xp=xp, **kw)
    rows = weights.leaf_values(salts[_program_kimi_linear.TABLE],
                               (kw["padded_vocab"], d), xp=xp,
                               rows=plan["rows"], **kw)
    enorm = 1.0 + weights.leaf_values(salts["mtp.enorm"], (d,), xp=xp, **kw)
    w_e = weights.leaf_values(salts["mtp.w_eh"], (2 * d, d), xp=xp, **kw)[:d]
    normed = rows / xp.sqrt(xp.mean(rows * rows, axis=-1, keepdims=True)) \
        * enorm
    # (a class at a time: sums of products, which NumPy and XLA both have)
    boost = xp.asarray(plan["boost"][layer]) * xp.float32(np.sqrt(2.0))
    for c in range(len(plan["rows"])):
        along = xp.sum(normed[c][:, None] * w_e, axis=0)
        unit = along / xp.sqrt(xp.sum(along * along))
        out = out + unit[:, None] * boost[c][None, :]
    return out


def seeded_leaf(salts, name: str, shape, kw: dict, xp=np):
    """The seeded values of leaf ``name`` (the module's docstring)."""
    kw = dict(kw)
    mtp_layer = kw.pop("mtp_layer")
    if _program_kimi_linear.leaf_of(name) in GAINS:
        plain = {k: v for k, v in kw.items() if k != "router_plan"}
        return 1.0 + weights.leaf_values(
            salts[name], (int(np.prod(shape)),), xp=xp, **plain).reshape(shape)
    if name == MTP_BLOCK + "router":
        return module_router(salts, shape, kw, mtp_layer, xp=xp)
    if name.startswith(MTP_BLOCK):      # an expert layer's leaf, by its salt
        alias = f"layers.{mtp_layer}.{name[len(MTP_BLOCK):]}"
        salts, name = {**salts, alias: salts[name]}, alias
    return _program_kimi_linear.seeded_leaf(salts, name, shape, kw, xp=xp)


def bias_shape(cfg) -> tuple:
    return (cfg.decoder_layers - cfg.dense_layers + cfg.mtp_depth,
            cfg.moe_experts)


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
    if cfg.model != "glm4_moe_lite" or cfg.optimizer.lower() != "adam" \
            or cfg.l2_reg or trainer.mesh_info.data_size > 1:
        raise ValueError("the reference follows the latent-attention MoE "
                         "decoder with its multi-token-prediction module "
                         "under Adam on one chip, without L2")
    return {"learning_rate": cfg.learning_rate,
            "sizes": {"nope_dim": cfg.mla_nope_dim,
                      "rope_dim": cfg.mla_rope_dim, "eps": cfg.rms_norm_eps,
                      "theta": cfg.rope_theta, "top_k": cfg.moe_top_k,
                      "route_scale": cfg.moe_route_scale,
                      "first_expert": cfg.moe_first_expert,
                      "mtp_weight": cfg.mtp_loss_weight}}
