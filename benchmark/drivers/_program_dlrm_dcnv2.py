"""What the seam to the program (``_program``) lacks for ``--model
dlrm_dcnv2``: the settings its reference needs, and where the program's
optimizer state keeps Adagrad's accumulator. Everything else of the seam
(``make_config``, ``build_trainer``, ``seeded_state``, ``leaf_specs``,
``weight_kwargs``, ``import_tasks_beside``) serves this model as it is.
"""

from __future__ import annotations


def reference_settings(trainer, cell_config: dict) -> dict:
    """What the reference needs to know of the run, as plain numbers read
    from the configuration the trainer was built with; Adagrad's two
    constants are the configuration file's (``assumed``), which states what
    the program's ``optax.adagrad`` is built with."""
    cfg = trainer.cfg
    if cfg.model != "dlrm_dcnv2" or cfg.optimizer.lower() != "adagrad" \
            or cfg.embedding_update != "dense" or cfg.l2_reg \
            or any(keep < 1.0 for keep in cfg.dropout_rates) \
            or trainer.mesh_info.data_size > 1:
        raise ValueError("the reference follows DLRM-DCNv2 under dense "
                         "Adagrad on one chip, without L2 or dropout")
    assumed = cell_config["assumed"]
    return {"n_bottom": len(cfg.bottom_layer_sizes),
            "n_cross": cfg.cross_layers, "n_top": len(cfg.deep_layer_sizes),
            "learning_rate": cfg.learning_rate,
            "adagrad_init": assumed["adagrad_initial_accumulator"],
            "adagrad_eps": assumed["adagrad_eps"]}


def accumulator(opt_state):
    """Adagrad's sum of squared gradients, a tree shaped like the
    parameters, out of the program's optimizer state."""
    import optax

    return optax.tree_utils.tree_get(opt_state, "sum_of_squares")
