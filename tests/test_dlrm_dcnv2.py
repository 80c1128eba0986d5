"""``--model dlrm_dcnv2`` (MLPerf DLRM-DCNv2) on the trainer's normal path.

The program against the benchmark's plain reference
(``benchmark/reference_dlrm_dcnv2.py``, which imports nothing of the
program) at a small size; what ``Config`` refuses for this model; the named
scopes its blocks carry into the compiled step; and the launcher's train ->
evaluate -> export -> ``load_serving`` round trip."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_dlrm_dcnv2 as ref  # noqa: E402
from benchmark.drivers._program import leaf_name  # noqa: E402
from deepfm_tpu.config import Config  # noqa: E402
from deepfm_tpu.data import libsvm  # noqa: E402
from deepfm_tpu.train import Trainer, tasks  # noqa: E402
from deepfm_tpu.utils import export as export_lib  # noqa: E402
from deepfm_tpu.utils import profiling  # noqa: E402

# The published shape with every width cut by 16 to 64: 13 numeric + 26
# categorical fields, K 8, bottom 8/4/8, three rank-4 cross layers on
# 27 * 8 = 216, top 16/16/8/4.
V, F, N, K, B = 2013, 39, 13, 8, 32
SMALL = dict(model="dlrm_dcnv2", feature_size=V, field_size=F,
             numeric_fields=N, embedding_size=K, bottom_layers="8,4,8",
             cross_layers=3, cross_rank=4, deep_layers="16,16,8,4",
             dropout="1,1,1,1", optimizer="Adagrad", learning_rate=0.004,
             l2_reg=0.0, batch_size=B, steps_per_loop=1, log_steps=0,
             scale_lr_by_world=False, mesh_data=1, mesh_model=1)
SHAPE = dict(n_bottom=3, n_cross=3, n_top=4)
ADAGRAD = dict(learning_rate=0.004, adagrad_init=1e-8, adagrad_eps=1e-7)

# Program and reference do the same float32 arithmetic in another order
# (XLA:CPU fuses and reassociates the reductions of the two differently), so
# they part by a few float32 roundings through ~10 matrix products: 1e-5 of
# a leaf's norm holds with a decade to spare (measured up to 1e-6), and
# bfloat16 compute, whose rounding is 4e-3 a product, misses it by a
# hundredfold and more.
TOL = 1e-5


def flat(tree):
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {leaf_name(p): np.array(x) for p, x in leaves}    # a copy


def rel(got, want):
    """|got - want| over |want|, by norm; 0 for two all-zero arrays."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def batches(n, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(N, V // 2, (n, B, F)).astype(np.int32)   # rows of
    ids[..., :N] = np.arange(N)                # the upper half stay untouched
    vals = np.ones((n, B, F), np.float32)
    vals[..., :N] = rng.lognormal(0.0, 0.5, (n, B, N))
    label = (rng.random((n, B, 1)) < 0.3).astype(np.float32)
    return [{"feat_ids": ids[i], "feat_vals": vals[i], "label": label[i]}
            for i in range(n)]


def seeded(trainer, seed=1):
    """The trainer's initial state with every leaf drawn anew, biases
    included (the model starts them at zero, where a dropped bias hides)."""
    state = trainer.init_state(seed)
    rng = np.random.default_rng(seed)
    params = jax.tree.map(
        lambda x: jnp.asarray(rng.uniform(-0.3, 0.3, x.shape), x.dtype),
        state.params)
    params["fm_v"] = params["fm_v"].at[V:].set(0.0)          # pad rows
    return trainer._place(state.replace(
        params=params, opt_state=trainer.tx.init(params)))


@pytest.fixture(scope="module", params=[
    ("float32", 1, 1), ("bfloat16", 1, 1), ("float32", 2, 1),
    ("float32", 1, 2)], ids=lambda p: "%s-data%d-model%d" % p)
def pair(request):
    """(compute dtype, trainer, its seeded state, the same parameters flat
    for the reference), on one device, over two data replicas and with the
    table's rows over two devices (conftest's virtual CPU devices)."""
    dtype, data, model = request.param
    trainer = Trainer(Config(**{**SMALL, "compute_dtype": dtype,
                                "mesh_data": data, "mesh_model": model}))
    state = seeded(trainer)
    return dtype, trainer, state, flat(state.params)


def judge(dtype, gap, what):
    """float32 compute has to meet the tolerance, bfloat16 has to miss it:
    the comparison is tight enough to tell the two apart."""
    if dtype == "float32":
        assert gap < TOL, (what, gap)
    else:
        assert gap > 10 * TOL, (what, gap)


def test_logits_and_loss_match_the_reference(pair):
    dtype, trainer, state, params0 = pair
    batch = batches(1)[0]
    got, _ = trainer.model.apply(
        state.params, state.model_state, batch["feat_ids"],
        batch["feat_vals"], train=False)
    with jax.default_matmul_precision("highest"):
        want = ref.logits({k: jnp.asarray(v) for k, v in params0.items()},
                          batch["feat_ids"][:, N:], batch["feat_vals"][:, :N],
                          **SHAPE)
    judge(dtype, rel(got, want), "logits")
    loss = float(trainer._mean_loss(got, batch))
    want_loss = float(ref.log_loss(want, batch["label"].reshape(-1)))
    if dtype == "float32":
        assert abs(loss - want_loss) < 1e-6


def test_gradients_of_every_leaf_match_the_reference(pair):
    dtype, trainer, state, params0 = pair
    batch = batches(1)[0]

    def loss(params):
        return trainer._loss_terms(
            params, state.model_state, batch, train=True, rng=None,
            shard_axis=None, data_axis=None)[1]

    got = flat(jax.grad(loss)(state.params))
    follower = ref.Follower(params0, np.arange(trainer.model.padded_vocab),
                            **SHAPE, **ADAGRAD)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(follower._loss)(
            follower.params, jnp.asarray(batch["feat_ids"][:, N:]),
            jnp.asarray(batch["feat_vals"][:, :N]),
            jnp.asarray(batch["label"].reshape(-1)))
    assert set(got) == set(want) and len(got) == 1 + 6 + 9 + 10
    worst = max(rel(got[k], want[k]) for k in want)
    judge(dtype, worst, "gradients")
    if dtype == "float32":
        assert all(np.any(g != 0) for g in got.values())   # every leaf learns


def test_three_adagrad_steps_match_and_untouched_rows_do_not_move(pair):
    dtype, trainer, state, params0 = pair
    steps = batches(3, seed=5)
    state = jax.tree.map(jnp.copy, state)    # train_step donates its state
    rows = np.arange(trainer.model.padded_vocab)
    follower = ref.Follower(params0, rows, **SHAPE, **ADAGRAD)
    xent = None
    for batch in steps:
        state, metrics = trainer.train_step(state, trainer.put_batch(batch))
        xent = follower.step(batch["feat_ids"][:, N:],
                             batch["feat_vals"][:, :N], batch["label"])
    touched = np.isin(rows, np.unique(
        np.stack([b["feat_ids"][:, N:] for b in steps])))
    assert 0 < touched.sum() < V // 2
    from benchmark.drivers._program_dlrm_dcnv2 import accumulator

    gaps = ref.dispatch_gaps(
        flat(state.params), flat(accumulator(state.opt_state)),
        float(metrics["xent"]), follower, xent, params0, {"fm_v"},
        len(rows), touched)
    # No L2 term: a row the batches did not look up (the numeric fields'
    # slot rows among them) keeps its value and its accumulator bit for bit.
    assert gaps["untouched_rows_moved"] == 0
    assert not touched[:N].any()
    got, want = flat(state.params), flat(follower.params)
    worst = max(rel(got[k] - params0[k], want[k] - params0[k]) for k in want)
    judge(dtype, worst, "the parameters' change")
    if dtype == "float32":
        assert gaps["xent_gap"] < 1e-6
        assert gaps["accumulator_gap"] < TOL > gaps["param_change_gap"]


def test_reference_matches_a_hand_computed_example():
    # One numeric and one categorical field, K=2, bottom 1 -> 2, one cross
    # layer of rank 1 on D=4, no hidden top layer.
    p = {"bottom.layers.0.w": np.array([[1.0, -2.0]], np.float32),
         "bottom.layers.0.b": np.array([0.5, 0.5], np.float32),
         "fm_v": np.array([[9.0, 9.0], [3.0, -1.0]], np.float32),
         "cross.0.v": np.array([[1.0], [0.0], [2.0], [1.0]], np.float32),
         "cross.0.u": np.array([[0.5, 1.0, 0.0, -1.0]], np.float32),
         "cross.0.b": np.array([0.0, 0.1, 0.2, 0.3], np.float32),
         "tower.out.w": np.array([[1.0], [2.0], [3.0], [4.0]], np.float32),
         "tower.out.b": np.array([-0.25], np.float32)}
    got = ref.logits(p, np.array([[1]]), np.array([[2.0]], np.float32),
                     n_bottom=1, n_cross=1, n_top=0)
    b = np.maximum([2.0 * 1.0 + 0.5, 2.0 * -2.0 + 0.5], 0.0)    # [2.5, 0]
    x0 = np.concatenate([b, [3.0, -1.0]])                       # [2.5,0,3,-1]
    xv = x0 @ [1.0, 0.0, 2.0, 1.0]                              # 7.5
    x1 = x0 * (xv * np.array([0.5, 1.0, 0.0, -1.0])       # [11.875,0,3.6,6.2]
               + [0.0, 0.1, 0.2, 0.3]) + x0
    want = float(x1 @ [1.0, 2.0, 3.0, 4.0]) - 0.25
    assert abs(want - 47.225) < 1e-9 and abs(float(got[0]) - want) < 1e-5


def test_reference_adagrad_step_is_optaxs():
    import optax

    p0 = {"w": jnp.asarray([0.5, -0.25, 2.0]), "t": jnp.ones((3, 2))}
    g = {"w": jnp.asarray([1e-5, -0.3, 0.0]),
         "t": jnp.asarray([[0.1, -1e-6], [0.0, 0.0], [2.0, 0.5]])}
    tx = optax.adagrad(0.004, initial_accumulator_value=1e-8)
    updates, opt = tx.update(g, tx.init(p0), p0)
    want = optax.apply_updates(p0, updates)
    s = jax.tree.map(lambda a, b: np.float32(1e-8) + b * b, p0, g)
    got = jax.tree.map(lambda p, a, b: p - 0.004 * b / jnp.sqrt(a + 1e-7),
                       p0, s, g)
    for k in p0:
        assert np.allclose(got[k], want[k], rtol=1e-6, atol=0)
        assert np.array_equal(np.asarray(got[k])[np.asarray(g[k]) == 0],
                              np.asarray(p0[k])[np.asarray(g[k]) == 0])
    assert np.allclose(opt[0].sum_of_squares["t"], s["t"], rtol=1e-6)


@pytest.mark.parametrize("change, says", [
    (dict(history_max_len=4), "history_max_len"),
    (dict(tasks="ctr,cvr"), "tasks"),
    (dict(batch_norm=True), "batch_norm"),
    (dict(embedding_update="sparse", optimizer="Adam"), "embedding_update"),
    (dict(bottom_layers="8,4"), "bottom_layers ending in embedding_size"),
    (dict(bottom_layers=""), "bottom_layers ending in embedding_size"),
    (dict(numeric_fields=0), "numeric_fields"),
    (dict(numeric_fields=F), "numeric_fields"),
    (dict(cross_layers=0), "cross_layers"),
    (dict(model="deepfm"), "belong to --model dlrm_dcnv2"),
    (dict(model="dcnv2", bottom_layers=""), "belong to --model dlrm_dcnv2"),
])
def test_config_says_plainly_what_the_model_does_not_take(change, says):
    with pytest.raises(ValueError, match=says):
        Config(**{**SMALL, **change})


def test_the_model_is_registered_with_one_table_leaf():
    from deepfm_tpu import models

    assert "dlrm_dcnv2" in models.registered_models()
    model = models.get_model(Config(**SMALL))
    assert model.embedding_param_names() == ("fm_v",)
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(params) == {"fm_v", "bottom", "cross", "tower"}
    assert params["cross"][0]["v"].shape == (27 * K, 4)
    assert params["tower"]["layers"][0]["w"].shape == (27 * K, 16)
    # the DLRM-style class keeps both leaves: the names are per model
    assert models.get_model(Config(model="dlrm")).embedding_param_names() \
        == ("fm_w", "fm_v")


@pytest.mark.parametrize("model, has, lacks", [
    ("dlrm_dcnv2", {"embed", "bottom", "cross", "tower", "loss", "opt"},
     {"fm", "l2"}),
    ("dcnv2", {"embed", "cross", "tower", "loss", "opt"}, {"fm", "bottom"}),
    ("dlrm", {"embed", "fm", "cross", "tower", "loss", "opt"}, {"bottom"}),
    ("deepfm", {"embed", "fm", "tower", "loss", "opt"}, {"cross", "bottom"}),
])
def test_compiled_steps_carry_each_blocks_scope(model, has, lacks):
    """``cross_network`` and ``dot_interaction`` are charged to ``cross``
    and the bottom MLP to ``bottom`` in the compiled step's op names, which
    is where the benchmark's per-layer device times come from."""
    small = dict(SMALL, model=model, optimizer="Adam", l2_reg=0.0)
    if model != "dlrm_dcnv2":
        small.update(numeric_fields=0, bottom_layers="", deep_layers="16,8",
                     dropout="1,1", field_size=5, use_pallas=False)
    scopes = set(profiling.hlo_op_scopes(
        Trainer(Config(**small)).step_hlo_text()).values())
    assert has <= scopes and not (lacks & scopes), scopes


def test_launcher_trains_evaluates_exports_and_serves(tmp_path, capsys,
                                                      monkeypatch):
    from deepfm_tpu import launch

    # What serves below is the StableHLO artifact. The TensorFlow sidecar
    # beside it (a worker's first costs the TensorFlow import and autograph
    # over jax2tf, 57 of this test's 74 s) is ``test_savedmodel_export``'s.
    monkeypatch.setenv("DEEPFM_TPU_SKIP_TF_EXPORT", "1")
    data = tmp_path / "data"
    for prefix, n, seed in (("tr", 512, 1), ("va", 256, 2)):
        libsvm.generate_synthetic_ctr(
            str(data), num_files=2, examples_per_file=n, feature_size=V,
            field_size=F, prefix=prefix, seed=seed)
    argv = ["--data_dir", str(data), "--val_data_dir", str(data),
            "--model_dir", str(tmp_path / "ckpt"), "--num_epochs", "2",
            "--compute_dtype", "float32", "--batch_size", "64",
            "--steps_per_loop", "4", "--learning_rate", "0.05"]
    for key, value in SMALL.items():
        if "--" + key not in argv:
            argv += ["--" + key, str(value)]
    assert launch.main(argv + ["--task_type", "train", "--servable_model_dir",
                               str(tmp_path / "servable")]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["task"] == "train" and line["steps"] == 2 * (2 * 512 // 64)
    assert 0.0 <= line["auc"] <= 1.0
    assert line["saved_model"].startswith("skipped: DEEPFM_TPU_SKIP_TF_EXPORT")
    assert launch.main(argv + ["--task_type", "eval"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["task"] == "eval" and np.isfinite(line["loss"])

    (version,) = os.listdir(tmp_path / "servable")
    artifact = str(tmp_path / "servable" / version)
    meta = json.load(open(os.path.join(artifact, "model_config.json")))
    assert meta["signature"]["inputs"]["feat_ids"] == ["batch", F, "int32"]
    serve = export_lib.load_serving(artifact)
    batch = batches(1, seed=9)[0]
    cfg = Config(**{**SMALL, "compute_dtype": "float32", "batch_size": 64,
                    "model_dir": str(tmp_path / "ckpt")})
    trainer = Trainer(cfg)
    state = tasks._restore_or_init(trainer, cfg, require=True)
    (want,) = trainer.predict(state, [batch])
    got = serve(batch["feat_ids"], batch["feat_vals"])
    assert got.shape == (B,) and np.allclose(got, want, atol=1e-6)
