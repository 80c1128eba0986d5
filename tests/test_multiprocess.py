"""True multi-process distributed test: 2 OS processes, jax.distributed
rendezvous, a 4x2 ('data','model') mesh spanning both — DP gradient psum AND
cross-process row-sharded embeddings, end-to-end through the CLI launcher.

This is the "local cluster" validation the reference did by hand-building
TF_CONFIG and launching ps/chief/worker processes (``set_dist_env``,
``1-ps-cpu/...py:294-339``) — here it's automated (SURVEY.md §4).
"""

import json
import os
import socket
import subprocess
import sys

import pytest

from deepfm_tpu.data import libsvm

# Every test here spawns a real 2-process jax.distributed cluster on the CPU
# backend; gated on the conftest cross-process-collectives probe. Also
# `slow`: each cluster pays two interpreter+jax cold starts plus a
# rendezvous, minutes per test on a 1-core host — run with `-m slow`
# (tier 2, see README "Running the tests").
pytestmark = [pytest.mark.mp_collectives, pytest.mark.slow]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RUNNER = """
import jax
jax.config.update('jax_platforms', 'cpu')
import sys
from deepfm_tpu.launch import main
sys.exit(main(sys.argv[1:]))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def mp_workdir(tmp_path_factory):
    d = tmp_path_factory.mktemp("mp")
    libsvm.generate_synthetic_ctr(
        str(d / "data"), num_files=4, examples_per_file=128,
        feature_size=300, field_size=5, prefix="tr", seed=11)
    libsvm.generate_synthetic_ctr(
        str(d / "data"), num_files=1, examples_per_file=128,
        feature_size=300, field_size=5, prefix="va", seed=12)
    libsvm.generate_synthetic_ctr(
        str(d / "data"), num_files=1, examples_per_file=100,
        feature_size=300, field_size=5, prefix="te", seed=13)
    return d


def test_two_process_train(mp_workdir):
    port = _free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=_REPO,
    )
    args = [
        "--task_type", "train",
        "--dist_mode", "1",
        "--num_processes", "2",
        "--coordinator_address", f"localhost:{port}",
        "--data_dir", str(mp_workdir / "data"),
        "--val_data_dir", str(mp_workdir / "data"),
        "--model_dir", str(mp_workdir / "ckpt"),
        "--feature_size", "300", "--field_size", "5",
        "--embedding_size", "8", "--deep_layers", "16,8",
        "--dropout", "1.0,1.0", "--batch_size", "64",
        "--num_epochs", "2", "--learning_rate", "0.05",
        "--scale_lr_by_world", "false",
        "--compute_dtype", "float32",
        "--mesh_data", "4", "--mesh_model", "2",
        "--log_steps", "0", "--save_checkpoints_steps", "5",
        "--seed", "3",
    ]
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _RUNNER] + args + ["--process_id", str(r)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=_REPO)
        for r in range(2)
    ]
    outs = []
    for r, p in enumerate(procs):
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"rank {r} failed:\n{err[-3000:]}"
        outs.append(out)

    results = []
    for out in outs:
        line = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
        results.append(json.loads(line))

    # Replicated-by-construction training: every rank reports the SAME
    # loss/AUC (the broadcast-hook analog holds through real psum traffic).
    assert results[0]["steps"] == 2 * (4 * 128 // 64)
    assert results[0]["loss"] == pytest.approx(results[1]["loss"], abs=1e-6)
    assert results[0]["auc"] == pytest.approx(results[1]["auc"], abs=1e-6)
    assert results[0]["auc"] > 0.55, results[0]

    # Chief-only checkpointing: rank 0 wrote it, rank 1 did not duplicate.
    assert os.path.isdir(mp_workdir / "ckpt")

    # ---- sharded infer: each rank predicts half the records, chief
    # re-interleaves global order; must match single-process infer exactly.
    infer_args = [a if a != "train" else "infer" for a in args]
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _RUNNER] + infer_args
            + ["--process_id", str(r)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=_REPO)
        for r in range(2)
    ]
    for r, p in enumerate(procs):
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"infer rank {r} failed:\n{err[-3000:]}"
    pred_path = mp_workdir / "data" / "pred.txt"
    assert pred_path.exists()
    mp_preds = [float(x) for x in pred_path.read_text().split()]
    assert len(mp_preds) == 100  # 100 te records, odd tail exercised

    # Single-process reference run (1x1 mesh) over the same checkpoint.
    sp_env = dict(env, XLA_FLAGS="--xla_force_host_platform_device_count=1")
    sp_args = [a for a in infer_args]
    for key, val in (("--mesh_data", "1"), ("--mesh_model", "1"),
                     ("--dist_mode", "0"), ("--num_processes", "1")):
        sp_args[sp_args.index(key) + 1] = val
    p = subprocess.run(
        [sys.executable, "-c", _RUNNER] + sp_args + ["--process_id", "0"],
        env=sp_env, capture_output=True, text=True, cwd=_REPO, timeout=420)
    assert p.returncode == 0, f"single-proc infer failed:\n{p.stderr[-3000:]}"
    sp_preds = [float(x) for x in pred_path.read_text().split()]
    assert len(sp_preds) == 100
    assert mp_preds == pytest.approx(sp_preds, abs=2e-6)


def test_fanout_spawns_local_cluster(mp_workdir):
    """ONE fanout command starts worker_per_host local processes that
    rendezvous into a jax.distributed cluster and train (the MPI
    processes_per_host analog, reference hvd-gpu.ipynb:87-92)."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=_REPO,
    )
    # Workers must pin jax to CPU before backend init; fanout children run
    # deepfm_tpu.launch directly, so the pin travels in their environment.
    cmd = [
        sys.executable, "-m", "deepfm_tpu.fanout",
        "--worker_per_host", "2",
        "--task_type", "train",
        "--data_dir", str(mp_workdir / "data"),
        "--val_data_dir", str(mp_workdir / "data"),
        "--feature_size", "300", "--field_size", "5",
        "--embedding_size", "8", "--deep_layers", "16,8",
        "--dropout", "1.0,1.0", "--batch_size", "64",
        "--num_epochs", "1", "--learning_rate", "0.05",
        "--scale_lr_by_world", "false", "--compute_dtype", "float32",
        "--mesh_data", "4", "--mesh_model", "2",
        "--log_steps", "0", "--seed", "3",
    ]
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       cwd=_REPO, timeout=420)
    assert p.returncode == 0, f"fanout failed:\n{p.stdout[-2000:]}\n{p.stderr[-2000:]}"
    # Both workers report the same result line (replicated training).
    lines = [ln for ln in p.stdout.splitlines() if '"task": "train"' in ln]
    assert len(lines) == 2, p.stdout[-2000:]
    r0 = json.loads(lines[0].split("] ", 1)[1])
    r1 = json.loads(lines[1].split("] ", 1)[1])
    assert r0["steps"] == 4 * 128 // 64
    assert r0["loss"] == pytest.approx(r1["loss"], abs=1e-6)


@pytest.fixture(scope="module")
def multipath_workdir(tmp_path_factory):
    """Private-channel layout: eval channel + one training channel per local
    worker (the hvd enable_data_multi_path contract, README-EN.md:78-84)."""
    d = tmp_path_factory.mktemp("multipath")
    for i in range(2):
        libsvm.generate_synthetic_ctr(
            str(d / "data" / f"train_{i}"), num_files=2,
            examples_per_file=64, feature_size=300, field_size=5,
            prefix="tr", seed=31 + i)
    libsvm.generate_synthetic_ctr(
        str(d / "data" / "eval"), num_files=1, examples_per_file=64,
        feature_size=300, field_size=5, prefix="va", seed=33)
    return d


def _multipath_args(workdir, port, model_dir):
    return [
        "--task_type", "train",
        "--dist_mode", "1",
        "--num_processes", "2",
        "--coordinator_address", f"localhost:{port}",
        "--data_dir", str(workdir / "data"),
        "--channels", '["eval", "train_0", "train_1"]',
        "--enable_data_multi_path", "true",
        "--worker_per_host", "2",
        "--model_dir", model_dir,
        "--feature_size", "300", "--field_size", "5",
        "--embedding_size", "8", "--deep_layers", "16,8",
        "--dropout", "1.0,1.0", "--batch_size", "64",
        "--num_epochs", "2", "--learning_rate", "0.05",
        "--scale_lr_by_world", "false", "--compute_dtype", "float32",
        "--mesh_data", "2", "--mesh_model", "1",
        "--log_steps", "0", "--seed", "3",
        "--steps_per_loop", "1", "--save_checkpoints_steps", "2",
    ]


def _mp_run(args, extra_env=None, expect_fail=False, timeout=420):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # One local device per process: the ('data','model') mesh is built
        # over ALL global devices, so local device count x processes must
        # equal mesh_data x mesh_model (= 2x1 here).
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        PYTHONPATH=_REPO,
        **(extra_env or {}),
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _RUNNER] + args + ["--process_id", str(r)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=_REPO)
        for r in range(2)
    ]
    results = []
    for r, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"rank {r} hung (resume decision desync?)")
        if expect_fail:
            assert p.returncode != 0, f"rank {r} unexpectedly succeeded"
            results.append(err)
            continue
        assert p.returncode == 0, f"rank {r} failed:\n{err[-3000:]}"
        line = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
        results.append(json.loads(line))
    return results


def test_multipath_resume_sibling_channel_edit(multipath_workdir):
    """ADVICE r4 high, behaviorally: under enable_data_multi_path each rank
    trains its own private channel, so (pre-fix) per-rank files digests
    diverged and a resume could mid-epoch-skip on the chief while replaying
    on its sibling — desynchronizing the lockstep collectives. The fix makes
    the chief hash ALL local channels and broadcast the resume decision.

    Asserts both halves: (a) an untouched resume mid-epoch-skips exactly on
    every rank; (b) editing a SIBLING channel (one the chief does NOT train
    from) forces cluster-wide epoch-replay — and neither case hangs.

    Schedule on these shards: 128 records/rank, local batch 32 -> 4
    steps/epoch; fault after 3 steps with checkpoints every 2 -> restored
    step 2, 2 steps into epoch 0."""
    # Crash two training runs identically (separate model dirs so each can
    # be resumed under a different condition).
    dirs = {}
    for tag in ("control", "edited"):
        model_dir = str(multipath_workdir / f"ckpt_{tag}")
        dirs[tag] = model_dir
        errs = _mp_run(
            _multipath_args(multipath_workdir, _free_port(), model_dir),
            extra_env={"DEEPFM_TPU_FAULT_AFTER_STEPS": "3"},
            expect_fail=True)
        for err in errs:
            assert "fault injection" in err, err[-1500:]
        meta = json.load(
            open(os.path.join(model_dir, "resume_meta.json")))
        assert meta["step"] == 2 and meta["steps_into_epoch"] == 2

    # (a) Untouched files: exact mid-epoch skip -> 2 epochs x 4 steps.
    results = _mp_run(
        _multipath_args(multipath_workdir, _free_port(), dirs["control"]))
    assert results[0]["steps"] == 2 * 4
    assert results[0]["loss"] == pytest.approx(results[1]["loss"], abs=1e-6)

    # (b) Rename a shard in train_1 — the CHIEF's own channel (train_0) is
    # untouched, so a chief-local digest would wrongly match. The all-
    # channel digest must mismatch -> cluster-wide epoch-replay: restored
    # step 2 + num_epochs*4 fresh steps.
    chan = multipath_workdir / "data" / "train_1"
    victim = sorted(chan.glob("tr*.tfrecords"))[0]
    victim.rename(chan / "tr_renamed.tfrecords")
    results = _mp_run(
        _multipath_args(multipath_workdir, _free_port(), dirs["edited"]))
    assert results[0]["steps"] == 2 + 2 * 4
    assert results[0]["loss"] == pytest.approx(results[1]["loss"], abs=1e-6)
