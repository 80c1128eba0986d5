"""End-to-end task tests: train->checkpoint->resume, eval, infer (pred.txt),
export->load_serving round trip. The integration layer of the test pyramid
(SURVEY.md §4): exercises the full L1-L5 stack on synthetic Criteo-shaped
data with the 8-device CPU mesh."""

import json
import os

import numpy as np
import pytest

from deepfm_tpu.config import Config
from deepfm_tpu.data import libsvm
from deepfm_tpu.train import tasks
from deepfm_tpu.utils import export as export_lib


@pytest.fixture(scope="module")
def workdir(tmp_path_factory):
    d = tmp_path_factory.mktemp("e2e")
    data = d / "data"
    libsvm.generate_synthetic_ctr(
        str(data), num_files=3, examples_per_file=256,
        feature_size=300, field_size=5, prefix="tr", seed=7)
    libsvm.generate_synthetic_ctr(
        str(data), num_files=1, examples_per_file=256,
        feature_size=300, field_size=5, prefix="va", seed=8)
    libsvm.generate_synthetic_ctr(
        str(data), num_files=1, examples_per_file=128,
        feature_size=300, field_size=5, prefix="te", seed=9)
    return d


def _cfg(workdir, **kw):
    base = dict(
        feature_size=300, field_size=5, embedding_size=8,
        deep_layers="16,8", dropout="1.0,1.0", batch_size=64,
        compute_dtype="float32", learning_rate=0.05, num_epochs=2,
        data_dir=str(workdir / "data"), val_data_dir=str(workdir / "data"),
        model_dir=str(workdir / "ckpt"), log_steps=0,
        save_checkpoints_steps=5, mesh_data=4, mesh_model=2,
        scale_lr_by_world=False, seed=3,
    )
    base.update(kw)
    return Config(**base)


class TestTrainTask:
    # Seeded 2-epoch convergence threshold calibrated under bit-exact mesh
    # numerics; on drifting XLA CPU builds the 4x2-mesh trajectory lands
    # elsewhere (see conftest capability probes).
    @pytest.mark.mesh_bitexact
    def test_train_eval_export_and_resume(self, workdir, monkeypatch):
        # (the artifact read back below is the StableHLO one; the TensorFlow
        # sidecar, 50 of this test's 67 s, is ``test_savedmodel_export``'s)
        monkeypatch.setenv("DEEPFM_TPU_SKIP_TF_EXPORT", "1")
        cfg = _cfg(workdir, servable_model_dir=str(workdir / "servable"))
        result = tasks.run(cfg)
        assert result["auc"] > 0.6, result
        steps_first = result["steps"]
        assert steps_first == 2 * (3 * 256 // 64)

        # checkpoints exist
        assert os.path.isdir(cfg.model_dir)
        # resume: two more epochs continue from the restored step
        result2 = tasks.run(_cfg(workdir, num_epochs=1,
                                 servable_model_dir=""))
        assert result2["steps"] == steps_first + 3 * 256 // 64

        # servable artifact exists and round-trips
        sub = os.listdir(str(workdir / "servable"))
        assert len(sub) == 1
        artifact = str(workdir / "servable" / sub[0])
        serve = export_lib.load_serving(artifact)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 300, (16, 5)).astype(np.int32)
        vals = rng.normal(size=(16, 5)).astype(np.float32)
        probs = serve(ids, vals)
        assert probs.shape == (16,)
        assert ((probs >= 0) & (probs <= 1)).all()

        meta = json.load(open(os.path.join(artifact, "model_config.json")))
        assert meta["signature"]["inputs"]["feat_ids"] == ["batch", 5, "int32"]

    def test_clear_existing_model(self, workdir):
        cfg = _cfg(workdir, num_epochs=1, clear_existing_model=True,
                   model_dir=str(workdir / "ckpt_clear"))
        tasks.run(cfg)
        first = tasks.run(cfg)  # cleared -> starts from step 0 again
        assert first["steps"] == 3 * 256 // 64


@pytest.fixture(scope="module")
def ckpt(workdir):
    """Checkpoint for the require=True tasks (eval/infer/export/CLI).

    Trained here rather than borrowed from TestTrainTask so these tests stay
    independent of its mesh_bitexact gate (it skips on drifting XLA CPU
    builds) and of test ordering.
    """
    d = str(workdir / "ckpt_pre")
    if not os.path.isdir(d):
        tasks.run(_cfg(workdir, model_dir=d))
    return d


class TestEvalInferTasks:
    def test_eval_task(self, workdir, ckpt):
        ev = tasks.run(_cfg(workdir, task_type="eval", model_dir=ckpt))
        assert 0.5 < ev["auc"] <= 1.0

    def test_infer_writes_pred_txt(self, workdir, ckpt):
        out = tasks.run(_cfg(workdir, task_type="infer", model_dir=ckpt))
        assert out["num_predictions"] == 128
        pred = open(os.path.join(str(workdir / "data"), "pred.txt")).read().split()
        assert len(pred) == 128
        vals = np.array([float(p) for p in pred])
        assert ((vals >= 0) & (vals <= 1)).all()

    def test_export_task(self, workdir, ckpt):
        out_dir = str(workdir / "servable2")
        tasks.run(_cfg(workdir, task_type="export", model_dir=ckpt,
                       servable_model_dir=out_dir))
        sub = os.listdir(out_dir)
        assert len(sub) == 1

    def test_eval_requires_checkpoint(self, workdir):
        cfg = _cfg(workdir, task_type="eval", model_dir=str(workdir / "nope"))
        with pytest.raises(FileNotFoundError):
            tasks.run(cfg)


class TestLaunchCli:
    def test_cli_roundtrip(self, workdir, ckpt, capsys):
        from deepfm_tpu import launch
        rc = launch.main([
            "--task_type", "eval",
            "--data_dir", str(workdir / "data"),
            "--val_data_dir", str(workdir / "data"),
            "--model_dir", ckpt,
            "--feature_size", "300", "--field_size", "5",
            "--embedding_size", "8", "--deep_layers", "16,8",
            "--dropout", "1.0,1.0", "--batch_size", "64",
            "--compute_dtype", "float32", "--mesh_data", "4",
            "--mesh_model", "2", "--log_steps", "0",
        ])
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        payload = json.loads(out)
        assert payload["task"] == "eval"
        assert payload["auc"] > 0.5

    def test_cli_train_logs_and_returns_the_rate(self, workdir, capsys,
                                                 caplog):
        """The operator's instrument: a launcher train run logs
        ``examples/sec=`` every ``log_steps`` and returns
        ``examples_per_sec`` in its result line, beside no timing of the
        staging ring (that is the ``stage.*`` spans' job)."""
        import logging

        from deepfm_tpu import launch
        model_flags = [
            "--data_dir", str(workdir / "data"),
            "--val_data_dir", str(workdir / "data"),
            "--model_dir", str(workdir / "ckpt_cli_train"),
            "--feature_size", "300", "--field_size", "5",
            "--embedding_size", "8", "--deep_layers", "16,8",
            "--dropout", "1.0,1.0", "--batch_size", "64",
            "--compute_dtype", "float32", "--mesh_data", "1",
            "--mesh_model", "1"]
        with caplog.at_level(logging.INFO, logger="deepfm_tpu"):
            rc = launch.main(["--task_type", "train", "--num_epochs", "1",
                              "--log_steps", "4", *model_flags])
        assert rc == 0
        assert any("examples/sec=" in r.getMessage()
                   for r in caplog.records)
        payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert payload["task"] == "train" and payload["steps"] == 12
        assert payload["examples_per_sec"] > 0
        assert not [k for k in payload if k.startswith("staging_")]
        rc = launch.main(["--task_type", "eval", "--log_steps", "0",
                          *model_flags])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert payload["task"] == "eval" and 0.0 <= payload["auc"] <= 1.0


class TestStreamingMode:
    """Pipe-mode analog (--pipe_mode 1): one sequential stream, epochs
    replayed producer-side (reference 2-hvd-gpu/...py:403-405)."""

    def test_streaming_train(self, workdir):
        cfg = _cfg(workdir, pipe_mode=1, num_epochs=2,
                   model_dir=str(workdir / "ckpt_stream"))
        result = tasks.run(cfg)
        # same number of steps as file mode: 2 epochs x 3x256 examples / 64
        assert result["steps"] == 2 * (3 * 256 // 64)
        assert result["auc"] > 0.55, result

    def test_chained_stream_replays_epochs(self, workdir):
        from deepfm_tpu.data import pipeline as pipe_lib
        files = sorted(
            str(p) for p in (workdir / "data").glob("tr*.tfrecords"))
        one = pipe_lib.ChainedFileStream(files, num_epochs=1)
        two = pipe_lib.ChainedFileStream(files, num_epochs=2)
        b1 = one.read(1 << 30)
        b2 = two.read(1 << 30)
        assert b2 == b1 + b1
        assert one.read(10) == b""

    def test_streaming_pipeline_single_pass(self, workdir):
        from deepfm_tpu.data import pipeline as pipe_lib
        files = sorted(
            str(p) for p in (workdir / "data").glob("tr*.tfrecords"))
        p = pipe_lib.StreamingCtrPipeline(
            pipe_lib.ChainedFileStream(files), field_size=5, batch_size=64,
            prefetch_batches=0)
        n = sum(1 for _ in p)
        assert n == 3 * 256 // 64
        with pytest.raises(RuntimeError):  # FIFO semantics: no second pass
            next(iter(p))

    def test_streaming_record_shard(self, workdir):
        """Ranks sharing one stream must see disjoint records (the pipe-mode
        dataset.shard analog)."""
        from deepfm_tpu.data import pipeline as pipe_lib
        files = sorted(
            str(p) for p in (workdir / "data").glob("tr*.tfrecords"))
        seen = []
        for rank in range(2):
            p = pipe_lib.StreamingCtrPipeline(
                pipe_lib.ChainedFileStream(files), field_size=5,
                batch_size=64, prefetch_batches=0, record_shard=(2, rank))
            ids = np.concatenate(
                [b["feat_ids"].ravel() for b in p])
            seen.append(ids)
        # each rank got half the steps
        assert len(seen[0]) == len(seen[1])
        # and the shards differ (disjoint records)
        assert not np.array_equal(seen[0], seen[1])


class TestShouldSaveCrossing:
    """should_save fires on interval crossings (steps advance by
    steps_per_loop per query) and seeds from the latest checkpoint so a
    resumed run does not save off-schedule."""

    def test_crossing_semantics(self, tmp_path):
        from deepfm_tpu.utils import checkpoint as ckpt_lib
        mgr = ckpt_lib.CheckpointManager(
            str(tmp_path / "c"), save_interval_steps=10)
        try:
            assert not mgr.should_save(8)
            assert mgr.should_save(16)      # crossed 10
            assert mgr.should_save(24)      # crossed 20
            assert not mgr.should_save(26)
        finally:
            mgr.close()

    def test_resume_seeds_from_latest(self, tmp_path):
        import numpy as np
        from deepfm_tpu.utils import checkpoint as ckpt_lib
        d = str(tmp_path / "c")
        mgr = ckpt_lib.CheckpointManager(d, save_interval_steps=10)
        try:
            mgr.save(24, {"w": np.zeros(3)})
        finally:
            mgr.close()
        mgr2 = ckpt_lib.CheckpointManager(d, save_interval_steps=10)
        try:
            assert not mgr2.should_save(26)  # would be spurious on resume
            assert mgr2.should_save(32)      # genuine crossing of 30
        finally:
            mgr2.close()


class TestTensorBoardScalars:
    def test_train_writes_event_file(self, workdir):
        """--tensorboard_dir writes TF-summary scalars (loss at log_steps
        cadence + per-eval AUC) — the Estimator summary-writer analog.
        Events files are TFRecords; this repo's own reader verifies they
        contain records."""
        pytest.importorskip("tensorflow")
        tb_dir = str(workdir / "tb")
        cfg = Config(
            feature_size=300, field_size=5, embedding_size=8,
            deep_layers="16,8", dropout="1.0,1.0", batch_size=64,
            compute_dtype="float32", learning_rate=0.05, num_epochs=1,
            data_dir=str(workdir / "data"), val_data_dir=str(workdir / "data"),
            model_dir="", log_steps=2, steps_per_loop=1, mesh_data=1,
            scale_lr_by_world=False, seed=3, tensorboard_dir=tb_dir)
        result = tasks.run(cfg)
        assert "auc" in result
        import glob as _glob
        events = _glob.glob(tb_dir + "/events.out.tfevents.*")
        assert len(events) == 1
        from deepfm_tpu.data import tfrecord
        recs = list(tfrecord.iter_records(events[0], verify_crc=True))
        # file version header + >= (12 steps / log_steps=2) loss scalars
        # + eval_auc/eval_loss
        assert len(recs) > 6


class TestFilesFingerprint:
    """The resume-sidecar files digest (tasks._files_fingerprint)."""

    def _make_channels(self, tmp_path, n=2):
        for i in range(n):
            libsvm.generate_synthetic_ctr(
                str(tmp_path / f"train_{i}"), num_files=2,
                examples_per_file=64, feature_size=300, field_size=5,
                prefix="tr", seed=10 + i)
        (tmp_path / "eval").mkdir()

    def test_multipath_rank_invariant_and_covers_siblings(self, tmp_path):
        self._make_channels(tmp_path)
        cfg = Config(
            feature_size=300, field_size=5, data_dir=str(tmp_path),
            enable_data_multi_path=True, worker_per_host=2,
            channels='["eval", "train_0", "train_1"]')
        d_rank0 = tasks._files_fingerprint(cfg, ["rank0-view"])
        d_rank1 = tasks._files_fingerprint(cfg, ["a", "different", "view"])
        # Rank-invariant: each rank's own-channel file list is ignored, ALL
        # local channels are hashed (ADVICE r4 high — per-rank digests
        # desynchronized the resume decision).
        assert d_rank0 == d_rank1
        # Editing a SIBLING channel (one the chief never trains from) must
        # still invalidate the digest.
        files = sorted((tmp_path / "train_1").glob("tr*.tfrecords"))
        files[0].rename(tmp_path / "train_1" / "tr_renamed.tfrecords")
        assert tasks._files_fingerprint(cfg, ["rank0-view"]) != d_rank0

    def test_tracks_files_arg_and_tolerates_missing(self, tmp_path):
        self._make_channels(tmp_path, n=1)
        files = sorted(str(p) for p in (tmp_path / "train_0").glob("*"))
        cfg = Config(feature_size=300, field_size=5, data_dir=str(tmp_path))
        d = tasks._files_fingerprint(cfg, files)
        assert tasks._files_fingerprint(cfg, files) == d
        assert tasks._files_fingerprint(cfg, files[:-1]) != d
        # A file that fails to stat degrades to a sentinel (ADVICE r4 low:
        # gfile raises OpError, not OSError), never crashes startup.
        assert tasks._files_fingerprint(
            cfg, files + [str(tmp_path / "nope.tfrecords")]) != d


class TestStepAccurateResume:
    """SURVEY hard-part #5: preemption mid-epoch must resume at the exact
    batch, not replay the epoch (the reference punts on this). Simulates a
    spot kill by raising from the tracer hook after the interval checkpoint
    landed, then re-runs the same invocation."""

    def _cfg(self, workdir, model_dir, **kw):
        base = dict(
            feature_size=300, field_size=5, embedding_size=8,
            deep_layers="16,8", dropout="1.0,1.0", batch_size=64,
            compute_dtype="float32", learning_rate=0.05, num_epochs=2,
            data_dir=str(workdir / "data"), val_data_dir="",
            model_dir=model_dir, log_steps=0, steps_per_loop=1,
            save_checkpoints_steps=5, mesh_data=1,
            scale_lr_by_world=False, seed=3,
        )
        base.update(kw)
        return Config(**base)

    def test_mid_epoch_resume_exact(self, workdir, monkeypatch):
        from deepfm_tpu.utils import profiling as prof_lib

        model_dir = str(workdir / "ckpt_preempt")
        cfg = self._cfg(workdir, model_dir)
        steps_per_epoch = 3 * 256 // 64  # 12

        class CrashAt:
            def __init__(self, *a, **k):
                self.n = 0

            def on_step(self, steps_done=1):
                self.n += steps_done
                if self.n >= 7:
                    raise RuntimeError("simulated preemption")

            def close(self):
                pass

        orig_tracer = prof_lib.StepWindowTracer
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", CrashAt)
        with pytest.raises(RuntimeError, match="preemption"):
            tasks.run(cfg)
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", orig_tracer)

        meta = tasks._read_resume_meta(model_dir)
        tr_files = tasks.resolve_files(
            tasks.resolve_channel_dirs(cfg)[0], "tr")
        assert meta == {"step": 5, "epoch": 0, "steps_into_epoch": 5,
                        "epoch_base": 0, "num_epochs": 2, "pipe_mode": 0,
                        "layout": tasks._consumption_layout(cfg),
                        "files": tasks._files_fingerprint(cfg, tr_files),
                        "completed": False}

        # Resume the SAME invocation: restores step 5, skips the 5 trained
        # batches of epoch 0, finishes epoch 0 + epoch 1 -> exactly 2 epochs
        # total. (Epoch-replay semantics would end at 5 + 24 = 29.)
        result = tasks.run(self._cfg(workdir, model_dir))
        assert result["steps"] == 2 * steps_per_epoch

        meta = tasks._read_resume_meta(model_dir)
        assert meta["completed"] is True
        assert meta["step"] == 2 * steps_per_epoch

        # A fresh invocation after completion trains num_epochs MORE, with
        # epoch_base advanced so shuffle orders don't repeat.
        result = tasks.run(self._cfg(workdir, model_dir))
        assert result["steps"] == 4 * steps_per_epoch
        meta = tasks._read_resume_meta(model_dir)
        assert meta["epoch_base"] == 2

    def _private_data(self, tmp_path):
        """Function-private data dir — these tests mutate the file list,
        which must not poison the module-scoped ``workdir`` fixture."""
        libsvm.generate_synthetic_ctr(
            str(tmp_path / "data"), num_files=3, examples_per_file=256,
            feature_size=300, field_size=5, prefix="tr", seed=7)
        return tmp_path

    def _crash_once(self, monkeypatch, cfg, at_step):
        """Run cfg until the tracer hook kills it after ``at_step`` steps,
        then restore the real tracer."""
        from deepfm_tpu.utils import profiling as prof_lib

        class CrashAt:
            def __init__(self, *a, **k):
                self.n = 0

            def on_step(self, steps_done=1):
                self.n += steps_done
                if self.n >= at_step:
                    raise RuntimeError("simulated preemption")

            def close(self):
                pass

        orig = prof_lib.StepWindowTracer
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", CrashAt)
        with pytest.raises(RuntimeError, match="preemption"):
            tasks.run(cfg)
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", orig)

    def test_resume_files_changed_replays_epoch(self, tmp_path, monkeypatch):
        """The files-digest guard (tasks._resume_position): renaming a shard
        between interruption and resume changes the per-epoch shuffle order
        and shard assignment, so a mid-epoch skip would skip the WRONG
        records — the resume must fall back to epoch-replay (the reference's
        behavior, 1-ps-cpu/...py:434-435) instead of mis-skipping."""
        workdir = self._private_data(tmp_path)
        model_dir = str(tmp_path / "ckpt")
        self._crash_once(monkeypatch, self._cfg(workdir, model_dir), 7)
        meta = tasks._read_resume_meta(model_dir)
        assert meta["step"] == 5 and meta["steps_into_epoch"] == 5

        data = tmp_path / "data"
        files = sorted(data.glob("tr*.tfrecords"))
        files[0].rename(data / "tr_renamed.tfrecords")

        result = tasks.run(self._cfg(workdir, model_dir))
        # Epoch-replay: restored step 5 + num_epochs*12 fresh steps. A
        # (wrong) mid-epoch skip would end at 24.
        assert result["steps"] == 5 + 24
        meta = tasks._read_resume_meta(model_dir)
        assert meta["completed"] is True
        assert meta["epoch_base"] == 1  # interrupted epoch 0's order burned

    def test_resume_same_files_skips_exactly(self, tmp_path, monkeypatch):
        """Control for the digest guard: untouched files -> the sidecar
        matches and the resume mid-epoch-skips (no replay)."""
        workdir = self._private_data(tmp_path)
        model_dir = str(tmp_path / "ckpt")
        self._crash_once(monkeypatch, self._cfg(workdir, model_dir), 7)
        result = tasks.run(self._cfg(workdir, model_dir))
        assert result["steps"] == 24  # exactly num_epochs*12, no replay

    def test_resume_layout_change_replays_epoch(self, tmp_path, monkeypatch):
        """Same files but different consumption geometry (steps_per_loop
        changes the pooled emission order): the layout fingerprint must
        force epoch-replay."""
        workdir = self._private_data(tmp_path)
        model_dir = str(tmp_path / "ckpt")
        self._crash_once(monkeypatch, self._cfg(workdir, model_dir), 7)
        result = tasks.run(self._cfg(workdir, model_dir, steps_per_loop=2))
        assert result["steps"] == 5 + 24

    def test_resume_matches_uninterrupted_run_k8(self, workdir, monkeypatch):
        """Gold-standard exactness under the PRODUCTION config
        (steps_per_loop=8, native loader): crash mid-epoch, resume, and the
        final weights must match an uninterrupted run — proving the skip
        trims the same k-pooled stream training consumes (a k=1 skip
        stream would diverge past the first drain and silently train some
        examples twice)."""
        import numpy as np
        from deepfm_tpu.utils import checkpoint as ckpt_lib
        from deepfm_tpu.utils import profiling as prof_lib

        ref_dir = str(workdir / "ckpt_ref_k8")
        ref = tasks.run(self._cfg(workdir, ref_dir, steps_per_loop=8,
                                  save_checkpoints_steps=0))
        assert ref["steps"] == 24

        crash_dir = str(workdir / "ckpt_crash_k8")
        cfg = self._cfg(workdir, crash_dir, steps_per_loop=8,
                        save_checkpoints_steps=8)

        class CrashAt:
            def __init__(self, *a, **k):
                self.n = 0

            def on_step(self, steps_done=1):
                self.n += steps_done
                if self.n >= 10:
                    raise RuntimeError("simulated preemption")

            def close(self):
                pass

        orig_tracer = prof_lib.StepWindowTracer
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", CrashAt)
        with pytest.raises(RuntimeError, match="preemption"):
            tasks.run(cfg)
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", orig_tracer)

        meta = tasks._read_resume_meta(crash_dir)
        assert meta["step"] == 8 and meta["steps_into_epoch"] == 8

        result = tasks.run(self._cfg(workdir, crash_dir, steps_per_loop=8,
                                     save_checkpoints_steps=8))
        assert result["steps"] == 24

        # Compare final weights: restore both checkpoints and diff.
        from deepfm_tpu.train import Trainer
        ref_state = ckpt_lib.CheckpointManager(ref_dir).restore(
            Trainer(self._cfg(workdir, ref_dir)).init_state())
        res_state = ckpt_lib.CheckpointManager(crash_dir).restore(
            Trainer(self._cfg(workdir, crash_dir)).init_state())
        for key in ("fm_w", "fm_v", "fm_b"):
            np.testing.assert_allclose(
                np.asarray(ref_state.params[key]),
                np.asarray(res_state.params[key]), rtol=1e-6, atol=1e-7,
                err_msg=key)

    def test_epoch_boundary_checkpoint_rolls_over(self, workdir, monkeypatch):
        """A checkpoint landing exactly on an epoch's last step rolls the
        sidecar to the next epoch, so resume starts there instead of
        decode-skipping 100% of a trained epoch (and a zero-step fit)."""
        from deepfm_tpu.utils import profiling as prof_lib

        model_dir = str(workdir / "ckpt_boundary")
        cfg = self._cfg(workdir, model_dir, save_checkpoints_steps=4)

        class CrashAt:
            def __init__(self, *a, **k):
                self.n = 0

            def on_step(self, steps_done=1):
                self.n += steps_done
                if self.n >= 14:  # epoch 1, before its first save at 16
                    raise RuntimeError("simulated preemption")

            def close(self):
                pass

        orig_tracer = prof_lib.StepWindowTracer
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", CrashAt)
        with pytest.raises(RuntimeError, match="preemption"):
            tasks.run(cfg)
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", orig_tracer)

        meta = tasks._read_resume_meta(model_dir)
        # saved at 12 == epoch-0 end -> sidecar rolled to epoch 1, offset 0
        assert (meta["step"], meta["epoch"], meta["steps_into_epoch"]) \
            == (12, 1, 0)
        result = tasks.run(self._cfg(workdir, model_dir,
                                     save_checkpoints_steps=4))
        assert result["steps"] == 24

    def test_layout_mismatch_falls_back(self, workdir, monkeypatch):
        """A resume with a different consumption layout (steps_per_loop)
        must NOT attempt a mid-epoch skip (the k-pooled orders differ) —
        it degrades to a fresh invocation with advanced epoch_base."""
        model_dir = str(workdir / "ckpt_layout")
        cfg = self._cfg(workdir, model_dir, steps_per_loop=8,
                        save_checkpoints_steps=8)
        from deepfm_tpu.utils import profiling as prof_lib

        class CrashAt:
            def __init__(self, *a, **k):
                self.n = 0

            def on_step(self, steps_done=1):
                self.n += steps_done
                if self.n >= 10:
                    raise RuntimeError("simulated preemption")

            def close(self):
                pass

        orig_tracer = prof_lib.StepWindowTracer
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", CrashAt)
        with pytest.raises(RuntimeError, match="preemption"):
            tasks.run(cfg)
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", orig_tracer)

        # Resume with steps_per_loop=1: layout differs -> fresh 2 epochs
        # from step 8 (epoch-replay fallback), not a mid-epoch skip.
        result = tasks.run(self._cfg(workdir, model_dir, steps_per_loop=1))
        assert result["steps"] == 8 + 24

    def test_pipe_mode_resume_exact(self, workdir, monkeypatch):
        """Streaming resume: position is steps into the single-pass stream
        (epochs are producer-side); the trained prefix is skipped."""
        from deepfm_tpu.utils import profiling as prof_lib

        model_dir = str(workdir / "ckpt_preempt_pipe")
        cfg = self._cfg(workdir, model_dir, pipe_mode=1)

        class CrashAt:
            def __init__(self, *a, **k):
                self.n = 0

            def on_step(self, steps_done=1):
                self.n += steps_done
                if self.n >= 7:
                    raise RuntimeError("simulated preemption")

            def close(self):
                pass

        orig_tracer = prof_lib.StepWindowTracer
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", CrashAt)
        with pytest.raises(RuntimeError, match="preemption"):
            tasks.run(cfg)
        monkeypatch.setattr(tasks.prof_lib, "StepWindowTracer", orig_tracer)

        meta = tasks._read_resume_meta(model_dir)
        assert meta["step"] == 5 and meta["pipe_mode"] == 1
        result = tasks.run(self._cfg(workdir, model_dir, pipe_mode=1))
        assert result["steps"] == 2 * (3 * 256 // 64)

    def test_stale_meta_ignored(self, workdir):
        """A sidecar whose step doesn't match the restored checkpoint (e.g.
        a lost async save) must be ignored -> epoch-replay fallback."""
        model_dir = str(workdir / "ckpt_stale")
        cfg = self._cfg(workdir, model_dir, num_epochs=1)
        tasks.run(cfg)  # completes: ckpt at step 12, meta completed
        tasks._write_resume_meta(model_dir, {
            "step": 999, "epoch": 0, "steps_into_epoch": 3, "epoch_base": 0,
            "num_epochs": 1, "pipe_mode": 0, "completed": False})
        result = tasks.run(self._cfg(workdir, model_dir, num_epochs=1))
        assert result["steps"] == 2 * (3 * 256 // 64)  # full extra epoch


class TestChannelWiring:
    """Per-rank channel resolution (reference 2-hvd-gpu/...py:376-380,403:
    SM_CHANNELS sorted eval-first; multi_path = one private training channel
    per local worker)."""

    def _cfg(self, tmp_path, **kw):
        from deepfm_tpu.config import Config
        base = dict(
            data_dir=str(tmp_path), feature_size=300, field_size=5,
            embedding_size=8, deep_layers="16,8", dropout="1.0,1.0",
            batch_size=32, log_steps=0)
        base.update(kw)
        return Config(**base)

    def test_no_channels_falls_back_to_dirs(self, tmp_path):
        from deepfm_tpu.train.tasks import resolve_channel_dirs
        cfg = self._cfg(tmp_path, val_data_dir="/va")
        assert resolve_channel_dirs(cfg) == (str(tmp_path), "/va")

    def test_eval_channel_is_first(self, tmp_path):
        from deepfm_tpu.train.tasks import resolve_channel_dirs
        for name in ("evaluation", "training"):
            (tmp_path / name).mkdir()
        cfg = self._cfg(tmp_path, channels='["evaluation", "training"]')
        tr, ev = resolve_channel_dirs(cfg)
        assert tr == str(tmp_path / "training")
        assert ev == str(tmp_path / "evaluation")

    def test_multi_path_ranks_read_disjoint_dirs(self, tmp_path):
        from deepfm_tpu.train.tasks import resolve_channel_dirs
        for name in ("evaluation", "train-1", "train-2"):
            (tmp_path / name).mkdir()
        cfg = self._cfg(
            tmp_path, channels='["evaluation", "train-1", "train-2"]',
            enable_data_multi_path=True, worker_per_host=2)
        tr0, _ = resolve_channel_dirs(cfg, process_index=0)
        tr1, _ = resolve_channel_dirs(cfg, process_index=1)
        tr2, _ = resolve_channel_dirs(cfg, process_index=2)  # host 1 worker 0
        assert tr0 == str(tmp_path / "train-1")
        assert tr1 == str(tmp_path / "train-2")
        assert tr0 != tr1
        assert tr2 == tr0  # same local_rank on the next host -> same channel

    def test_multi_path_requires_channel_per_worker(self, tmp_path):
        import pytest as _pytest
        from deepfm_tpu.train.tasks import resolve_channel_dirs
        cfg = self._cfg(
            tmp_path, channels='["evaluation", "train-1"]',
            enable_data_multi_path=True, worker_per_host=4)
        with _pytest.raises(ValueError, match="one training channel per"):
            resolve_channel_dirs(cfg, process_index=0)

    def test_sm_channel_env_override(self, tmp_path, monkeypatch):
        from deepfm_tpu.train.tasks import resolve_channel_dirs
        monkeypatch.setenv("SM_CHANNEL_TRAIN_1", "/mnt/ch/t1")
        cfg = self._cfg(tmp_path, channels='["evaluation", "train-1"]',
                        enable_data_multi_path=True, worker_per_host=1)
        tr, _ = resolve_channel_dirs(cfg, process_index=0)
        assert tr == "/mnt/ch/t1"

    def test_train_task_reads_channel_dirs(self, tmp_path):
        from deepfm_tpu.data import libsvm
        from deepfm_tpu.train import tasks
        libsvm.generate_synthetic_ctr(
            str(tmp_path / "train-1"), num_files=2, examples_per_file=128,
            feature_size=300, field_size=5, prefix="tr", seed=5)
        libsvm.generate_synthetic_ctr(
            str(tmp_path / "evaluation"), num_files=1, examples_per_file=64,
            feature_size=300, field_size=5, prefix="va", seed=6)
        cfg = self._cfg(
            tmp_path, channels='["evaluation", "train-1"]',
            enable_data_multi_path=True, worker_per_host=1,
            num_epochs=1, mesh_data=1)
        result = tasks.run(cfg)
        assert result["steps"] == 2 * 128 // 32
        assert "auc" in result  # eval channel was found and used


class TestMultiPathHostShard:
    def test_multi_path_no_s3_shards_across_hosts(self):
        from deepfm_tpu.data import sharding
        files = [f"f{i}" for i in range(4)]
        # 2 hosts x 2 workers; same channel replicated across hosts.
        s_h0 = sharding.shard_files(
            files, enable_data_multi_path=True, enable_s3_shard=False,
            rank=0, local_rank=0, world_size=4, workers_per_host=2)
        s_h1 = sharding.shard_files(
            files, enable_data_multi_path=True, enable_s3_shard=False,
            rank=2, local_rank=0, world_size=4, workers_per_host=2)
        assert set(s_h0.files) | set(s_h1.files) == set(files)
        assert not set(s_h0.files) & set(s_h1.files)
        # s3-sharded storage: already disjoint, no further split.
        s = sharding.shard_files(
            files, enable_data_multi_path=True, enable_s3_shard=True,
            rank=2, local_rank=0, world_size=4, workers_per_host=2)
        assert s.files == tuple(sorted(files))


class TestThrottledEval:
    """train_and_evaluate timing semantics (reference 1-ps-cpu/...py:440-442):
    first eval no earlier than eval_start_delay_secs, then at most every
    eval_throttle_secs."""

    def _setup(self, tmp_path):
        from deepfm_tpu.config import Config
        from deepfm_tpu.data import libsvm
        from deepfm_tpu.train import Trainer
        libsvm.generate_synthetic_ctr(
            str(tmp_path), num_files=1, examples_per_file=64,
            feature_size=300, field_size=5, prefix="va", seed=7)
        cfg = Config(
            data_dir=str(tmp_path), feature_size=300, field_size=5,
            embedding_size=8, deep_layers="16,8", dropout="1.0,1.0",
            batch_size=32, log_steps=0, mesh_data=1,
            eval_start_delay_secs=10, eval_throttle_secs=5)
        trainer = Trainer(cfg)
        state = trainer.init_state()
        return cfg, trainer, state

    def test_hook_timing(self, tmp_path, monkeypatch):
        import time as time_mod
        from deepfm_tpu.train import tasks
        cfg, trainer, state = self._setup(tmp_path)
        va_files = tasks.resolve_files(str(tmp_path), "va")

        clock = [1000.0]
        monkeypatch.setattr(time_mod, "time", lambda: clock[0])
        result = {}
        hook = tasks._make_throttled_eval_hook(trainer, cfg, va_files, result)

        clock[0] = 1005.0
        hook(state, {})                      # before start_delay: no eval
        assert result["mid_train_evals"] == 0
        clock[0] = 1011.0
        hook(state, {})                      # past start_delay: first eval
        assert result["mid_train_evals"] == 1
        assert "auc" in result
        clock[0] = 1013.0
        hook(state, {})                      # within throttle window: skipped
        assert result["mid_train_evals"] == 1
        clock[0] = 1017.0
        hook(state, {})                      # throttle elapsed: second eval
        assert result["mid_train_evals"] == 2

    def test_train_task_respects_start_delay(self, tmp_path):
        from deepfm_tpu.config import Config
        from deepfm_tpu.data import libsvm
        from deepfm_tpu.train import tasks
        libsvm.generate_synthetic_ctr(
            str(tmp_path), num_files=1, examples_per_file=128,
            feature_size=300, field_size=5, prefix="tr", seed=8)
        libsvm.generate_synthetic_ctr(
            str(tmp_path), num_files=1, examples_per_file=64,
            feature_size=300, field_size=5, prefix="va", seed=9)
        cfg = Config(
            data_dir=str(tmp_path), feature_size=300, field_size=5,
            embedding_size=8, deep_layers="16,8", dropout="1.0,1.0",
            batch_size=32, log_steps=0, num_epochs=2, mesh_data=1,
            eval_start_delay_secs=10_000, eval_throttle_secs=10_000)
        result = tasks.run(cfg)
        assert result["mid_train_evals"] == 0   # delay never elapsed
        assert "auc" in result                  # but the final eval ran


def test_interleave_rank_shards():
    import numpy as np
    from deepfm_tpu.train.tasks import _interleave_rank_shards
    # world=2, rank0 held records 0,2,4,6 (4), rank1 held 1,3,5 (3)
    gathered = np.array([[0., 2., 4., 6.], [1., 3., 5., 0.]], np.float32)
    out = _interleave_rank_shards(gathered, np.array([4, 3]))
    np.testing.assert_array_equal(out, np.arange(7, dtype=np.float32))
    # equal counts
    g = np.array([[0., 3.], [1., 4.], [2., 5.]], np.float32)
    out = _interleave_rank_shards(g, np.array([2, 2, 2]))
    np.testing.assert_array_equal(out, np.arange(6, dtype=np.float32))
