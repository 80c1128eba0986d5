"""Multi-process input service tests (data/workers.py + pipeline wiring).

The contract under test is BIT-IDENTICAL parity: with ``input_workers > 0``
the pipeline must emit byte-for-byte the stream the in-process pooled path
emits (same golden hashes), because resume skip-counting replays along this
exact order. Everything else — crash policy, respawn replay, health
aggregation, eligibility fallbacks — is tested against that same invariant.

These tests spawn real processes (spawn context, like production); they use
small files and ``poll_secs`` well under a second so the whole module stays
inside tier-1 time. Pure protocol mechanics live in tests/test_shm_ring.py.
"""

import glob
import warnings

import numpy as np
import pytest

from deepfm_tpu.data import example_codec, libsvm, pipeline, sharding, tfrecord
from deepfm_tpu.data import workers as workers_mod
from deepfm_tpu.utils import retry as retry_lib

pytestmark = [
    pytest.mark.input_service,
    pytest.mark.skipif(not pipeline._native_loader(),
                       reason="native decoder unavailable"),
]

NO_SLEEP = retry_lib.RetryPolicy(base_delay=0.0, max_delay=0.0)


@pytest.fixture
def data_dir(tmp_path):
    libsvm.generate_synthetic_ctr(
        str(tmp_path), num_files=4, examples_per_file=60, feature_size=300,
        field_size=6, prefix="tr", seed=11)
    return tmp_path


def _files(data_dir):
    return sorted(glob.glob(str(data_dir / "tr*.tfrecords")))


def _emissions(files, k=4, **kw):
    base = dict(field_size=6, batch_size=32, num_epochs=2, shuffle=True,
                shuffle_files=True, shuffle_buffer=150, drop_remainder=True,
                seed=7, prefetch_batches=0)
    base.update(kw)
    out = []
    for rows, m, n_ex in pipeline.CtrPipeline(files, **base) \
            .iter_superbatches(k):
        out.append((m, n_ex, {key: v.copy() for key, v in rows.items()}))
    return out


def _assert_same_emissions(a, b):
    assert len(a) == len(b)
    for (m1, n1, r1), (m2, n2, r2) in zip(a, b):
        assert (m1, n1) == (m2, n2)
        for key in r1:
            np.testing.assert_array_equal(r1[key], r2[key], err_msg=key)


def _reference_rows(files, field_size):
    """All records of ``files`` decoded in file order (codec path — fully
    independent of the chunk reader under test)."""
    labs, idss, valss = [], [], []
    for path in files:
        for rec in tfrecord.read_all_records(path):
            lab, ids, vals = example_codec.decode_ctr_example(rec, field_size)
            labs.append(lab)
            idss.append(ids)
            valss.append(vals)
    return (np.array(labs, np.float32),
            np.stack(idss).astype(np.int32),
            np.stack(valss).astype(np.float32))


def _collect_service_rows(service):
    labs, idss, valss = [], [], []
    with service:
        for labels, ids, vals in service.chunks(copy=True):
            labs.append(labels)
            idss.append(ids)
            valss.append(vals)
    return (np.concatenate(labs), np.concatenate(idss),
            np.concatenate(valss))


class TestPipelineParity:
    def test_shuffle_parity_with_fragmentation(self, data_dir):
        """Worker path == in-process path, bit for bit, across 2 epochs
        (separate service fleets) with slabs forced smaller than a chunk so
        multi-fragment reassembly is exercised."""
        files = _files(data_dir)
        _assert_same_emissions(
            _emissions(files),
            _emissions(files, input_workers=2,
                       input_worker_slab_records=25))

    def test_noshuffle_parity_copy_mode(self, data_dir):
        """shuffle=False consumes the service in copy mode (no scatter ever
        releases the slabs): still identical to in-process."""
        files = _files(data_dir)
        kw = dict(shuffle=False, num_epochs=1)
        _assert_same_emissions(
            _emissions(files, **kw),
            _emissions(files, input_workers=2,
                       input_worker_slab_records=25, **kw))

    def test_worker_path_reproduces_golden_hash(self, tmp_path):
        """The strongest pin: the worker path reproduces the SAME golden
        emission hash TestPooledEmissionGolden freezes for the in-process
        path — the two paths cannot drift without tripping this."""
        import hashlib
        libsvm.generate_synthetic_ctr(
            str(tmp_path), num_files=3, examples_per_file=500,
            feature_size=1000, field_size=7, prefix="tr", seed=5)
        files = sorted(str(p) for p in tmp_path.glob("tr*.tfrecords"))
        pipe = pipeline.CtrPipeline(
            files, field_size=7, batch_size=64, num_epochs=2, shuffle=True,
            shuffle_files=True, shuffle_buffer=300, drop_remainder=True,
            seed=9, input_workers=2)
        h = hashlib.sha256()
        for rows, m, n_ex in pipe.iter_superbatches(8):
            h.update(str(m).encode())
            h.update(str(n_ex).encode())
            h.update(rows["feat_ids"].tobytes())
            h.update(rows["feat_vals"].tobytes())
            h.update(rows["label"].tobytes())
        # Must match tests/test_data.py::TestPooledEmissionGolden.GOLDEN
        # for (k=8, bs=64, skip=0, drop=True).
        assert h.hexdigest()[:24] == "26fff204f1d9b877c88d8696"


class TestServiceProtocol:
    def test_chunks_match_reference_decode(self, data_dir):
        files = _files(data_dir)
        got = _collect_service_rows(workers_mod.ShmInputService(
            files, field_size=6, num_workers=2, poll_secs=0.05))
        want = _reference_rows(files, 6)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_worker_count_clamped_to_files(self, data_dir):
        files = _files(data_dir)[:2]
        svc = workers_mod.ShmInputService(
            files, field_size=6, num_workers=8, poll_secs=0.05)
        assert svc.num_workers == 2
        got = _collect_service_rows(svc)
        np.testing.assert_array_equal(got[0], _reference_rows(files, 6)[0])

    def test_empty_files_raise(self, tmp_path):
        empty = str(tmp_path / "empty.tfrecords")
        open(empty, "wb").close()
        with pytest.raises(IOError, match="no records"):
            _collect_service_rows(workers_mod.ShmInputService(
                [empty], field_size=6, num_workers=1, poll_secs=0.05))

    def test_decode_error_reraised_in_parent(self, data_dir):
        """A corrupt record with policy 'raise' fails INSIDE the worker;
        the parent re-raises with matching type (IOError) and the worker's
        detail text."""
        files = _files(data_dir)
        # Flip a data-CRC byte of record 3 of the first file (framing ok).
        import struct
        data = bytearray(open(files[0], "rb").read())
        pos = 0
        for _ in range(3):
            (length,) = struct.unpack_from("<Q", data, pos)
            pos += 16 + length
        (length,) = struct.unpack_from("<Q", data, pos)
        data[pos + 12 + length] ^= 0xFF
        open(files[0], "wb").write(bytes(data))
        with pytest.raises(IOError, match="data CRC mismatch"):
            _collect_service_rows(workers_mod.ShmInputService(
                files, field_size=6, num_workers=1, verify_crc=True,
                on_bad_record="raise", retry_policy=NO_SLEEP,
                poll_secs=0.05))

    def test_invalid_death_policy_rejected(self, data_dir):
        with pytest.raises(ValueError, match="on_worker_death"):
            workers_mod.ShmInputService(
                _files(data_dir), field_size=6, num_workers=1,
                on_worker_death="retry")


class TestWorkerDeath:
    def test_crash_raises_by_default(self, data_dir):
        """A worker hard-killed mid-stream (os._exit — no farewell message)
        must surface as an error, never a silent truncation."""
        svc = workers_mod.ShmInputService(
            _files(data_dir), field_size=6, num_workers=1,
            fault_die_after=1, poll_secs=0.05)
        with pytest.raises(RuntimeError, match="input worker 0 died"):
            _collect_service_rows(svc)

    def test_respawn_replays_exactly(self, data_dir):
        """on_worker_death='respawn': the replacement replays from the
        first sequence number of the incomplete chunk, so the delivered
        stream is exactly the crash-free stream — no loss, no duplicates."""
        files = _files(data_dir)
        got = _collect_service_rows(workers_mod.ShmInputService(
            files, field_size=6, num_workers=1, fault_die_after=2,
            on_worker_death="respawn", max_respawns=2, poll_secs=0.05))
        want = _reference_rows(files, 6)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_respawn_budget_exhausted_raises(self, data_dir):
        svc = workers_mod.ShmInputService(
            _files(data_dir), field_size=6, num_workers=1,
            fault_die_after=1, on_worker_death="respawn", max_respawns=0,
            poll_secs=0.05)
        with pytest.raises(RuntimeError, match="respawns used 0/0"):
            _collect_service_rows(svc)


class TestHealthAggregation:
    def test_worker_bad_records_reach_pipeline_health(self, data_dir):
        """Corruption skipped INSIDE a worker process must land in the
        trainer-side pipeline.health ledger (snapshot deltas at eof/done)."""
        files = _files(data_dir)
        import struct
        data = bytearray(open(files[1], "rb").read())
        (length,) = struct.unpack_from("<Q", data, 0)
        data[12 + length] ^= 0xFF  # record 0's data CRC
        open(files[1], "wb").write(bytes(data))
        pipe = pipeline.CtrPipeline(
            files, field_size=6, batch_size=16, num_epochs=1, shuffle=True,
            shuffle_buffer=150, drop_remainder=False, seed=7, verify_crc=True,
            on_bad_record="skip", retry_policy=NO_SLEEP, prefetch_batches=0,
            input_workers=2)
        total = sum(n_ex for _, _, n_ex in pipe.iter_superbatches(2))
        assert total == 4 * 60 - 1
        snap = pipe.health.snapshot()
        assert snap["bad_records"] == 1
        assert snap["per_file"][files[1]]["skipped"] == 1


class TestEligibilityAndFallback:
    def test_record_shard_uses_in_process_silently(self, data_dir):
        """Record-sharding is ineligible (workers have no global record
        index): the pipeline must use the in-process path with NO warning —
        this is a config choice, not a degradation."""
        files = _files(data_dir)[:1]
        spec = sharding.shard_files(files, rank=1, world_size=3)
        assert spec.record_shard == (3, 1)
        kw = dict(shard=spec, shuffle=False, num_epochs=1,
                  drop_remainder=False, batch_size=8)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            shm = _emissions(files, input_workers=2, **kw)
        _assert_same_emissions(_emissions(files, **kw), shm)

    def test_service_failure_raises(self, data_dir, monkeypatch):
        """If the fleet cannot start (sandboxed /dev/shm, fork server
        restrictions...), the run fails with the cause: input_workers asked
        for decode processes, and the in-process decode is a different,
        slower host path that must not be taken on the quiet."""
        files = _files(data_dir)

        class Unstartable:
            def __init__(self, *a, **kw):
                raise OSError("shm forbidden")

        monkeypatch.setattr(workers_mod, "ShmInputService", Unstartable)
        with pytest.raises(OSError, match="shm forbidden"):
            _emissions(files, input_workers=2)

    def test_config_rejects_negative(self):
        from deepfm_tpu.config import Config
        with pytest.raises(ValueError, match="input_workers"):
            Config(input_workers=-1)

    def test_config_flag_reaches_pipeline(self, data_dir):
        from deepfm_tpu.config import Config
        from deepfm_tpu.train import tasks
        cfg = Config(data_dir=str(data_dir), field_size=6, batch_size=16,
                     input_workers=3)
        pipe = tasks.make_pipeline(cfg, _files(data_dir), epochs=1,
                                   shuffle=True)
        assert pipe.input_workers == 3
