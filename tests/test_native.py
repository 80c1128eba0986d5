"""Native C++ decoder tests: parity with the pure-Python codec on every path,
CRC vectors, corruption detection, and a sanity perf ratio."""

import os
import time

import numpy as np
import pytest

from deepfm_tpu.data import example_codec, libsvm, pipeline, tfrecord
from deepfm_tpu.native import loader

pytestmark = pytest.mark.skipif(
    not loader.available(), reason="native toolchain unavailable")


@pytest.fixture(scope="module")
def sample_file(tmp_path_factory):
    d = tmp_path_factory.mktemp("native")
    [path] = libsvm.generate_synthetic_ctr(
        str(d), num_files=1, examples_per_file=300,
        feature_size=1000, field_size=7, seed=5)
    return path


def test_crc32c_vectors():
    assert loader.crc32c(b"\x00" * 32) == 0x8A9136AA
    assert loader.crc32c(b"123456789") == 0xE3069283
    # agree with the Python implementation on random data
    data = os.urandom(1000)
    assert loader.crc32c(data) == tfrecord.crc32c(data)


def test_split_frames_matches_python(sample_file):
    buf = open(sample_file, "rb").read()
    offsets, lengths = loader.split_frames(buf)
    py_records = tfrecord.read_all_records(sample_file)
    assert len(offsets) == len(py_records)
    for off, ln, rec in zip(offsets, lengths, py_records):
        assert buf[off:off + ln] == rec


def test_decode_batch_matches_python(sample_file):
    records = tfrecord.read_all_records(sample_file)
    l_n, i_n, v_n = loader.decode_batch(records, 7)
    l_p, i_p, v_p = pipeline.decode_batch_python(records, 7)
    np.testing.assert_array_equal(l_n, l_p)
    np.testing.assert_array_equal(i_n, i_p)
    np.testing.assert_array_equal(v_n, v_p)


def test_decode_file_bytes(sample_file):
    buf = open(sample_file, "rb").read()
    labels, ids, vals = loader.decode_file_bytes(buf, 7)
    assert labels.shape == (300,)
    assert ids.shape == (300, 7)
    recs = tfrecord.read_all_records(sample_file)
    lab0, ids0, vals0 = example_codec.decode_ctr_example(recs[0], 7)
    assert labels[0] == lab0
    np.testing.assert_array_equal(ids[0], ids0)


def test_crc_corruption_detected(sample_file, tmp_path):
    data = bytearray(open(sample_file, "rb").read())
    data[40] ^= 0xFF
    with pytest.raises(IOError):
        loader.split_frames(bytes(data), verify_crc=True)
    # without verification it still frames (payload is damaged, not framing)
    offsets, _ = loader.split_frames(bytes(data), verify_crc=False)
    assert len(offsets) == 300


def test_wrong_field_size_errors(sample_file):
    records = tfrecord.read_all_records(sample_file)[:4]
    with pytest.raises(ValueError):
        loader.decode_batch(records, 9)


def test_negative_and_large_ids():
    # int64 boundary handling through the int32 narrowing path
    rec = example_codec.encode_ctr_example(
        1.0, np.array([0, 2**31 - 1, 5], np.int64),
        np.array([1.0, -2.5, 3.5], np.float32))
    labels, ids, vals = loader.decode_batch([rec], 3)
    np.testing.assert_array_equal(ids[0], [0, 2**31 - 1, 5])
    np.testing.assert_allclose(vals[0], [1.0, -2.5, 3.5])


def test_pipeline_uses_native(sample_file):
    p = pipeline.CtrPipeline(
        [sample_file], field_size=7, batch_size=50, shuffle=False,
        use_native_decoder=True, prefetch_batches=0)
    q = pipeline.CtrPipeline(
        [sample_file], field_size=7, batch_size=50, shuffle=False,
        use_native_decoder=False, prefetch_batches=0)
    for bn, bp in zip(p, q):
        np.testing.assert_array_equal(bn["feat_ids"], bp["feat_ids"])
        np.testing.assert_array_equal(bn["feat_vals"], bp["feat_vals"])
        np.testing.assert_array_equal(bn["label"], bp["label"])


def test_native_is_faster(sample_file):
    records = tfrecord.read_all_records(sample_file) * 10
    t0 = time.perf_counter()
    loader.decode_batch(records, 7)
    t_native = time.perf_counter() - t0
    t0 = time.perf_counter()
    pipeline.decode_batch_python(records, 7)
    t_python = time.perf_counter() - t0
    assert t_native < t_python, (t_native, t_python)


def test_split_frames_partial_chunk_boundaries(sample_file):
    """Partial splitter: cutting the buffer anywhere yields a clean carry."""
    buf = open(sample_file, "rb").read()
    full_off, full_len = loader.split_frames(buf)
    for cut in (0, 5, 13, 100, len(buf) // 2, len(buf) - 3, len(buf)):
        o1, l1, consumed = loader.split_frames_partial(buf[:cut])
        assert consumed <= cut
        # records found so far are a prefix of the full framing
        assert list(o1) == [o for o in full_off if o - 12 < consumed]
        # resume from the carry: remainder must frame to the rest
        rest = buf[consumed:]
        o2, l2, consumed2 = loader.split_frames_partial(rest)
        assert consumed + consumed2 == len(buf)
        assert len(o1) + len(o2) == len(full_off)


def test_chunked_pipeline_reader_matches(sample_file, monkeypatch):
    """The chunked native reader yields identical records at tiny chunk sizes
    (forcing many carry-over boundaries)."""
    want = tfrecord.read_all_records(sample_file)
    monkeypatch.setattr(pipeline, "_NATIVE_CHUNK_BYTES", 97)
    got = list(pipeline._iter_file_records(sample_file, use_native=True))
    assert got == want


def test_chunked_reader_truncated_file_errors(sample_file, tmp_path):
    buf = open(sample_file, "rb").read()
    bad = tmp_path / "trunc.tfrecords"
    bad.write_bytes(buf[:-7])  # cut inside the final record
    with pytest.raises(IOError):
        list(pipeline._iter_file_records(str(bad), use_native=True))


class TestDecodeSpansScatterValidation:
    """The C scatter writes labels[dest[i]] unchecked — these guards are the
    only thing between a caller bug and silent out-of-bounds heap writes."""

    def _spans(self, sample_file, n=10):
        buf = open(sample_file, "rb").read()
        offsets, lengths = loader.split_frames(buf)
        return buf, offsets[:n], lengths[:n]

    def _pool(self, rows):
        return (np.empty(rows, np.float32), np.empty((rows, 7), np.int32),
                np.empty((rows, 7), np.float32))

    def test_scatter_matches_gather_paths(self, sample_file):
        buf, offsets, lengths = self._spans(sample_file)
        labels, ids, vals = self._pool(10)
        dest = np.arange(10, dtype=np.int64)[::-1].copy()  # reversed rows
        loader.decode_spans_scatter(buf, offsets, lengths, 7, dest,
                                    labels, ids, vals)
        recs = tfrecord.read_all_records(sample_file)[:10]
        l_ref, i_ref, v_ref = loader.decode_batch(recs, 7)
        np.testing.assert_array_equal(labels, l_ref[::-1])
        np.testing.assert_array_equal(ids, i_ref[::-1])
        np.testing.assert_array_equal(vals, v_ref[::-1])

    def test_dest_length_mismatch_raises(self, sample_file):
        buf, offsets, lengths = self._spans(sample_file)
        labels, ids, vals = self._pool(10)
        with pytest.raises(ValueError, match="len\\(dest\\)"):
            loader.decode_spans_scatter(
                buf, offsets, lengths, 7, np.arange(9, dtype=np.int64),
                labels, ids, vals)

    def test_dest_out_of_bounds_raises(self, sample_file):
        buf, offsets, lengths = self._spans(sample_file)
        labels, ids, vals = self._pool(10)
        dest = np.arange(10, dtype=np.int64)
        dest[3] = 10  # == rows: one past the end
        with pytest.raises(ValueError, match="dest range"):
            loader.decode_spans_scatter(buf, offsets, lengths, 7, dest,
                                        labels, ids, vals)
        dest[3] = -1
        with pytest.raises(ValueError, match="dest range"):
            loader.decode_spans_scatter(buf, offsets, lengths, 7, dest,
                                        labels, ids, vals)

    def test_bounds_use_smallest_pool_array(self, sample_file):
        """A short vals array shrinks the valid dest range: the guard must
        bound by min(len) across the three pools, not just labels."""
        buf, offsets, lengths = self._spans(sample_file)
        labels = np.empty(10, np.float32)
        ids = np.empty((10, 7), np.int32)
        vals = np.empty((9, 7), np.float32)  # one row short
        with pytest.raises(ValueError, match="dest range"):
            loader.decode_spans_scatter(
                buf, offsets, lengths, 7, np.arange(10, dtype=np.int64),
                labels, ids, vals)

    def test_empty_spans_noop(self, sample_file):
        buf, _, _ = self._spans(sample_file)
        labels, ids, vals = self._pool(4)
        loader.decode_spans_scatter(
            buf, np.empty(0, np.int64), np.empty(0, np.int64), 7,
            np.empty(0, np.int64), labels, ids, vals)


class TestAssembleSpans:
    """Fused multi-chunk decode->assemble (``dfm_decode_ctr_assemble``): one
    GIL-released C call scattering every chunk's records into permuted rows
    of the transfer-layout pool. Must be bit-identical to both the pure-
    Python mirror and the per-chunk scatter path it replaces."""

    def _jobs(self, sample_file, n_chunks=3, per=10, rows=None, rng_seed=3):
        """Split the first n_chunks*per spans into chunks with a permuted
        destination vector spanning all of them."""
        buf = open(sample_file, "rb").read()
        offsets, lengths = loader.split_frames(buf)
        total = n_chunks * per
        rows = total if rows is None else rows
        dest_all = np.random.default_rng(rng_seed).permutation(total)
        jobs = []
        for c in range(n_chunks):
            s = slice(c * per, (c + 1) * per)
            jobs.append((buf, offsets[s], lengths[s],
                         dest_all[s].astype(np.int64)))
        return buf, jobs, dest_all, total

    def _pools(self, rows, label_2d=False):
        lab_shape = (rows, 1) if label_2d else rows
        return (np.zeros(lab_shape, np.float32),
                np.zeros((rows, 7), np.int32), np.zeros((rows, 7), np.float32))

    @pytest.mark.skipif(not loader.has_assemble(),
                        reason="stale .so without fused entry")
    def test_matches_python_mirror_multichunk(self, sample_file):
        _, jobs, dest_all, total = self._jobs(sample_file)
        l_c, i_c, v_c = self._pools(total, label_2d=True)
        loader.assemble_spans(jobs, 7, l_c, i_c, v_c)
        l_p, i_p, v_p = self._pools(total, label_2d=True)
        loader.assemble_spans_python(jobs, 7, l_p, i_p, v_p)
        assert l_c.tobytes() == l_p.tobytes()
        assert i_c.tobytes() == i_p.tobytes()
        assert v_c.tobytes() == v_p.tobytes()
        # and against the in-order gather decode, un-permuted
        recs = tfrecord.read_all_records(sample_file)[:total]
        l_ref, i_ref, v_ref = loader.decode_batch(recs, 7)
        np.testing.assert_array_equal(l_c.reshape(-1)[dest_all], l_ref)
        np.testing.assert_array_equal(i_c[dest_all], i_ref)
        np.testing.assert_array_equal(v_c[dest_all], v_ref)

    @pytest.mark.skipif(not loader.has_assemble(),
                        reason="stale .so without fused entry")
    def test_label_column_1d_and_2d_identical(self, sample_file):
        """[P] and [P, 1] float32 label buffers are the same contiguous
        memory; the fused entry must accept both (the drain passes the
        transfer-layout [P, 1] column)."""
        _, jobs, _, total = self._jobs(sample_file, n_chunks=2)
        l1, i1, v1 = self._pools(total, label_2d=False)
        loader.assemble_spans(jobs, 7, l1, i1, v1)
        l2, i2, v2 = self._pools(total, label_2d=True)
        loader.assemble_spans(jobs, 7, l2, i2, v2)
        assert l1.tobytes() == l2.tobytes()
        assert i1.tobytes() == i2.tobytes()

    def test_dest_length_mismatch_raises(self, sample_file):
        buf, jobs, _, total = self._jobs(sample_file, n_chunks=1)
        labels, ids, vals = self._pools(total)
        bad = [(buf, jobs[0][1], jobs[0][2], jobs[0][3][:-1])]
        with pytest.raises(ValueError, match="len\\(dest\\)"):
            loader.assemble_spans(bad, 7, labels, ids, vals)
        with pytest.raises(ValueError, match="len\\(dest\\)"):
            loader.assemble_spans_python(bad, 7, labels, ids, vals)

    def test_dest_out_of_bounds_raises(self, sample_file):
        buf, jobs, _, total = self._jobs(sample_file, n_chunks=2)
        labels, ids, vals = self._pools(total)
        dest = jobs[1][3].copy()
        dest[0] = total  # one past the end of the pool
        bad = [jobs[0], (buf, jobs[1][1], jobs[1][2], dest)]
        with pytest.raises(ValueError, match="dest range"):
            loader.assemble_spans(bad, 7, labels, ids, vals)
        with pytest.raises(ValueError, match="dest range"):
            loader.assemble_spans_python(bad, 7, labels, ids, vals)

    def test_bounds_use_smallest_pool_array(self, sample_file):
        _, jobs, _, total = self._jobs(sample_file, n_chunks=1)
        labels = np.zeros(total, np.float32)
        ids = np.zeros((total, 7), np.int32)
        vals = np.zeros((total - 1, 7), np.float32)  # one row short
        with pytest.raises(ValueError, match="dest range"):
            loader.assemble_spans(jobs, 7, labels, ids, vals)

    @pytest.mark.skipif(not loader.has_assemble(),
                        reason="stale .so without fused entry")
    def test_corruption_reports_chunk_and_record(self, sample_file):
        """A record that fails protobuf parsing must surface the CHUNK index
        and the chunk-local RECORD index (the -(100+i) / err_chunk
        contract), so an operator can locate the bad bytes in a multi-chunk
        drain."""
        buf, jobs, _, total = self._jobs(sample_file, n_chunks=2)
        labels, ids, vals = self._pools(total)
        # chunk 1, record 3: point its span at garbage bytes (a CRC header
        # region is not a valid Example payload)
        offsets = jobs[1][1].copy()
        offsets[3] = 0  # file offset 0 is the first frame's length header
        bad = [jobs[0], (buf, offsets, jobs[1][2], jobs[1][3])]
        with pytest.raises(ValueError, match=r"record 3 of chunk 1"):
            loader.assemble_spans(bad, 7, labels, ids, vals)

    def test_empty_jobs_noop(self):
        loader.assemble_spans([], 7, np.empty(0, np.float32),
                              np.empty((0, 7), np.int32),
                              np.empty((0, 7), np.float32))

    @pytest.mark.skipif(not loader.has_assemble(),
                        reason="stale .so without fused entry")
    def test_stale_so_falls_back_per_chunk(self, sample_file, monkeypatch):
        """A cached .so predating the fused entry must degrade to the
        per-chunk scatter path with identical bytes (the has_assemble()
        probe contract)."""
        real = loader.load()

        class _StaleLib:
            def __getattr__(self, name):
                if name == "dfm_decode_ctr_assemble":
                    raise AttributeError(name)
                return getattr(real, name)

        _, jobs, _, total = self._jobs(sample_file)
        l_f, i_f, v_f = self._pools(total, label_2d=True)
        loader.assemble_spans(jobs, 7, l_f, i_f, v_f)  # fused
        stale = _StaleLib()
        monkeypatch.setattr(loader, "load", lambda: stale)
        assert not loader.has_assemble()
        l_s, i_s, v_s = self._pools(total, label_2d=True)
        loader.assemble_spans(jobs, 7, l_s, i_s, v_s)  # per-chunk fallback
        assert l_f.tobytes() == l_s.tobytes()
        assert i_f.tobytes() == i_s.tobytes()
        assert v_f.tobytes() == v_s.tobytes()


class TestHistoryDecode:
    """Native history decode (``dfm_decode_ctr_hist``): golden-pinned bytes,
    bit-parity with the Python codec mirror on every path (multi-record,
    empty, truncated), typed bad-record codes (-25/-26/-27), and the
    stale-.so fallback contract (``has_hist()``)."""

    MAX_LEN = 5

    @pytest.fixture(scope="class")
    def hist_file(self, tmp_path_factory):
        d = tmp_path_factory.mktemp("native_hist")
        [path] = libsvm.generate_synthetic_ctr(
            str(d), num_files=1, examples_per_file=200,
            feature_size=500, field_size=7, seed=11, history=self.MAX_LEN)
        return path

    def _python_mirror(self, records, field_size, max_len):
        n = len(records)
        labels = np.empty(n, np.float32)
        ids = np.empty((n, field_size), np.int32)
        vals = np.empty((n, field_size), np.float32)
        hid = np.zeros((n, max_len), np.int32)
        hval = np.zeros((n, max_len), np.float32)
        hlen = np.zeros(n, np.int32)
        for i, rec in enumerate(records):
            lab, rid, rval, h_i, h_v, h_n = \
                example_codec.decode_ctr_example_hist(rec, field_size, max_len)
            labels[i], ids[i], vals[i] = lab, rid.astype(np.int32), rval
            hid[i], hval[i], hlen[i] = h_i, h_v, h_n
        return labels, ids, vals, hid, hval, hlen

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_matches_python_mirror_bit_identical(self, hist_file):
        records = tfrecord.read_all_records(hist_file)
        native = loader.decode_batch_hist(records, 7, self.MAX_LEN)
        mirror = self._python_mirror(records, 7, self.MAX_LEN)
        for a, b in zip(native, mirror):
            assert a.tobytes() == b.tobytes()
        # the synthetic stream's click-gated histories are actually ragged:
        # some empty, some full (otherwise this parity test proves little)
        hlen = native[5]
        assert hlen.min() == 0 and hlen.max() == self.MAX_LEN

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_golden_pinned_record(self):
        """Hand-built record with known history -> pinned decoded arrays,
        through BOTH decoders."""
        rec = example_codec.encode_ctr_example(
            1.0, np.array([3, 1, 4, 1, 5], np.int64),
            np.array([0.5, -1.0, 2.0, 0.0, 1.5], np.float32),
            hist_ids=np.array([7, 9, 11], np.int64))
        for decode in (
                lambda: loader.decode_batch_hist([rec], 5, 4),
                lambda: self._python_mirror([rec], 5, 4)):
            labels, ids, vals, hid, hval, hlen = decode()
            assert labels[0] == 1.0
            np.testing.assert_array_equal(ids[0], [3, 1, 4, 1, 5])
            np.testing.assert_allclose(vals[0], [0.5, -1.0, 2.0, 0.0, 1.5])
            np.testing.assert_array_equal(hid[0], [7, 9, 11, 0])
            np.testing.assert_array_equal(hval[0], [1.0, 1.0, 1.0, 0.0])
            assert hlen[0] == 3

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_absent_history_decodes_empty(self):
        """A plain single-label record (no hist keys) stays decodable:
        hist_len 0, all-zero columns — old files feed sequence models."""
        rec = example_codec.encode_ctr_example(
            0.0, np.arange(3, dtype=np.int64), np.ones(3, np.float32))
        _, _, _, hid, hval, hlen = loader.decode_batch_hist([rec], 3, 4)
        assert hlen[0] == 0
        np.testing.assert_array_equal(hid[0], np.zeros(4))
        np.testing.assert_array_equal(hval[0], np.zeros(4))

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_truncation_keeps_head(self):
        """History longer than max_len truncates to the first max_len
        entries, identically in both decoders."""
        rec = example_codec.encode_ctr_example(
            1.0, np.arange(3, dtype=np.int64), np.ones(3, np.float32),
            hist_ids=np.array([10, 20, 30, 40, 50, 60], np.int64),
            hist_vals=np.array([1, 1, 1, 1, 1, 1], np.float32))
        n_ids, n_hid, n_hlen = (lambda r: (r[1], r[3], r[5]))(
            loader.decode_batch_hist([rec], 3, 4))
        p_ids, p_hid, p_hlen = (lambda r: (r[1], r[3], r[5]))(
            self._python_mirror([rec], 3, 4))
        np.testing.assert_array_equal(n_hid[0], [10, 20, 30, 40])
        assert n_hlen[0] == 4
        assert n_hid.tobytes() == p_hid.tobytes()
        assert n_hlen.tobytes() == p_hlen.tobytes()

    # -- typed bad-record codes ---------------------------------------------

    def _raw_example(self, features):
        """Assemble an Example from raw Feature BYTES (lets a test plant
        malformed wire inside one feature)."""
        feat_map = bytearray()
        for name, feat in features.items():
            entry = bytearray()
            example_codec._write_len_delimited(1, name.encode(), entry)
            example_codec._write_len_delimited(2, feat, entry)
            example_codec._write_len_delimited(1, bytes(entry), feat_map)
        out = bytearray()
        example_codec._write_len_delimited(1, bytes(feat_map), out)
        return bytes(out)

    def _base_features(self):
        return {
            "label": example_codec.encode_feature([1.0], "float"),
            "ids": example_codec.encode_feature([1, 2, 3], "int64"),
            "values": example_codec.encode_feature([1.0, 1.0, 1.0], "float"),
        }

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_malformed_hist_ids_wire_reports_25(self):
        feats = self._base_features()
        bad = bytearray()
        # Feature { int64_list = 3 } whose payload is a truncated varint
        example_codec._write_len_delimited(3, b"\x80", bad)
        feats["hist_ids"] = bytes(bad)
        feats["hist_vals"] = example_codec.encode_feature([1.0], "float")
        with pytest.raises(ValueError, match="malformed 'hist_ids'"):
            loader.decode_batch_hist([self._raw_example(feats)], 3, 4)

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_malformed_hist_vals_wire_reports_26(self):
        feats = self._base_features()
        feats["hist_ids"] = example_codec.encode_feature([5], "int64")
        bad = bytearray()
        example_codec._write_len_delimited(2, b"\x80", bad)
        feats["hist_vals"] = bytes(bad)
        with pytest.raises(ValueError, match="malformed 'hist_vals'"):
            loader.decode_batch_hist([self._raw_example(feats)], 3, 4)

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_length_mismatch_reports_27_with_record_index(self):
        good = example_codec.encode_ctr_example(
            1.0, np.arange(3, dtype=np.int64), np.ones(3, np.float32),
            hist_ids=np.array([5], np.int64))
        feats = self._base_features()
        feats["hist_ids"] = example_codec.encode_feature([5, 6, 7], "int64")
        feats["hist_vals"] = example_codec.encode_feature([1.0, 1.0], "float")
        with pytest.raises(ValueError, match="record 1.*lengths differ"):
            loader.decode_batch_hist([good, self._raw_example(feats)], 3, 4)

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_half_present_pair_reports_27(self):
        feats = self._base_features()
        feats["hist_ids"] = example_codec.encode_feature([5, 6], "int64")
        with pytest.raises(ValueError, match="lengths differ"):
            loader.decode_batch_hist([self._raw_example(feats)], 3, 4)

    def test_python_mirror_rejects_mismatch_too(self):
        feats = self._base_features()
        feats["hist_ids"] = example_codec.encode_feature([5, 6], "int64")
        with pytest.raises(ValueError, match="history length mismatch"):
            example_codec.decode_ctr_example_hist(
                self._raw_example(feats), 3, 4)

    # -- stale-.so fallback --------------------------------------------------

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_stale_so_falls_back_bit_identical(self, hist_file, monkeypatch):
        """A cached .so predating the history entry must degrade to the
        Python codec mirror with identical bytes (the has_hist() probe
        contract, same discipline as the fused-assemble fallback)."""
        records = tfrecord.read_all_records(hist_file)[:50]
        native = loader.decode_batch_hist(records, 7, self.MAX_LEN)
        real = loader.load()

        class _StaleLib:
            def __getattr__(self, name):
                if name == "dfm_decode_ctr_hist":
                    raise AttributeError(name)
                return getattr(real, name)

        stale = _StaleLib()
        monkeypatch.setattr(loader, "load", lambda: stale)
        assert not loader.has_hist()
        fallback = loader.decode_batch_hist(records, 7, self.MAX_LEN)
        for a, b in zip(native, fallback):
            assert a.tobytes() == b.tobytes()

    @pytest.mark.skipif(not loader.has_hist(),
                        reason="stale .so without history entry")
    def test_pipeline_history_native_matches_python(self, hist_file):
        """End of the chain: CtrPipeline(history=True) emits identical
        batches (packed-then-split hist columns included) through the native
        and pure-Python decoders."""
        kw = dict(field_size=7, batch_size=40, shuffle=False,
                  prefetch_batches=0, history=True,
                  history_max_len=self.MAX_LEN)
        p = pipeline.CtrPipeline([hist_file], use_native_decoder=True, **kw)
        q = pipeline.CtrPipeline([hist_file], use_native_decoder=False, **kw)
        n = 0
        for bn, bp in zip(p, q):
            for key in ("label", "feat_ids", "feat_vals",
                        "hist_ids", "hist_mask"):
                np.testing.assert_array_equal(bn[key], bp[key], err_msg=key)
            assert bn["hist_ids"].shape[1] == self.MAX_LEN
            n += 1
        assert n == 5


class TestBuildFromCleanCheckout:
    """The library is built on first use into a file named after the source's
    hash — never committed, never chosen by mtime — and a build that cannot
    happen is an error, not a quiet switch to the Python codec."""

    def test_builds_hash_keyed_file_into_clean_build_dir(self, tmp_path,
                                                         monkeypatch):
        import hashlib

        monkeypatch.setattr(loader, "_BUILD_DIR", str(tmp_path / "_build"))
        monkeypatch.setattr(loader, "_lib", None)
        loader.load()
        with open(loader._SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        built = tmp_path / "_build" / f"libtfrecord-{digest}.so"
        assert os.listdir(tmp_path / "_build") == [built.name]
        assert loader.crc32c(b"123456789") == 0xE3069283
        # Same source, next process: the existing file is loaded, not rebuilt.
        stamp = built.stat().st_mtime_ns
        monkeypatch.setattr(loader, "_lib", None)
        loader.load()
        assert built.stat().st_mtime_ns == stamp

    def test_no_compiler_fails_loudly(self, tmp_path, monkeypatch):
        monkeypatch.setattr(loader, "_BUILD_DIR", str(tmp_path / "_build"))
        monkeypatch.setattr(loader, "_lib", None)
        monkeypatch.setenv("PATH", str(tmp_path))
        with pytest.raises(loader.NativeBuildError, match=r"g\+\+"):
            loader.load()
        assert not loader.available()
        # use_native_decoder=True (the default) propagates it...
        with pytest.raises(loader.NativeBuildError):
            pipeline._get_decoder(True)
        # ...and only an explicit False takes the Python codec.
        assert pipeline._get_decoder(False) is pipeline.decode_batch_python
