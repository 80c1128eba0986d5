"""Host-side input pipeline: TFRecord -> fixed-shape numpy batches for TPU.

TPU-native re-design of the reference's two ``input_fn`` flavors
(``1-ps-cpu/...py:76-133`` file/pipe, ``2-hvd-gpu/...py:74-133`` horovod):

  * File mode: per-epoch file-list shuffle, shard policy (``sharding.py``),
    record shuffle buffer, batch -> *vectorized* decode (the reference decodes
    with ``tf.parse_example`` after ``.batch()`` — here the batched decode is
    the native C++ decoder or the pure-Python codec), drop_remainder, repeat.
  * Streaming mode (Pipe analog): sequential non-seekable stream, one pass,
    no re-open per epoch (the FIFO pitfall at ``2-hvd-gpu/...py:396``).
  * Prefetch: a background thread keeps ``prefetch_batches`` ready, the host
    analog of ``dataset.prefetch`` — with TPU async dispatch this overlaps
    host decode with device step time.

Outputs fixed-shape batches ``{"feat_ids": int32[B,F], "feat_vals": f32[B,F],
"label": f32[B,1]}`` — static shapes so every step hits the same XLA program.
With ``num_labels=2`` (multi-task training, ``--tasks ctr,cvr``) batches gain
a ``"label2"`` f32[B,1] column decoded from the optional on-disk key.
"""

from __future__ import annotations

import collections
import contextlib
import os
import queue
import threading
from typing import BinaryIO, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import trace as trace_lib
from . import example_codec, fileio, sharding, tfrecord
from .health import BadRecordPolicy, DataHealth

Batch = Dict[str, np.ndarray]


def decode_batch_python(records: Sequence[bytes], field_size: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batched decode with the Python codec (``use_native_decoder=False``,
    and the reference the native decoder is tested against)."""
    n = len(records)
    labels = np.empty((n,), np.float32)
    ids = np.empty((n, field_size), np.int32)
    vals = np.empty((n, field_size), np.float32)
    for i, rec in enumerate(records):
        lab, rid, rval = example_codec.decode_ctr_example(rec, field_size)
        labels[i] = lab
        ids[i] = rid.astype(np.int32)
        vals[i] = rval
    return labels, ids, vals


def decode_batch2_python(records: Sequence[bytes], field_size: int
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                    np.ndarray]:
    """Two-label Python decode (multi-task input): ``label2`` defaults to
    0.0 for single-label records. Mirrors ``native.loader.decode_batch2``."""
    n = len(records)
    labels = np.empty((n,), np.float32)
    labels2 = np.empty((n,), np.float32)
    ids = np.empty((n, field_size), np.int32)
    vals = np.empty((n, field_size), np.float32)
    for i, rec in enumerate(records):
        lab, lab2, rid, rval = example_codec.decode_ctr_example2(
            rec, field_size)
        labels[i] = lab
        labels2[i] = lab2
        ids[i] = rid.astype(np.int32)
        vals[i] = rval
    return labels, labels2, ids, vals


def _get_decoder(use_native: bool):
    """The batched decoder the caller asked for: the native one builds on
    first use and a failed build raises (``native.loader.NativeBuildError``)
    — it never degrades to the per-record Python codec on its own."""
    if use_native:
        return _native_loader().decode_batch
    return decode_batch_python


def _get_decoder2(use_native: bool):
    """Two-label sibling of ``_get_decoder``."""
    if use_native:
        return _native_loader().decode_batch2
    return decode_batch2_python


def decode_batch_hist_python(records: Sequence[bytes], field_size: int,
                             max_len: int
                             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                        np.ndarray, np.ndarray, np.ndarray]:
    """History Python decode (sequence-model input): the ragged
    ``hist_ids``/``hist_vals`` pair zero-padded/truncated to ``max_len`` per
    record. Mirrors ``native.loader.decode_batch_hist``."""
    n = len(records)
    labels = np.empty((n,), np.float32)
    ids = np.empty((n, field_size), np.int32)
    vals = np.empty((n, field_size), np.float32)
    hist_ids = np.zeros((n, max_len), np.int32)
    hist_vals = np.zeros((n, max_len), np.float32)
    hist_len = np.zeros((n,), np.int32)
    for i, rec in enumerate(records):
        lab, rid, rval, hid, hval, hn = example_codec.decode_ctr_example_hist(
            rec, field_size, max_len)
        labels[i] = lab
        ids[i] = rid.astype(np.int32)
        vals[i] = rval
        hist_ids[i] = hid
        hist_vals[i] = hval
        hist_len[i] = hn
    return labels, ids, vals, hist_ids, hist_vals, hist_len


def _get_decoder_hist(use_native: bool):
    """History sibling of ``_get_decoder``."""
    if use_native:
        return _native_loader().decode_batch_hist
    return decode_batch_hist_python


# Chunk size for the native streaming reader: big enough to amortize the
# per-call framing cost, small enough to keep RSS constant on huge shards.
_NATIVE_CHUNK_BYTES = 64 << 20

# Minimum records per sub-span when the fused drain decode splits one big
# chunk across reader threads (below this the spawn overhead beats the win).
# Module-level so tests can lower it to exercise the split arithmetic.
_SCATTER_SPLIT_MIN = 4096

# Read size used past a file's stat()ed length: files can grow between the
# stat and the read, so probe for extra bytes — but with a bounded request,
# not a full chunk (BufferedReader pre-allocates the entire requested size,
# so a 64MB request that returns 0 bytes at EOF still costs a 64MB alloc).
_EOF_PROBE_BYTES = 64 << 10


def _native_loader():
    """The native decoder module, built and loaded (first use compiles it;
    ``NativeBuildError`` propagates when that fails)."""
    from ..native import loader  # noqa: PLC0415 (lazy: builds .so on first use)
    loader.load()
    return loader


def _iter_framed_stream(stream: BinaryIO, loader, verify_crc: bool = True,
                        *, path: str = "", policy: Optional[BadRecordPolicy] = None,
                        size_hint: Optional[int] = None
                        ) -> Iterator[Tuple[bytes, np.ndarray, np.ndarray]]:
    """Chunked read() + C-speed framing with a carried partial tail: yields
    (buf, offsets, lengths) per chunk from any sequential byte source.
    Constant memory on multi-GB inputs, and plain I/O errors stay catchable
    Python exceptions (an mmap would turn them into SIGBUS). The single
    framing state machine shared by the record iterator, the vectorized
    file path, and the streaming (Pipe-mode) path.

    Bad frames: the native framer rejects a corrupt chunk wholesale; the
    chunk is then re-scanned by the pure-Python framer, which locates the
    exact absolute byte offset (for the path+offset error message) and
    applies the same raise/skip ``policy`` as the pure-Python decode path —
    so both decoder paths surface identical locations and skip-policy
    behavior. Clean data never takes the re-scan, keeping the fast path
    byte-identical (TestPooledEmissionGolden).

    ``size_hint`` (the stat()ed file length, when the caller has one) caps
    each read request at the bytes actually remaining: BufferedReader
    pre-allocates the full requested size per call, so an unhinted 64MB
    request against a 5MB file costs a 64MB alloc + trim every chunk — the
    second-largest host-path overhead in the r6 per-stage breakdown. Past
    the hint the loop keeps reading in ``_EOF_PROBE_BYTES`` requests (files
    may grow after the stat), so the emitted spans are identical with or
    without the hint."""
    carry = b""
    carry_base = 0  # absolute stream offset of carry[0]
    read_size = _NATIVE_CHUNK_BYTES
    pos = 0  # bytes read from the stream so far
    while True:
        if size_hint is not None and size_hint > pos:
            want = min(read_size, size_hint - pos)
        elif size_hint is not None:
            want = _EOF_PROBE_BYTES
        else:
            want = read_size
        with trace_lib.span("input.read"):
            chunk = stream.read(want)
        if not chunk:
            if carry:
                # Strict parse of the leftover: surfaces truncated-input
                # as an error (or a counted skip under the policy).
                with trace_lib.span("input.frame"):
                    try:
                        offsets, lengths = loader.split_frames(
                            carry, verify_crc=verify_crc)
                    except IOError:
                        offsets, lengths, _, _ = tfrecord.scan_frames_partial(
                            carry, verify_crc=verify_crc, final=True,
                            base_offset=carry_base, path=path, policy=policy)
                yield carry, offsets, lengths
            return
        pos += len(chunk)
        buf = carry + chunk if carry else chunk
        buf_base = carry_base
        abort = False
        with trace_lib.span("input.frame"):
            try:
                offsets, lengths, consumed = loader.split_frames_partial(
                    buf, verify_crc=verify_crc)
            except IOError:
                offsets, lengths, consumed, abort = \
                    tfrecord.scan_frames_partial(
                        buf, verify_crc=verify_crc, final=False,
                        base_offset=buf_base, path=path, policy=policy)
        yield buf, offsets, lengths
        if abort:  # framing cannot resync past the corruption
            return
        carry = buf[consumed:]
        carry_base = buf_base + consumed
        # A record larger than the read size frames nothing (consumed=0);
        # double the next read so it completes in O(n) total copying
        # rather than O(n^2) re-copies of the growing carry.
        read_size = (_NATIVE_CHUNK_BYTES if consumed
                     else max(read_size * 2, _NATIVE_CHUNK_BYTES))


def _health_retry_cb(policy: Optional[BadRecordPolicy], path: str):
    """on_retry hook recording healed transient reads into DataHealth."""
    if policy is None:
        return None
    health = policy.health
    return lambda exc, n: health.record_retry(path)


def _iter_framed_chunks(path: str, loader, verify_crc: bool = True, *,
                        policy: Optional[BadRecordPolicy] = None,
                        retry_policy=None
                        ) -> Iterator[Tuple[bytes, np.ndarray, np.ndarray]]:
    """File-path front-end of ``_iter_framed_stream`` (local or gs://),
    reading through a ResilientStream so transient mid-file errors heal.
    The stat()ed length becomes the framer's ``size_hint`` (right-sized
    read buffers); a failed stat degrades to unhinted reads, not an error."""
    try:
        size_hint: Optional[int] = fileio.size(path)
    except Exception:
        size_hint = None
    with fileio.open_resilient(path, policy=retry_policy,
                               on_retry=_health_retry_cb(policy, path)) as f:
        yield from _iter_framed_stream(f, loader, verify_crc,
                                       path=path, policy=policy,
                                       size_hint=size_hint)


def _iter_file_records(path: str, use_native: bool, verify_crc: bool = True,
                       *, policy: Optional[BadRecordPolicy] = None,
                       retry_policy=None) -> Iterator[bytes]:
    """Per-file record iterator with the same CRC policy on both paths
    (same integrity guarantee regardless of toolchain)."""
    loader = _native_loader() if use_native else None
    if loader is not None:
        for buf, offsets, lengths in _iter_framed_chunks(
                path, loader, verify_crc, policy=policy,
                retry_policy=retry_policy):
            for off, ln in zip(offsets.tolist(), lengths.tolist()):
                yield buf[off:off + ln]
        return
    yield from tfrecord.iter_records(
        path, verify_crc=verify_crc, policy=policy, resilient=True,
        retry_policy=retry_policy, on_retry=_health_retry_cb(policy, path))


def _available_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))  # respects cgroup/affinity limits
    except AttributeError:  # non-Linux
        return os.cpu_count() or 1


def _trim_skip(src: Iterator[Tuple[Batch, int, int]], skip: int, bs: int
               ) -> Iterator[Tuple[Batch, int, int]]:
    """Drop the first ``skip`` batches from a grouped ``(rows, m, n_ex)``
    stream — whole emissions dropped, a partially-covered group sliced (the
    surviving rows stay one contiguous block)."""
    for rows, m, n_ex in src:
        if skip:
            if m <= skip:
                skip -= m
                continue
            rows = {key: v[skip * bs:] for key, v in rows.items()}
            m -= skip
            n_ex -= skip * bs
            skip = 0
        yield rows, m, n_ex


def _group_plain_batches(batches: Iterator[Batch], k: int, bs: int
                         ) -> Iterator[Tuple[Batch, int, int]]:
    """Fallback superbatch grouping over a per-batch stream (stack copy):
    full groups of k, short tails flushed as singles."""
    group: List[Batch] = []
    for b in batches:
        if b["label"].shape[0] == bs:
            group.append(b)
            if len(group) == k:
                yield ({key: np.concatenate([g[key] for g in group])
                        for key in group[0]}, k, k * bs)
                group = []
        else:  # short tail: flush pending then emit single
            for g in group:
                yield g, 1, bs
            group = []
            yield b, 1, b["label"].shape[0]
    for g in group:
        yield g, 1, bs


class _DrainPool:
    """Lazily-created drain-decode thread pool, owned by ONE iterator.

    Persistent across every pool drain of that iterator (spawn/join per
    drain would recur every shuffle_buffer records), but private to it: a
    pipeline-shared executor let one iterator's epoch-end release kill a
    concurrent iterator's in-flight drain (advisor r5).
    """

    def __init__(self, n_threads: int):
        self._n = n_threads
        self._ex = None

    def get(self):
        if self._ex is None:
            from concurrent.futures import ThreadPoolExecutor  # noqa: PLC0415
            self._ex = ThreadPoolExecutor(
                self._n, thread_name_prefix="pipeline-decode")
        return self._ex

    def shutdown(self) -> None:
        if self._ex is not None:
            self._ex.shutdown(wait=False)
            self._ex = None


class CtrPipeline:
    """TFRecord CTR input pipeline producing fixed-shape numpy batches."""

    def __init__(
        self,
        files: Sequence[str],
        *,
        field_size: int,
        batch_size: int,
        num_epochs: int = 1,
        shuffle: bool = True,
        shuffle_files: bool = True,
        shuffle_buffer: int = 10000,
        drop_remainder: bool = True,
        seed: int = 42,
        shard: Optional[sharding.ShardSpec] = None,
        prefetch_batches: int = 4,
        use_native_decoder: bool = True,
        native_assembly: bool = True,
        reader_threads: int = 4,
        verify_crc: bool = False,  # speed-over-parity default (see Config); codec fns keep True
        epoch_offset: int = 0,
        skip_batches: int = 0,
        on_bad_record: str = "raise",
        max_bad_records: int = 0,
        retry_policy=None,
        input_workers: int = 0,
        input_worker_slab_records: Optional[int] = None,
        input_worker_death: str = "raise",
        stall_timeout_s: float = 0.0,
        decoded_cache: str = "off",
        decoded_cache_dir: str = "",
        num_labels: int = 1,
        history: bool = False,
        history_max_len: int = 20,
    ):
        if shard is not None:
            self._files: Tuple[str, ...] = shard.files
            self._record_shard = shard.record_shard
        else:
            self._files = tuple(files)
            self._record_shard = None
        self.field_size = field_size
        self.batch_size = batch_size
        self.num_epochs = num_epochs
        self.shuffle = shuffle
        self.shuffle_files = shuffle_files
        self.shuffle_buffer = shuffle_buffer
        self.drop_remainder = drop_remainder
        self.seed = seed
        self.prefetch_batches = prefetch_batches
        # Clamp to AVAILABLE cores: on a 1-core host a 4-thread decode pool
        # only adds contention (~6% measured); extra threads help only when
        # the GIL-released C decoder can actually run in parallel. Use the
        # scheduler affinity mask where exposed (cgroup/CI-quota accurate),
        # not os.cpu_count() (physical cores).
        self.reader_threads = max(1, min(reader_threads, _available_cores()))
        self._use_native = use_native_decoder
        # Fused decode->assemble (one C call per drain writing straight into
        # the transfer-layout pool). Off = per-chunk scatter-decode, which
        # emits bit-identical bytes — the flag exists as a kill switch and
        # for the tests to pin that parity. Ignored when
        # the built .so predates the entry point (loader.has_assemble()).
        self.native_assembly = bool(native_assembly)
        self.verify_crc = verify_crc
        # Shifts the internal epoch index used for shuffle seeding. The task
        # driver recreates the pipeline per epoch with num_epochs=1 (the
        # reference's file-mode shape, 2-hvd-gpu/...py:390-394); without the
        # offset every driver epoch would replay epoch-0's byte-identical
        # shuffle order (VERDICT r2 weak #2).
        self.epoch_offset = epoch_offset
        # Step-accurate resume: drop the first N emitted batches (the
        # already-trained prefix of an interrupted epoch). Applied INSIDE
        # each emission path so the skipped stream is identical to the one
        # the interrupted run trained on — an external wrapper would both
        # hide iter_superbatches (killing the zero-copy feed) and, worse,
        # skip along the k=1 pooled stream while training had consumed the
        # k-pooled stream, whose batch order differs past the first drain.
        self.skip_batches = skip_batches
        # Multi-label emission (--tasks ctr,cvr): batches gain a "label2"
        # [B, 1] column decoded from the optional on-disk key. The
        # multi-label stream takes the eager decode path only — the fused
        # drain entry, the shm worker slabs, and the decoded cache are
        # single-label layouts by design, so they are forced off here
        # rather than silently dropping the second column.
        self.num_labels = max(1, int(num_labels))
        if self.num_labels > 2:
            raise ValueError(
                f"num_labels must be 1 or 2, got {num_labels} (the on-disk "
                "schema carries at most one extra 'label2' column)")
        if self.num_labels > 1:
            input_workers = 0
            native_assembly = False
            self.native_assembly = False
            decoded_cache = "off"
        # History emission (sequence models): batches gain fixed "hist_ids"
        # int32[B, L] / "hist_mask" f32[B, L] columns decoded from the
        # optional ragged on-disk pair, padded/truncated to history_max_len
        # (the mask is the decoded hist_vals column — zero past each
        # record's actual length, so it doubles as attention weights). Like
        # num_labels>1, the history stream takes the eager decode path only:
        # the fused drain entry, the shm worker slabs, and the decoded
        # cache are fixed-arity single-label layouts by design.
        self.history = bool(history)
        self.history_max_len = int(history_max_len)
        if self.history and self.num_labels > 1:
            raise ValueError(
                "history=True is incompatible with num_labels>1 (one "
                "optional schema extension per stream)")
        if self.history and self.history_max_len < 1:
            raise ValueError(
                f"history_max_len must be >= 1 when history=True, got "
                f"{history_max_len}")
        if self.history:
            input_workers = 0
            native_assembly = False
            self.native_assembly = False
            decoded_cache = "off"
        # Pool/chunk column width: history rides the existing (labels, ids,
        # vals) chunk tuples as extra packed columns (ids -> [n, F+L] int32
        # feat||hist ids, vals -> [n, F+L] f32 feat vals||hist mask), split
        # back out at batch-assembly time.
        self._pool_cols = self.field_size + (
            self.history_max_len if self.history else 0)
        self._decode = _get_decoder(use_native_decoder)
        self._decode2 = _get_decoder2(use_native_decoder)
        self._decode_hist = _get_decoder_hist(use_native_decoder)
        # Multi-process input service (opt-in, see workers.py): decode
        # worker processes feed shared-memory slabs; 0 = in-process decode
        # (the default path, byte-for-byte unchanged). Engaged only where
        # its determinism contract holds: native decoder present and no
        # record-level shard (workers see per-file streams, not global
        # record indices).
        self.input_workers = max(0, int(input_workers))
        self.input_worker_slab_records = input_worker_slab_records
        self.input_worker_death = input_worker_death
        # Stall watchdog on ring reads: a wedged-but-alive worker (hung
        # mount, deadlocked decoder) raises InputStallError instead of
        # polling forever. 0 = wait indefinitely (the pre-watchdog behavior).
        self.stall_timeout_s = float(stall_timeout_s)
        # Fault tolerance: one DataHealth/BadRecordPolicy pair per pipeline
        # (skip budget spans every epoch of this pipeline's life); the
        # retry policy governs opens + mid-file reopen-and-seek healing.
        self.health = DataHealth()
        self._bad_policy = BadRecordPolicy(
            on_bad_record, max_bad_records, self.health)
        self._retry_policy = retry_policy
        # Decoded-epoch cache (opt-in, see cache.py): frame+decode once,
        # serve later epochs from contiguous column slabs through the same
        # shuffle pool. Disabled under record-sharding — the 1/world filter
        # keys off the global record index of the per-epoch file order, so
        # the kept-row set is epoch-dependent and uncacheable.
        self._on_bad_record = on_bad_record
        self._max_bad_records = max_bad_records
        if decoded_cache != "off" and self._record_shard is not None:
            import warnings  # noqa: PLC0415
            warnings.warn(
                "decoded_cache disabled: record-level sharding keeps rows "
                "by per-epoch global index, which a cache cannot reproduce",
                RuntimeWarning, stacklevel=2)
            decoded_cache = "off"
        self.decoded_cache = decoded_cache
        self.decoded_cache_dir = decoded_cache_dir
        self._cache_cols = None  # built/loaded lazily, reused across epochs

    # ------------------------------------------------------------------
    # Vectorized fast path (native decode straight to arrays).
    # ------------------------------------------------------------------
    def _iter_decoded_chunks(self, epoch: int, loader
                             ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Per ~64MB chunk: frame + eager decode -> (labels, ids, vals)
        arrays. Framing, file order, CRC, and shard selection all come from
        ``_iter_framed_span_chunks`` (the single source shared with the
        fused path); the record-shard filter is applied to the SPAN arrays
        before decode, so sharded ranks decode only their own rows. Decode
        runs on a thread pool (the C decoder releases the GIL, so this
        scales on real cores) while framing/IO stays on the producer;
        bounded in-flight depth keeps memory ~threads x chunk; FIFO
        consumption preserves deterministic chunk order."""
        def decode(job: Tuple[bytes, np.ndarray, np.ndarray]):
            buf, offsets, lengths = job
            if self.num_labels > 1:
                labels, labels2, ids, vals = loader.decode_spans2(
                    buf, offsets, lengths, self.field_size)
                return np.stack([labels, labels2], axis=1), ids, vals
            if self.history:
                labels, ids, vals, hid, hmask, _ = loader.decode_spans_hist(
                    buf, offsets, lengths, self.field_size,
                    self.history_max_len)
                # Packed-column chunk layout (see __init__): feat||hist.
                return (labels, np.hstack([ids, hid]),
                        np.hstack([vals, hmask]))
            return loader.decode_spans(buf, offsets, lengths, self.field_size)

        jobs = self._iter_framed_span_chunks(epoch, loader)
        n_threads = self.reader_threads
        if n_threads <= 1:
            for job in jobs:
                yield decode(job)
        else:
            import collections  # noqa: PLC0415
            from concurrent.futures import ThreadPoolExecutor  # noqa: PLC0415
            with ThreadPoolExecutor(
                    n_threads, thread_name_prefix="pipeline-read") as ex:
                inflight: "collections.deque" = collections.deque()
                for job in jobs:
                    inflight.append(ex.submit(decode, job))
                    while len(inflight) >= n_threads + 1:
                        yield inflight.popleft().result()
                while inflight:
                    yield inflight.popleft().result()

    def _iter_batches_vectorized(self, loader) -> Iterator[Batch]:
        """Pool decoded chunks to >= max(shuffle_buffer, chunk) rows, permute
        the pool, then slice batches — at least the record path's shuffle
        quality (the pool is the whole epoch on small data, a >= 64MB window
        on large), with zero per-record Python."""
        for rows, _, _ in self._iter_pooled(loader, 1):
            yield rows

    def _epoch_files(self, epoch: int) -> List[str]:
        """THE per-epoch file order: deterministic seeded reshuffle
        (reference shuffles the file list once at :373-377; here it varies
        per epoch). Single source shared by the record path, the chunk
        paths, and the input-service worker assignment — worker-path batch
        reproducibility rests on all of them agreeing on this order."""
        files = list(self._files)
        if self.shuffle_files:
            np.random.default_rng(self.seed + epoch).shuffle(files)
        return files

    def _epoch_file_order(self, epoch: int) -> List[int]:
        """Canonical-file INDICES in ``_epoch_files`` order (shuffling a
        position list consumes the rng identically to shuffling the path
        list, so both views of the per-epoch order always agree)."""
        order = list(range(len(self._files)))
        if self.shuffle_files:
            np.random.default_rng(self.seed + epoch).shuffle(order)
        return order

    # ------------------------------------------------------------------
    # Decoded-epoch cache (tier 1 of the input acceleration layer).
    # ------------------------------------------------------------------
    def _make_cache(self):
        from . import cache as cache_lib  # noqa: PLC0415
        return cache_lib.DecodedEpochCache(
            self.decoded_cache, self.decoded_cache_dir, list(self._files),
            field_size=self.field_size, verify_crc=self.verify_crc,
            on_bad_record=self._on_bad_record,
            max_bad_records=self._max_bad_records, health=self.health)

    def _build_cache_columns(self):
        """One frame+decode pass in CANONICAL file order -> contiguous
        columns + per-file counts. Reuses the exact framing/CRC/bad-record
        machinery of the streaming paths, so a cached dataset contains
        precisely the rows a streamed epoch would have trained on."""
        from . import cache as cache_lib  # noqa: PLC0415
        loader = _native_loader() if self._use_native else None
        counts = np.zeros(len(self._files), np.int64)
        labs: List[np.ndarray] = []
        idss: List[np.ndarray] = []
        valss: List[np.ndarray] = []
        for fi, path in enumerate(self._files):
            n_file = 0
            if loader is not None:
                for buf, offsets, lengths in _iter_framed_chunks(
                        path, loader, self.verify_crc,
                        policy=self._bad_policy,
                        retry_policy=self._retry_policy):
                    if len(offsets) == 0:
                        continue
                    lab, ids, vals = loader.decode_spans(
                        buf, offsets, lengths, self.field_size)
                    labs.append(lab)
                    idss.append(ids)
                    valss.append(vals)
                    n_file += len(lab)
            else:
                recs = list(_iter_file_records(
                    path, False, self.verify_crc, policy=self._bad_policy,
                    retry_policy=self._retry_policy))
                if recs:
                    lab, ids, vals = self._decode(recs, self.field_size)
                    labs.append(lab)
                    idss.append(ids.astype(np.int32, copy=False))
                    valss.append(vals)
                    n_file += len(lab)
            counts[fi] = n_file
        if counts.sum() == 0 and len(self._files):
            raise IOError(f"no records found in {len(self._files)} files")
        return cache_lib.CacheColumns(
            np.concatenate(labs).astype(np.float32, copy=False),
            np.concatenate(idss),
            np.concatenate(valss),
            counts)

    def decoded_epoch_columns(self):
        """The dataset as cached columns, building the cache on miss (also
        the upload source for the device-resident fit path). Raises if the
        cache is off."""
        if self.decoded_cache == "off":
            raise RuntimeError("decoded_epoch_columns requires decoded_cache")
        if self._cache_cols is None:
            self._cache_cols = self._make_cache().get_or_build(
                self._build_cache_columns)
        return self._cache_cols

    def decoded_cache_fingerprint(self) -> str:
        """Identity of the cached columns (device-upload cache key)."""
        return self._make_cache().fingerprint

    def device_epoch_indices(self, epoch: int, k: int = 1) -> np.ndarray:
        """Row indices into the cached columns in EXACTLY the order the
        staged pooled path would emit them this epoch — the tiny per-epoch
        upload of the device-resident fit (4 bytes/record vs re-sending
        every row).

        Valid only in the single-drain regime (the pool covers the whole
        epoch: ``n < max(shuffle_buffer, k*batch_size)``), where the final
        drain scatters arrival row j to position perm[j] of one full
        permutation, so the emitted sequence is ``arrival[argsort(perm)]``.
        With a smaller pool the drain points depend on chunk boundaries and
        the caller must keep the staged path instead."""
        cols = self.decoded_epoch_columns()
        starts = np.zeros(len(cols.counts) + 1, np.int64)
        np.cumsum(cols.counts, out=starts[1:])
        arrival = np.concatenate([
            np.arange(starts[fi], starts[fi + 1], dtype=np.int64)
            for fi in self._epoch_file_order(epoch)]) if len(cols.counts) \
            else np.zeros((0,), np.int64)
        if not self.shuffle:
            return arrival.astype(np.int32)
        n = len(arrival)
        if n >= max(self.shuffle_buffer, k * self.batch_size):
            raise ValueError(
                "device_epoch_indices requires the shuffle pool to cover "
                f"the epoch (n={n} >= pool target); use the staged path")
        perm = np.random.default_rng(
            self.seed * 1_000_003 + epoch).permutation(n)
        return arrival[np.argsort(perm)].astype(np.int32)

    def _make_input_service(self, epoch: int):
        """Spawn the decode-worker fleet for one epoch. A start failure
        (spawn or POSIX shm restricted) propagates: the caller asked for
        ``input_workers`` decode processes, and the in-process decode is a
        different, slower host path."""
        from . import workers  # noqa: PLC0415 (keeps module import light)
        return workers.ShmInputService(
            self._epoch_files(epoch),
            field_size=self.field_size,
            num_workers=self.input_workers,
            slab_records=self.input_worker_slab_records,
            verify_crc=self.verify_crc,
            on_bad_record=self._bad_policy.on_bad,
            max_bad_records=self._bad_policy.max_bad,
            retry_policy=self._retry_policy,
            health=self.health,
            on_worker_death=self.input_worker_death,
            stall_timeout_s=self.stall_timeout_s,
        ).start()

    def _iter_framed_span_chunks(self, epoch: int, loader
                                 ) -> Iterator[Tuple[bytes, np.ndarray,
                                                     np.ndarray]]:
        """Frame (+CRC-check) chunks WITHOUT decoding: yields
        ``(buf, offsets, lengths)`` with the record-shard filter applied to
        the span index arrays. THE single source of file order, CRC
        semantics, and shard selection for the pooled paths —
        ``_iter_decoded_chunks`` consumes this same stream, so the fused
        (decode-at-drain) and eager-decode emissions cannot drift apart."""
        files = self._epoch_files(epoch)
        n_seen = 0
        got_any = False
        for path in files:
            for buf, offsets, lengths in _iter_framed_chunks(
                    path, loader, self.verify_crc,
                    policy=self._bad_policy,
                    retry_policy=self._retry_policy):
                if len(offsets) == 0:
                    continue
                got_any = True
                base = n_seen
                n_seen += len(offsets)
                if self._record_shard is not None:
                    world, rank = self._record_shard
                    keep = (np.arange(base, base + len(offsets))
                            % world) == rank
                    offsets, lengths = offsets[keep], lengths[keep]
                    if len(offsets) == 0:
                        continue
                yield buf, offsets, lengths
        if not got_any and files:
            raise IOError(f"no records found in {len(files)} files")

    def close(self) -> None:
        """Kept for API compatibility: the drain-decode executor is now
        per-iterator (``_DrainPool``), owned and released by each
        ``_iter_pooled_raw`` generator — a second live iterator of the
        same pipeline no longer loses its pool when the first one ends
        an epoch (advisor r5)."""

    def _scatter_decode_raw(self, loader, raw, perm: np.ndarray, off: int,
                            labels: np.ndarray, ids: np.ndarray,
                            vals: np.ndarray, pool: "_DrainPool") -> None:
        """Decode every raw span chunk straight into its permuted pool rows.
        Rows are disjoint across chunks and the C calls release the GIL, so
        chunks decode on the reader pool when more than one core is
        available; big single chunks are split into contiguous sub-spans
        (>= _SCATTER_SPLIT_MIN records each) to fill the pool.

        With ``native_assembly`` and a library that exports the fused entry,
        the single-threaded case crosses ctypes ONCE for the whole drain
        (``loader.assemble_spans`` over every chunk) instead of once per
        chunk — each GIL reacquisition after a released C call can stall up
        to a switch interval behind the prefetch consumer, so on a loaded
        1-core host the per-chunk calls cost real wall time. The threaded
        case keeps per-sub-span calls (that's what parallelizes). Both
        routes and the non-fused scatter emit bit-identical pool bytes."""
        jobs = []
        for buf, offsets, lengths in raw:
            m = len(offsets)
            parts = max(1, min(self.reader_threads, m // _SCATTER_SPLIT_MIN))
            step = (m + parts - 1) // parts
            for s in range(0, m, step):
                e = min(s + step, m)
                jobs.append((buf, offsets[s:e], lengths[s:e],
                             perm[off + s:off + e]))
            off += m

        if (self.native_assembly and hasattr(loader, "has_assemble")
                and loader.has_assemble()):
            if len(jobs) <= 1 or self.reader_threads <= 1:
                loader.assemble_spans(jobs, self.field_size,
                                      labels, ids, vals)
            else:
                list(pool.get().map(
                    lambda job: loader.assemble_spans(
                        [job], self.field_size, labels, ids, vals),
                    jobs))
            return

        lab_flat = labels.reshape(-1)

        def run(job):
            buf, offs, lens, dest = job
            loader.decode_spans_scatter(
                buf, offs, lens, self.field_size, dest, lab_flat, ids, vals)

        if len(jobs) <= 1 or self.reader_threads <= 1:
            for job in jobs:
                run(job)
        else:
            list(pool.get().map(run, jobs))

    def _iter_pooled(self, loader, k: int
                     ) -> Iterator[Tuple[Batch, int, int]]:
        """``_iter_pooled_raw`` with the resume skip applied: the first
        ``skip_batches`` batches are trimmed FROM THIS stream (whole
        emissions dropped; a partially-trained group is sliced — the rows
        stay one contiguous block), so the surviving order is exactly what
        an uninterrupted run would have trained after that prefix."""
        yield from _trim_skip(self._iter_pooled_raw(loader, k),
                              self.skip_batches, self.batch_size)

    def _iter_pooled_raw(self, loader, k: int
                         ) -> Iterator[Tuple[Batch, int, int]]:
        """THE pool/permute/drain machinery (single source for both the
        per-batch and the k-step superbatch feeds): yields ``(rows, m,
        n_examples)`` where ``rows`` is ``m`` stacked batches as contiguous
        ``[m*batch_size, ...]`` arrays (``m <= k``; the tail of each epoch
        emits single batches, the last possibly short). Non-final drains
        emit only full ``k*bs`` groups so k-groups stay contiguous pool
        slices; the per-epoch file shuffle and pool permutation are seeded
        from (seed, epoch + epoch_offset) exactly like the record path."""
        bs = self.batch_size
        sb = bs * max(k, 1)
        # Multi-process path (opt-in): decode runs in worker processes and
        # this generator pools zero-copy shared-memory views. The chunk
        # stream the service yields is exactly the in-process
        # ``_iter_decoded_chunks`` stream (same files, order, chunk
        # boundaries), so pooling it through the eager branch below emits
        # bit-identical batches (tests/test_input_workers.py). Disabled
        # under record-sharding (workers see per-file streams, not the
        # global record index the 1/world filter needs).
        # Cached columns trump every decode path: no framing, no decode,
        # no worker fleet — chunks are zero-copy views into the slab.
        cached_cols = None
        if self.decoded_cache != "off":
            from . import cache as cache_lib  # noqa: PLC0415
            cached_cols = self.decoded_epoch_columns()
        use_shm = (cached_cols is None and self.input_workers > 0
                   and loader is not None and self._record_shard is None)
        # Fused scatter-decode (r5): with shuffle on and the native decoder
        # available, the proto decode is DEFERRED to drain time and each
        # record decodes straight into its permuted pool row — one pass per
        # record instead of decode-then-scatter (two full passes over the
        # pool; the scatter was ~30% of the staged-path ns/record). The
        # permutation, chunk arrival order, and rng stream are identical to
        # the decode-then-scatter path, so the emission is bit-identical
        # (pinned by TestPooledEmissionGolden) and the resume layout
        # version is unchanged. Disabled under record-sharding: the fused
        # pool holds RAW chunk buffers until drain, and with a 1/world
        # filter those buffers hold ~world x the rows that count toward
        # pool_target — a world-fold RSS regression; the eager path decodes
        # (only) the kept rows and frees each buffer immediately.
        fused = (cached_cols is None and not use_shm and self.shuffle
                 and loader is not None
                 and self._record_shard is None and self.num_labels == 1
                 and not self.history
                 and hasattr(loader, "decode_spans_scatter"))
        # Drain-decode executor: per-ITERATOR, not per-pipeline — two live
        # iterators of one pipeline must not share (advisor r5: the first
        # one's epoch-end close() killed the second's in-flight drain).
        drain_pool = _DrainPool(self.reader_threads)
        try:
            for e in range(self.num_epochs):
                epoch = e + self.epoch_offset
                rng = np.random.default_rng(self.seed * 1_000_003 + epoch)
                pool_target = (max(self.shuffle_buffer, sb)
                               if self.shuffle else sb)
                pend: "collections.deque" = collections.deque()
                raw: List[Tuple[bytes, np.ndarray, np.ndarray]] = []
                n_pend = 0
                service = self._make_input_service(epoch) if use_shm else None
                trace_lib.instant("input.epoch", epoch=epoch,
                                  files=len(self._files))

                def drain(final: bool, service=service
                          ) -> Iterator[Tuple[Batch, int, int]]:
                    nonlocal pend, raw, n_pend
                    if self.shuffle and n_pend > 0 and (pend or raw):
                        # Single-scatter permutation: each row lands at its
                        # shuffled destination in ONE preallocated pool write
                        # (vs concatenate-then-gather = two full copies).
                        # Uniform: row j goes to position perm[j] of a full
                        # permutation. The drain-remainder (pend, already
                        # decoded) scatters first, then raw chunks decode
                        # directly to their rows — matching the arrival order
                        # the permutation indexes.
                        with trace_lib.span("input.pool_drain", epoch=epoch,
                                            records=n_pend):
                            perm = rng.permutation(n_pend)
                            # Transfer-layout pool: the label column is
                            # [n, 1] so a batch slice IS the emitted
                            # ``label`` array (the 1-D pool forced a full
                            # reshape+astype copy per emission). Same bytes,
                            # one less pass per batch.
                            labels = np.empty((n_pend, self.num_labels),
                                              np.float32)
                            lab_col = labels.reshape(-1)
                            ids = np.empty((n_pend, self._pool_cols),
                                           np.int32)
                            vals = np.empty((n_pend, self._pool_cols),
                                            np.float32)
                            off = 0
                            for lab, idx, val in pend:
                                dest = perm[off:off + len(lab)]
                                if self.num_labels == 1:
                                    lab_col[dest] = lab.reshape(-1)
                                else:
                                    labels[dest] = lab.reshape(len(lab), -1)
                                ids[dest] = idx
                                vals[dest] = val
                                off += len(lab)
                            if raw:
                                self._scatter_decode_raw(
                                    loader, raw, perm, off, labels, ids,
                                    vals, drain_pool)
                        pend = collections.deque([(labels, ids, vals)])
                        raw = []
                        if service is not None:
                            # Every held slab view has been scattered into
                            # the fresh pool arrays above — hand the slots
                            # back so workers refill them while we slice.
                            service.release_consumed()
                    hl = self.history_max_len if self.history else 0
                    while n_pend >= sb:
                        with trace_lib.span("input.emit", records=sb):
                            rows = self._assemble_batch(pend, sb, hl)
                        yield rows, k, sb
                        n_pend -= sb
                    if final:
                        while n_pend >= bs:
                            with trace_lib.span("input.emit", records=bs):
                                rows = self._assemble_batch(pend, bs, hl)
                            yield rows, 1, bs
                            n_pend -= bs
                        if n_pend and not self.drop_remainder:
                            with trace_lib.span("input.emit",
                                                records=n_pend):
                                rows = self._assemble_batch(pend, n_pend, hl)
                            yield rows, 1, n_pend
                            n_pend = 0

                def fill(chunks) -> bool:
                    """Pull chunks into the pool until it holds
                    ``pool_target`` records; False once the source ended."""
                    nonlocal n_pend
                    with trace_lib.span("input.pool_fill", epoch=epoch) as sp:
                        n0 = n_pend
                        for chunk in chunks:
                            # A framed span is (buf, offsets, lengths); a
                            # decoded chunk (labels, ids, vals).
                            (raw if fused else pend).append(chunk)
                            n_pend += len(chunk[1 if fused else 0])
                            if n_pend >= pool_target:
                                break
                        sp.add(records=n_pend - n0)
                    return n_pend >= pool_target

                with contextlib.ExitStack() as held:
                    if cached_cols is not None:
                        chunks = iter(cache_lib.epoch_chunks(
                            cached_cols, self._epoch_file_order(epoch)))
                    elif service is not None:
                        held.enter_context(service)
                        # shuffle=False never scatters, so views would stay
                        # referenced by batch slices indefinitely: copy out
                        # of the slabs instead of holding them.
                        chunks = iter(service.chunks(copy=not self.shuffle))
                    elif fused:
                        chunks = self._iter_framed_span_chunks(epoch, loader)
                    else:
                        chunks = self._iter_decoded_chunks(epoch, loader)
                    while fill(chunks):
                        yield from drain(final=False)
                    yield from drain(final=True)
        finally:
            # Release the drain-decode executor when the generator ends OR
            # is abandoned (GeneratorExit lands here). It persists across
            # every pool drain of every epoch of THIS iterator.
            drain_pool.shutdown()

    def iter_superbatches(self, k: int
                          ) -> Iterator[Tuple[Batch, int, int]]:
        """Yield ``(rows, m, n_examples)`` where ``rows`` holds ``m`` stacked
        batches as contiguous ``[m*batch_size, ...]`` arrays (``m <= k``;
        tail emissions may be single short batches with ``m == 1``).

        This is the zero-copy feed for the K-step dispatch loop: after the
        shuffle pool is permuted it is ONE contiguous array, so slicing
        ``k*bs`` rows and reshaping to ``[k, bs, ...]`` at transfer time
        costs nothing — versus ``np.stack`` over k single batches, which
        re-copies every row on the host core that is also doing the decode
        (the e2e bottleneck on small hosts; VERDICT r2 #5).
        """
        loader = _native_loader() if self._use_native else None
        if (loader is None and self.decoded_cache == "off") or k <= 1:
            # Per-record path: group plain batches (stack copy at transfer;
            # skip/prefetch handled by __iter__).
            yield from _group_plain_batches(iter(self), k, self.batch_size)
            return
        # Native pooled path bypasses __iter__'s prefetch; add the
        # decode-ahead stage here (depth in k-groups) so decode overlaps the
        # consumer's transfer+dispatch work. The fallback above iterates
        # ``self`` and is therefore already prefetched.
        src = self._iter_pooled(loader, k)
        if self.prefetch_batches > 0:
            src = _prefetch(src, max(1, self.prefetch_batches // k))
        yield from src

    @staticmethod
    def _assemble_batch(pend: "collections.deque",
                        bs: int, hist_len: int = 0) -> Batch:
        """Pop exactly ``bs`` rows off the front of the pending chunk
        deque (O(1) per chunk; a list's pop(0) re-shifts the whole pool
        every batch). With ``hist_len > 0`` the chunks carry packed
        feat||hist columns (see ``__init__``); the trailing ``hist_len``
        columns split out into the ``hist_ids``/``hist_mask`` batch keys."""
        take: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        need = bs
        while need:
            labels, ids, vals = pend[0]
            if len(labels) <= need:
                take.append(pend.popleft())
                need -= len(labels)
            else:
                take.append((labels[:need], ids[:need], vals[:need]))
                pend[0] = (labels[need:], ids[need:], vals[need:])
                need = 0
        if len(take) == 1:
            labels, ids, vals = take[0]
        else:
            labels = np.concatenate([t[0] for t in take])
            ids = np.concatenate([t[1] for t in take])
            vals = np.concatenate([t[2] for t in take])
        if hist_len:
            fs = ids.shape[1] - hist_len
            return {
                "feat_ids": np.ascontiguousarray(ids[:, :fs], np.int32),
                "feat_vals": np.ascontiguousarray(vals[:, :fs], np.float32),
                "hist_ids": np.ascontiguousarray(ids[:, fs:], np.int32),
                "hist_mask": np.ascontiguousarray(vals[:, fs:], np.float32),
                "label": np.ascontiguousarray(
                    labels.reshape(-1, 1), np.float32),
            }
        # ascontiguousarray, not astype: a contiguous float32 pool slice
        # (the shuffled drain's [n, 1] label column, and all ids/vals)
        # passes through as a zero-copy view — same bytes, no per-emission
        # label copy. Non-contiguous or 1-D chunk labels still normalize
        # to the same [bs, 1] float32 layout.
        if labels.ndim == 2 and labels.shape[1] > 1:
            # Multi-label chunks ([n, 2] columns): split into the batch
            # contract's named [bs, 1] label columns.
            return {
                "feat_ids": np.ascontiguousarray(ids, np.int32),
                "feat_vals": np.ascontiguousarray(vals, np.float32),
                "label": np.ascontiguousarray(labels[:, :1], np.float32),
                "label2": np.ascontiguousarray(labels[:, 1:2], np.float32),
            }
        return {
            "feat_ids": np.ascontiguousarray(ids, np.int32),
            "feat_vals": np.ascontiguousarray(vals, np.float32),
            "label": np.ascontiguousarray(labels.reshape(-1, 1), np.float32),
        }

    # ------------------------------------------------------------------
    def _iter_raw_records(self, epoch: int) -> Iterator[bytes]:
        files = self._epoch_files(epoch)
        n_seen = 0
        for path in files:
            for rec in _iter_file_records(path, self._use_native,
                                          self.verify_crc,
                                          policy=self._bad_policy,
                                          retry_policy=self._retry_policy):
                keep = (
                    self._record_shard is None
                    or n_seen % self._record_shard[0] == self._record_shard[1]
                )
                n_seen += 1
                if keep:
                    yield rec
        if n_seen == 0 and files:
            raise IOError(f"no records found in {len(files)} files")

    def _iter_shuffled(self, epoch: int) -> Iterator[bytes]:
        """Buffered uniform shuffle (tf.data.Dataset.shuffle semantics)."""
        if not self.shuffle or self.shuffle_buffer <= 1:
            yield from self._iter_raw_records(epoch)
            return
        rng = np.random.default_rng(self.seed * 1_000_003 + epoch)
        buf: List[bytes] = []
        for rec in self._iter_raw_records(epoch):
            if len(buf) < self.shuffle_buffer:
                buf.append(rec)
                continue
            j = int(rng.integers(0, len(buf)))
            yield buf[j]
            buf[j] = rec
        rng.shuffle(buf)
        yield from buf

    def _iter_batches_sync(self) -> Iterator[Batch]:
        skip = self.skip_batches
        for e in range(self.num_epochs):
            epoch = e + self.epoch_offset
            pending: List[bytes] = []
            for rec in self._iter_shuffled(epoch):
                pending.append(rec)
                if len(pending) == self.batch_size:
                    if skip:
                        skip -= 1
                    else:
                        yield self._make_batch(pending)
                    pending = []
            if pending and not self.drop_remainder:
                if skip:
                    skip -= 1
                else:
                    yield self._make_batch(pending)

    def _make_batch(self, records: List[bytes]) -> Batch:
        if self.num_labels > 1:
            labels, labels2, ids, vals = self._decode2(
                records, self.field_size)
            return {
                "feat_ids": np.ascontiguousarray(ids, np.int32),
                "feat_vals": np.ascontiguousarray(vals, np.float32),
                "label": labels.reshape(-1, 1).astype(np.float32),
                "label2": labels2.reshape(-1, 1).astype(np.float32),
            }
        if self.history:
            labels, ids, vals, hid, hmask, _ = self._decode_hist(
                records, self.field_size, self.history_max_len)
            return {
                "feat_ids": np.ascontiguousarray(ids, np.int32),
                "feat_vals": np.ascontiguousarray(vals, np.float32),
                "hist_ids": np.ascontiguousarray(hid, np.int32),
                "hist_mask": np.ascontiguousarray(hmask, np.float32),
                "label": labels.reshape(-1, 1).astype(np.float32),
            }
        labels, ids, vals = self._decode(records, self.field_size)
        return {
            "feat_ids": np.ascontiguousarray(ids, np.int32),
            "feat_vals": np.ascontiguousarray(vals, np.float32),
            "label": labels.reshape(-1, 1).astype(np.float32),
        }

    def _batch_source(self) -> Iterator[Batch]:
        """Vectorized native path when available (whole chunks decoded to
        arrays, numpy-level shuffle — the reference's 'vectorized map'
        insight taken to its conclusion); per-record Python path otherwise.
        Shuffle note: the vectorized path permutes within ~64MB decode
        chunks (typically >> the 10k-record buffer of the record path),
        plus the per-epoch file-order shuffle."""
        loader = _native_loader() if self._use_native else None
        if loader is not None or self.decoded_cache != "off":
            # Cached columns need no decoder, so the pooled path also
            # serves toolchain-less hosts once the cache is warm.
            return self._iter_batches_vectorized(loader)
        return self._iter_batches_sync()

    # ------------------------------------------------------------------
    def __iter__(self) -> Iterator[Batch]:
        if self.prefetch_batches <= 0:
            yield from self._batch_source()
            return
        yield from _prefetch(self._batch_source(), self.prefetch_batches)

    def count_examples(self) -> int:
        """One full pass counting records (respecting the shard)."""
        return sum(1 for _ in self._iter_raw_records(epoch=0))


class ChainedFileStream:
    """Sequential read()-only view over a list of files, replayed N times.

    The producer side of the Pipe-mode analog: SageMaker's FIFO replays the
    channel once per epoch (``num_epochs`` lives with the producer, not the
    consumer — the FIFO cannot be re-opened, ``2-hvd-gpu/...py:396``). The
    consumer (``StreamingCtrPipeline``) sees one continuous byte stream.
    """

    def __init__(self, files: Sequence[str], *, num_epochs: int = 1,
                 shuffle_each_epoch: bool = False, seed: int = 42,
                 epoch_offset: int = 0, retry_policy=None,
                 health: Optional[DataHealth] = None):
        if not files:
            raise ValueError("ChainedFileStream needs at least one file")
        self._files: List[str] = []
        for e in range(num_epochs):
            epoch = e + epoch_offset  # continues across resumed invocations
            fs = list(files)
            if shuffle_each_epoch:
                # Seeded per-epoch reshuffle of the replay order: strictly
                # better for convergence than byte-identical epochs (the
                # reference FIFO replays identically; see ADVICE r1).
                np.random.default_rng(seed + epoch).shuffle(fs)
            self._files.extend(fs)
        self._idx = 0
        self._fh: Optional[BinaryIO] = None
        self._retry_policy = retry_policy
        self._health = health

    def _open_next(self, path: str) -> BinaryIO:
        # Per-file resilient opens: a transient mid-file fault heals inside
        # the producer, so the consumer's single-pass stream never breaks.
        on_retry = None
        if self._health is not None:
            health = self._health
            on_retry = lambda exc, n, p=path: health.record_retry(p)  # noqa: E731
        return fileio.open_resilient(path, policy=self._retry_policy,
                                     on_retry=on_retry)

    def read(self, n: int = -1) -> bytes:
        if n is None or n < 0:
            raise ValueError("ChainedFileStream only supports bounded reads")
        out = bytearray()
        while len(out) < n:
            if self._fh is None:
                if self._idx >= len(self._files):
                    break
                self._fh = self._open_next(self._files[self._idx])
                self._idx += 1
            chunk = self._fh.read(n - len(out))
            if not chunk:
                self._fh.close()
                self._fh = None
                continue
            out += chunk
        return bytes(out)

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


class StreamingCtrPipeline:
    """Pipe-mode analog: decode batches from a sequential byte stream.

    Single pass only — the reference's FIFO cannot be re-opened per epoch
    (``2-hvd-gpu/...py:396`` comment); callers wanting multiple epochs pass
    ``num_epochs`` to the *producer* side, exactly like SageMaker Pipe mode
    replays the channel.
    """

    def __init__(
        self,
        stream: BinaryIO,
        *,
        field_size: int,
        batch_size: int,
        drop_remainder: bool = True,
        prefetch_batches: int = 4,
        use_native_decoder: bool = True,
        record_shard: Optional[Tuple[int, int]] = None,
        verify_crc: bool = False,  # speed-over-parity default (see Config); codec fns keep True
        skip_batches: int = 0,
        on_bad_record: str = "raise",
        max_bad_records: int = 0,
        stream_label: str = "<stream>",
        health: Optional[DataHealth] = None,
        num_labels: int = 1,
    ):
        self.stream = stream
        self.field_size = field_size
        self.batch_size = batch_size
        self.drop_remainder = drop_remainder
        self.prefetch_batches = prefetch_batches
        self._use_native = use_native_decoder
        self._decode = _get_decoder(use_native_decoder)
        self._decode2 = _get_decoder2(use_native_decoder)
        self.num_labels = max(1, int(num_labels))
        if self.num_labels > 2:
            raise ValueError(
                f"num_labels must be 1 or 2, got {num_labels}")
        self._record_shard = record_shard
        self.verify_crc = verify_crc
        self.skip_batches = skip_batches  # resume: drop the trained prefix
        self._consumed = False
        # Shared-health option: ChainedFileStream heals retries on the
        # producer side; passing its DataHealth here gives one unified
        # stats object across the stream's producer and consumer.
        self.health = health if health is not None else DataHealth()
        self._stream_label = stream_label
        self._bad_policy = BadRecordPolicy(
            on_bad_record, max_bad_records, self.health)

    def _iter_records(self) -> Iterator[bytes]:
        """Stream records, applying the (world, rank) record shard when this
        process shares the stream with others (the dataset.shard analog for
        Pipe mode — without it every rank would train the identical bytes)."""
        it = tfrecord.iter_records_from_stream(
            self.stream, verify_crc=self.verify_crc,
            path=self._stream_label, policy=self._bad_policy)
        if self._record_shard is None:
            yield from it
            return
        world, rank = self._record_shard
        for i, rec in enumerate(it):
            if i % world == rank:
                yield rec

    def _iter_vectorized(self, loader) -> Iterator[Batch]:
        for rows, _, _ in self._iter_vectorized_grouped(loader, 1):
            yield rows

    def _iter_vectorized_grouped(self, loader, k: int
                                 ) -> Iterator[Tuple[Batch, int, int]]:
        """Native streaming fast path: C-speed chunked framing + vectorized
        decode straight off the byte stream — the same machinery as the
        file path (the reference's PipeModeDataset is a C++ reader, X3;
        round 1 framed pipe-mode records one-by-one in Python). Emits
        ``(rows, m, n_ex)`` groups of up to ``k`` stacked batches; since
        there is no shuffle, the batch sequence is stream order regardless
        of k (only the grouping differs)."""
        bs = self.batch_size
        sb = bs * max(k, 1)
        pend: "collections.deque" = collections.deque()
        n_pend = 0
        n_seen = 0
        for buf, offsets, lengths in _iter_framed_stream(
                self.stream, loader, self.verify_crc,
                path=self._stream_label, policy=self._bad_policy):
            if len(offsets) == 0:
                continue
            if self.num_labels > 1:
                lab1, lab2, ids, vals = loader.decode_spans2(
                    buf, offsets, lengths, self.field_size)
                labels = np.stack([lab1, lab2], axis=1)
            else:
                labels, ids, vals = loader.decode_spans(
                    buf, offsets, lengths, self.field_size)
            if self._record_shard is not None:
                world, rank = self._record_shard
                keep = (np.arange(n_seen, n_seen + len(labels))
                        % world) == rank
                labels, ids, vals = labels[keep], ids[keep], vals[keep]
            n_seen += len(offsets)
            if not len(labels):
                continue
            pend.append((labels, ids, vals))
            n_pend += len(labels)
            while n_pend >= sb:
                yield CtrPipeline._assemble_batch(pend, sb), k, sb
                n_pend -= sb
        while n_pend >= bs:
            yield CtrPipeline._assemble_batch(pend, bs), 1, bs
            n_pend -= bs
        if n_pend and not self.drop_remainder:
            yield CtrPipeline._assemble_batch(pend, n_pend), 1, n_pend

    def _batch_from_records(self, records: List[bytes]) -> Batch:
        if self.num_labels > 1:
            labels, labels2, ids, vals = self._decode2(
                records, self.field_size)
            return {
                "feat_ids": np.ascontiguousarray(ids, np.int32),
                "feat_vals": np.ascontiguousarray(vals, np.float32),
                "label": labels.reshape(-1, 1).astype(np.float32),
                "label2": labels2.reshape(-1, 1).astype(np.float32),
            }
        labels, ids, vals = self._decode(records, self.field_size)
        return {
            "feat_ids": np.ascontiguousarray(ids, np.int32),
            "feat_vals": np.ascontiguousarray(vals, np.float32),
            "label": labels.reshape(-1, 1).astype(np.float32),
        }

    def _iter_record_batches(self) -> Iterator[Batch]:
        """Pure-Python fallback: per-record framing + batched decode."""
        pending: List[bytes] = []
        for rec in self._iter_records():
            pending.append(rec)
            if len(pending) == self.batch_size:
                yield self._batch_from_records(pending)
                pending = []
        if pending and not self.drop_remainder:
            yield self._batch_from_records(pending)

    def _iter_sync(self) -> Iterator[Batch]:
        if self._consumed:
            raise RuntimeError(
                "StreamingCtrPipeline is single-pass (Pipe-mode FIFO semantics); "
                "create a new stream for another epoch")
        self._consumed = True
        loader = _native_loader() if self._use_native else None
        src = (self._iter_vectorized(loader) if loader is not None
               else self._iter_record_batches())
        skip = self.skip_batches
        for b in src:
            if skip:
                skip -= 1
                continue
            yield b

    def __iter__(self) -> Iterator[Batch]:
        if self.prefetch_batches <= 0:
            return self._iter_sync()
        return _prefetch(self._iter_sync(), self.prefetch_batches)

    def iter_superbatches(self, k: int) -> Iterator[Tuple[Batch, int, int]]:
        """Zero-stack superbatch feed for the K-step dispatch loop (same
        contract as CtrPipeline.iter_superbatches). Single-pass like every
        other read of this stream; batch sequence is identical to __iter__
        (stream order, no shuffle), so resume skip counts line up across
        both consumption paths."""
        loader = _native_loader() if self._use_native else None
        if loader is None or k <= 1:
            # skip/single-pass/prefetch handled by __iter__.
            yield from _group_plain_batches(iter(self), k, self.batch_size)
            return
        if self._consumed:
            raise RuntimeError(
                "StreamingCtrPipeline is single-pass (Pipe-mode FIFO "
                "semantics); create a new stream for another epoch")
        self._consumed = True
        src = _trim_skip(self._iter_vectorized_grouped(loader, k),
                         self.skip_batches, self.batch_size)
        if self.prefetch_batches > 0:
            src = _prefetch(src, max(1, self.prefetch_batches // k))
        yield from src


def _prefetch(it: Iterator[Batch], depth: int) -> Iterator[Batch]:
    """Run ``it`` in a daemon thread, keeping up to ``depth`` items ready.

    Consumer-abandonment-safe: if the consumer stops iterating early (e.g.
    ragged-shard min-truncation drops a rank's tail mid-epoch), closing this
    generator sets a stop flag; the producer's bounded put polls it, drops
    out, and closes the source iterator — no permanently-blocked thread, no
    leaked file handle."""
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    _END = object()
    stop = threading.Event()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker() -> None:
        try:
            for item in it:
                if not _put(item):
                    return
            _put(_END)
        except BaseException as e:  # propagate into consumer
            _put(e)
        finally:
            if stop.is_set():
                close = getattr(it, "close", None)
                if close is not None:
                    close()

    t = threading.Thread(target=worker, daemon=True,
                         name="pipeline-prefetch")
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                # `from None` severs the misleading implicit context (the
                # queue.Full/Empty juggling above); the note names the
                # producer thread so consumer-side tracebacks distinguish
                # pipeline faults from trainer faults.
                note = (f"raised in pipeline prefetch thread {t.name!r} "
                        "(data pipeline fault, not a trainer fault)")
                if hasattr(item, "add_note"):  # py3.11+
                    item.add_note(note)
                else:
                    notes = getattr(item, "__notes__", None)
                    if isinstance(notes, list):
                        notes.append(note)
                    else:
                        item.__notes__ = [note]
                raise item from None
            yield item
    finally:
        stop.set()
