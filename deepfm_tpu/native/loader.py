"""ctypes loader for the native TFRecord decoder (builds on first use).

Compiles ``tfrecord_native.cc`` with g++ into a shared library under
``_build/`` (never committed) and exposes:
  * ``split_frames(buf, verify_crc)`` -> (offsets, lengths) int64 arrays
  * ``decode_batch(records, field_size)`` -> (labels, ids, vals) — drop-in
    replacement for ``pipeline.decode_batch_python``
  * ``decode_file_bytes(buf, field_size, verify_crc)`` — whole-buffer
    one-pass framing + CRC + proto decode (the true hot path)

The built file is named after a hash of the source, so a checkout always
loads the library its own ``.cc`` describes: a fresh copy (where mtimes mean
nothing) builds once, an edited source builds again, and a library left by
another source is never picked up. A build or load failure raises
:class:`NativeBuildError` — the per-record Python codec is a far slower
host path, so a run asked to use the native decoder does not quietly take it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "tfrecord_native.cc")
_BUILD_DIR = os.path.join(_HERE, "_build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


class NativeBuildError(RuntimeError):
    """The native decoder could not be built or loaded on this machine."""


def _so_path() -> str:
    """``_build/libtfrecord-<source hash>.so`` for the source as it is now."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libtfrecord-{digest}.so")


def _build(so: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # Build beside the target and rename: input workers and parallel test
    # processes may all find the library missing at once, and none of them
    # may dlopen a half-written file.
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except FileNotFoundError as e:
        raise NativeBuildError(
            "native TFRecord decoder needs g++ on PATH to build "
            f"{os.path.basename(_SRC)} (or run with "
            "--use_native_decoder false)") from e
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            f"g++ failed building the native TFRecord decoder:\n"
            f"{e.stderr.decode(errors='replace')[-2000:]}") from e
    except subprocess.TimeoutExpired as e:
        raise NativeBuildError(
            "g++ timed out building the native TFRecord decoder") from e
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def load() -> ctypes.CDLL:
    """The decoder library, built first if this source has no build yet."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            _build(so)
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            raise NativeBuildError(
                f"built native decoder {so} failed to load: {e}") from e
        # Buffer params are raw pointers (not c_char_p) so zero-copy views of
        # bytes AND mmap objects both work via np.frombuffer.
        lib.dfm_split_frames.restype = ctypes.c_long
        lib.dfm_split_frames.argtypes = [
            ctypes.POINTER(ctypes.c_ubyte), ctypes.c_long, ctypes.c_long,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_long)]
        lib.dfm_split_frames_ex.restype = ctypes.c_long
        lib.dfm_split_frames_ex.argtypes = [
            ctypes.POINTER(ctypes.c_ubyte), ctypes.c_long, ctypes.c_long,
            ctypes.c_long, ctypes.c_long,
            ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long)]
        lib.dfm_decode_ctr.restype = ctypes.c_long
        lib.dfm_decode_ctr.argtypes = [
            ctypes.POINTER(ctypes.c_ubyte), ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long), ctypes.c_long, ctypes.c_long,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float)]
        lib.dfm_decode_ctr_ex.restype = ctypes.c_long
        lib.dfm_decode_ctr_ex.argtypes = [
            ctypes.POINTER(ctypes.c_ubyte), ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long), ctypes.c_long, ctypes.c_long,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_long)]
        lib.dfm_decode_ctr_scatter.restype = ctypes.c_long
        lib.dfm_decode_ctr_scatter.argtypes = [
            ctypes.POINTER(ctypes.c_ubyte), ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long), ctypes.c_long, ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_long)]
        # Fused multi-chunk assemble entry: absent from pre-r6 cached .so
        # builds (the mtime check rebuilds when the source is newer, but a
        # clock-skewed checkout can leave a stale library) — probe instead
        # of assuming, and let callers key off has_assemble().
        try:
            lib.dfm_decode_ctr_assemble.restype = ctypes.c_long
            lib.dfm_decode_ctr_assemble.argtypes = [
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_long), ctypes.c_long, ctypes.c_long,
                ctypes.POINTER(ctypes.c_long),
                ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_long)]
        except AttributeError:
            pass
        # Two-label decode entry (multi-task input): same stale-.so probe
        # discipline as the assemble entry above; callers key off
        # has_labels2() and fall back to the Python codec mirror.
        try:
            lib.dfm_decode_ctr2_ex.restype = ctypes.c_long
            lib.dfm_decode_ctr2_ex.argtypes = [
                ctypes.POINTER(ctypes.c_ubyte), ctypes.POINTER(ctypes.c_long),
                ctypes.POINTER(ctypes.c_long), ctypes.c_long, ctypes.c_long,
                ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_long)]
        except AttributeError:
            pass
        # History decode entry (sequence models): same stale-.so probe
        # discipline; callers key off has_hist() and fall back to the
        # Python codec mirror.
        try:
            lib.dfm_decode_ctr_hist.restype = ctypes.c_long
            lib.dfm_decode_ctr_hist.argtypes = [
                ctypes.POINTER(ctypes.c_ubyte), ctypes.POINTER(ctypes.c_long),
                ctypes.POINTER(ctypes.c_long), ctypes.c_long, ctypes.c_long,
                ctypes.c_long,
                ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_long)]
        except AttributeError:
            pass
        lib.dfm_crc32c.restype = ctypes.c_uint32
        lib.dfm_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_long]
        _lib = lib
        return _lib


def available() -> bool:
    """Whether the decoder builds and loads here — for test skips and
    tooling. The input pipeline does not ask: with ``use_native_decoder``
    it calls into the library and lets :class:`NativeBuildError` propagate."""
    try:
        load()
    except NativeBuildError:
        return False
    return True


def crc32c(data: bytes) -> int:
    lib = load()
    return int(lib.dfm_crc32c(data, len(data)))


def split_frames_partial(buf, *, verify_crc: bool = True
                         ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Like split_frames but tolerates an incomplete trailing record.

    Returns (offsets, lengths, consumed): ``consumed`` is the byte count of
    fully-framed records; the caller carries ``buf[consumed:]`` into the next
    chunk. This is the chunked-streaming primitive — constant memory on
    multi-GB shards, ordinary read() I/O (no mmap SIGBUS hazard on network
    filesystems)."""
    lib = load()
    cap = max(len(buf) // 16, 1)
    offsets = np.empty(cap, dtype=np.int64)
    lengths = np.empty(cap, dtype=np.int64)
    consumed = ctypes.c_long(0)
    n = lib.dfm_split_frames_ex(
        _as_ubyte_ptr(buf), len(buf), int(verify_crc), 1, cap,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        ctypes.byref(consumed))
    if n == -2:
        raise IOError("corrupt TFRecord: CRC mismatch")
    if n < 0:
        raise IOError(f"TFRecord split error {n}")
    return offsets[:n], lengths[:n], int(consumed.value)


def _as_ubyte_ptr(buf) -> "ctypes.POINTER(ctypes.c_ubyte)":
    """Zero-copy pointer to a bytes-like object (bytes, mmap, memoryview)."""
    arr = np.frombuffer(buf, dtype=np.uint8)
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte))


def split_frames(buf, *, verify_crc: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """Frame offsets/lengths of every record in a TFRecord byte buffer."""
    lib = load()
    # Upper bound: every record is >= 16 bytes on disk.
    cap = max(len(buf) // 16, 1)
    offsets = np.empty(cap, dtype=np.int64)
    lengths = np.empty(cap, dtype=np.int64)
    n = lib.dfm_split_frames(
        _as_ubyte_ptr(buf), len(buf), int(verify_crc), cap,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_long)))
    if n == -1:
        raise IOError("truncated TFRecord")
    if n == -2:
        raise IOError("corrupt TFRecord: CRC mismatch")
    if n < 0:
        raise IOError(f"TFRecord split error {n}")
    return offsets[:n], lengths[:n]


def decode_spans(buf, offsets: np.ndarray, lengths: np.ndarray,
                 field_size: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    lib = load()
    n = len(offsets)
    labels = np.empty(n, dtype=np.float32)
    ids = np.empty((n, field_size), dtype=np.int32)
    vals = np.empty((n, field_size), dtype=np.float32)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    detail = ctypes.c_long(0)
    rc = lib.dfm_decode_ctr_ex(
        _as_ubyte_ptr(buf),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        n, field_size,
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.byref(detail))
    if rc != 0:
        raise ValueError(f"native decode failed at record {-rc - 100}: "
                         f"{_decode_reason(detail.value, field_size)}")
    return labels, ids, vals


def _decode_reason(code: int, field_size: int) -> str:
    """Human-readable reason for a parse_ctr_example error code (shared by
    every decode entry point)."""
    reasons = {
        -20: "'label' is not a single float",
        -21: f"'ids' length != field_size={field_size}",
        -22: f"'values' length != field_size={field_size}",
        -23: ("required keys missing — need 'label' plus 'ids'/'values' "
              "(reference schema) or 'feat_ids'/'feat_vals' (legacy)"),
        -24: "'label2' is not a single float",
        -25: "malformed 'hist_ids' int64 list",
        -26: "malformed 'hist_vals' float list",
        -27: "'hist_ids'/'hist_vals' lengths differ (or one key missing)",
    }
    return reasons.get(code, f"malformed Example wire data (code {code})")


def has_labels2() -> bool:
    """True when the built library exports the two-label decode entry
    (``dfm_decode_ctr2_ex``). False on a stale cached .so — callers fall
    back to the Python codec mirror, which emits identical values."""
    lib = load()
    return lib is not None and hasattr(lib, "dfm_decode_ctr2_ex")


def decode_spans2(buf, offsets: np.ndarray, lengths: np.ndarray,
                  field_size: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Two-label variant of :func:`decode_spans` for multi-task input:
    returns ``(labels, labels2, ids, vals)`` with ``labels2[i]`` from the
    optional ``label2`` key (0.0 when absent). Falls back to the
    bit-identical Python codec mirror when the cached library predates the
    entry (same discipline as ``assemble_spans``)."""
    lib = load()
    n = len(offsets)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    if lib is None or not hasattr(lib, "dfm_decode_ctr2_ex"):
        from ..data import example_codec  # noqa: PLC0415 (avoid module cycle)
        labels = np.empty(n, dtype=np.float32)
        labels2 = np.empty(n, dtype=np.float32)
        ids = np.empty((n, field_size), dtype=np.int32)
        vals = np.empty((n, field_size), dtype=np.float32)
        for i, (off, ln) in enumerate(zip(offsets.tolist(), lengths.tolist())):
            lab, lab2, rid, rval = example_codec.decode_ctr_example2(
                bytes(buf[off:off + ln]), field_size)
            labels[i] = lab
            labels2[i] = lab2
            ids[i] = rid.astype(np.int32)
            vals[i] = rval
        return labels, labels2, ids, vals
    labels = np.empty(n, dtype=np.float32)
    labels2 = np.empty(n, dtype=np.float32)
    ids = np.empty((n, field_size), dtype=np.int32)
    vals = np.empty((n, field_size), dtype=np.float32)
    detail = ctypes.c_long(0)
    rc = lib.dfm_decode_ctr2_ex(
        _as_ubyte_ptr(buf),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        n, field_size,
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        labels2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.byref(detail))
    if rc != 0:
        raise ValueError(f"native 2-label decode failed at record "
                         f"{-rc - 100}: "
                         f"{_decode_reason(detail.value, field_size)}")
    return labels, labels2, ids, vals


def decode_batch2(records: Sequence[bytes], field_size: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Two-label sibling of :func:`decode_batch`."""
    buf = b"".join(records)
    lengths = np.fromiter((len(r) for r in records), dtype=np.int64,
                          count=len(records))
    offsets = np.zeros(len(records), dtype=np.int64)
    if len(records) > 1:
        np.cumsum(lengths[:-1], out=offsets[1:])
    return decode_spans2(buf, offsets, lengths, field_size)


def has_hist() -> bool:
    """True when the built library exports the history decode entry
    (``dfm_decode_ctr_hist``). False on a stale cached .so — callers fall
    back to the Python codec mirror, which emits identical values."""
    lib = load()
    return lib is not None and hasattr(lib, "dfm_decode_ctr_hist")


def decode_spans_hist(
        buf, offsets: np.ndarray, lengths: np.ndarray, field_size: int,
        max_len: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray,
           np.ndarray]:
    """History variant of :func:`decode_spans` for sequence models:
    returns ``(labels, ids, vals, hist_ids [n, max_len] int32,
    hist_vals [n, max_len] float32, hist_len [n] int32)`` with the ragged
    ``hist_ids``/``hist_vals`` pair zero-padded and truncated to ``max_len``
    per record (absent pair -> empty history). Falls back to the
    bit-identical Python codec mirror when the cached library predates the
    entry (same discipline as ``decode_spans2``)."""
    lib = load()
    n = len(offsets)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    labels = np.empty(n, dtype=np.float32)
    ids = np.empty((n, field_size), dtype=np.int32)
    vals = np.empty((n, field_size), dtype=np.float32)
    hist_ids = np.zeros((n, max_len), dtype=np.int32)
    hist_vals = np.zeros((n, max_len), dtype=np.float32)
    hist_len = np.zeros(n, dtype=np.int32)
    if lib is None or not hasattr(lib, "dfm_decode_ctr_hist"):
        from ..data import example_codec  # noqa: PLC0415 (avoid module cycle)
        for i, (off, ln) in enumerate(zip(offsets.tolist(), lengths.tolist())):
            lab, rid, rval, hid, hval, hn = example_codec.decode_ctr_example_hist(
                bytes(buf[off:off + ln]), field_size, max_len)
            labels[i] = lab
            ids[i] = rid.astype(np.int32)
            vals[i] = rval
            hist_ids[i] = hid
            hist_vals[i] = hval
            hist_len[i] = hn
        return labels, ids, vals, hist_ids, hist_vals, hist_len
    detail = ctypes.c_long(0)
    rc = lib.dfm_decode_ctr_hist(
        _as_ubyte_ptr(buf),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        n, field_size, max_len,
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        hist_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        hist_vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        hist_len.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.byref(detail))
    if rc != 0:
        raise ValueError(f"native history decode failed at record "
                         f"{-rc - 100}: "
                         f"{_decode_reason(detail.value, field_size)}")
    return labels, ids, vals, hist_ids, hist_vals, hist_len


def decode_batch_hist(records: Sequence[bytes], field_size: int, max_len: int
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray, np.ndarray, np.ndarray]:
    """History sibling of :func:`decode_batch`."""
    buf = b"".join(records)
    lengths = np.fromiter((len(r) for r in records), dtype=np.int64,
                          count=len(records))
    offsets = np.zeros(len(records), dtype=np.int64)
    if len(records) > 1:
        np.cumsum(lengths[:-1], out=offsets[1:])
    return decode_spans_hist(buf, offsets, lengths, field_size, max_len)


def decode_spans_scatter(buf, offsets: np.ndarray, lengths: np.ndarray,
                         field_size: int, dest: np.ndarray,
                         labels: np.ndarray, ids: np.ndarray,
                         vals: np.ndarray) -> None:
    """Fused decode + scatter: decode record i of ``buf`` into row
    ``dest[i]`` of the caller-provided pool arrays (``labels`` [P],
    ``ids`` [P, field_size] int32, ``vals`` [P, field_size] float32, all
    C-contiguous). One pass over the records instead of decode-then-scatter
    (see ``CtrPipeline._iter_pooled_raw``); the caller guarantees every
    ``dest[i]`` is in bounds and disjoint across concurrent calls (the GIL
    is released inside the C call, so threads may fill disjoint rows of the
    same pool in parallel)."""
    lib = load()
    n = len(offsets)
    assert labels.flags.c_contiguous and ids.flags.c_contiguous \
        and vals.flags.c_contiguous
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    dest = np.ascontiguousarray(dest, dtype=np.int64)
    # The C side scatters unchecked (labels[dest[i]] etc.) — a caller bug
    # here is silent out-of-bounds heap writes, so validate the index
    # vector before handing over the pointers (advisor r5).
    if len(dest) != n:
        raise ValueError(
            f"decode_spans_scatter: len(dest)={len(dest)} != "
            f"len(offsets)={n}")
    rows = min(len(labels), len(ids), len(vals))
    if n and (int(dest.min()) < 0 or int(dest.max()) >= rows):
        raise ValueError(
            f"decode_spans_scatter: dest range [{int(dest.min())}, "
            f"{int(dest.max())}] outside pool of {rows} rows")
    if n == 0:
        return
    detail = ctypes.c_long(0)
    rc = lib.dfm_decode_ctr_scatter(
        _as_ubyte_ptr(buf),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        n, field_size,
        dest.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.byref(detail))
    if rc != 0:
        # Index is relative to THIS (possibly sub-span) call, not the chunk.
        raise ValueError(
            f"native scatter-decode failed at span-local record {-rc - 100}: "
            f"{_decode_reason(detail.value, field_size)}")


def has_assemble() -> bool:
    """True when the built library exports the fused multi-chunk
    decode->assemble entry (``dfm_decode_ctr_assemble``). False on a stale
    cached .so from an older source tree — callers fall back to the
    per-chunk ``decode_spans_scatter`` path, which emits identical bytes."""
    lib = load()
    return lib is not None and hasattr(lib, "dfm_decode_ctr_assemble")


def _validate_assemble_jobs(jobs, labels, ids, vals):
    """Shared bounds check for the fused entry and its Python fallback: the
    C side scatters unchecked, so every destination row must be validated
    before the pointers are handed over (same contract as
    ``decode_spans_scatter``)."""
    assert labels.flags.c_contiguous and ids.flags.c_contiguous \
        and vals.flags.c_contiguous
    rows = min(labels.shape[0], ids.shape[0], vals.shape[0])
    for offsets, _, dest in ((j[1], j[2], j[3]) for j in jobs):
        if len(dest) != len(offsets):
            raise ValueError(
                f"assemble_spans: len(dest)={len(dest)} != "
                f"len(offsets)={len(offsets)}")
        if len(dest) and (int(dest.min()) < 0 or int(dest.max()) >= rows):
            raise ValueError(
                f"assemble_spans: dest range [{int(dest.min())}, "
                f"{int(dest.max())}] outside pool of {rows} rows")


def assemble_spans(jobs, field_size: int, labels: np.ndarray,
                   ids: np.ndarray, vals: np.ndarray) -> None:
    """Fused decode->assemble: decode EVERY framed chunk span straight into
    its permuted rows of the transfer-layout output buffers, in ONE
    GIL-released C call per drain.

    ``jobs`` is a sequence of ``(buf, offsets, lengths, dest)`` — chunk
    bytes plus int64 span/destination arrays; ``labels`` is the label
    column ([P] or [P, 1] float32 — same contiguous memory either way),
    ``ids``/``vals`` are [P, field_size]. The caller owns destination
    bounds and disjointness, exactly like ``decode_spans_scatter``; unlike
    it, the whole drain crosses ctypes once, so a contended host pays one
    GIL reacquisition per drain instead of one per chunk."""
    lib = load()
    if not jobs:
        return
    if not hasattr(lib, "dfm_decode_ctr_assemble"):
        # Stale .so without the entry: per-chunk scatter, identical bytes.
        for buf, offsets, lengths, dest in jobs:
            decode_spans_scatter(buf, offsets, lengths, field_size, dest,
                                 labels.reshape(-1), ids, vals)
        return
    n_chunks = len(jobs)
    norm = []
    for buf, offsets, lengths, dest in jobs:
        norm.append((buf,
                     np.ascontiguousarray(offsets, dtype=np.int64),
                     np.ascontiguousarray(lengths, dtype=np.int64),
                     np.ascontiguousarray(dest, dtype=np.int64)))
    _validate_assemble_jobs(norm, labels, ids, vals)
    # Per-chunk pointer tables + the concatenated dest vector. The np
    # arrays in ``norm`` (and the raw buffers) stay referenced until the
    # call returns, so every pointer below stays live.
    bufs_arr = (ctypes.c_void_p * n_chunks)(
        *(ctypes.cast(_as_ubyte_ptr(j[0]), ctypes.c_void_p) for j in norm))
    offs_arr = (ctypes.c_void_p * n_chunks)(
        *(j[1].ctypes.data for j in norm))
    lens_arr = (ctypes.c_void_p * n_chunks)(
        *(j[2].ctypes.data for j in norm))
    counts = np.fromiter((len(j[1]) for j in norm), dtype=np.int64,
                         count=n_chunks)
    dest_all = (norm[0][3] if n_chunks == 1
                else np.concatenate([j[3] for j in norm]))
    err_chunk = ctypes.c_long(-1)
    detail = ctypes.c_long(0)
    rc = lib.dfm_decode_ctr_assemble(
        bufs_arr, offs_arr, lens_arr,
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        n_chunks, field_size,
        dest_all.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.byref(err_chunk), ctypes.byref(detail))
    if rc != 0:
        raise ValueError(
            f"native assemble failed at record {-rc - 100} of chunk "
            f"{err_chunk.value}: {_decode_reason(detail.value, field_size)}")


def assemble_spans_python(jobs, field_size: int, labels: np.ndarray,
                          ids: np.ndarray, vals: np.ndarray) -> None:
    """Pure-Python mirror of ``assemble_spans`` (bit-identical emission):
    each record decodes with the Python Example codec straight into its
    destination row of the same transfer-layout buffers. The reference
    implementation the fused C entry is tested against, and the forced
    fallback when the toolchain is unavailable."""
    from ..data import example_codec  # noqa: PLC0415 (avoid module cycle)
    _validate_assemble_jobs(
        [(j[0], np.asarray(j[1]), np.asarray(j[2]), np.asarray(j[3]))
         for j in jobs],
        labels, ids, vals)
    lab_flat = labels.reshape(-1)
    for buf, offsets, lengths, dest in jobs:
        for off, ln, d in zip(np.asarray(offsets).tolist(),
                              np.asarray(lengths).tolist(),
                              np.asarray(dest).tolist()):
            lab, rid, rval = example_codec.decode_ctr_example(
                bytes(buf[off:off + ln]), field_size)
            lab_flat[d] = lab
            ids[d] = rid.astype(np.int32)
            vals[d] = rval


def decode_batch(records: Sequence[bytes], field_size: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized decode of a list of serialized Examples (pipeline hook)."""
    buf = b"".join(records)
    lengths = np.fromiter((len(r) for r in records), dtype=np.int64,
                          count=len(records))
    offsets = np.zeros(len(records), dtype=np.int64)
    if len(records) > 1:
        np.cumsum(lengths[:-1], out=offsets[1:])
    return decode_spans(buf, offsets, lengths, field_size)


def decode_file_bytes(buf: bytes, field_size: int, *, verify_crc: bool = True
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One-pass decode of a whole TFRecord file buffer."""
    offsets, lengths = split_frames(buf, verify_crc=verify_crc)
    return decode_spans(buf, offsets, lengths, field_size)
