"""ctypes bindings for the native runtime (csrc/dtf_runtime.cc).

No pybind11 in this environment — the library exposes a plain C ABI and
this module wraps it. The library is built on demand with ``make`` the
first time it is requested (set ``DTF_NO_NATIVE=1`` to disable entirely).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libdtf_runtime.so")
_SRC = os.path.join(_DIR, "csrc", "dtf_runtime.cc")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _build() -> bool:
    try:
        subprocess.run(
            ["make", "-C", _DIR],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return os.path.exists(_SO)
    except (OSError, subprocess.SubprocessError):
        return False


def _out_of_date() -> bool:
    """True when there is no library, or its source is newer — the
    library is git-ignored, so a copied tree can carry one built from
    sources that have since changed."""
    try:
        return os.path.getmtime(_SO) < os.path.getmtime(_SRC)
    except OSError:
        return not os.path.exists(_SO)


def load_library() -> ctypes.CDLL:
    """Load (building if needed) the native library; raises ImportError if
    unavailable so callers can fall back to pure Python. A library older
    than its source is rebuilt first (``make`` owns the dependency); one
    that cannot be rebuilt is NOT loaded. A ``.so`` missing newer symbols
    is rebuilt once; if symbols are still missing the failure surfaces as
    ImportError so the pure-Python fallbacks engage rather than
    AttributeError escaping."""
    global _lib, _tried
    with _lock:
        if _lib is not None:
            return _lib
        if os.environ.get("DTF_NO_NATIVE"):
            raise ImportError("native runtime disabled via DTF_NO_NATIVE")
        if _out_of_date():
            if _tried or not _build() or _out_of_date():
                _tried = True
                raise ImportError(
                    "libdtf_runtime.so missing or older than its source "
                    "(build failed)"
                )
        _tried = True
        lib = ctypes.CDLL(_SO)
        try:
            _bind(lib)
        except AttributeError as exc:
            # dlopen caches by pathname: close the stale mapping or the
            # post-rebuild CDLL call would hand back the old library.
            import _ctypes

            _ctypes.dlclose(lib._handle)
            try:
                os.remove(_SO)
            except OSError:
                pass
            if not _build():
                raise ImportError(
                    f"stale libdtf_runtime.so and rebuild failed: {exc}"
                ) from exc
            lib = ctypes.CDLL(_SO)
            try:
                _bind(lib)
            except AttributeError as exc2:
                raise ImportError(
                    f"libdtf_runtime.so missing symbol after rebuild: {exc2}"
                ) from exc2
        _lib = lib
        return lib


def _bind(lib: ctypes.CDLL) -> None:
    """Declare C ABI signatures; raises AttributeError on a missing symbol."""
    lib.dtf_load_idx_images.restype = ctypes.c_long
    lib.dtf_load_idx_images.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_long,
    ]
    lib.dtf_load_idx_labels.restype = ctypes.c_long
    lib.dtf_load_idx_labels.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_long),
        ctypes.c_long,
    ]
    lib.dtf_shuffle_perm.restype = None
    lib.dtf_shuffle_perm.argtypes = [
        ctypes.POINTER(ctypes.c_long),
        ctypes.c_long,
        ctypes.c_uint64,
    ]
    lib.dtf_gather_rows.restype = None
    lib.dtf_gather_rows.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_long),
        ctypes.c_long,
        ctypes.c_long,
        ctypes.POINTER(ctypes.c_float),
    ]
    lib.dtf_coord_start.restype = ctypes.c_void_p
    lib.dtf_coord_start.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.dtf_coord_start2.restype = ctypes.c_void_p
    lib.dtf_coord_start2.argtypes = [
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.dtf_coord_alive_count.restype = ctypes.c_int
    lib.dtf_coord_alive_count.argtypes = [ctypes.c_void_p]
    lib.dtf_coord_failed_count.restype = ctypes.c_int
    lib.dtf_coord_failed_count.argtypes = [ctypes.c_void_p]
    lib.dtf_coord_ms_since_seen.restype = ctypes.c_long
    lib.dtf_coord_ms_since_seen.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.dtf_coord_progress.restype = ctypes.c_long
    lib.dtf_coord_progress.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.dtf_coord_ms_since_progress.restype = ctypes.c_long
    lib.dtf_coord_ms_since_progress.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.dtf_coord_stalled_count.restype = ctypes.c_int
    lib.dtf_coord_stalled_count.argtypes = [ctypes.c_void_p, ctypes.c_long]
    lib.dtf_coord_stop.restype = None
    lib.dtf_coord_stop.argtypes = [ctypes.c_void_p]
    lib.dtf_worker_start.restype = ctypes.c_void_p
    lib.dtf_worker_start.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.dtf_worker_set_progress.restype = None
    lib.dtf_worker_set_progress.argtypes = [ctypes.c_void_p, ctypes.c_long]
    lib.dtf_worker_stop.restype = None
    lib.dtf_worker_stop.argtypes = [ctypes.c_void_p]
    lib.dtf_crc32c.restype = ctypes.c_uint32
    lib.dtf_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.dtf_crc32c_masked.restype = ctypes.c_uint32
    lib.dtf_crc32c_masked.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.dtf_bpe_train.restype = ctypes.c_long
    lib.dtf_bpe_train.argtypes = [
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_long),
        ctypes.c_long,
        ctypes.c_long,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.dtf_bpe_encode.restype = ctypes.c_long
    lib.dtf_bpe_encode.argtypes = [
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_long,
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_long,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.dtf_bpe_encode_batch.restype = ctypes.c_long
    lib.dtf_bpe_encode_batch.argtypes = [
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_long,
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_long),
        ctypes.c_long,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_long),
    ]


def available() -> bool:
    try:
        load_library()
        return True
    except ImportError:
        return False


# ---------------------------------------------------------------------------
# Data pipeline bindings
# ---------------------------------------------------------------------------


def load_idx_images(path: str) -> np.ndarray:
    lib = load_library()
    n = lib.dtf_load_idx_images(path.encode(), None, 0)
    if n < 0:
        raise OSError(f"failed to parse IDX images: {path}")
    # IDX MNIST rows*cols is always 784; query again with a buffer.
    out = np.empty(n * 784, dtype=np.float32)
    got = lib.dtf_load_idx_images(
        path.encode(), out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), out.size
    )
    if got != n:
        raise OSError(f"short read from IDX images: {path}")
    return out.reshape(n, 784)


def load_idx_labels(path: str) -> np.ndarray:
    lib = load_library()
    n = lib.dtf_load_idx_labels(path.encode(), None, 0)
    if n < 0:
        raise OSError(f"failed to parse IDX labels: {path}")
    out = np.empty(n, dtype=np.int64)
    got = lib.dtf_load_idx_labels(
        path.encode(), out.ctypes.data_as(ctypes.POINTER(ctypes.c_long)), out.size
    )
    if got != n:
        raise OSError(f"short read from IDX labels: {path}")
    return out


def shuffle_perm(n: int, seed: int) -> np.ndarray:
    lib = load_library()
    out = np.empty(n, dtype=np.int64)
    lib.dtf_shuffle_perm(
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_long)), n, seed & (2**64 - 1)
    )
    return out


def gather_rows(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    lib = load_library()
    src = np.ascontiguousarray(src, dtype=np.float32)
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    out = np.empty((idx.shape[0], src.shape[1]), dtype=np.float32)
    lib.dtf_gather_rows(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        idx.shape[0],
        src.shape[1],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return out


# ---------------------------------------------------------------------------
# BPE bindings (data/text.py's fast path)
# ---------------------------------------------------------------------------


def bpe_train(docs: list[str], num_merges: int) -> list[tuple[int, int]]:
    """Train byte-level BPE merges natively; bit-identical to
    data/text.py's ``_bpe_train_py`` (pinned by tests/test_text.py).
    Raises ImportError (→ the caller's pure-Python fallback) for corpora
    beyond the native path's int32 position indexing (~2 GiB)."""
    lib = load_library()
    blobs = [d.encode("utf-8") for d in docs]
    lens = np.asarray([len(b) for b in blobs], np.int64)
    if int(lens.sum()) > 0x7FFFFFF0:
        raise ImportError("corpus exceeds native BPE int32 indexing")
    data = np.frombuffer(b"".join(blobs), np.uint8)
    data = np.ascontiguousarray(data)
    out = np.empty(2 * max(num_merges, 1), np.int32)
    got = lib.dtf_bpe_train(
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        len(blobs),
        num_merges,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if got < 0:
        raise ImportError("native BPE train refused the corpus")
    return [(int(out[2 * k]), int(out[2 * k + 1])) for k in range(got)]


def bpe_encode(merges, data: bytes) -> np.ndarray:
    """Encode UTF-8 bytes with learned merges (list of pairs, or the
    pre-flattened [2K] int32 array BPETokenizer caches); bit-identical to
    data/text.py's ``_bpe_encode_py``."""
    lib = load_library()
    pairs = np.ascontiguousarray(np.asarray(merges, np.int32).reshape(-1))
    buf = np.frombuffer(data, np.uint8)
    buf = np.ascontiguousarray(buf)
    out = np.empty(max(len(buf), 1), np.int32)
    got = lib.dtf_bpe_encode(
        pairs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(pairs) // 2,
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        len(buf),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return out[:got].copy()


def bpe_encode_batch(merges, docs: list[bytes]) -> list[np.ndarray]:
    """Encode many documents in one native call (ranks map built once) —
    the fast path under data/text.py's ``pack_documents``."""
    lib = load_library()
    pairs = np.ascontiguousarray(np.asarray(merges, np.int32).reshape(-1))
    lens = np.asarray([len(b) for b in docs], np.int64)
    data = np.ascontiguousarray(np.frombuffer(b"".join(docs), np.uint8))
    out = np.empty(max(len(data), 1), np.int32)
    out_lens = np.empty(max(len(docs), 1), np.int64)
    lib.dtf_bpe_encode_batch(
        pairs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(pairs) // 2,
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        len(docs),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
    )
    pieces, off = [], 0
    for n in out_lens[: len(docs)]:
        pieces.append(out[off : off + int(n)].copy())
        off += int(n)
    return pieces


# ---------------------------------------------------------------------------
# Failure detection bindings (SURVEY.md §5 upgrade)
# ---------------------------------------------------------------------------


class HeartbeatCoordinator:
    """Chief-side liveness tracker: workers that reported once and then went
    silent past ``timeout_ms`` count as failed, and workers that NEVER report
    count as failed once ``grace_ms`` (default 5x timeout) has elapsed since
    start — so a worker dead at t=0 is detected rather than waited on forever
    (the reference's chief blocked indefinitely in
    ``prepare_or_wait_for_session``, reference tfdist_between.py:83)."""

    def __init__(
        self,
        port: int,
        expected_workers: int,
        timeout_ms: int = 5000,
        grace_ms: int | None = None,
    ):
        self._lib = load_library()
        if grace_ms is None:
            grace_ms = 5 * timeout_ms
        self._h = self._lib.dtf_coord_start2(
            port, expected_workers, timeout_ms, grace_ms
        )
        if not self._h:
            raise OSError(f"failed to bind heartbeat coordinator on :{port}")

    def alive_count(self) -> int:
        return self._lib.dtf_coord_alive_count(self._h)

    def failed_count(self) -> int:
        return self._lib.dtf_coord_failed_count(self._h)

    def ms_since_seen(self, worker_id: int) -> int:
        return self._lib.dtf_coord_ms_since_seen(self._h, worker_id)

    def progress(self, worker_id: int) -> int:
        """Last progress-counter value in ``worker_id``'s beats; -1 if it
        never reported one (round 7: the payload is ``HB <id> <progress>``,
        bumped by trainers at epoch boundaries)."""
        return self._lib.dtf_coord_progress(self._h, worker_id)

    def ms_since_progress(self, worker_id: int) -> int:
        """Milliseconds since ``worker_id``'s progress counter last changed
        (first report counts); -1 if it never reported progress."""
        return self._lib.dtf_coord_ms_since_progress(self._h, worker_id)

    def stalled_count(self, stall_timeout_ms: int) -> int:
        """Workers ALIVE (beating within timeout) whose progress counter has
        not moved for more than ``stall_timeout_ms`` — the live-but-stalled
        class (a rank hung in a collective keeps beating; only the progress
        payload can expose it). Never-progressed workers are not counted."""
        return self._lib.dtf_coord_stalled_count(self._h, stall_timeout_ms)

    def stop(self) -> None:
        if self._h:
            self._lib.dtf_coord_stop(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


class HeartbeatWorker:
    """Worker-side heartbeat sender. Every beat carries the monotonic
    progress counter last handed to :meth:`set_progress` — the sender runs
    on a native thread, so beats (and the frozen counter) keep flowing even
    while the Python main thread hangs in a collective, which is exactly
    what lets the coordinator tell *stalled* from *dead*."""

    def __init__(self, host: str, port: int, worker_id: int, interval_ms: int = 1000):
        self._lib = load_library()
        self._h = self._lib.dtf_worker_start(host.encode(), port, worker_id, interval_ms)
        if not self._h:
            raise OSError(f"failed to start heartbeat worker to {host}:{port}")

    def set_progress(self, progress: int) -> None:
        """Advance the monotonic progress counter carried by each beat
        (trainers call this at epoch boundaries with the global step).
        Until the first call, beats carry NO counter — the detector's
        never-reported-progress carve-out covers startup import/compile."""
        if self._h:
            self._lib.dtf_worker_set_progress(self._h, max(0, int(progress)))

    def stop(self) -> None:
        if self._h:
            self._lib.dtf_worker_stop(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


# ---------------------------------------------------------------------------
# TFRecord checksum bindings (utils/summary.py's hot path)
# ---------------------------------------------------------------------------


def crc32c(data: bytes) -> int:
    return int(load_library().dtf_crc32c(data, len(data)))


def crc32c_buffer(a: np.ndarray) -> int:
    """CRC32C over an ndarray's buffer without the ``tobytes`` copy — the
    checkpoint-manifest writer (train/resilience.py) checksums every state
    leaf per save, so large parameter tables go through the C kernel
    directly. Same value as ``crc32c(a.tobytes())``."""
    a = np.ascontiguousarray(a)
    return int(
        load_library().dtf_crc32c(
            a.ctypes.data_as(ctypes.c_char_p), a.nbytes
        )
    )


def crc32c_masked(data: bytes) -> int:
    """TFRecord-masked CRC32C (rotate-right-15 + magic), computed natively."""
    return int(load_library().dtf_crc32c_masked(data, len(data)))
