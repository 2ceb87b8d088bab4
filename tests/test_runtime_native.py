"""Native runtime tests: IDX parsing vs the numpy parser, shuffle/gather
determinism, and UDP heartbeat failure detection on localhost."""

import os
import struct
import time

import numpy as np
import pytest

from distributed_tensorflow_tpu.runtime import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native runtime unavailable (no toolchain)"
)


def _write_idx(tmp_path, n=50):
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(n, 28, 28), dtype=np.uint8)
    labels = rng.integers(0, 10, size=n, dtype=np.uint8)
    img_path = os.path.join(tmp_path, "train-images-idx3-ubyte")
    lab_path = os.path.join(tmp_path, "train-labels-idx1-ubyte")
    with open(img_path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, n, 28, 28))
        f.write(images.tobytes())
    with open(lab_path, "wb") as f:
        f.write(struct.pack(">II", 2049, n))
        f.write(labels.tobytes())
    return img_path, lab_path, images, labels


def test_idx_images_match_numpy_parser(tmp_path):
    img_path, lab_path, images, labels = _write_idx(str(tmp_path))
    got = native.load_idx_images(img_path)
    want = images.reshape(-1, 784).astype(np.float32) / 255.0
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(native.load_idx_labels(lab_path), labels)


def test_idx_bad_magic(tmp_path):
    p = os.path.join(str(tmp_path), "bad")
    with open(p, "wb") as f:
        f.write(struct.pack(">IIII", 1234, 1, 28, 28))
        f.write(bytes(784))
    with pytest.raises(OSError):
        native.load_idx_images(p)


def test_shuffle_perm_is_permutation_and_deterministic():
    a = native.shuffle_perm(1000, seed=42)
    b = native.shuffle_perm(1000, seed=42)
    c = native.shuffle_perm(1000, seed=43)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert sorted(a.tolist()) == list(range(1000))


def test_gather_rows():
    src = np.arange(40, dtype=np.float32).reshape(10, 4)
    idx = np.array([3, 0, 7], dtype=np.int64)
    np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])


def test_read_data_sets_uses_native_idx_path(tmp_path):
    # End-to-end: a directory of real IDX files flows through read_data_sets
    # via the native parser (data/mnist.py tries runtime.native_loader first).
    from distributed_tensorflow_tpu.data import read_data_sets

    d = str(tmp_path)
    _write_idx(d, n=6000)
    # test split files
    rng = np.random.default_rng(1)
    timgs = rng.integers(0, 256, size=(100, 28, 28), dtype=np.uint8)
    tlabs = rng.integers(0, 10, size=100, dtype=np.uint8)
    with open(os.path.join(d, "t10k-images-idx3-ubyte"), "wb") as f:
        f.write(struct.pack(">IIII", 2051, 100, 28, 28))
        f.write(timgs.tobytes())
    with open(os.path.join(d, "t10k-labels-idx1-ubyte"), "wb") as f:
        f.write(struct.pack(">II", 2049, 100))
        f.write(tlabs.tobytes())

    ds = read_data_sets(d, one_hot=True)
    assert ds.train.num_examples == 1000  # 6000 - 5000 validation
    assert ds.test.num_examples == 100
    np.testing.assert_array_equal(ds.test.labels.argmax(1), tlabs)


def test_bootstrap_with_heartbeat():
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.config import ClusterConfig

    cfg = ClusterConfig.from_lists(["127.0.0.1:2223", "127.0.0.1:2224"])
    chief = bootstrap(
        cfg, "worker", 0, initialize_distributed=False, heartbeat_port=19431
    )
    worker = bootstrap(
        cfg, "worker", 1, initialize_distributed=False, heartbeat_port=19431
    )
    try:
        assert chief.heartbeat is not None and worker.heartbeat is not None
        time.sleep(0.3)
        assert chief.heartbeat.alive_count() >= 1
    finally:
        worker.heartbeat.stop()
        chief.heartbeat.stop()


def test_heartbeat_failure_detection():
    port = 19427
    with native.HeartbeatCoordinator(port, expected_workers=2, timeout_ms=600) as coord:
        w0 = native.HeartbeatWorker("127.0.0.1", port, worker_id=0, interval_ms=100)
        w1 = native.HeartbeatWorker("127.0.0.1", port, worker_id=1, interval_ms=100)
        time.sleep(0.4)
        assert coord.alive_count() == 2
        assert coord.failed_count() == 0
        assert coord.ms_since_seen(0) >= 0
        # Kill worker 1: it must transition alive→failed after the timeout.
        w1.stop()
        time.sleep(1.0)
        assert coord.alive_count() == 1
        assert coord.failed_count() == 1
        w0.stop()
    # Never-seen workers are not failed inside the grace period (they may
    # still be scheduling) but ARE flagged once it elapses — a worker dead
    # at t=0 must not stall the job forever (round-1 finding).
    with native.HeartbeatCoordinator(
        port + 1, expected_workers=3, timeout_ms=500, grace_ms=400
    ) as c2:
        assert c2.failed_count() == 0
        assert c2.ms_since_seen(2) == -1
        w0 = native.HeartbeatWorker("127.0.0.1", port + 1, worker_id=0, interval_ms=100)
        time.sleep(0.7)  # past grace_ms: workers 1 and 2 never reported
        assert c2.failed_count() == 2
        assert c2.alive_count() == 1
        w0.stop()


def test_heartbeat_progress_payload():
    """Round 7: every beat carries a monotonic progress counter
    ("HB <id> <progress>") so the detector can tell LIVE-BUT-STALLED
    (beating, counter frozen) from dead (beats stopped) — the verdict the
    elastic agent (train/elastic.py) recovers from."""
    port = 19437
    with native.HeartbeatCoordinator(port, expected_workers=2, timeout_ms=600) as coord:
        w0 = native.HeartbeatWorker("127.0.0.1", port, worker_id=0, interval_ms=100)
        w1 = native.HeartbeatWorker("127.0.0.1", port, worker_id=1, interval_ms=100)
        try:
            time.sleep(0.4)
            # Until the first set_progress, beats carry NO counter: the
            # startup carve-out — a beating-but-never-progressed worker
            # (import, first compile) must not be judged stalled.
            assert coord.alive_count() == 2
            assert coord.progress(0) == -1 and coord.progress(1) == -1
            assert coord.ms_since_progress(0) == -1
            assert coord.stalled_count(100) == 0
            assert coord.progress(5) == -1  # out of range: never
            w0.set_progress(7)
            w1.set_progress(1)
            time.sleep(0.3)
            assert coord.progress(0) == 7 and coord.progress(1) == 1
            # stamped when the coordinator SAW the post-update beat — recent
            # relative to any realistic stall window, not to the sleep
            assert coord.ms_since_progress(0) <= 450
            # w1's counter now freezes: after the stall window it is
            # stalled; a fresh UPDATE resets w0's clock.
            time.sleep(0.5)
            w0.set_progress(8)
            time.sleep(0.3)
            assert coord.progress(0) == 8
            assert coord.ms_since_progress(0) <= 450
            assert coord.ms_since_progress(1) >= 700
            assert coord.stalled_count(700) == 1  # w1 only
            assert coord.stalled_count(60_000) == 0
        finally:
            w0.stop()
            w1.stop()
        # Dead workers (beats stopped) are NOT stalled — they are failed;
        # stall is strictly the live-and-frozen class.
        time.sleep(0.8)
        assert coord.failed_count() == 2
        assert coord.stalled_count(100) == 0


def test_stale_library_missing_symbols_raises_importerror(tmp_path, monkeypatch):
    """A .so built from older sources (missing newer symbols) must surface as
    ImportError — so `except (ImportError, OSError)` fallbacks engage — and a
    successful rebuild must recover (round-1 advisor finding: AttributeError
    escaped every fallback until a manual rebuild)."""
    import shutil
    import subprocess

    real_so = native._SO
    native.load_library()  # ensure the real library exists on disk
    src = tmp_path / "stub.c"
    src.write_text(
        "long dtf_load_idx_images(const char* p, float* o, long n)"
        " { (void)p; (void)o; (void)n; return -1; }\n"
    )
    stale = tmp_path / "libdtf_runtime.so"

    def make_stub():
        subprocess.run(
            ["gcc", "-shared", "-fPIC", "-o", str(stale), str(src)], check=True
        )

    make_stub()
    monkeypatch.setattr(native, "_SO", str(stale))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)

    # Stale symbols + failing rebuild → ImportError, never AttributeError.
    monkeypatch.setattr(native, "_build", lambda: False)
    with pytest.raises(ImportError):
        native.load_library()

    # Stale symbols + successful rebuild → transparent recovery.
    make_stub()
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_build", lambda: bool(shutil.copy(real_so, stale)))
    lib = native.load_library()
    assert lib.dtf_crc32c(b"x", 1) != 0


def test_library_older_than_source_is_rebuilt(tmp_path, monkeypatch):
    """The library is git-ignored and a copied tree can carry one built
    from older sources: a source newer than the library triggers the
    on-demand build, and a library that cannot be rebuilt is not loaded."""
    import shutil

    native.load_library()  # ensure the real library exists on disk
    so = tmp_path / "libdtf_runtime.so"
    shutil.copy(native._SO, so)
    src = tmp_path / "dtf_runtime.cc"
    src.write_text("// newer than the library\n")
    os.utime(so, (1, 1))
    monkeypatch.setattr(native, "_SO", str(so))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)

    monkeypatch.setattr(native, "_build", lambda: False)
    with pytest.raises(ImportError, match="older than its source"):
        native.load_library()

    built = []
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(
        native, "_build", lambda: bool(built.append(1) or os.utime(so) or True)
    )
    assert native.load_library().dtf_crc32c(b"x", 1) != 0
    assert built == [1]


def test_native_crc32c_matches_python_table():
    pytest.importorskip("distributed_tensorflow_tpu.runtime.native")
    from distributed_tensorflow_tpu.runtime import native
    from distributed_tensorflow_tpu.utils import summary as s

    if not native.available():
        pytest.skip("native runtime unavailable")
    rng = np.random.default_rng(0)
    cases = [b"", b"a", b"hello tfrecord", bytes(rng.integers(0, 256, 4096, dtype=np.uint8))]
    cases.append(b"with\x00embedded\x00nuls")
    for data in cases:
        assert native.crc32c(data) == s.crc32c(data), data[:16]
        assert native.crc32c_masked(data) == s._masked_crc_py(data), data[:16]
