"""Native C++ runtime component tests (native/dttpu_native.cpp via ctypes).

The pure-Python implementations act as cross-check oracles; if the toolchain
cannot build the library these tests skip and every consumer falls back.
"""
import os

import numpy as np
import pytest

from distributed_tensorflow_tpu.summary.crc32c import (py_crc32c,
                                                       py_masked_crc32c)
from distributed_tensorflow_tpu.utils import native

pytestmark = pytest.mark.skipif(not native.native_available(),
                                reason="native library unavailable")


def test_crc32c_matches_python_oracle():
    rng = np.random.default_rng(0)
    for n in (0, 1, 7, 8, 9, 63, 64, 1000, 4097):
        data = bytes(rng.integers(0, 256, n, dtype=np.uint8))
        assert native.crc32c(data) == py_crc32c(data)
        assert native.masked_crc32c(data) == py_masked_crc32c(data)


def test_summary_crc_builds_native_only_for_bulk_payloads(monkeypatch):
    """summary.crc32c never runs a compiler for a small record, but asks
    for the one-time build when handed a checkpoint-leaf-sized payload
    (the Python byte loop is a few MB/s); either way the value is the
    oracle's."""
    import importlib
    crc_mod = importlib.import_module(
        "distributed_tensorflow_tpu.summary.crc32c")
    asked = []
    real = native.native_available

    def spy(build=True):
        asked.append(build)
        return real(build=build)

    monkeypatch.setattr(native, "native_available", spy)
    small = bytes(range(256)) * 4
    assert crc_mod.masked_crc32c(small) == py_masked_crc32c(small)
    assert asked == [False]
    bulk = small * (crc_mod._BUILD_NATIVE_AT // len(small))
    assert crc_mod.crc32c(bulk[:4096]) == py_crc32c(bulk[:4096])
    got = crc_mod.masked_crc32c(bulk)
    assert asked == [False, False, True]
    assert got == native.masked_crc32c(bulk)     # built: native answers


def test_crc32c_known_vector():
    # RFC 3720 test vector: crc32c of 32 zero bytes.
    assert native.crc32c(b"\x00" * 32) == 0x8A9136AA


def test_crc32c_incremental():
    data = b"hello, tpu world" * 10
    crc_all = native.crc32c(data)
    crc_inc = native.crc32c(data[7:], native.crc32c(data[:7]))
    assert crc_all == crc_inc == py_crc32c(data)


def test_xor_generate_labels_and_determinism():
    x, y = native.xor_generate(500, 32, seed=5)
    assert x.shape == (500, 64) and y.shape == (500, 32)
    assert set(np.unique(x)) <= {0.0, 1.0}
    np.testing.assert_array_equal(
        y, np.bitwise_xor(x[:, :32].astype(int), x[:, 32:].astype(int)))
    x2, _ = native.xor_generate(500, 32, seed=5)
    np.testing.assert_array_equal(x, x2)
    x3, _ = native.xor_generate(500, 32, seed=6)
    assert not np.array_equal(x, x3)
    # bits look fair
    assert 0.45 < x.mean() < 0.55


def test_loader_epoch_coverage_and_shapes():
    n, b = 103, 10
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    y = np.arange(n, dtype=np.int32)
    ld = native.NativeLoader(x, y, b, seed=1)
    assert ld.batches_per_epoch == 10
    seen = []
    for xb, yb in ld:
        assert xb.shape == (b, 3) and xb.dtype == np.float32
        assert yb.shape == (b,) and yb.dtype == np.int32
        np.testing.assert_array_equal(xb[:, 0], yb * 3)  # rows stay aligned
        seen.append(yb)
    seen = np.concatenate(seen)
    assert len(np.unique(seen)) == 100  # each row at most once per epoch
    ld.close()


def test_loader_epochs_reshuffle_and_streaming():
    n, b = 64, 8
    x = np.arange(n, dtype=np.float32).reshape(n, 1)
    ld = native.NativeLoader(x, None, b, seed=3)
    e1 = np.concatenate([xb[0].ravel() for xb in ld])
    e2 = np.concatenate([xb[0].ravel() for xb in ld])
    assert not np.array_equal(e1, e2)  # per-epoch reshuffle
    assert len(np.unique(e1)) == len(e1)
    ld.close()


def test_loader_no_shuffle_preserves_order():
    n, b = 20, 5
    x = np.arange(n, dtype=np.float32).reshape(n, 1)
    ld = native.NativeLoader(x, None, b, shuffle=False)
    batches = [xb[0].ravel() for xb in ld]
    np.testing.assert_array_equal(np.concatenate(batches), np.arange(n))
    ld.close()


def test_dataset_native_backend_coverage():
    from distributed_tensorflow_tpu import data
    x = np.arange(100, dtype=np.float32).reshape(100, 1)
    y = np.arange(100, dtype=np.int32)
    ds = data.Dataset([x, y], 32, seed=0, backend="native")
    b1 = list(ds)
    assert len(b1) == 3
    seen = np.concatenate([b[1] for b in b1])
    assert len(np.unique(seen)) == 96
    b2 = list(ds)  # next epoch reshuffles
    assert not np.array_equal(b1[0][1], b2[0][1])
    # partial consumption then restart stays well-formed
    it = iter(ds)
    next(it)
    del it
    assert len(list(ds)) == 3


def test_dataset_numpy_backend_unchanged_by_native_presence():
    from distributed_tensorflow_tpu import data
    x = np.arange(10, dtype=np.float32).reshape(10, 1)
    ds = data.Dataset([x], 2, shuffle=False, backend="numpy")
    np.testing.assert_array_equal(next(iter(ds))[0].ravel(), [0.0, 1.0])


def test_no_native_env_forces_fallback():
    import subprocess
    import sys
    code = (
        "import os; os.environ['DTTPU_NO_NATIVE']='1';"
        "from distributed_tensorflow_tpu.utils import native;"
        "assert not native.native_available();"
        "import importlib;"
        "c = importlib.import_module("
        "'distributed_tensorflow_tpu.summary.crc32c');"
        "assert c.crc32c(b'abc') == c.py_crc32c(b'abc')"
    )
    env = dict(os.environ, DTTPU_NO_NATIVE="1", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_loader_stress_many_threads_and_epochs():
    """Regression for the slot claim-jumping deadlock: many workers, small
    ring, several epoch boundaries, coverage verified every epoch."""
    n, b = 48, 4
    x = np.arange(n, dtype=np.float32).reshape(n, 1)
    ld = native.NativeLoader(x, None, b, seed=9, num_threads=4,
                             queue_depth=5)
    for _ in range(5):
        rows = np.concatenate([xb[0].ravel() for xb in ld])
        assert len(np.unique(rows)) == n
    ld.close()


def test_native_bpe_matches_python():
    """dt_bpe_encode produces the exact segmentation of the Python loop
    (rank-greedy, left-to-right non-overlapping) on trained merges."""
    import numpy as np
    import pytest
    from distributed_tensorflow_tpu.data.text import BPETokenizer
    from distributed_tensorflow_tpu.utils import native

    if not native.native_available():
        pytest.skip("native library unavailable")
    corpus = ["the quick brown fox jumps over the lazy dog " * 20,
              "pack my box with five dozen liquor jugs " * 20]
    tok = BPETokenizer.train(corpus, vocab_size=300)
    assert tok.merges   # learned something
    for text in corpus + ["the fox", "zzz unseen bytes éü",
                          "", "a"]:
        py = tok.encode(text, backend="python")
        nat = tok.encode(text, backend="auto")
        np.testing.assert_array_equal(np.asarray(nat), np.asarray(py))
        # and both decode back to the input
        assert tok.decode(nat) == text


def test_native_bpe_bos_eos_and_speed():
    import time
    import numpy as np
    import pytest
    from distributed_tensorflow_tpu.data.text import BPETokenizer
    from distributed_tensorflow_tpu.utils import native

    if not native.native_available():
        pytest.skip("native library unavailable")
    tok = BPETokenizer.train(["ababababab abab " * 50], vocab_size=270)
    out = tok.encode("abab", bos=True, eos=True)
    assert out[0] == tok.bos_id and out[-1] == tok.eos_id
    # the native path should not be slower on a long text
    text = "ababababab abab " * 2000
    t0 = time.perf_counter(); tok.encode(text, backend="python")
    t_py = time.perf_counter() - t0
    t0 = time.perf_counter(); tok.encode(text, backend="auto")
    t_nat = time.perf_counter() - t0
    assert t_nat < t_py * 1.5   # loose: just prove it's wired + not broken
