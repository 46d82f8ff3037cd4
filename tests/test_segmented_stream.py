"""Unit tests for the segmented double-buffered batch_verify_stream path
(the flagship 10k-validator optimization: segment i+1's pack+transfer
overlaps segment i's device compute).

The device kernel itself is covered differentially by test_sparse_verify /
test_ed25519_jax; here the dispatch step is faked so the orchestration
(segment sizing, ordering, boundary reassembly, ok-mask merge, pipeline
depth) is tested without compiling segment-shaped XLA kernels on CPU.
"""

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as host
from tendermint_tpu.crypto.ed25519_jax import verify as V


def test_segment_sizes():
    assert V._segment_sizes(1) == [1]
    assert V._segment_sizes(2) == [1, 1]
    assert V._segment_sizes(5) == [3, 2]
    assert V._segment_sizes(10) == [5, 5]
    assert V._segment_sizes(11) == [6, 5]
    assert V._segment_sizes(16) == [8, 8]
    assert V._segment_sizes(30) == [10, 10, 10]
    assert V._segment_sizes(31) == [8, 8, 8, 7]
    for k in range(1, 200):
        sizes = V._segment_sizes(k)
        assert sum(sizes) == k
        assert all(0 < s <= V.SEG_CHUNKS for s in sizes)
        if k > 1:
            assert len(sizes) >= 2  # two segments minimum for overlap
            assert max(sizes) - min(sizes) <= 1  # near-equal


class _FakeDev:
    """Stands in for the device verdict array; np.asarray(fake) works."""

    def __init__(self, arr):
        self._arr = arr

    def __array__(self, dtype=None, copy=None):
        return self._arr


def test_segmented_reassembly_and_ordering(monkeypatch):
    """Verdicts land at the right global offsets regardless of worker
    completion order, and the ok-mask merges per segment."""
    calls = []

    def fake_dispatch(pks, msgs, sigs, chunk):
        calls.append(len(pks))
        # verdict: sig == b"good" + index bytes; ok-mask: pk length valid
        verd = np.array([s[:4] == b"good" for s in sigs])
        ok = np.array([len(p) == 32 for p in pks])
        # pad to whole chunks like the real kernel output
        k = -(-len(pks) // chunk)
        verd = np.pad(verd, (0, k * chunk - len(pks)))
        return _FakeDev(verd), ok

    monkeypatch.setattr(V, "_dispatch_stream", fake_dispatch)
    n = 1000
    chunk = V.LANE  # 128 -> 8 chunks -> segments [4, 4]
    pks = [b"\x01" * 32] * n
    msgs = [b"m"] * n
    sigs = [b"good" + bytes([i % 251]) for i in range(n)]
    bad = {0, 127, 128, 511, 512, 999}
    for i in bad:
        sigs[i] = b"bad!" + bytes(1)
    badpk = {5, 513}
    for i in badpk:
        pks[i] = b"\x01" * 31

    monkeypatch.setattr(V, "SEG_MIN_SIGS", 256)
    out = V._verify_segmented(pks, msgs, sigs, chunk)
    want = np.ones(n, bool)
    for i in bad | badpk:
        want[i] = False
    np.testing.assert_array_equal(out, want)
    assert len(calls) == 2 and sum(calls) == n and calls[0] == 512


def test_stream_entry_routes_large_batches_to_segments(monkeypatch):
    seen = []

    def fake_segmented(pks, msgs, sigs, chunk, t_entry=None):
        seen.append(len(pks))
        return np.ones(len(pks), bool)

    monkeypatch.setattr(V, "_verify_segmented", fake_segmented)
    monkeypatch.setattr(V, "SEG_MIN_SIGS", 300)
    pks = [b"\x01" * 32] * 400
    msgs = [b"same message"] * 400
    sigs = [b"\x02" * 64] * 400
    out = V.batch_verify_stream(pks, msgs, sigs, chunk=V.LANE)
    assert seen == [400] and out.all()


def test_segmented_worker_exception_propagates(monkeypatch):
    def boom(pks, msgs, sigs, chunk):
        raise RuntimeError("device dropped the connection")

    monkeypatch.setattr(V, "_dispatch_stream", boom)
    with pytest.raises(RuntimeError, match="device dropped"):
        V._verify_segmented([b"\x01" * 32] * 512, [b"m"] * 512,
                            [b"\x02" * 64] * 512, V.LANE)


def test_dispatch_stream_dense_fallback_shapes():
    """_dispatch_stream's dense branch (dissimilar messages) keeps the
    (K, NBLK, 32, B, LANE) layout contract: verdicts land in row order.
    Small shapes only — the heavy differential coverage is in
    test_sparse_verify (CPU) and test_tpu_device (real chip, segmented)."""
    import pytest

    pytest.importorskip("cryptography", reason="needs the optional 'cryptography' package (absent in slim containers)")
    rng = np.random.default_rng(2)
    pks, msgs, sigs = [], [], []
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    for i in range(144):  # > one 128-lane chunk -> K=2 stream kernel
        priv = Ed25519PrivateKey.from_private_bytes(
            bytes(rng.integers(0, 256, 32, dtype=np.uint8)))
        m = bytes(rng.integers(0, 256, 120, dtype=np.uint8))  # dissimilar
        s = priv.sign(m)
        if i in (0, 127, 128, 143):
            s = s[:32] + bytes(32)
        pks.append(priv.public_key().public_bytes_raw())
        msgs.append(m)
        sigs.append(s)
    assert V.prepare_sparse_stream(pks, msgs, sigs, 128) is None
    dev, ok = V._dispatch_stream(pks, msgs, sigs, 128)
    out = np.asarray(dev).reshape(-1)[:144] & ok
    truth = np.array([host.verify(p, m, s)
                      for p, m, s in zip(pks, msgs, sigs)])
    np.testing.assert_array_equal(out, truth)
