"""Unit tests for the segmented double-buffered batch_verify_stream path
(the flagship 10k-validator optimization: segment i+1's pack+transfer
overlaps segment i's device compute).

The sparse and one-call kernels are covered differentially by
test_sparse_verify / test_ed25519_jax; here the dispatch step is conftest's
``device_standin``, so the orchestration (segment sizing, ordering, boundary
reassembly, ok-mask merge, pipeline depth) runs without a build — and the
last test builds the ONE dense stream program of a tier-1 run (K=2 chunks of
128 lanes, NBLK 2), the fallback ``_dispatch_stream`` takes for dissimilar
messages.
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


def test_segmented_reassembly_and_ordering(monkeypatch, device_standin):
    """Verdicts land at the right global offsets regardless of worker
    completion order, and the ok-mask merges per segment."""
    # rows are not signatures here: a verdict is "the sig starts with good"
    device_standin.rule = lambda pk, msg, sig: sig[:4] == b"good"
    n = 1000
    chunk = V.LANE  # 128 -> 8 chunks -> segments [4, 4]
    pks = [b"\x01" * 32] * n
    msgs = [b"m"] * n
    sigs = [(b"good" + bytes([i % 251])).ljust(64, b"\x00")
            for i in range(n)]
    bad = {0, 127, 128, 511, 512, 999}
    for i in bad:
        sigs[i] = b"bad!".ljust(64, b"\x00")
    badpk = {5, 513}  # the packer's own ok-mask: a 31-byte key
    for i in badpk:
        pks[i] = b"\x01" * 31

    monkeypatch.setattr(V, "SEG_MIN_SIGS", 256)
    out = V._verify_segmented(pks, msgs, sigs, chunk)
    want = np.ones(n, bool)
    for i in bad | badpk:
        want[i] = False
    np.testing.assert_array_equal(out, want)
    calls = device_standin.calls
    assert len(calls) == 2 and sum(calls) == n and calls[0] == 512


def test_stream_entry_routes_large_batches_to_segments(monkeypatch,
                                                       device_standin):
    device_standin.rule = lambda pk, msg, sig: True
    monkeypatch.setattr(V, "SEG_MIN_SIGS", 300)
    pks = [b"\x01" * 32] * 400
    msgs = [b"same message"] * 400
    sigs = [b"\x02" * 64] * 400
    out = V.batch_verify_stream(pks, msgs, sigs, chunk=V.LANE)
    assert out.all()
    # 4 chunks -> pipeline segments [2, 2], not one dispatch of 400
    assert sorted(device_standin.calls) == [144, 256]


def test_segmented_worker_exception_propagates(device_standin):
    def boom(pk, msg, sig):
        raise RuntimeError("device dropped the connection")

    device_standin.rule = boom
    with pytest.raises(RuntimeError, match="device dropped"):
        V._verify_segmented([b"\x01" * 32] * 512, [b"m"] * 512,
                            [b"\x02" * 64] * 512, V.LANE)


@pytest.mark.parametrize("tampered", [(), (0, 127, 128, 143)],
                         ids=["all_valid", "tampered_at_chunk_edges"])
def test_dissimilar_messages_take_the_dense_stream(tampered):
    """Messages too dissimilar for the sparse wire format fall back to the
    dense stream kernel, whose (K, NBLK, 32, B, LANE) layout keeps verdicts
    in row order and equal to the host spec's. The real program, at the
    one shape tier-1 builds it (144 rows: a second chunk, and padding in
    it); the segmented shapes run on the chip (test_tpu_device)."""
    pytest.importorskip("cryptography", reason="needs the optional 'cryptography' package (absent in slim containers)")
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    rng = np.random.default_rng(2)
    pks, msgs, sigs = [], [], []
    for i in range(144):
        priv = Ed25519PrivateKey.from_private_bytes(
            bytes(rng.integers(0, 256, 32, dtype=np.uint8)))
        m = bytes(rng.integers(0, 256, 120, dtype=np.uint8))  # dissimilar
        s = priv.sign(m)
        if i in tampered:
            s = s[:32] + bytes(32)
        pks.append(priv.public_key().public_bytes_raw())
        msgs.append(m)
        sigs.append(s)
    assert V.prepare_sparse_stream(pks, msgs, sigs, 128) is None
    out = V.batch_verify_stream(pks, msgs, sigs, chunk=128)
    truth = np.array([host.verify(p, m, s)
                      for p, m, s in zip(pks, msgs, sigs)])
    assert truth.sum() == 144 - len(tampered)
    np.testing.assert_array_equal(out, truth)
