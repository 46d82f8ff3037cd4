"""Differential tests for the sparse template wire format: on-device SHA
preimage assembly must be byte-identical to the dense prepare_batch path
and to the host spec (the reference's scalar verify semantics,
crypto/ed25519/ed25519.go:148-155).

The sparse path exists because commit/vote batches share almost the whole
message (types/canonical.go sign-bytes differ only in timestamp bytes), so
shipping a template + differing columns cuts host->device transfer ~2.5x.

This file builds the ONE ``_verify_sparse_stream_kernel`` program of a
tier-1 run: every corpus is 140 rows in chunks of 128 (K=2: a second chunk
with its own template, and padding in it), MLEN 192, 32 diff columns. The
dense fallback's program is built in tests/test_segmented_stream.py.
"""

import jax
import numpy as np
import pytest
pytest.importorskip("cryptography", reason="needs the optional 'cryptography' package (absent in slim containers)")

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from tendermint_tpu.crypto import ed25519 as host
from tendermint_tpu.crypto.ed25519_jax import verify as V


def _mk_corpus(n=140, seed=3):
    rng = np.random.default_rng(seed)
    base = bytes(rng.integers(0, 256, 120, dtype=np.uint8))
    pks, msgs, sigs = [], [], []
    for i in range(n):
        priv = Ed25519PrivateKey.from_private_bytes(
            bytes(rng.integers(0, 256, 32, dtype=np.uint8)))
        m = bytearray(base)
        m[40:48] = int(i).to_bytes(8, "little")
        if i % 7 == 0:
            m = m[:100 + (i % 19)]  # length variation within one bucket
        m = bytes(m)
        s = priv.sign(m)
        if i % 11 == 0:
            s = s[:32] + bytes(32)  # corrupt scalar -> reject
        if i % 13 == 0:
            m = m[:1] + bytes([m[1] ^ 1]) + m[2:]  # tamper -> reject
        pks.append(priv.public_key().public_bytes_raw())
        msgs.append(m)
        sigs.append(s)
    return pks, msgs, sigs


def test_sparse_matches_dense_and_host():
    pks, msgs, sigs = _mk_corpus()
    n = len(pks)
    truth = np.array([host.verify(p, m, s)
                      for p, m, s in zip(pks, msgs, sigs)])
    assert truth.sum() not in (0, n)  # corpus mixes accepts and rejects

    sp = V.prepare_sparse_stream(pks, msgs, sigs, chunk=128)
    assert sp is not None, "vote-like corpus must take the sparse path"
    args, ok = sp
    assert args[0].shape == (2, 192) and args[1].shape == (32,)  # the shape
    v_sparse = np.asarray(
        V._verify_sparse_stream_kernel(*args)).reshape(-1)[:n] & ok
    np.testing.assert_array_equal(v_sparse, truth)

    # the preimage words assembled on the device are the dense packer's,
    # byte for byte (a seconds-sized program of its own; padding rows
    # differ by design: the sparse ones mirror their template)
    (blocks_d, nblk_d, _s), _ok = V._pack_stream_dense(pks, msgs, sigs, 128)
    templates, cols, diff_vals, mlen, r_b, a_b, _s_b = args
    assemble = jax.jit(V._assemble_blocks)
    for k in range(2):
        rows = min(128, n - 128 * k)
        words, nblk = assemble(templates[k], cols, diff_vals[k], mlen[k],
                               r_b[k], a_b[k])
        np.testing.assert_array_equal(np.asarray(words)[..., :rows],
                                      blocks_d[k][..., :rows])
        np.testing.assert_array_equal(np.asarray(nblk)[..., :rows],
                                      nblk_d[k][..., :rows])

    # the public stream entry routes through sparse and agrees
    v_stream = V.batch_verify_stream(pks, msgs, sigs, chunk=128)
    np.testing.assert_array_equal(v_stream, truth)


def test_sparse_rejects_bad_lengths_and_noncanonical():
    pks, msgs, sigs = _mk_corpus(seed=9)
    # malformed inputs the host path rejects before any curve math
    sigs[0] = sigs[0][:63]          # short sig
    pks[1] = pks[1] + b"\x00"       # long pk
    sigs[2] = sigs[2][:32] + (host.L).to_bytes(32, "little")  # s == L
    sigs[3] = sigs[3][:32] + b"\xff" * 32                     # s >> L
    truth = np.array([host.verify(p, m, s)
                      for p, m, s in zip(pks, msgs, sigs)])
    assert not truth[:4].any()
    v = V.batch_verify_stream(pks, msgs, sigs, chunk=128)
    np.testing.assert_array_equal(v, truth)


def test_pk_device_cache_reuses_buffer():
    pks, msgs, sigs = _mk_corpus(seed=5)
    V._PK_DEVICE_CACHE.clear()
    sp1 = V.prepare_sparse_stream(pks, msgs, sigs, chunk=128)
    assert sp1 is not None and len(V._PK_DEVICE_CACHE) == 1
    buf1 = sp1[0][5]
    # same keys again (fast-sync: same valset every block) -> same buffer
    sp2 = V.prepare_sparse_stream(pks, msgs, sigs, chunk=128)
    assert sp2[0][5] is buf1
    # verdicts unaffected by the cache hit
    n = len(pks)
    v1 = np.asarray(V._verify_sparse_stream_kernel(*sp1[0])).reshape(-1)[:n] & sp1[1]
    truth = np.array([host.verify(p, m, s)
                      for p, m, s in zip(pks, msgs, sigs)])
    np.testing.assert_array_equal(v1, truth)


def test_columns_alone_verify_as_their_rows_do():
    """A batch that comes as columns alone (``msgs`` None: the VerifyCommit*
    entries hand a uniform commit over so, PR 27) packs and verifies as the
    same batch with its rows, at this file's one shape: 140 equal-length
    rows that differ in 20 byte positions (the 32-column bucket)."""
    import hashlib

    from tendermint_tpu.crypto.signcols import sign_columns_from_rows

    rng = np.random.default_rng(27)
    base = bytes(rng.integers(0, 256, 120, dtype=np.uint8))
    pks, msgs, sigs = [], [], []
    for i in range(140):
        priv = Ed25519PrivateKey.from_private_bytes(
            bytes(rng.integers(0, 256, 32, dtype=np.uint8)))
        m = base[:40] + hashlib.sha256(b"%d" % i).digest()[:20] + base[60:]
        s = priv.sign(m)
        if i % 11 == 0:
            s = s[:32] + bytes(32)  # corrupt scalar -> reject
        pks.append(priv.public_key().public_bytes_raw())
        msgs.append(m)
        sigs.append(s)
    truth = np.array([host.verify(p, m, s)
                      for p, m, s in zip(pks, msgs, sigs)])
    assert truth.sum() not in (0, 140)
    cols = sign_columns_from_rows(msgs)
    assert cols is not None and cols.rows() == msgs
    with_rows = V.prepare_sparse_stream(pks, msgs, sigs, 128, columns=cols)
    alone = V.prepare_sparse_stream(pks, None, sigs, 128, columns=cols)
    assert with_rows[0][0].shape == (2, 192) and with_rows[0][1].shape == (32,)
    for a, b in zip(with_rows[0], alone[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        V.batch_verify_stream(pks, None, sigs, chunk=128, columns=cols), truth)
    np.testing.assert_array_equal(
        V.batch_verify_stream(pks, msgs, sigs, chunk=128, columns=cols), truth)
    # below one chunk the one-call program packs rows: built from the columns
    # (140 rows at chunk 256 would build it; the packing alone is read here)
    assert V._rows_of(None, cols) == msgs
    with pytest.raises(ValueError, match="do not align"):
        V.batch_verify_stream(pks, None, sigs, chunk=128,
                              columns=cols.slice(0, 100))
