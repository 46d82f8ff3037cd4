"""Differential tests: batched TPU/JAX ed25519 verify vs the host spec.

Byte-identical accept/reject is the contract (SURVEY.md north star):
every decision of ed25519_jax.batch_verify must equal
tendermint_tpu.crypto.ed25519.verify on the same inputs.

This file builds the ONE ``_verify_kernel`` program of a tier-1 run (128
lanes, one SHA block: every batch here is at most 128 rows of messages
under 48 bytes — keep it so, a second shape is a second build of minutes).
"""

import random

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.crypto.ed25519_jax import batch_verify


def _differential(cases):
    pks = [c[0] for c in cases]
    msgs = [c[1] for c in cases]
    sigs = [c[2] for c in cases]
    got = batch_verify(pks, msgs, sigs)
    want = np.array([ed.verify(p, m, s) for p, m, s in cases])
    assert got.dtype == bool
    mismatches = [
        (i, bool(got[i]), bool(want[i])) for i in range(len(cases)) if got[i] != want[i]
    ]
    assert not mismatches, f"decision mismatches: {mismatches}"
    return want


def _valid_cases(n, seed, msg_len=40):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        priv, pub = ed.keygen(bytes(rng.randrange(256) for _ in range(32)))
        msg = bytes(rng.randrange(256) for _ in range(msg_len))
        out.append((pub, msg, ed.sign(priv, msg)))
    return out


def test_valid_batch():
    want = _differential(_valid_cases(32, seed=1))
    assert want.all()  # sanity: these really are valid sigs


def test_corrupted_batch():
    rng = random.Random(2)
    cases = []
    for pub, msg, sig in _valid_cases(24, seed=3):
        which = rng.randrange(3)
        if which == 0:
            b = bytearray(sig)
            b[rng.randrange(64)] ^= 1 << rng.randrange(8)
            sig = bytes(b)
        elif which == 1:
            msg = msg + b"!"
        else:
            b = bytearray(pub)
            b[rng.randrange(32)] ^= 1 << rng.randrange(8)
            pub = bytes(b)
        cases.append((pub, msg, sig))
    want = _differential(cases)
    assert not want.all()  # most should be rejected


def test_adversarial_batch():
    """Non-canonical s, non-canonical y, small-order keys, zero sig, identity."""
    priv, pub = ed.keygen(b"\x07" * 32)
    msg = b"adversarial"
    sig = ed.sign(priv, msg)
    s_int = int.from_bytes(sig[32:], "little")

    cases = [
        (pub, msg, sig),                                             # valid
        (pub, msg, sig[:32] + (s_int + ed.L).to_bytes(32, "little")),  # s >= L
        ((ed.P + 1).to_bytes(32, "little"), msg, sig),               # y = p+1 >= p
        ((ed.P - 1).to_bytes(32, "little"), msg, sig),               # canonical y, likely off-curve
        (b"\x01" + b"\x00" * 31, msg, sig),                          # y=1: identity point A
        (b"\x00" * 32, msg, sig),                                    # y=0 small-order candidate
        (pub, msg, b"\x00" * 64),                                    # zero signature
        (pub, msg, (b"\x01" + b"\x00" * 31) + b"\x00" * 32),         # R = identity enc, s=0
        (pub, b"", sig),                                             # truncated msg
        (pub, msg, sig[:32] + (ed.L - 1).to_bytes(32, "little")),    # s = L-1 canonical
        # sign-bit variants
        (bytes(pub[:31]) + bytes([pub[31] ^ 0x80]), msg, sig),       # flipped A sign
        (bytes([sig[0] ^ 0x01]) + sig[1:], msg, sig),                # corrupt R (len 64 kept below)
    ]
    # fix the last case's signature structure (msg arg mistake guard)
    cases[-1] = (pub, msg, bytes([sig[0] ^ 0x01]) + sig[1:])
    _differential(cases)


def test_identity_pubkey_with_forged_sig():
    """A = identity: [s]B - [h]*identity = [s]B; R = [s]B encoding passes the
    cofactorless equation. Both paths must AGREE (this is the kind of edge
    where implementations diverge)."""
    id_pub = b"\x01" + b"\x00" * 31  # y=1, x=0: the identity point
    msg = b"forged"
    s = 12345
    sB = ed._pt_mul(s, (ed.B[0], ed.B[1], 1, ed.B[0] * ed.B[1] % ed.P))
    sig = ed._pt_encode(sB) + s.to_bytes(32, "little")
    _differential([(id_pub, msg, sig)])


def test_large_batch_and_padding():
    cases = _valid_cases(5, seed=9)  # pads 5 -> 128 lanes
    bad = list(cases[2])
    bad[2] = bad[2][:63] + bytes([bad[2][63] ^ 0x40])
    cases[2] = tuple(bad)
    _differential(cases)


def test_empty_batch():
    assert batch_verify([], [], []).shape == (0,)


def test_wrong_lengths():
    priv, pub = ed.keygen(b"\x09" * 32)
    sig = ed.sign(priv, b"m")
    _differential([
        (pub[:31], b"m", sig),
        (pub, b"m", sig[:63]),
        (pub + b"\x00", b"m", sig),
        (pub, b"m", sig + b"\x00"),
    ])


def test_openssl_path_rejects_x0_sign1_pubkeys():
    """Regression (consensus-split): x=0 with sign bit 1 encodings must be
    rejected by the OpenSSL fast path, matching the strict spec + TPU path."""
    from tendermint_tpu.crypto import Ed25519PubKey

    for y in (1, ed.P - 1):
        pub = (y | 1 << 255).to_bytes(32, "little")
        s = 7
        sB = ed._pt_mul(s, (ed.B[0], ed.B[1], 1, ed.B[0] * ed.B[1] % ed.P))
        sig = ed._pt_encode(sB) + s.to_bytes(32, "little")
        assert not ed.verify(pub, b"forged", sig)
        assert not Ed25519PubKey(pub).verify_signature(b"forged", sig)
        assert not batch_verify([pub], [b"forged"], [sig])[0]
    # the unset-sign siblings are legitimately decodable points — paths agree
    for y in (1, ed.P - 1):
        pub = y.to_bytes(32, "little")
        assert ed._pt_decode(pub) is not None
