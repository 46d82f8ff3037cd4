"""Seeded data, made by the benchmark and by nothing of the program.

Validator keys, the order of a validator set, canonical precommit
sign-bytes and signed commits as plain Python values. The drivers turn
these into the program's objects (its input format); the plain references
read them as they are. Nothing here imports ``tendermint_tpu`` or jax.

Signing uses OpenSSL through ``cryptography`` (the package the program's own
tests sign with); sign-bytes follow Tendermint v0.34's CanonicalVote
(types/canonical.go, proto/tendermint/types/canonical.proto).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
)

PRECOMMIT = 2          # SignedMsgType
FLAG_COMMIT = 2        # BlockIDFlag


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _bytes_field(tag: int, body: bytes) -> bytes:
    return bytes([tag << 3 | 2]) + _varint(len(body)) + body


def _sfixed64_field(tag: int, v: int) -> bytes:
    # proto3: a zero scalar is left out
    return b"" if v == 0 else bytes([tag << 3 | 1]) + v.to_bytes(
        8, "little", signed=True)


@dataclass(frozen=True)
class PlainBlockID:
    hash: bytes
    parts_total: int
    parts_hash: bytes

    def canonical(self) -> bytes:
        psh = (b"\x08" + _varint(self.parts_total) if self.parts_total
               else b"") + _bytes_field(2, self.parts_hash)
        return _bytes_field(1, self.hash) + _bytes_field(2, psh)


def vote_sign_bytes(chain_id: str, height: int, round_: int,
                    block_id: PlainBlockID,
                    timestamps_ns: Sequence[int]) -> List[bytes]:
    """Canonical precommit sign-bytes, one per timestamp: the
    length-prefixed CanonicalVote {type=1, height=2 sfixed64, round=3
    sfixed64, block_id=4, timestamp=5, chain_id=6}."""
    head = (b"\x08" + _varint(PRECOMMIT) + _sfixed64_field(2, height)
            + _sfixed64_field(3, round_)
            + _bytes_field(4, block_id.canonical()))
    tail = _bytes_field(6, chain_id.encode())
    out = []
    for ns in timestamps_ns:
        seconds, nanos = divmod(ns, 1_000_000_000)
        ts = ((b"\x08" + _varint(seconds) if seconds else b"")
              + (b"\x10" + _varint(nanos) if nanos else b""))
        body = head + _bytes_field(5, ts) + tail
        out.append(_varint(len(body)) + body)
    return out


@dataclass
class PlainValidators:
    """A validator set in the order Tendermint keeps it: voting power
    descending, then address ascending (address = SHA-256(pubkey)[:20])."""
    pubkeys: List[bytes]
    powers: List[int]
    signers: List[Ed25519PrivateKey]

    def __len__(self) -> int:
        return len(self.pubkeys)

    @property
    def total_power(self) -> int:
        return sum(self.powers)


def make_validators(n: int, seed: int, n_heavy: int = 0, heavy_power: int = 30,
                    power: int = 10) -> PlainValidators:
    """``n`` seeded validators in two stake tiers (real sets are skewed):
    ``n_heavy`` hold ``heavy_power``, the rest ``power``."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        sk = Ed25519PrivateKey.from_private_bytes(rng.bytes(32))
        pk = sk.public_key().public_bytes_raw()
        rows.append((-(heavy_power if i < n_heavy else power),
                     hashlib.sha256(pk).digest()[:20], pk, sk))
    rows.sort(key=lambda r: (r[0], r[1]))
    return PlainValidators([r[2] for r in rows], [-r[0] for r in rows],
                           [r[3] for r in rows])


@dataclass
class PlainCommit:
    chain_id: str
    height: int
    round: int
    block_id: PlainBlockID
    timestamps_ns: List[int]
    signatures: List[bytes]          # one per validator, all FLAG_COMMIT
    tampered_rows: Tuple[int, ...] = ()


def block_id_for(height: int, label: bytes = b"bench-block") -> PlainBlockID:
    return PlainBlockID(hashlib.sha256(label + b"-%d" % height).digest(), 1,
                        hashlib.sha256(label + b"-parts").digest())


def sign_commit(vals: PlainValidators, chain_id: str,
                height: int) -> PlainCommit:
    """Every validator's precommit for ``height``. Row i signs base + i ns
    (mid-second, so every row's nanos varint has one length): rows differ
    in the low timestamp bytes only, as a real commit's do."""
    bid = block_id_for(height)
    base = 1_700_000_000_500_000_000 + height * 1_000_000_000
    ts = [base + i for i in range(len(vals))]
    sbs = vote_sign_bytes(chain_id, height, 0, bid, ts)
    sigs = [sk.sign(sb) for sk, sb in zip(vals.signers, sbs)]
    return PlainCommit(chain_id, height, 0, bid, ts, sigs)


def tamper(commit: PlainCommit, row: int) -> PlainCommit:
    """The same commit with one bit of signature ``row``'s scalar flipped."""
    sigs = list(commit.signatures)
    s = sigs[row]
    sigs[row] = s[:40] + bytes([s[40] ^ 1]) + s[41:]
    return PlainCommit(commit.chain_id, commit.height, commit.round,
                       commit.block_id, commit.timestamps_ns, sigs,
                       commit.tampered_rows + (row,))


def first_row_past(vals: PlainValidators, num: int, den: int) -> int:
    """The first row whose cumulative power (in set order) exceeds
    num/den of the total: rows from here on lie outside every prefix a
    num/den early exit reads."""
    need = vals.total_power * num // den
    acc = 0
    for i, p in enumerate(vals.powers):
        acc += p
        if acc > need:
            return i + 1
    return len(vals)
