"""Plain reference for a light-client serving deployment: the answer a node
owes a light client that trusts height ``tip - gap`` and asks it to verify
its newest header ``tip`` (Tendermint v0.34 light/verifier.go ``Verify``).

Reads the chain as plain data the driver extracted from the chain it built
(each header's fields; each seen commit as a data.py ``PlainCommit``) and
the validator set as data.py values. Imports nothing of the program. The
header fields themselves are the program's (its app hash, its results and
evidence hashes, the times ``apply_block`` gave); this reference hashes
them anew (``header_hash``) and hashes the validator set anew, so a commit
that names another header hash, or a header that names another set, is
refused here whatever the program made of them. Every signature is checked
with OpenSSL over this benchmark's own sign-bytes, each row of a commit
once.

    gap 1      VerifyAdjacent (verifier.go:93): the trusted header has not
               expired; the new header's checks (below); its validators hash
               equals the trusted header's next-validators hash;
               VerifyCommitLight over the new set
    gap > 1    VerifyNonAdjacent (verifier.go:32): not expired; the new
               header's checks; VerifyCommitLightTrusting over the TRUSTED
               set at the trust level, then VerifyCommitLight

The new header's checks (verifyNewHeaderAndVals): its commit signs it (the
block ID names its hash), it is higher and later than the trusted one, not
from the future (now + max clock drift), and its validators hash is the
hash of the set given with it. The chain is static: the trusted header
names the same set, as its own and as the next. The light check reads rows in set order and
stops where the signers hold more than 2/3 of the power; the trusting check
where they hold more than the trust level of the trusted set's power; a
wrong signature read before the stop refuses.

An answer is a tuple, as the driver records the program's:

    ("accept",)
    ("ErrWrongSignature", row)       the trusting check read a wrong row
                                     (the skipping path passes it on as is)
    ("ErrInvalidHeader", row)        the light check read a wrong row (both
                                     paths wrap it), or a header check
                                     failed (row None)
    ("ErrOldHeaderExpired", None)    the trusted header is past its period
    ("ErrNewValSetCantBeTrusted", None)  the trusted set signed too little
"""

from __future__ import annotations

import hashlib
from typing import Dict, List

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey,
)

from data import PlainValidators, vote_sign_bytes


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


def _bytes_field(field: int, b: bytes) -> bytes:
    """A proto3 bytes field; empty is left out."""
    return b"" if not b else bytes([field << 3 | 2]) + _varint(len(b)) + b


def _varint_field(field: int, v: int) -> bytes:
    """A proto3 varint field; zero is left out."""
    return b"" if v == 0 else bytes([field << 3]) + _varint(v)


def _merkle_root(items: List[bytes]) -> bytes:
    """RFC 6962 tree (crypto/merkle/tree.go): split at the largest power of
    two below n; leaves SHA-256(0x00 || item), nodes SHA-256(0x01 || l ||
    r)."""
    if not items:
        return hashlib.sha256(b"").digest()
    if len(items) == 1:
        return hashlib.sha256(b"\x00" + items[0]).digest()
    k = 1
    while k * 2 < len(items):
        k *= 2
    return hashlib.sha256(b"\x01" + _merkle_root(items[:k])
                          + _merkle_root(items[k:])).digest()


def validator_set_hash(vals: PlainValidators) -> bytes:
    """ValidatorSet.Hash (types/validator_set.go:347): the merkle root of
    every validator's SimpleValidator {pub_key = PublicKey{ed25519}, power}
    proto encoding, in set order."""
    rows = []
    for pk, power in zip(vals.pubkeys, vals.powers):
        key = b"\x0a" + _varint(len(pk)) + pk
        rows.append(b"\x0a" + _varint(len(key)) + key + b"\x10"
                    + _varint(power))
    return _merkle_root(rows)


def header_hash(h: dict) -> bytes:
    """Header.Hash (types/block.go:440): the merkle root of its fourteen
    fields, each proto-encoded on its own: Consensus {block, app}; the
    chain ID, height and each hash wrapped as a one-field message (field 1,
    empty left out); Timestamp {seconds, nanos}; BlockID {hash, PartSetHeader
    {total, hash}}, the PartSetHeader written even when empty."""
    block, app = h["version"]
    seconds, nanos = divmod(h["time_ns"], 1_000_000_000)
    lb = h["last_block_id"]
    parts = _varint_field(1, lb.parts_total) + _bytes_field(2, lb.parts_hash)
    leaves = [
        _varint_field(1, block) + _varint_field(2, app),
        _bytes_field(1, h["chain_id"].encode()),
        _varint_field(1, h["height"]),
        _varint_field(1, seconds) + _varint_field(2, nanos),
        _bytes_field(1, lb.hash) + b"\x12" + _varint(len(parts)) + parts,
    ] + [_bytes_field(1, h[k]) for k in (
        "last_commit_hash", "data_hash", "validators_hash",
        "next_validators_hash", "consensus_hash", "app_hash",
        "last_results_hash", "evidence_hash", "proposer_address")]
    return _merkle_root(leaves)


class _Refused(Exception):
    def __init__(self, name: str, row=None):
        super().__init__(name)
        self.answer = (name, row)


class Spec:
    """Answers for one chain. ``control`` is the CONTROL, not the spec: a
    node that takes every signature on trust. It breaks the guarantee the
    configuration states (every signature an answer relies on is verified)
    and is what a later PR would be tempted to serve: it accepts every
    header, the tampered ones too."""

    def __init__(self, vals: PlainValidators, chain: dict,
                 control: bool = False):
        self.vals = vals
        self.chain = chain
        self.trust = control
        self.keys = [Ed25519PublicKey.from_public_bytes(pk)
                     for pk in vals.pubkeys]
        self.vals_hash = validator_set_hash(vals)
        self._rows: Dict[int, List[bool]] = {}
        self._answers: Dict[tuple, tuple] = {}

    def _row_ok(self, height: int, row: int) -> bool:
        """Row ``row`` of the seen commit for ``height``, checked once."""
        if self.trust:
            return True
        done = self._rows.setdefault(height, [])
        commit = self.chain["seen"][height]
        if row >= len(done):
            sbs = vote_sign_bytes(commit.chain_id, commit.height,
                                  commit.round, commit.block_id,
                                  commit.timestamps_ns[len(done):row + 1])
            for i, sb in enumerate(sbs, start=len(done)):
                try:
                    self.keys[i].verify(commit.signatures[i], sb)
                    done.append(True)
                except InvalidSignature:
                    done.append(False)
        return done[row]

    def _tally(self, height: int, num: int, den: int, wrong: str,
               short: str) -> None:
        """Rows in set order until the signers hold more than num/den of
        the power; a wrong one read before that refuses with ``wrong``,
        too little power with ``short``."""
        needed = self.vals.total_power * num // den
        tallied = 0
        for row, power in enumerate(self.vals.powers):
            if not self._row_ok(height, row):
                raise _Refused(wrong, row)
            tallied += power
            if tallied > needed:
                return
        raise _Refused(short)

    def _verify(self, trusted: dict, new: dict) -> None:
        c = self.chain
        now, drift_ns = c["now_ns"], int(c["max_clock_drift_s"] * 1e9)
        if trusted["time_ns"] + int(c["trusting_period_s"] * 1e9) <= now:
            raise _Refused("ErrOldHeaderExpired")
        commit = c["seen"][new["height"]]
        if (new["chain_id"] != c["chain_id"]
                or commit.height != new["height"]
                or commit.block_id.hash != header_hash(new)
                or new["height"] <= trusted["height"]
                or new["time_ns"] <= trusted["time_ns"]
                or new["time_ns"] >= now + drift_ns
                or new["validators_hash"] != self.vals_hash):
            raise _Refused("ErrInvalidHeader")
        # a static chain: the trusted header names this one set, as the next
        # (what VerifyAdjacent holds the new header's set to) and as its own
        # (the set VerifyNonAdjacent's trusting check tallies)
        if (trusted["next_validators_hash"] != self.vals_hash
                or trusted["validators_hash"] != self.vals_hash):
            raise _Refused("ErrInvalidHeader")
        if new["height"] != trusted["height"] + 1:
            num, den = c["trust_level"]
            self._tally(new["height"], num, den, "ErrWrongSignature",
                        "ErrNewValSetCantBeTrusted")
        self._tally(new["height"], 2, 3, "ErrInvalidHeader",
                    "ErrInvalidHeader")

    def answer(self, tip: int, gap: int) -> tuple:
        key = (tip, gap)
        if key not in self._answers:
            headers = self.chain["headers"]
            try:
                self._verify(headers[tip - gap], headers[tip])
                self._answers[key] = ("accept",)
            except _Refused as e:
                self._answers[key] = e.answer
        return self._answers[key]
