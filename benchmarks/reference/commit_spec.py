"""Plain reference for a commit-verification deployment (Tendermint v0.34
``ValidatorSet.VerifyCommit``, types/validator_set.go:667).

Straightforward Python over the benchmark's plain data (data.py), with
OpenSSL's Ed25519 as the verifier. Imports nothing of the program and takes
nothing the program made. An answer is a tuple:

    ("accept",)
    ("wrong_signature", row)            the first row, in set order, whose
                                        signature does not verify
    ("not_enough_power", got, needed)   tallied power <= 2/3 of the total
"""

from __future__ import annotations

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey,
)

from data import PlainCommit, PlainValidators, vote_sign_bytes


def _verifies(pk: bytes, msg: bytes, sig: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(pk).verify(sig, msg)
    except InvalidSignature:
        return False
    return True


def verify_commit(vals: PlainValidators, commit: PlainCommit,
                  stop_at_two_thirds: bool = False) -> tuple:
    """Every signature checked, in set order; the commit stands when the
    signers hold more than 2/3 of the power.

    ``stop_at_two_thirds`` is the CONTROL, not the spec: VerifyCommitLight's
    early exit, which leaves the signatures past the 2/3 prefix unread. It
    breaks the guarantee the configuration states (every signature checked)
    and is what a later PR would be tempted to serve in its place."""
    sbs = vote_sign_bytes(commit.chain_id, commit.height, commit.round,
                          commit.block_id, commit.timestamps_ns)
    needed = vals.total_power * 2 // 3
    tallied = 0
    for row, (pk, power) in enumerate(zip(vals.pubkeys, vals.powers)):
        if not _verifies(pk, sbs[row], commit.signatures[row]):
            return ("wrong_signature", row)
        tallied += power
        if stop_at_two_thirds and tallied > needed:
            return ("accept",)
    if tallied <= needed:
        return ("not_enough_power", tallied, needed)
    return ("accept",)


def control(vals: PlainValidators, commit: PlainCommit) -> tuple:
    return verify_commit(vals, commit, stop_at_two_thirds=True)
