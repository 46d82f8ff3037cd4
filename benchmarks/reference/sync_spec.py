"""Plain reference for a catch-up (fast sync) deployment: what a node must
hold after a peer has offered it the first ``n`` block pairs of a chain.

Reads a source chain as plain data (data.py values the driver extracted
from the chain it built): the transactions of each block and the commit
that the validators signed for each height. Imports nothing of the program.

Tendermint v0.34's blockchain/v0 reactor applies block H only when

    the light rule   the commit for H (carried as block H+1's LastCommit)
                     verifies as VerifyCommitLight does: signatures read in
                     set order until the signers hold more than 2/3 of the
                     power, a wrong one among them refuses the block
                     (blockchain/v0/reactor.go poolRoutine)
    the full rule    block H's own LastCommit, the commit for H-1, verifies
                     as VerifyCommit does: every signature checked
                     (state/validation.go validateBlock)

both hold, and stops at the first block for which one does not. So a chain
whose every commit is sound is synced to its end, and a chain with one
wrong signature in the commit for height h stops at h-1 (the row lies
inside the 2/3 prefix: the light rule of block h) or at h (the row lies
past it: the full rule of block h+1). Every signature is checked with
OpenSSL over this benchmark's own sign-bytes.

    height         the last block applied
    app hash       the kvstore application's: the number of transactions
                   delivered so far, 8 bytes big-endian
                   (abci/example/kvstore/kvstore.go State.Hash)
    last block ID  the block ID the commit for that height names
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey,
)

from data import PlainCommit, PlainValidators, vote_sign_bytes


class Verifier:
    """Row verdicts of commits, each commit object checked once (two source
    chains that share a prefix share its commit objects)."""

    def __init__(self, vals: PlainValidators, trust_signatures: bool = False):
        self.vals = vals
        self.trust = trust_signatures
        self.keys = [Ed25519PublicKey.from_public_bytes(pk)
                     for pk in vals.pubkeys]
        self.needed = vals.total_power * 2 // 3
        self._rows: Dict[int, List[bool]] = {}

    def rows(self, commit: PlainCommit) -> List[bool]:
        hit = self._rows.get(id(commit))
        if hit is not None:
            return hit
        if self.trust:
            out = [True] * len(commit.signatures)
        else:
            sbs = vote_sign_bytes(commit.chain_id, commit.height,
                                  commit.round, commit.block_id,
                                  commit.timestamps_ns)
            out = []
            for key, sb, sig in zip(self.keys, sbs, commit.signatures):
                try:
                    key.verify(sig, sb)
                    out.append(True)
                except InvalidSignature:
                    out.append(False)
        self._rows[id(commit)] = out
        return out

    def light(self, commit: PlainCommit) -> bool:
        tallied = 0
        for ok, power in zip(self.rows(commit), self.vals.powers):
            if not ok:
                return False
            tallied += power
            if tallied > self.needed:
                return True
        return False

    def full(self, commit: PlainCommit) -> bool:
        rows = self.rows(commit)
        return all(rows) and self.vals.total_power > self.needed


def outcome(verifier: Verifier, txs_per_block: Sequence[int],
            commits: Dict[int, PlainCommit], n: int) -> tuple:
    """What a fresh node holds once a peer has offered it blocks 1..n+1."""
    height = 0
    for h in range(1, n + 1):
        if commits[h].height != h:
            raise ValueError(f"commit for height {commits[h].height} "
                             f"filed under {h}")
        if not verifier.light(commits[h]):
            break
        if h > 1 and not verifier.full(commits[h - 1]):
            break
        height = h
    app_hash = sum(txs_per_block[:height]).to_bytes(8, "big")
    if height == 0:
        return ("stopped", 0, app_hash, b"", 0, b"")
    bid = commits[height].block_id
    return ("synced" if height == n else "stopped", height, app_hash,
            bid.hash, bid.parts_total, bid.parts_hash)


def signatures_relied_on(answer: tuple, n_validators: int) -> int:
    """The signatures a node that holds ``answer`` has relied on, at the
    least: both planes of every block it applied (the commit for each of
    heights 1..H under the light rule, for 1..H-1 under the full rule;
    every validator signs every commit of these chains)."""
    height = answer[1]
    return max(0, 2 * height - 1) * n_validators


def expected(vals: PlainValidators, chains: Sequence[dict],
             control: bool = False) -> List[tuple]:
    """The answer for each source chain ({"txs_per_block", "commits", "n"}).

    ``control`` is the CONTROL, not the spec: a node that takes every
    signature on trust. It breaks the guarantee the configuration states
    (both signature planes of every block pair are verified) and is what a
    later PR would be tempted to serve: it syncs the sound chain to the
    same state, faster, and follows a chain with a wrong signature to its
    end."""
    verifier = Verifier(vals, trust_signatures=control)
    return [outcome(verifier, c["txs_per_block"], c["commits"], c["n"])
            for c in chains]
