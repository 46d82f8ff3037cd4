"""Plain reference for a catch-up (fast sync) over a chain whose validator
set changes at every height: what a node must hold after a peer has offered
it the first ``n`` block pairs of such a chain.

Reads the source chain as plain data (data.py values the driver extracted
from the chain it built): the genesis validators, the transactions of each
block and the commit that the validators signed for each height. Imports
nothing of the program.

The sets. The kvstore application delivers every transaction; one of the
form ``val:<pubkey_hex>!<power>`` is a validator update, which EndBlock
returns (power 0 removes the validator). Tendermint v0.34 applies the
updates of EndBlock(H) to the set of height H+2 (state/execution.go
updateState), so

    set(1) = set(2) = the genesis set
    set(H+2)        = set(H+1) with the updates of block H

each ordered by voting power descending, then address ascending (address =
SHA-256(pubkey)[:20]; types/validator.go ValidatorsByVotingPower).

The rules (sync_spec.py, with each height's own set): block H is applied
only when the commit for H verifies under the light rule against set(H),
row i against set(H)'s validator i, read in order until the tally exceeds
2/3 of set(H)'s power, and block H's own LastCommit (the commit for H-1)
verifies under the full rule against set(H-1). Every signature is checked
with OpenSSL over this benchmark's own sign-bytes.

    height         the last block applied
    app hash       the kvstore application's: the number of transactions
                   delivered so far, 8 bytes big-endian
    last block ID  the block ID the commit for that height names
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Sequence, Tuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey,
)

from data import PlainCommit, PlainValidators, vote_sign_bytes

VALIDATOR_TX = b"val:"

#: a set as the reference holds it: (pubkeys, powers), in set order
Set = Tuple[List[bytes], List[int]]


def ordered(power_of: Dict[bytes, int]) -> Set:
    rows = sorted(power_of.items(),
                  key=lambda kv: (-kv[1], hashlib.sha256(kv[0]).digest()[:20]))
    return [pk for pk, _ in rows], [p for _, p in rows]


def updates(txs: Sequence[bytes]) -> List[Tuple[bytes, int]]:
    """The validator updates a block's transactions make, in order."""
    out = []
    for tx in txs:
        if tx.startswith(VALIDATOR_TX):
            pubkey_hex, power = tx[len(VALIDATOR_TX):].decode().split("!")
            out.append((bytes.fromhex(pubkey_hex), int(power)))
    return out


def sets_by_height(genesis: PlainValidators, txs: Sequence[Sequence[bytes]],
                   n: int) -> Dict[int, Set]:
    """set(h) for h in 1..n, by the H+2 rule above."""
    power_of = dict(zip(genesis.pubkeys, genesis.powers))
    sets = {1: ordered(power_of), 2: ordered(power_of)}
    for h in range(1, n - 1):
        for pk, power in updates(txs[h - 1]):
            if power:
                power_of[pk] = power
            else:
                del power_of[pk]
        sets[h + 2] = ordered(power_of)
    return {h: s for h, s in sets.items() if h <= n}


def sizes_by_height(genesis: PlainValidators,
                    txs: Sequence[Sequence[bytes]], n: int) -> Dict[int, int]:
    """len(set(h)) for h in 1..n, by the same rule, without ordering."""
    members = set(genesis.pubkeys)
    sizes = {1: len(members), 2: len(members)}
    for h in range(1, n - 1):
        for pk, power in updates(txs[h - 1]):
            (members.add if power else members.discard)(pk)
        sizes[h + 2] = len(members)
    return {h: k for h, k in sizes.items() if h <= n}


class Verifier:
    """Row verdicts of a commit against a set, each pair checked once: the
    pair is keyed by content, since a tampered chain repeats the sound
    chain's commits up to its tampered height."""

    def __init__(self, trust_signatures: bool = False):
        self.trust = trust_signatures
        self._rows: Dict[tuple, List[bool]] = {}
        self._keys: Dict[bytes, Ed25519PublicKey] = {}

    def rows(self, commit: PlainCommit, vset: Set) -> List[bool]:
        memo = (commit.chain_id, commit.height, commit.round,
                commit.block_id, tuple(commit.timestamps_ns),
                tuple(commit.signatures), tuple(vset[0]))
        hit = self._rows.get(memo)
        if hit is not None:
            return hit
        if self.trust:
            out = [True] * len(commit.signatures)
        else:
            sbs = vote_sign_bytes(commit.chain_id, commit.height,
                                  commit.round, commit.block_id,
                                  commit.timestamps_ns)
            out = []
            for pk, sb, sig in zip(vset[0], sbs, commit.signatures):
                key = self._keys.get(pk)
                if key is None:
                    key = self._keys[pk] = Ed25519PublicKey.from_public_bytes(
                        pk)
                try:
                    key.verify(sig, sb)
                    out.append(True)
                except InvalidSignature:
                    out.append(False)
        self._rows[memo] = out
        return out

    def light(self, commit: PlainCommit, vset: Set) -> bool:
        if len(commit.signatures) != len(vset[0]):
            return False
        needed = sum(vset[1]) * 2 // 3
        tallied = 0
        for ok, power in zip(self.rows(commit, vset), vset[1]):
            if not ok:
                return False
            tallied += power
            if tallied > needed:
                return True
        return False

    def full(self, commit: PlainCommit, vset: Set) -> bool:
        if len(commit.signatures) != len(vset[0]):
            return False
        return all(self.rows(commit, vset)) and (
            sum(vset[1]) > sum(vset[1]) * 2 // 3)


def outcome(verifier: Verifier, genesis: PlainValidators, chain: dict
            ) -> tuple:
    """What a fresh node holds once a peer has offered it blocks 1..n+1
    of ``chain`` ({"txs", "commits", "n"})."""
    n, commits, txs = chain["n"], chain["commits"], chain["txs"]
    sets = sets_by_height(genesis, txs, n)
    height = 0
    for h in range(1, n + 1):
        if commits[h].height != h:
            raise ValueError(f"commit for height {commits[h].height} "
                             f"filed under {h}")
        if not verifier.light(commits[h], sets[h]):
            break
        if h > 1 and not verifier.full(commits[h - 1], sets[h - 1]):
            break
        height = h
    app_hash = sum(len(t) for t in txs[:height]).to_bytes(8, "big")
    if height == 0:
        return ("stopped", 0, app_hash, b"", 0, b"")
    bid = commits[height].block_id
    return ("synced" if height == n else "stopped", height, app_hash,
            bid.hash, bid.parts_total, bid.parts_hash)


def signatures_relied_on(genesis: PlainValidators, chain: dict,
                         answer: tuple) -> int:
    """The signatures a node that holds ``answer`` has relied on, at the
    least: both planes of every block it applied (the commit for each of
    heights 1..H under the light rule, for 1..H-1 under the full rule;
    every member of a height's set signs its commit)."""
    height = answer[1]
    sizes = sizes_by_height(genesis, chain["txs"], max(height, 1))
    return sum(sizes[h] for h in range(1, height + 1)) + sum(
        sizes[h] for h in range(1, height))


def expected(genesis: PlainValidators, chains: Sequence[dict],
             control: bool = False) -> List[tuple]:
    """The answer for each source chain.

    ``control`` is the CONTROL, not the spec: a node that takes every
    signature on trust. It breaks the guarantee the configuration states
    (each block's two rules run against the sets of their heights) and is
    what a later PR would be tempted to serve: it syncs the sound chain to
    the same state and follows a chain with a wrong signature to its
    end."""
    verifier = Verifier(trust_signatures=control)
    return [outcome(verifier, genesis, c) for c in chains]
