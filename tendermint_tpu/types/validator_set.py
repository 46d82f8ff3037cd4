"""ValidatorSet: sorted set, deterministic proposer rotation, commit verification.

Semantics mirror reference types/validator_set.go exactly (int64 clipping,
priority rescale/center, update/removal merge order, error precedence in the
three VerifyCommit variants at :667/:722/:775). The difference is HOW commits
are verified: all candidate signatures are collected into one BatchVerifier
call (TPU Pallas kernel batch) and the scalar loop's decisions — including
VerifyCommitLight's early exit at 2/3 — are replayed over the batch verdicts,
so accept/reject and error selection are byte-identical to the reference while
the crypto runs as one device batch instead of N host calls. Candidates,
verdicts and the tally are arrays (flags, the first False, a cumulative sum of
powers): no Python runs per row of a large commit.
"""

from __future__ import annotations

from operator import attrgetter
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..crypto import Ed25519PubKey
from ..crypto.batch import STREAM_CHUNK, BatchVerifier
from ..libs import protowire as pw
from .basic import BlockID, BlockIDFlag, encode_stats
from .errors import (
    ErrInvalidCommitHeight,
    ErrInvalidCommitSignatures,
    ErrNotEnoughVotingPowerSigned,
    ErrWrongSignature,
)
from .validator import (
    MAX_TOTAL_VOTING_POWER,
    PRIORITY_TAG,
    PRIORITY_WINDOW_SIZE_FACTOR,
    Validator,
    safe_add_clip,
    safe_mul,
    safe_sub_clip,
)

# Fraction as (numerator, denominator) — reference libs/math.Fraction.
Fraction = Tuple[int, int]


def _is_aggregated(commit) -> bool:
    """Duck-typed (types.block.AggregatedCommit carries agg_sig/signers) so
    this module need not import types.block."""
    return hasattr(commit, "agg_sig")


def _observe_aggregated_wire_size(commit) -> None:
    """Feed the verified commit's encoded size into the aggregated-commit
    wire-size histogram (telemetry only; never affects the verdict)."""
    from ..crypto import phases as _phases

    m = _phases.metrics
    if m is None:
        return
    try:
        m.aggregated_commit_bytes.observe(float(len(commit.encode())))
    except Exception:
        pass


_PRIORITY_OF = attrgetter("proposer_priority")


def _by_voting_power(v: Validator):
    """Sort key: power desc, address asc (reference types/validator.go ValidatorsByVotingPower)."""
    return (-v.voting_power, v.address)


def _raise_first_wrong(commit, idxs: np.ndarray, ok: np.ndarray) -> None:
    """ErrWrongSignature for the first candidate row whose verdict is False
    (candidate order is row order), as the scalar loop meets it."""
    bad = np.flatnonzero(~ok)
    if bad.shape[0]:
        idx = int(idxs[bad[0]])
        raise ErrWrongSignature(idx, commit.signatures[idx].signature)


def _replay_light(commit, idxs: np.ndarray, ok: np.ndarray,
                  powers: np.ndarray, needed: int) -> None:
    """VerifyCommitLight's loop over candidate rows ``idxs`` (verdicts
    ``ok``, voting ``powers``): each row's signature is checked, then its
    power tallied, and the loop returns where the tally first exceeds
    ``needed``. A wrong signature up to that row raises; one after it is
    never looked at."""
    cum = np.cumsum(powers)
    over = np.flatnonzero(cum > needed)
    read = int(over[0]) + 1 if over.shape[0] else len(idxs)
    _raise_first_wrong(commit, idxs[:read], ok[:read])
    if not over.shape[0]:
        raise ErrNotEnoughVotingPowerSigned(
            int(cum[-1]) if len(idxs) else 0, needed)


class ValidatorSet:
    def __init__(self, validators: Optional[Sequence[Validator]] = None):
        """NewValidatorSet semantics (validator_set.go:70): copies, validates,
        sorts, and runs one IncrementProposerPriority(1)."""
        self.validators: List[Validator] = []
        self.proposer: Optional[Validator] = None
        self._total_voting_power: Optional[int] = None
        # structural-mutation counter: every mutator that changes membership
        # or ORDER bumps it, so the _addr_index/hash memos below cannot go
        # stale even for an in-place mutation that preserves the list
        # object's identity and length (advisor finding at _addr_index)
        self._mutations = 0
        if validators is not None:
            self._update_with_change_set([v.copy() for v in validators], allow_deletes=False)
            if len(self.validators) > 0:
                self.increment_proposer_priority(1)

    @classmethod
    def from_existing(cls, validators: Sequence[Validator]) -> "ValidatorSet":
        """(validator_set.go ValidatorSetFromExistingValidators) rebuild a
        set whose proposer priorities are ALREADY live — RPC /validators
        answers, statesync bootstrap — without NewValidatorSet's extra
        IncrementProposerPriority(1). The proposer is recovered from the
        existing priorities; re-incrementing here desynchronizes proposer
        selection from the running network (found by the statesync e2e
        manifest: the synced node rejected every proposal)."""
        vs = cls()
        vs.validators = sorted((v.copy() for v in validators),
                               key=_by_voting_power)
        vs._bump_mutations()
        if vs.validators:
            # findPreviousProposer (validator_set.go:832): the chosen
            # proposer was decremented by the total power, so it is the one
            # that LOSES the priority comparison against every other
            prev = None
            for v in vs.validators:
                if prev is None:
                    prev = v
                elif prev is prev.compare_proposer_priority(v):
                    prev = v
            vs.proposer = prev
        return vs

    # -- basic accessors ---------------------------------------------------

    def __len__(self) -> int:
        return len(self.validators)

    def size(self) -> int:
        return len(self.validators)

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def copy(self) -> "ValidatorSet":
        vs = ValidatorSet()
        vs.validators = [v.copy() for v in self.validators]
        vs.proposer = self.proposer
        vs._total_voting_power = self._total_voting_power
        # membership, keys and powers are identical, so the merkle hash,
        # the verify arrays and the rows' encoded prefixes carry over
        # (priorities are part of none), and so do the encoded rows (which
        # hold the priorities they were built from, compared on every
        # use); re-keyed to the copy's own list + mutation count so later
        # structural mutations invalidate normally
        for name in ("_hash_cache", "_verify_cache", "_prefix_cache",
                     "_rows_cache"):
            value = self._kept(name)
            if value is not None:
                vs._keep(name, value)
        return vs

    def _bump_mutations(self) -> None:
        """Every structural mutator (membership OR order change) must call
        this; the _addr_index/hash memos key on the counter, so an in-place
        mutation that preserves list identity and length still invalidates."""
        self._mutations += 1

    def _kept(self, name: str):
        """What ``_keep(name, ...)`` kept, or None once the validators list
        is another object or of another length, or a structural mutator
        has bumped ``_mutations``."""
        cache = self.__dict__.get(name)
        if (cache is None or cache[0] is not self.validators
                or cache[1] != self._mutations
                or cache[2] != len(self.validators)):
            return None
        return cache[3]

    def _keep(self, name: str, value):
        self.__dict__[name] = (self.validators, self._mutations,
                               len(self.validators), value)
        return value

    def _memo(self, name: str, build):
        """``build()``'s result (never None), kept under ``_kept``'s rule."""
        value = self._kept(name)
        return self._keep(name, build()) if value is None else value

    def _addr_index(self) -> dict:
        """address -> index, rebuilt whenever the validators list object is
        replaced, resized, or a structural mutator bumps ``_mutations``
        (priority updates mutate Validator objects but never addresses or
        order, so the cache stays valid across IncrementProposerPriority).
        At light-client/commit-verification scale the linear scan was the
        single hottest host-side cost (1000-validator sets x 32k lookups)."""
        def build() -> dict:
            idx: dict = {}
            for i, v in enumerate(self.validators):
                idx.setdefault(v.address, i)  # first match wins, like the scan
            return idx

        return self._memo("_addr_cache", build)

    def _verify_arrays(self):
        """``(pubkey bytes per validator | None, voting powers array)`` for
        commit verification, under _addr_index's rule (list identity,
        ``_mutations``, length): the one thing a verify call keeps for the
        next, and it derives from the set alone. The key list is None when
        a key is not ed25519 (the columnar way into the verifier takes raw
        ed25519 keys). Powers are int64 — every partial sum fits while they
        are non-negative, total_voting_power() holding the total to
        MAX_TOTAL_VOTING_POWER = 2^63 / 8 — and Python ints in an object
        array otherwise, exact either way."""
        def build():
            vals = self.validators
            pks = ([v.pub_key.bytes() for v in vals]
                   if all(isinstance(v.pub_key, Ed25519PubKey) for v in vals)
                   else None)
            powers = [v.voting_power for v in vals]
            fits = all(0 <= p <= MAX_TOTAL_VOTING_POWER for p in powers)
            return pks, np.array(powers, dtype=np.int64 if fits else object)

        return self._memo("_verify_cache", build)

    def has_address(self, address: bytes) -> bool:
        return address in self._addr_index()

    def get_by_address(self, address: bytes) -> Tuple[int, Optional[Validator]]:
        i = self._addr_index().get(address)
        if i is None:
            return -1, None
        return i, self.validators[i].copy()

    def get_by_index(self, index: int) -> Tuple[bytes, Optional[Validator]]:
        if index < 0 or index >= len(self.validators):
            return b"", None
        v = self.validators[index]
        return v.address, v.copy()

    def total_voting_power(self) -> int:
        if self._total_voting_power is None:
            self._update_total_voting_power()
        return self._total_voting_power

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total = safe_add_clip(total, v.voting_power)
            if total > MAX_TOTAL_VOTING_POWER:
                raise OverflowError(
                    f"total voting power cannot be guarded to not exceed {MAX_TOTAL_VOTING_POWER}; got: {total}"
                )
        self._total_voting_power = total

    def hash(self) -> bytes:
        """Merkle root of SimpleValidator encodings (validator_set.go:347).

        Memoized under the same invalidation contract as _addr_index (list
        identity + length + the structural mutation counter): priority
        rotation — the only in-place mutation that doesn't bump the counter
        — does not touch bytes_for_hash. validate_block hashes two
        1000-validator sets per block, and copy() propagates the memo, so
        steady-state fast sync pays the merkle pass only when membership
        actually changes."""
        def build() -> bytes:
            from ..crypto import merkle

            return merkle.hash_from_byte_slices(
                [v.bytes_for_hash() for v in self.validators])

        return self._memo("_hash_cache", build)

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is nil or empty")
        for idx, v in enumerate(self.validators):
            try:
                v.validate_basic()
            except ValueError as e:
                raise ValueError(f"invalid validator #{idx}: {e}")
        if self.proposer is None:
            raise ValueError("proposer failed validate basic, error: nil validator")
        self.proposer.validate_basic()

    # -- proposer rotation (validator_set.go:107-256) ----------------------

    def get_proposer(self) -> Optional[Validator]:
        if len(self.validators) == 0:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        proposer = None
        for v in self.validators:
            proposer = v if proposer is None else proposer.compare_proposer_priority(v)
        return proposer

    def increment_proposer_priority(self, times: int) -> None:
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("cannot call IncrementProposerPriority with non-positive times")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = safe_add_clip(v.proposer_priority, v.voting_power)
        mostest = self._find_proposer()
        mostest.proposer_priority = safe_sub_clip(mostest.proposer_priority, self.total_voting_power())
        return mostest

    def rescale_priorities(self, diff_max: int) -> None:
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if diff_max <= 0:
            return
        diff = self._max_min_priority_diff()
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                # Go int division truncates toward zero; Python floors.
                p = v.proposer_priority
                v.proposer_priority = -((-p) // ratio) if p < 0 else p // ratio

    def _max_min_priority_diff(self) -> int:
        mx = max(v.proposer_priority for v in self.validators)
        mn = min(v.proposer_priority for v in self.validators)
        return abs(mx - mn)

    def _compute_avg_proposer_priority(self) -> int:
        n = len(self.validators)
        s = sum(v.proposer_priority for v in self.validators)
        # Go big.Int Div floors (Euclidean for positive divisor) — matches //.
        return s // n

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = safe_sub_clip(v.proposer_priority, avg)

    # -- updates (validator_set.go:371-665) --------------------------------

    def update_with_change_set(self, changes: Sequence[Validator]) -> None:
        self._update_with_change_set([c.copy() for c in changes], allow_deletes=True)

    def _update_with_change_set(self, changes: List[Validator], allow_deletes: bool) -> None:
        if len(changes) == 0:
            return
        updates, deletes = _process_changes(changes)
        if not allow_deletes and deletes:
            raise ValueError(f"cannot process validators with voting power 0: {deletes}")
        num_new = sum(1 for u in updates if not self.has_address(u.address))
        if num_new == 0 and len(self.validators) == len(deletes):
            raise ValueError("applying the validator changes would result in empty set")
        removed_power = self._verify_removals(deletes)
        tvp_after_updates = self._verify_updates(updates, removed_power)
        self._compute_new_priorities(updates, tvp_after_updates)
        self._apply_updates(updates)
        self._apply_removals(deletes)
        self._total_voting_power = None
        self._update_total_voting_power()
        self.rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()
        # reassign (not in-place sort) AND bump: either alone invalidates
        # the _addr_index/hash memos; both keeps the invariant obvious
        self.validators = sorted(self.validators, key=_by_voting_power)
        self._bump_mutations()

    def _verify_removals(self, deletes: List[Validator]) -> int:
        removed = 0
        for d in deletes:
            _, val = self.get_by_address(d.address)
            if val is None:
                raise ValueError(f"failed to find validator {d.address.hex().upper()} to remove")
            removed += val.voting_power
        if len(deletes) > len(self.validators):
            raise ValueError("more deletes than validators")
        return removed

    def _verify_updates(self, updates: List[Validator], removed_power: int) -> int:
        def delta(u: Validator) -> int:
            _, val = self.get_by_address(u.address)
            return u.voting_power - val.voting_power if val is not None else u.voting_power

        ordered = sorted(updates, key=delta)
        tvp_after_removals = self.total_voting_power() - removed_power
        for u in ordered:
            tvp_after_removals += delta(u)
            if tvp_after_removals > MAX_TOTAL_VOTING_POWER:
                raise OverflowError(
                    f"total voting power of resulting valset exceeds max {MAX_TOTAL_VOTING_POWER}"
                )
        return tvp_after_removals + removed_power

    def _compute_new_priorities(self, updates: List[Validator], updated_tvp: int) -> None:
        for u in updates:
            _, val = self.get_by_address(u.address)
            if val is None:
                # -1.125*totalVotingPower so rejoining validators can't reset
                # their priority (validator_set.go:483-490).
                u.proposer_priority = -(updated_tvp + (updated_tvp >> 3))
            else:
                u.proposer_priority = val.proposer_priority

    def _apply_updates(self, updates: List[Validator]) -> None:
        existing = sorted(self.validators, key=lambda v: v.address)
        merged: List[Validator] = []
        i = j = 0
        while i < len(existing) and j < len(updates):
            if existing[i].address < updates[j].address:
                merged.append(existing[i])
                i += 1
            else:
                merged.append(updates[j])
                if existing[i].address == updates[j].address:
                    i += 1
                j += 1
        merged.extend(existing[i:])
        merged.extend(updates[j:])
        self.validators = merged

    def _apply_removals(self, deletes: List[Validator]) -> None:
        if not deletes:
            return
        dset = {d.address for d in deletes}
        self.validators = [v for v in self.validators if v.address not in dset]

    # -- commit verification (validator_set.go:667-821) --------------------
    #
    # Each variant: one batched device call over the candidate signatures,
    # then the reference's scalar loop over the verdicts, computed on
    # arrays, so error precedence and early exits match exactly: the loop
    # stops at its first wrong signature or where the tally crosses, and
    # whichever row comes first decides.

    def verify_commit(self, chain_id: str, block_id: BlockID, height: int, commit) -> None:
        """All signatures checked; absent skipped; nil votes verified but not
        tallied (validator_set.go:667)."""
        self._check_commit_shape(commit, height, block_id)
        if _is_aggregated(commit):
            return self._verify_aggregated(chain_id, commit, mode="full")
        flags = commit.block_id_flags()
        idxs = np.flatnonzero(flags != BlockIDFlag.ABSENT)
        ok = self._batch_verify(chain_id, commit, idxs)
        needed = self.total_voting_power() * 2 // 3
        _raise_first_wrong(commit, idxs, ok)
        tallied = int(self._verify_arrays()[1][flags == BlockIDFlag.COMMIT]
                      .sum())
        if tallied <= needed:
            raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    def verify_commit_light(self, chain_id: str, block_id: BlockID, height: int, commit) -> None:
        """Stops at 2/3: signatures after the early-exit point are never
        examined (validator_set.go:722) — the replay preserves that."""
        self._check_commit_shape(commit, height, block_id)
        if _is_aggregated(commit):
            # one pairing over the whole bitmap: there is no cheaper
            # early-exit prefix to stop at
            return self._verify_aggregated(chain_id, commit, mode="light")
        idxs = np.flatnonzero(commit.block_id_flags() == BlockIDFlag.COMMIT)
        ok = self._batch_verify(chain_id, commit, idxs, plane="light")
        needed = self.total_voting_power() * 2 // 3
        _replay_light(commit, idxs, ok, self._verify_arrays()[1][idxs],
                      needed)

    def verify_commit_light_trusting(self, chain_id: str, commit,
                                     trust_level: Fraction,
                                     commit_vals: "ValidatorSet" = None) -> None:
        """Address-lookup variant over a *trusted* set (validator_set.go:775).

        `commit_vals` is only consulted for aggregated commits: the aggregate
        signature covers every key in the signer bitmap — positioned by index
        into the COMMIT's validator set, which the trusted set (self) may not
        contain — so the pairing needs the commit-height set while the
        trust-level tally intersects the bitmap with self."""
        numer, denom = trust_level
        if denom == 0:
            raise ValueError("trustLevel has zero Denominator")
        total_mul, overflow = safe_mul(self.total_voting_power(), numer)
        if overflow:
            raise OverflowError(
                "int64 overflow while calculating voting power needed. "
                "please provide smaller trustLevel numerator"
            )
        needed = total_mul // denom

        if _is_aggregated(commit):
            return self._verify_aggregated_trusting(
                chain_id, commit, needed, commit_vals)

        idxs, val_idxs = self._trusting_candidates(commit)
        ok = self._batch_verify(chain_id, commit, idxs, key_idxs=val_idxs,
                                plane="light")
        self._replay_trusting(commit, idxs, val_idxs, ok, needed)

    def _trusting_candidates(self, commit):
        """``(commit rows, validator index of each)``: the for-block rows
        whose address this (trusted) set knows, in row order."""
        addr_idx = self._addr_index()
        val_of = np.fromiter(
            (addr_idx.get(cs.validator_address, -1)
             for cs in commit.signatures),
            dtype=np.intp, count=len(commit.signatures))
        idxs = np.flatnonzero(
            (commit.block_id_flags() == BlockIDFlag.COMMIT) & (val_of >= 0))
        return idxs, val_of[idxs]

    def _replay_trusting(self, commit, idxs: np.ndarray, val_idxs: np.ndarray,
                         ok: np.ndarray, needed: int) -> None:
        """VerifyCommitLightTrusting's loop over its candidates. It refuses
        a validator's second vote before it looks at that row's signature:
        read the rows before the first repeat as the light rule does, then
        let the repeat speak if the loop got that far."""
        _, first_at = np.unique(val_idxs, return_index=True)
        repeat = np.ones(len(idxs), dtype=bool)
        repeat[first_at] = False
        stop = int(np.argmax(repeat)) if repeat.any() else len(idxs)
        try:
            _replay_light(commit, idxs[:stop], ok[:stop],
                          self._verify_arrays()[1][val_idxs[:stop]], needed)
        except ErrNotEnoughVotingPowerSigned:
            if stop == len(idxs):
                raise
            val_idx = int(val_idxs[stop])
            earlier = int(idxs[np.flatnonzero(val_idxs[:stop] == val_idx)[0]])
            raise ValueError(
                f"double vote from {self.validators[val_idx]}: "
                f"({earlier} and {int(idxs[stop])})") from None

    def _check_commit_shape(self, commit, height: int, block_id: BlockID) -> None:
        # commit.size(): CommitSig rows for plain commits, signer-bitmap
        # length for aggregated ones — both must equal the set size
        if self.size() != commit.size():
            raise ErrInvalidCommitSignatures(self.size(), commit.size())
        if height != commit.height:
            raise ErrInvalidCommitHeight(height, commit.height)
        if block_id != commit.block_id:
            raise ValueError(
                f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
            )

    def _verify_aggregated(self, chain_id: str, commit,
                           mode: str = "full") -> None:
        """One fast-aggregate-verify replaces the per-signature batch: apk
        over the bitmap's pubkeys, pairing against the shared zero-timestamp
        sign-bytes. Error precedence mirrors the scalar replay — shape
        (caller), then signature (ErrWrongSignature), then the 2/3 tally
        (ErrNotEnoughVotingPowerSigned)."""
        from ..crypto.bls12381.vec import fast_aggregate_verify_routed

        _observe_aggregated_wire_size(commit)
        signer_idxs = commit.signers.true_indices()
        pks = [self.validators[i].pub_key.bytes() for i in signer_idxs]
        msg = commit.sign_message(chain_id)
        if not fast_aggregate_verify_routed(pks, msg, commit.agg_sig,
                                            mode=mode):
            raise ErrWrongSignature(-1, commit.agg_sig)
        tallied = sum(self.validators[i].voting_power for i in signer_idxs)
        needed = self.total_voting_power() * 2 // 3
        if tallied <= needed:
            raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    def _verify_aggregated_trusting(self, chain_id: str, commit, needed: int,
                                    commit_vals: "ValidatorSet") -> None:
        """Trusting-mode aggregate check: the pairing must run over the FULL
        bitmap (the aggregate covers every signer), keyed by the commit
        validator set; only the trusted intersection tallies toward the
        trust level."""
        from ..crypto.bls12381.vec import fast_aggregate_verify_routed

        if commit_vals is None:
            # self must BE the commit-height set then (e.g. evidence checks
            # against the recorded set); a size mismatch means it is not
            commit_vals = self
        if commit_vals.size() != commit.size():
            raise ErrInvalidCommitSignatures(commit_vals.size(), commit.size())
        _observe_aggregated_wire_size(commit)
        signer_idxs = commit.signers.true_indices()
        pks = [commit_vals.validators[i].pub_key.bytes() for i in signer_idxs]
        msg = commit.sign_message(chain_id)
        if not fast_aggregate_verify_routed(pks, msg, commit.agg_sig,
                                            mode="trusting"):
            raise ErrWrongSignature(-1, commit.agg_sig)
        addr_idx = self._addr_index()
        tallied = 0
        for i in signer_idxs:
            val_idx = addr_idx.get(commit_vals.validators[i].address)
            if val_idx is None:
                continue
            tallied += self.validators[val_idx].voting_power
            if tallied > needed:
                return
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    def _batch_verify(self, chain_id: str, commit, idxs: np.ndarray,
                      key_idxs: Optional[np.ndarray] = None,
                      plane: str = "votes") -> np.ndarray:
        """Verdicts of the commit rows ``idxs``, each against the key of
        validator ``key_idxs[pos]`` (the same index where None), in one
        batch. How the batch is built follows from what the commit shows:

        * more than a stream chunk of candidates, all of one sign-bytes
          length class (flags, timestamp varint widths), every key of the
          set ed25519: keys, signatures and the sign-bytes COLUMNS go in
          whole (BatchVerifier.add_columns) and no row is built;
        * anything else: rows, from the same vectorised builder
          (Commit.vote_sign_bytes_all) — up to a chunk the one-call
          program packs rows anyway;
        * up to 32 candidates: the per-index encoder, nothing memoized.
        """
        n = len(idxs)
        if n == 0:
            return np.zeros(0, dtype=bool)
        bv = BatchVerifier(plane=plane)
        sigs = commit.signatures
        rows_at = idxs.tolist()
        keys_at = rows_at if key_idxs is None else key_idxs.tolist()
        pks = self._verify_arrays()[0]
        cols = (commit.vote_sign_bytes_columns(chain_id, idxs)
                if n > STREAM_CHUNK and pks is not None else None)
        if cols is not None:
            if key_idxs is not None or n != len(pks):
                pks = [pks[k] for k in keys_at]
            bv.add_columns(pks, [sigs[i].signature for i in rows_at], cols)
        else:
            # amortized sign-bytes: one shared-field encode for the whole
            # commit instead of n canonical encodes
            sb = commit.vote_sign_bytes_all(chain_id) if n > 32 else None
            vals = self.validators
            for idx, k in zip(rows_at, keys_at):
                msg = (sb[idx] if sb is not None
                       else commit.vote_sign_bytes(chain_id, idx))
                bv.add(vals[k].pub_key, msg, sigs[idx].signature)
        return np.asarray(bv.verify()[1], dtype=bool)

    # -- proto ------------------------------------------------------------

    def _encoded_rows(self) -> bytes:
        """Field 1 of ``encode``, every validator's framed encoding, built
        once for as long as the set stays what it was: kept under
        _addr_index's rule (list identity, ``_mutations``, length) AND the
        proposer priorities the rows were built from, which rotate without
        a structural mutation, so they are compared themselves on every
        use (whoever wrote them, a mutator or a caller). ``copy()`` carries
        the kept rows: a State's three sets are copies of one another a
        height apart, so a set is encoded once in its life, not once per
        State record it appears in. One pass from the rows' prefixes
        (address, key, power: kept apart under the structural rule alone,
        since priorities rotate every height and they do not) and the
        priority varints, no Writer per row."""
        vals = self.validators
        prios = tuple(map(_PRIORITY_OF, vals))
        rows = self._kept("_rows_cache")
        if rows is not None and rows[0] == prios:
            encode_stats["valset_encodes_reused"] += 1
            return rows[1]
        encode_stats["valset_encodes_built"] += 1
        prefixes = self._memo(
            "_prefix_cache", lambda: [v.encode_prefix() for v in vals])
        varint = pw.encode_varint
        body = pw.repeated_message(1, [
            prefix + PRIORITY_TAG + varint(pp) if pp else prefix
            for prefix, pp in zip(prefixes, prios)])
        self._keep("_rows_cache", (prios, body))
        return body

    def encode(self) -> bytes:
        w = pw.Writer()
        if self.proposer is not None:
            w.message(2, self.proposer.encode())
        w.varint(3, self.total_voting_power())
        return self._encoded_rows() + w.finish()

    @staticmethod
    def decode(data: bytes) -> "ValidatorSet":
        vs = ValidatorSet()
        for fn, _wt, v in pw.iter_fields(data):
            if fn == 1:
                vs.validators.append(Validator.decode(v))
            elif fn == 2:
                vs.proposer = Validator.decode(v)
        vs._total_voting_power = None
        vs._bump_mutations()
        return vs


def verify_commit_light_batched(
    entries: Sequence[Tuple["ValidatorSet", str, BlockID, int, object]],
) -> List[Optional[Exception]]:
    """Window-batched VerifyCommitLight: many (valset, commit) pairs, ONE
    device call.

    The fast-sync replay path (reference blockchain/v0/reactor.go:255 verifies
    one commit per loop iteration) is the TPU batch opportunity: all candidate
    signatures across a window of contiguous blocks go to the device together,
    then each commit's scalar precedence loop — including the 2/3 early exit —
    is replayed over its verdict slice. Per-entry outcome is None (ok) or the
    exact exception verify_commit_light would have raised.

    Entries: (val_set, chain_id, block_id, height, commit).
    """
    bv = BatchVerifier(plane="light")
    slices: List[Tuple[int, Sequence[int]]] = []  # (batch offset, candidate idxs)
    shape_errors: List[Optional[Exception]] = []
    agg_done: dict = {}  # entry position -> result for aggregated commits
    off = 0
    for pos_e, (val_set, chain_id, block_id, height, commit) in enumerate(entries):
        if _is_aggregated(commit):
            # already one pairing per commit — nothing to fold into the
            # ed25519 batch; verify inline and record the outcome
            try:
                val_set.verify_commit_light(chain_id, block_id, height, commit)
                agg_done[pos_e] = None
            except Exception as e:
                agg_done[pos_e] = e
            shape_errors.append(None)
            slices.append((off, []))
            continue
        try:
            val_set._check_commit_shape(commit, height, block_id)
        except Exception as e:  # shape errors surface per-entry, not batch-wide
            shape_errors.append(e)
            slices.append((off, []))
            continue
        shape_errors.append(None)
        idxs = np.flatnonzero(commit.block_id_flags() == BlockIDFlag.COMMIT)
        sb = commit.vote_sign_bytes_all(chain_id)
        vals = val_set.validators
        for idx in idxs.tolist():
            bv.add(vals[idx].pub_key, sb[idx], commit.signatures[idx].signature)
        slices.append((off, idxs))
        off += len(idxs)
    per_item = np.asarray(bv.verify()[1], dtype=bool)

    results: List[Optional[Exception]] = []
    for pos_e, (entry, shape_err, (start, idxs)) in enumerate(
            zip(entries, shape_errors, slices)):
        if pos_e in agg_done:
            results.append(agg_done[pos_e])
            continue
        if shape_err is not None:
            results.append(shape_err)
            continue
        val_set, commit = entry[0], entry[4]
        try:
            _replay_light(commit, idxs, per_item[start:start + len(idxs)],
                          val_set._verify_arrays()[1][idxs],
                          val_set.total_voting_power() * 2 // 3)
            results.append(None)
        except (ErrWrongSignature, ErrNotEnoughVotingPowerSigned) as e:
            results.append(e)
    return results


def verify_commit_light_trusting_batched(
    entries: Sequence[Tuple["ValidatorSet", str, object, "Fraction"]],
) -> List[Optional[Exception]]:
    """Window-batched VerifyCommitLightTrusting: the light client's bisection
    walk verifies a chain of headers against a *trusted* set
    (validator_set.go:775, light/verifier.go:32) — all candidate signatures
    across the window ride one batched device call, then each commit's
    scalar precedence loop (address lookup, duplicate-vote check, trust-level
    tally with early exit) replays over its verdict slice.

    Entries: (trusted_val_set, chain_id, commit, trust_level) or, for
    aggregated commits crossing a valset change, the 5-tuple
    (..., commit_vals) carrying the commit-height validator set — the
    bitmap indexes into THAT set, so the pairing needs it whenever it
    differs from the trusted set (mirrors light/verifier.py
    verify_non_adjacent).  Per-entry outcome is None (ok) or the exact
    exception verify_commit_light_trusting would have raised.
    """
    bv = BatchVerifier(plane="light")
    slices: List[Tuple[int, tuple]] = []  # (batch offset, candidates)
    pre_errors: List[Optional[Exception]] = []
    needed_list: List[int] = []
    agg_done: dict = {}  # entry position -> result for aggregated commits
    off = 0
    for pos_e, entry in enumerate(entries):
        val_set, chain_id, commit, trust_level = entry[:4]
        if _is_aggregated(commit):
            commit_vals = entry[4] if len(entry) > 4 else None
            try:
                val_set.verify_commit_light_trusting(chain_id, commit,
                                                     trust_level,
                                                     commit_vals=commit_vals)
                agg_done[pos_e] = None
            except Exception as e:
                agg_done[pos_e] = e
            pre_errors.append(None)
            slices.append((off, []))
            needed_list.append(0)
            continue
        numer, denom = trust_level
        if denom == 0:
            pre_errors.append(ValueError("trustLevel has zero Denominator"))
            slices.append((off, []))
            needed_list.append(0)
            continue
        total_mul, overflow = safe_mul(val_set.total_voting_power(), numer)
        if overflow:
            pre_errors.append(OverflowError(
                "int64 overflow while calculating voting power needed. "
                "please provide smaller trustLevel numerator"
            ))
            slices.append((off, []))
            needed_list.append(0)
            continue
        pre_errors.append(None)
        needed_list.append(total_mul // denom)
        sb = commit.vote_sign_bytes_all(chain_id)
        idxs, val_idxs = val_set._trusting_candidates(commit)
        vals = val_set.validators
        for idx, val_idx in zip(idxs.tolist(), val_idxs.tolist()):
            bv.add(vals[val_idx].pub_key, sb[idx],
                   commit.signatures[idx].signature)
        slices.append((off, (idxs, val_idxs)))
        off += len(idxs)
    per_item = np.asarray(bv.verify()[1], dtype=bool)

    results: List[Optional[Exception]] = []
    for pos_e, (entry, pre_err, (start, cand), needed) in enumerate(zip(
            entries, pre_errors, slices, needed_list)):
        if pos_e in agg_done:
            results.append(agg_done[pos_e])
            continue
        if pre_err is not None:
            results.append(pre_err)
            continue
        idxs, val_idxs = cand
        try:
            entry[0]._replay_trusting(
                entry[2], idxs, val_idxs,
                per_item[start:start + len(idxs)], needed)
            results.append(None)
        except (ErrWrongSignature, ErrNotEnoughVotingPowerSigned,
                ValueError) as e:
            results.append(e)
    return results


def _process_changes(changes: List[Validator]) -> Tuple[List[Validator], List[Validator]]:
    """Sort by address, reject dups/negatives, split updates/removals
    (validator_set.go:373)."""
    ordered = sorted(changes, key=lambda v: v.address)
    updates: List[Validator] = []
    removals: List[Validator] = []
    prev_addr = None
    for u in ordered:
        if u.address == prev_addr:
            raise ValueError(f"duplicate entry {u} in {ordered}")
        if u.voting_power < 0:
            raise ValueError(f"voting power can't be negative: {u.voting_power}")
        if u.voting_power > MAX_TOTAL_VOTING_POWER:
            raise ValueError(
                f"to prevent clipping/overflow, voting power can't be higher than "
                f"{MAX_TOTAL_VOTING_POWER}, got {u.voting_power}"
            )
        (removals if u.voting_power == 0 else updates).append(u)
        prev_addr = u.address
    return updates, removals
