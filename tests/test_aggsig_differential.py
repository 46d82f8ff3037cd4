"""Differential accept/reject parity: the SAME commit scenario, built on an
ed25519 chain (CommitSig list, batched per-signature verify) and on a BLS
aggregated chain (signer bitmap + one 48-byte aggregate, one pairing), must
produce the same verdict from every verify_commit* mode.  Plus the two
scheme-plane invariants that frame the A/B: default chains stay
byte-identical to the pre-scheme-plane artifacts, and BLS keys enter a
validator set only through the proof-of-possession gate."""

import pytest

from tendermint_tpu import crypto
from tendermint_tpu.crypto import schemes
from tendermint_tpu.crypto import bls12381 as bls
from tendermint_tpu.libs.bits import BitArray
from tendermint_tpu.types import (
    GenesisDoc,
    GenesisValidator,
    MockPV,
    Validator,
    ValidatorSet,
)
from tendermint_tpu.types.basic import (
    BlockID,
    BlockIDFlag,
    PartSetHeader,
    SignedMsgType,
)
from tendermint_tpu.types.block import AggregatedCommit, Commit, CommitSig
from tendermint_tpu.types.errors import (
    ErrNotEnoughVotingPowerSigned,
    ErrWrongSignature,
)
from tendermint_tpu.types.params import (
    ConsensusParams,
    SignatureParams,
    ValidatorParams,
)
from tendermint_tpu.types.vote import Vote
from tendermint_tpu.types.vote_set import VoteSet

N = 6
HEIGHT = 9
BID = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
NIL = BlockID()


class Rig:
    """One chain: privvals, validator set, and a commit builder."""

    def __init__(self, chain_id, scheme):
        self.chain_id = chain_id
        if scheme == "bls12381":
            schemes.register_chain(chain_id, SignatureParams("bls12381", True))
            self.pvs = [MockPV(crypto.Bls12381PrivKey.generate(
                b"diff" + bytes([i]) * 4)) for i in range(N)]
        else:
            self.pvs = [MockPV(crypto.Ed25519PrivKey.generate(
                bytes([0x40 + i]) * 32)) for i in range(N)]
        self.val_set = ValidatorSet([
            Validator(pv.get_pub_key().address(), pv.get_pub_key(), 10)
            for pv in self.pvs])
        # MockPV order != address-sorted set order: map pv -> set index
        self.idx_of = {pv.get_pub_key().address():
                       self.val_set.get_by_address(
                           pv.get_pub_key().address())[0]
                       for pv in self.pvs}

    def make_commit(self, block_voters, nil_voters=()):
        """Assemble via the real VoteSet path (what consensus runs)."""
        vs = VoteSet(self.chain_id, HEIGHT, 0, SignedMsgType.PRECOMMIT,
                     self.val_set)
        for pv in self.pvs:
            addr = pv.get_pub_key().address()
            idx = self.idx_of[addr]
            if idx in block_voters:
                bid = BID
            elif idx in nil_voters:
                bid = NIL
            else:
                continue
            v = Vote(SignedMsgType.PRECOMMIT, HEIGHT, 0, bid,
                     1_700_000_000_000_000_000 + idx, addr, idx, b"")
            pv.sign_vote(self.chain_id, v)
            added = vs.add_vote(v)
            assert added, (self.chain_id, idx)
        return vs.make_commit()

    def verify_all_modes(self, commit):
        self.val_set.verify_commit(self.chain_id, BID, HEIGHT, commit)
        self.val_set.verify_commit_light(self.chain_id, BID, HEIGHT, commit)
        self.val_set.verify_commit_light_trusting(
            self.chain_id, commit, (1, 3), commit_vals=self.val_set)


@pytest.fixture
def rigs():
    try:
        yield Rig("diff-ed", "ed25519"), Rig("diff-bls", "bls12381")
    finally:
        schemes.reset()
        bls.reset()


def _rejects(fn, *errs):
    with pytest.raises(errs or (ErrWrongSignature,
                                ErrNotEnoughVotingPowerSigned)):
        fn()


def test_valid_full_commit_accepted_by_both(rigs):
    ed, bl = rigs
    all_idx = set(range(N))
    c_ed = ed.make_commit(all_idx)
    c_bl = bl.make_commit(all_idx)
    assert not hasattr(c_ed, "agg_sig")
    assert hasattr(c_bl, "agg_sig")
    ed.verify_all_modes(c_ed)
    bl.verify_all_modes(c_bl)
    # and the aggregated wire form is a fraction of the CommitSig list
    assert len(c_bl.encode()) < len(c_ed.encode()) / 3


def test_one_bad_signature_rejected_by_both(rigs):
    ed, bl = rigs
    c_ed = ed.make_commit(set(range(N)))
    cs = c_ed.signatures[0]
    c_ed.signatures[0] = CommitSig(cs.block_id_flag, cs.validator_address,
                                   cs.timestamp_ns, bytes(64))
    _rejects(lambda: ed.val_set.verify_commit(ed.chain_id, BID, HEIGHT, c_ed),
             ErrWrongSignature)

    c_bl = bl.make_commit(set(range(N)))
    c_bl = AggregatedCommit(
        c_bl.height, c_bl.round, c_bl.block_id, [], signers=c_bl.signers,
        agg_sig=bytes([c_bl.agg_sig[0] ^ 0x01]) + c_bl.agg_sig[1:],
        timestamp_ns=c_bl.timestamp_ns)
    _rejects(lambda: bl.val_set.verify_commit(bl.chain_id, BID, HEIGHT, c_bl),
             ErrWrongSignature)


def test_sub_quorum_rejected_by_both(rigs):
    """3/6 of the power behind the block (50% <= 2/3): both planes must
    reject, whatever error-shape each one raises first."""
    ed, bl = rigs
    voters = {0, 1, 2}
    # VoteSet refuses to even assemble without maj23 — build directly, the
    # shape a byzantine proposer could ship
    sigs = []
    for idx in range(N):
        if idx not in voters:
            sigs.append(CommitSig.new_absent())
            continue
        pv = next(p for p in ed.pvs
                  if ed.idx_of[p.get_pub_key().address()] == idx)
        v = Vote(SignedMsgType.PRECOMMIT, HEIGHT, 0, BID,
                 1_700_000_000_000_000_000, pv.get_pub_key().address(),
                 idx, b"")
        pv.sign_vote(ed.chain_id, v)
        sigs.append(CommitSig.new_for_block(v.signature, v.validator_address,
                                            v.timestamp_ns))
    c_ed = Commit(HEIGHT, 0, BID, sigs)
    _rejects(lambda: ed.val_set.verify_commit(ed.chain_id, BID, HEIGHT, c_ed),
             ErrNotEnoughVotingPowerSigned)

    bls_sigs, signers = [], BitArray(N)
    for idx in sorted(voters):
        pv = next(p for p in bl.pvs
                  if bl.idx_of[p.get_pub_key().address()] == idx)
        v = Vote(SignedMsgType.PRECOMMIT, HEIGHT, 0, BID,
                 1_700_000_000_000_000_000, pv.get_pub_key().address(),
                 idx, b"")
        pv.sign_vote(bl.chain_id, v)
        bls_sigs.append(v.signature)
        signers.set_index(idx, True)
    c_bl = AggregatedCommit(HEIGHT, 0, BID, [], signers=signers,
                            agg_sig=bls.aggregate(bls_sigs),
                            timestamp_ns=1_700_000_000_000_000_000)
    _rejects(lambda: bl.val_set.verify_commit(bl.chain_id, BID, HEIGHT, c_bl),
             ErrNotEnoughVotingPowerSigned)
    # the FULL set's aggregate under that sub-quorum bitmap: whichever
    # check sees it first (pairing or tally), it is a rejection
    full = bl.make_commit(set(range(N)))
    c_mis = AggregatedCommit(HEIGHT, 0, BID, [], signers=signers,
                             agg_sig=full.agg_sig,
                             timestamp_ns=full.timestamp_ns)
    _rejects(lambda: bl.val_set.verify_commit(bl.chain_id, BID, HEIGHT,
                                              c_mis))


def test_duplicate_signer_rejected_by_both(rigs):
    """One validator's signature occupying two slots: slot 1's pubkey can't
    verify slot 0's vote on the ed side; on the BLS side the bitmap claims a
    key whose signature is not in the aggregate, so the pairing fails."""
    ed, bl = rigs
    c_ed = ed.make_commit(set(range(N)))
    dup = c_ed.signatures[0]
    c_ed.signatures[1] = CommitSig(dup.block_id_flag, dup.validator_address,
                                   dup.timestamp_ns, dup.signature)
    _rejects(lambda: ed.val_set.verify_commit(ed.chain_id, BID, HEIGHT, c_ed))

    msg_sigs = {}
    for pv in bl.pvs:
        idx = bl.idx_of[pv.get_pub_key().address()]
        v = Vote(SignedMsgType.PRECOMMIT, HEIGHT, 0, BID,
                 1_700_000_000_000_000_000, pv.get_pub_key().address(),
                 idx, b"")
        pv.sign_vote(bl.chain_id, v)
        msg_sigs[idx] = v.signature
    # fold validator 0 in twice, drop validator 1, but leave 1's bit set
    doubled = [msg_sigs[0], msg_sigs[0]] + [msg_sigs[i] for i in range(2, N)]
    signers = BitArray(N)
    for i in range(N):
        signers.set_index(i, True)
    c_bl = AggregatedCommit(HEIGHT, 0, BID, [], signers=signers,
                            agg_sig=bls.aggregate(doubled),
                            timestamp_ns=1_700_000_000_000_000_000)
    _rejects(lambda: bl.val_set.verify_commit(bl.chain_id, BID, HEIGHT, c_bl),
             ErrWrongSignature)


def test_nil_vote_mix_parity(rigs):
    """5 block + 1 nil (50/60 > 40 needed): both accept — the ed plane
    verifies the nil signature without tallying it, the BLS plane leaves the
    nil voter out of the bitmap.  4 block + 2 nil (40 <= 40): both reject."""
    ed, bl = rigs
    ed.verify_all_modes(ed.make_commit(set(range(5)), nil_voters={5}))
    bl.verify_all_modes(bl.make_commit(set(range(5)), nil_voters={5}))

    # 4 block + 2 nil never reaches +2/3, so the VoteSet refuses to even
    # assemble it — build the commits directly, as a byzantine proposer would
    def signed_vote(rig, pv, idx, bid):
        v = Vote(SignedMsgType.PRECOMMIT, HEIGHT, 0, bid,
                 1_700_000_000_000_000_000, pv.get_pub_key().address(),
                 idx, b"")
        pv.sign_vote(rig.chain_id, v)
        return v

    sigs = [None] * N
    for pv in ed.pvs:
        idx = ed.idx_of[pv.get_pub_key().address()]
        v = signed_vote(ed, pv, idx, BID if idx < 4 else NIL)
        sigs[idx] = CommitSig(
            BlockIDFlag.COMMIT if idx < 4 else BlockIDFlag.NIL,
            v.validator_address, v.timestamp_ns, v.signature)
    c_ed = Commit(HEIGHT, 0, BID, sigs)
    _rejects(lambda: ed.val_set.verify_commit(ed.chain_id, BID, HEIGHT, c_ed),
             ErrNotEnoughVotingPowerSigned)

    bls_sigs, signers = [], BitArray(N)
    for pv in bl.pvs:
        idx = bl.idx_of[pv.get_pub_key().address()]
        if idx >= 4:
            continue  # nil voters stay out of the bitmap
        bls_sigs.append(signed_vote(bl, pv, idx, BID).signature)
        signers.set_index(idx, True)
    c_bl = AggregatedCommit(HEIGHT, 0, BID, [], signers=signers,
                            agg_sig=bls.aggregate(bls_sigs),
                            timestamp_ns=1_700_000_000_000_000_000)
    _rejects(lambda: bl.val_set.verify_commit(bl.chain_id, BID, HEIGHT, c_bl),
             ErrNotEnoughVotingPowerSigned)


def test_param_off_artifacts_are_byte_identical():
    """A chain that never opts in must produce EXACTLY the pre-scheme-plane
    bytes: no genesis JSON section, plain Commit from the VoteSet, and an
    unregistered chain id resolves to the ed25519 default."""
    assert schemes.for_chain("never-registered").is_default
    assert not schemes.aggregated("never-registered")

    pvs = [MockPV(crypto.Ed25519PrivKey.generate(bytes([0x50 + i]) * 32))
           for i in range(4)]
    gen = GenesisDoc(
        chain_id="plain-chain", genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(pv.get_pub_key(), 10) for pv in pvs])
    gen.validate_and_complete()
    js = gen.to_json()
    assert '"signature"' not in js
    assert "bls" not in js
    # and the JSON round-trips without inventing a scheme section
    assert '"signature"' not in GenesisDoc.from_json(js).to_json()

    val_set = ValidatorSet([
        Validator(pv.get_pub_key().address(), pv.get_pub_key(), 10)
        for pv in pvs])
    vs = VoteSet("plain-chain", HEIGHT, 0, SignedMsgType.PRECOMMIT, val_set)
    for pv in pvs:
        addr = pv.get_pub_key().address()
        idx, _ = val_set.get_by_address(addr)
        v = Vote(SignedMsgType.PRECOMMIT, HEIGHT, 0, BID,
                 1_700_000_000_000_000_000 + idx, addr, idx, b"")
        pv.sign_vote("plain-chain", v)
        assert vs.add_vote(v)
    commit = vs.make_commit()
    assert type(commit) is Commit
    assert not hasattr(commit, "agg_sig")
    rt = Commit.decode(commit.encode())
    assert rt.encode() == commit.encode()
    assert type(rt) is Commit


def test_validator_update_pop_gate_rogue_key_regression():
    """The genesis PoP gate must also cover keys entering via ABCI validator
    updates (EndBlock/InitChain): on an aggregated chain with a dynamic
    validator set, an unchecked admission is exactly the rogue-key attack
    surface — pk* - sum(honest pks) would forge fast-aggregate commits."""
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.state.execution import validate_validator_updates

    params = ConsensusParams(validator=ValidatorParams(["bls12381"]),
                             signature=SignatureParams("bls12381", True))
    try:
        k1 = crypto.Bls12381PrivKey.generate(b"upd" + b"\x01" * 4)
        k2 = crypto.Bls12381PrivKey.generate(b"upd" + b"\x02" * 4)

        validate_validator_updates(
            [abci.ValidatorUpdate("bls12381", k1.pub_key().bytes(), 10,
                                  pop=k1.pop())], params)
        assert bls.is_registered(k1.pub_key().bytes())

        # no pop → refused, never registered
        with pytest.raises(ValueError, match="proof of possession"):
            validate_validator_updates(
                [abci.ValidatorUpdate("bls12381", k2.pub_key().bytes(), 10)],
                params)
        # a pop lifted from ANOTHER key must not stand in
        with pytest.raises(ValueError, match="proof of possession"):
            validate_validator_updates(
                [abci.ValidatorUpdate("bls12381", k2.pub_key().bytes(), 10,
                                      pop=k1.pop())], params)
        assert not bls.is_registered(k2.pub_key().bytes())

        # deletion (power 0) needs no pop
        validate_validator_updates(
            [abci.ValidatorUpdate("bls12381", k2.pub_key().bytes(), 0)],
            params)

        # an already-registered key STILL needs its pop on later updates:
        # the verdict must not depend on in-process registration state
        # (a restarted node has an empty set and must agree)
        with pytest.raises(ValueError, match="proof of possession"):
            validate_validator_updates(
                [abci.ValidatorUpdate("bls12381", k1.pub_key().bytes(), 20)],
                params)
        validate_validator_updates(
            [abci.ValidatorUpdate("bls12381", k1.pub_key().bytes(), 20,
                                  pop=k1.pop())], params)
    finally:
        bls.reset()


def test_validator_update_pop_wire_roundtrip():
    """The pop field survives the ABCI proto codec (ResponseEndBlock)."""
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.proto_codec import decode_response, encode_response
    from tendermint_tpu.libs import protowire as pw

    k = crypto.Bls12381PrivKey.generate(b"wire" + b"\x03" * 4)
    resp = abci.ResponseEndBlock(validator_updates=[
        abci.ValidatorUpdate("bls12381", k.pub_key().bytes(), 7, pop=k.pop()),
        abci.ValidatorUpdate("ed25519", b"\x11" * 32, 3),  # pop absent
    ])
    frame, _ = pw.read_length_delimited(encode_response("end_block", resp))
    _method, rt = decode_response(frame)
    assert rt.validator_updates[0].pop == k.pop()
    assert rt.validator_updates[0].pub_key_bytes == k.pub_key().bytes()
    assert rt.validator_updates[1].pop == b""


def test_aggregated_commit_time_window():
    """timestamp_ns is covered by no signature, so each validator bounds it
    subjectively before prevoting (consensus.state.check_aggregated_commit_time):
    within drift of its own recorded precommit times, never ahead of the
    local clock by more than drift."""
    from tendermint_tpu.consensus.state import check_aggregated_commit_time

    now = 1_700_000_000_000_000_000
    drift = 10_000_000_000  # 10s
    commit = AggregatedCommit(HEIGHT, 0, BID, [], signers=BitArray(N),
                              agg_sig=b"\x01" * 48, timestamp_ns=now)

    # in-window vs recorded precommit times
    seen = [now - 2_000_000_000, now, now + 1_000_000_000]
    check_aggregated_commit_time(commit, seen, now, drift)
    # no recorded votes (catching up): only the clock bound applies
    check_aggregated_commit_time(commit, [], now, drift)

    # proposer-invented future time: beyond clock drift
    commit.timestamp_ns = now + drift + 1
    with pytest.raises(ValueError, match="ahead of local time"):
        check_aggregated_commit_time(commit, seen, now, drift)

    # inside clock drift but outside the recorded-precommit window
    commit.timestamp_ns = now + drift - 1
    with pytest.raises(ValueError, match="outside the window"):
        check_aggregated_commit_time(commit, [now - 30_000_000_000], now, drift)
    # ... and a past time far below anything we saw is refused too
    commit.timestamp_ns = now - 60_000_000_000
    with pytest.raises(ValueError, match="outside the window"):
        check_aggregated_commit_time(commit, seen, now, drift)


def test_trusting_batched_aggregated_commit_vals_across_valset_change():
    """Aggregated entries of verify_commit_light_trusting_batched may carry
    the commit-height validator set as a 5th tuple element: whenever the
    trusted set differs from the commit's signer bitmap (any valset change
    between trusted and commit height) the pairing needs THAT set, exactly
    like the non-batched path with commit_vals (light/verifier.py
    verify_non_adjacent)."""
    from tendermint_tpu.types.canonical import vote_sign_bytes as vsb
    from tendermint_tpu.types.errors import ErrInvalidCommitSignatures
    from tendermint_tpu.types.validator_set import (
        verify_commit_light_trusting_batched,
    )

    try:
        trust = (1, 3)
        pks = [crypto.Bls12381PrivKey.generate(b"lbat" + bytes([i]) * 4)
               for i in range(5)]
        commit_vals = ValidatorSet([
            Validator(k.pub_key().address(), k.pub_key(), 10) for k in pks])
        msg = vsb("agg-batched", SignedMsgType.PRECOMMIT, HEIGHT, 0, BID, 0)
        signers = BitArray(5)
        for i in range(5):
            signers.set_index(i, True)
        commit = AggregatedCommit(
            HEIGHT, 0, BID, [], signers=signers,
            agg_sig=bls.aggregate([k.sign(msg) for k in pks]),
            timestamp_ns=1_700_000_000_000_000_000)

        # trusted set = commit set minus one validator: a different size,
        # the shape every bisection step with a valset change produces
        trusted = ValidatorSet([
            Validator(k.pub_key().address(), k.pub_key(), 10)
            for k in pks[:4]])

        # plain ed25519 entry rides the same batch, unaffected
        ed = Rig("agg-batched-ed", "ed25519")
        ed_commit = ed.make_commit(set(range(N)))

        results = verify_commit_light_trusting_batched([
            (trusted, "agg-batched", commit, trust, commit_vals),
            (ed.val_set, ed.chain_id, ed_commit, trust),
            (trusted, "agg-batched", commit, trust),  # no commit_vals: size mismatch
        ])
        assert results[0] is None
        assert results[1] is None
        assert isinstance(results[2], ErrInvalidCommitSignatures)

        # exact parity with the sequential path, both ways
        trusted.verify_commit_light_trusting("agg-batched", commit, trust,
                                             commit_vals=commit_vals)
        with pytest.raises(ErrInvalidCommitSignatures):
            trusted.verify_commit_light_trusting("agg-batched", commit, trust)
    finally:
        schemes.reset()
        bls.reset()


def test_genesis_pop_gate_rogue_key_regression():
    """A BLS validator enters genesis only with a proof of possession for
    ITS key: a missing pop, a replayed pop, and a wrong-scheme key must all
    refuse validate_and_complete."""
    try:
        pks = [crypto.Bls12381PrivKey.generate(b"gen" + bytes([i]) * 4)
               for i in range(4)]
        params = ConsensusParams(
            validator=ValidatorParams(["bls12381"]),
            signature=SignatureParams("bls12381", True))

        def gen(validators):
            return GenesisDoc(chain_id="bls-gen",
                              genesis_time_ns=1_700_000_000_000_000_000,
                              consensus_params=params, validators=validators)

        good = [GenesisValidator(k.pub_key(), 10, pop=k.pop()) for k in pks]
        gen(good).validate_and_complete()
        for k in pks:
            assert bls.is_registered(k.pub_key().bytes())

        bls.reset()
        missing = [GenesisValidator(pks[0].pub_key(), 10)]
        with pytest.raises(ValueError, match="proof of possession"):
            gen(missing).validate_and_complete()

        # the rogue-key shape: an attacker who computed a key to cancel the
        # honest apk cannot also produce a pop (no knowledge of its sk) —
        # a pop lifted from ANOTHER key must not stand in
        bls.reset()
        replayed = [GenesisValidator(pks[0].pub_key(), 10, pop=pks[0].pop()),
                    GenesisValidator(pks[1].pub_key(), 10, pop=pks[0].pop())]
        with pytest.raises(ValueError, match="possession"):
            gen(replayed).validate_and_complete()
        assert not bls.is_registered(pks[1].pub_key().bytes())

        bls.reset()
        wrong_scheme = [GenesisValidator(
            crypto.Ed25519PrivKey.generate(b"\x01" * 32).pub_key(), 10)]
        with pytest.raises(ValueError, match="bls12381"):
            gen(wrong_scheme).validate_and_complete()
    finally:
        schemes.reset()
        bls.reset()
