"""The columnar commit entry (PR 27): a uniform commit goes from its CommitSig
fields to the packer as arrays.

* the ONE vectorised sign-bytes builder (types/canonical.vote_sign_bytes_table
  behind vote_sign_bytes_batch / _columns_batch and Commit.vote_sign_bytes_*)
  against the scalar encoder, index by index;
* the three VerifyCommit* entries through the columnar way into the verifier
  (BatchVerifier.add_columns), against the reference's scalar loops written
  out below: accept / reject and every exception's type and fields;
* every way back to rows (a key that is not ed25519, a device error, an open
  breaker, a precomputed-verdict scope, the host backend) with the same
  answer;
* nothing survives a request but the validator set's own arrays.

No device program is built: the stream seam is stood in (``device_standin``,
tests/conftest.py) with the host spec's verdicts, from OpenSSL where the
image has it. The precedence tests lower the stream chunk to 256 signatures
so that 300 validators take the path 10,000 take at 2,048.
"""

import hashlib
from dataclasses import replace

import numpy as np
import pytest

from tendermint_tpu import crypto
from tendermint_tpu.crypto import batch as B
from tendermint_tpu.crypto import schemes
from tendermint_tpu.crypto.breaker import device_breaker
from tendermint_tpu.libs.faults import faults
from tendermint_tpu.types import validator_set as VS
from tendermint_tpu.types.basic import (
    ZERO_TIME_NS,
    BlockID,
    BlockIDFlag,
    PartSetHeader,
    SignedMsgType,
)
from tendermint_tpu.types.block import Commit, CommitSig
from tendermint_tpu.types.canonical import (
    vote_sign_bytes,
    vote_sign_bytes_batch,
    vote_sign_bytes_columns_batch,
)
from tendermint_tpu.types.errors import (
    ErrNotEnoughVotingPowerSigned,
    ErrWrongSignature,
)
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet

CHAIN = "columnar-chain"
BID = BlockID(b"\x31" * 32, PartSetHeader(2, b"\x32" * 32))
NIL = BlockID()
SEC = 1_700_000_000  # a commit's second; nanos vary row to row
PRE = SignedMsgType.PRECOMMIT


# -- the builder, against the scalar encoder -----------------------------------

def _ts(nanos, seconds=SEC):
    return [seconds * 1_000_000_000 + n for n in nanos]


def _step(lo, n, hi):
    """n nanos from lo upward, all below hi (one varint length)."""
    step = max(1, (hi - lo) // n)
    out = [lo + i * step for i in range(n)]
    assert out[-1] < hi
    return out


# name -> (height, round, [(block id, timestamp)], columns expected)
BUILDER_CASES = {
    "nanos_1_byte": (9, 1, [(BID, t) for t in _ts(_step(1, 100, 1 << 7))], True),
    "nanos_2_bytes": (9, 1, [(BID, t) for t in _ts(_step(1 << 7, 300, 1 << 14))], True),
    "nanos_3_bytes": (9, 1, [(BID, t) for t in _ts(_step(1 << 14, 300, 1 << 21))], True),
    "nanos_4_bytes": (9, 1, [(BID, t) for t in _ts(_step(1 << 21, 300, 1 << 28))], True),
    "nanos_5_bytes": (9, 1, [(BID, t) for t in _ts(_step(1 << 28, 300, 10**9))], True),
    "nanos_0": (9, 1, [(BID, t) for t in _ts([0] * 40)], True),
    "seconds_0": (9, 1, [(BID, t) for t in _ts(_step(1 << 28, 50, 10**9), seconds=0)], True),
    "seconds_0_nanos_0": (9, 1, [(BID, 0)] * 5, True),
    "height_0_round_0": (0, 0, [(BID, t) for t in _ts(_step(1 << 28, 50, 10**9))], True),
    "before_the_epoch": (9, 1, [(BID, -5 * 10**9 + n) for n in _step(1 << 28, 50, 10**9)], True),
    "seconds_past_32_bits": (9, 1, [(BID, t) for t in _ts(_step(1 << 28, 50, 10**9), seconds=1 << 33)], True),
    "all_nil": (9, 1, [(NIL, t) for t in _ts(_step(1 << 28, 50, 10**9))], True),
    "ragged_nanos": (9, 1, [(BID, t) for t in _ts(
        [5, 1 << 7, 1 << 14, 1 << 21, 1 << 28, 0, 999_999_999] * 9)], False),
    "ragged_seconds": (9, 1, [(BID, 5), (BID, SEC * 10**9 + 5), (BID, 5 + 200 * 10**9)], False),
    "nil_votes_mixed": (9, 1, [(NIL if i % 7 == 3 else BID, t) for i, t in enumerate(
        _ts(_step(1 << 28, 120, 10**9)))], False),
    "absent_rows": (9, 1, [(NIL, ZERO_TIME_NS) if i % 5 == 0 else (BID, t)
                           for i, t in enumerate(_ts(_step(1 << 28, 120, 10**9)))], False),
    "past_int64_nanoseconds": (9, 1, [(BID, ZERO_TIME_NS), (BID, -(1 << 63)), (BID, (1 << 63) - 1),
                                      (BID, 1 << 63)], False),
    "past_int64_seconds": (9, 1, [(BID, 1 << 100), (NIL, 5)], False),
    "rows_1": (9, 1, [(BID, t) for t in _ts(_step(1 << 28, 1, 10**9))], True),
    "rows_33": (9, 1, [(BID, t) for t in _ts(_step(1 << 28, 33, 10**9))], True),
    "rows_2049": (9, 1, [(BID, t) for t in _ts(_step(1 << 28, 2049, 10**9))], True),
    "rows_10000": (9, 1, [(BID, t) for t in _ts(_step(1 << 28, 10_000, 10**9))], True),
}


@pytest.mark.parametrize("name", list(BUILDER_CASES))
def test_builder_matches_the_scalar_encoder(name):
    height, round_, rows, uniform = BUILDER_CASES[name]
    bids = [b for b, _ in rows]
    ts = [t for _, t in rows]
    want = [vote_sign_bytes(CHAIN, PRE, height, round_, b, t) for b, t in rows]
    assert vote_sign_bytes_batch(CHAIN, PRE, height, round_, bids, ts) == want
    cols = vote_sign_bytes_columns_batch(CHAIN, PRE, height, round_, bids, ts)
    assert (cols is not None) == uniform
    if uniform:
        assert len(cols) == len(rows) and cols.rows() == want
        assert cols[len(rows) - 1] == want[-1]
        # the template is the first row, the columns exactly where rows differ
        assert cols.template.tobytes() == want[0]
        arr = np.frombuffer(b"".join(want), np.uint8).reshape(len(want), -1)
        assert cols.cols.tolist() == np.flatnonzero(
            (arr != arr[0]).any(axis=0)).tolist()


def _commit_of(rows, height=9, round_=1):
    """A Commit whose row i carries (flag, timestamp) rows[i]."""
    return Commit(height, round_, BID, [
        CommitSig(flag, b"" if flag == BlockIDFlag.ABSENT else bytes([i % 251]) * 20,
                  ts, b"" if flag == BlockIDFlag.ABSENT else b"\x01" * 64)
        for i, (flag, ts) in enumerate(rows)])


C, A, N = BlockIDFlag.COMMIT, BlockIDFlag.ABSENT, BlockIDFlag.NIL
UNIFORM_TS = _ts(_step(1 << 28, 60, 10**9))
COMMIT_CASES = {
    "uniform": ([(C, t) for t in UNIFORM_TS], True),
    "absent_rows": ([(A, ZERO_TIME_NS) if i % 6 == 1 else (C, t)
                     for i, t in enumerate(UNIFORM_TS)], False),
    "nil_votes": ([(N if i % 6 == 1 else C, t) for i, t in enumerate(UNIFORM_TS)], False),
    "ragged": ([(C, t) for t in _ts([1 << 21, 1 << 28] * 30)], False),
}


@pytest.mark.parametrize("zero_ts", [False, True], ids=["real_ts", "zero_ts_scheme"])
@pytest.mark.parametrize("name", list(COMMIT_CASES))
def test_commit_rows_and_columns_match_per_index(name, zero_ts):
    """Commit.vote_sign_bytes_all / _columns against Commit.vote_sign_bytes
    index by index; under a zero-timestamp scheme every row signs the epoch
    and the columns say so too."""
    rows, uniform = COMMIT_CASES[name]
    if zero_ts:
        schemes.register_chain(
            CHAIN, schemes.Scheme(schemes.SCHEME_ED25519, aggregate_commits=True))
    commit = _commit_of(rows)
    want = [commit.vote_sign_bytes(CHAIN, i) for i in range(len(rows))]
    assert commit.vote_sign_bytes_all(CHAIN) == want
    cols = commit.vote_sign_bytes_columns(CHAIN)
    all_for_block = all(flag == C for flag, _ in rows)
    assert (cols is not None) == (all_for_block and (uniform or zero_ts))
    if cols is not None:
        assert cols.rows() == want
    # the candidates alone (what an entry asks for) are uniform wherever the
    # for-block rows are, whatever the rows between them look like
    idxs = [i for i, (flag, _) in enumerate(rows) if flag == C]
    sub = commit.vote_sign_bytes_columns(CHAIN, idxs)
    assert (sub is not None) == (name != "ragged" or zero_ts)
    if sub is not None:
        assert sub.rows() == [want[i] for i in idxs]
    # a chain of another scheme gets no ed25519 columns
    schemes.register_chain(CHAIN, schemes.Scheme(schemes.SCHEME_BLS12381, False))
    assert commit.vote_sign_bytes_columns(CHAIN) is None


def test_an_unknown_flag_raises_as_the_row_encoder_does():
    commit = _commit_of([(C, t) for t in UNIFORM_TS])
    commit.signatures[7].block_id_flag = BlockIDFlag.UNKNOWN
    with pytest.raises(ValueError, match="Unknown BlockIDFlag"):
        commit.vote_sign_bytes(CHAIN, 7)
    with pytest.raises(ValueError, match="Unknown BlockIDFlag"):
        commit.vote_sign_bytes_all(CHAIN)
    commit.signatures[7].block_id_flag = 1 << 20  # no byte holds it
    with pytest.raises(ValueError, match="Unknown BlockIDFlag"):
        commit.block_id_flags()


# -- the three entries through the columnar way in -----------------------------

N_VALS = 300
TEST_CHUNK = 256          # stands for 2,048: 300 candidates stream, 280 do too
WHALES = 5                # five hold 200 each, the rest 10: total 3,950
TRUST = (1, 3)


def _signer(seed: bytes):
    """(sign(msg) -> sig, pubkey bytes): OpenSSL where the image has it."""
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
    except ImportError:
        sk = crypto.Ed25519PrivKey.generate(seed)
        return sk.sign, sk.pub_key().bytes()
    sk = Ed25519PrivateKey.from_private_bytes(seed)
    return sk.sign, sk.public_key().public_bytes_raw()


def _host_rule(pk, msg, sig):
    return crypto.Ed25519PubKey(pk).verify_signature(msg, sig)


class World:
    """300 validators, their signers, and one fully signed commit."""

    def __init__(self):
        self.sign = {}
        vals = []
        for i in range(N_VALS):
            sign, pk = _signer(hashlib.sha256(b"colval-%d" % i).digest())
            pub = crypto.Ed25519PubKey(pk)
            self.sign[pub.address()] = sign
            vals.append(Validator(pub.address(), pub, 200 if i < WHALES else 10))
        self.vs = ValidatorSet(vals)
        self.base = self.commit_for(self.vs)

    def commit_for(self, vs, height=12):
        ts = _ts(_step(1 << 28, len(vs.validators), 10**9))
        sigs = []
        for v, t in zip(vs.validators, ts):
            sb = vote_sign_bytes(CHAIN, PRE, height, 0, BID, t)
            sigs.append(CommitSig(C, v.address, t, self.sign[v.address](sb)))
        return Commit(height, 0, BID, sigs)


@pytest.fixture(scope="module")
def world():
    return World()


@pytest.fixture
def columnar(device_standin, monkeypatch):
    """The stream seam stood in with the host spec's verdicts, the chunk
    lowered so that N_VALS candidates stream, counters from nought."""
    device_standin.rule = _host_rule
    monkeypatch.setattr(B, "STREAM_CHUNK", TEST_CHUNK)
    monkeypatch.setattr(VS, "STREAM_CHUNK", TEST_CHUNK)
    for k in B.stats:
        monkeypatch.setitem(B.stats, k, 0)
    return device_standin


def _tamper(cs: CommitSig) -> CommitSig:
    s = cs.signature
    return replace(cs, signature=s[:40] + bytes([s[40] ^ 1]) + s[41:])


def _variant(base: Commit, wrong=(), absent=(), nil=(), copy_row=()):
    """``base`` with rows tampered, made absent, turned into (unsigned) nil
    votes, or overwritten by another row ((dst, src): a second vote of
    src's validator)."""
    sigs = list(base.signatures)
    for dst, src in copy_row:
        sigs[dst] = sigs[src]
    for i in wrong:
        sigs[i] = _tamper(sigs[i])
    for i in absent:
        sigs[i] = CommitSig.new_absent()
    for i in nil:
        sigs[i] = replace(sigs[i], block_id_flag=N)
    return Commit(base.height, base.round, base.block_id, sigs)


# The 2/3 tally (needed 2,633) crosses at row 168, the 1/3 trusting tally
# (needed 1,316) at row 36: rows past them are never read by the light rules.
CROSS_LIGHT, CROSS_TRUST = 168, 36
VARIANTS = {
    "accept": {},
    "wrong_first_row": {"wrong": (0,)},
    "wrong_last_row": {"wrong": (N_VALS - 1,)},
    "wrong_at_the_trusting_crossing": {"wrong": (CROSS_TRUST,)},
    "wrong_past_the_trusting_crossing": {"wrong": (CROSS_TRUST + 1,)},
    "wrong_at_the_light_crossing": {"wrong": (CROSS_LIGHT,)},
    "wrong_past_the_light_crossing": {"wrong": (CROSS_LIGHT + 1,)},
    "two_wrong": {"wrong": (210, 20)},
    "two_wrong_astride_the_crossings": {"wrong": (250, 100)},
    "not_enough_power": {"absent": tuple(range(WHALES)) + tuple(range(100, 135))},
    "not_enough_power_and_wrong": {
        "absent": tuple(range(WHALES)) + tuple(range(100, 135)), "wrong": (290,)},
    "nil_votes_mixed": {"nil": (3, 200)},
    "second_vote_before_the_crossing": {"copy_row": ((30, 2),)},
    "second_vote_after_a_wrong_row": {"copy_row": ((30, 2),), "wrong": (10,)},
    "second_vote_before_a_wrong_row": {"copy_row": ((20, 2),), "wrong": (25,)},
    "second_vote_past_the_crossing": {"copy_row": ((250, 2),)},
}
ENTRIES = ("full", "light", "trusting")


def _outcome(fn):
    try:
        fn()
    except ErrWrongSignature as e:
        return ("wrong_signature", e.idx, str(e))
    except ErrNotEnoughVotingPowerSigned as e:
        return ("not_enough_power", e.got, e.needed, str(e))
    except ValueError as e:
        return ("value_error", str(e))
    return ("accept",)


def _call(entry, vs, commit):
    if entry == "full":
        return _outcome(lambda: vs.verify_commit(CHAIN, BID, commit.height, commit))
    if entry == "light":
        return _outcome(lambda: vs.verify_commit_light(CHAIN, BID, commit.height, commit))
    return _outcome(lambda: vs.verify_commit_light_trusting(CHAIN, commit, TRUST))


def _spec(entry, vs, commit):
    """The reference's scalar loops (validator_set.go:667 / :722 / :775):
    one row at a time, the per-index encoder, the host verifier."""
    def check(idx, val):
        cs = commit.signatures[idx]
        if not val.pub_key.verify_signature(commit.vote_sign_bytes(CHAIN, idx),
                                            cs.signature):
            raise ErrWrongSignature(idx, cs.signature)

    def full():
        tallied, needed = 0, vs.total_voting_power() * 2 // 3
        for idx, cs in enumerate(commit.signatures):
            if cs.absent():
                continue
            check(idx, vs.validators[idx])
            if cs.for_block():
                tallied += vs.validators[idx].voting_power
        if tallied <= needed:
            raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    def light():
        tallied, needed = 0, vs.total_voting_power() * 2 // 3
        for idx, cs in enumerate(commit.signatures):
            if not cs.for_block():
                continue
            check(idx, vs.validators[idx])
            tallied += vs.validators[idx].voting_power
            if tallied > needed:
                return
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    def trusting():
        tallied = 0
        needed = vs.total_voting_power() * TRUST[0] // TRUST[1]
        seen = {}
        for idx, cs in enumerate(commit.signatures):
            if not cs.for_block():
                continue
            val_idx, val = vs.get_by_address(cs.validator_address)
            if val is None:
                continue
            if val_idx in seen:
                raise ValueError(
                    f"double vote from {val}: ({seen[val_idx]} and {idx})")
            seen[val_idx] = idx
            check(idx, val)
            tallied += val.voting_power
            if tallied > needed:
                return
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    return _outcome({"full": full, "light": light, "trusting": trusting}[entry])


def test_the_crossing_rows_are_where_the_variants_say(world):
    powers = np.array([v.voting_power for v in world.vs.validators])
    total = int(powers.sum())
    cum = np.cumsum(powers)
    assert int(np.argmax(cum > total * 2 // 3)) == CROSS_LIGHT
    assert int(np.argmax(cum > total * TRUST[0] // TRUST[1])) == CROSS_TRUST


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("name", list(VARIANTS))
def test_entry_matches_the_scalar_spec(world, columnar, name, entry):
    # (a second vote sits out of the set's order: the positional rules read
    # row i against validator i and refuse its signature, as the spec does)
    commit = _variant(world.base, **VARIANTS[name])
    got = _call(entry, world.vs, commit)
    assert got == _spec(entry, world.vs, commit)
    # the way in: whole where the candidates are uniform, rows otherwise
    uniform = name != "nil_votes_mixed" or entry != "full"
    assert B.stats["columnar_batches"] == int(uniform)
    assert B.stats["columnar_fallbacks"] == 0
    assert B.stats["device_sigs"] == sum(columnar.calls) > TEST_CHUNK
    if uniform:
        assert B.stats["columnar_sigs"] == B.stats["device_sigs"]
    assert B.stats["host_sigs"] == 0


def test_the_variants_tell_the_rules_apart(world, columnar):
    """The cases mean what their names say (else the comparison above could
    agree on the wrong thing)."""
    out = {(n, e): _call(e, world.vs, _variant(world.base, **VARIANTS[n]))
           for n in ("wrong_past_the_light_crossing", "wrong_at_the_light_crossing",
                     "two_wrong", "not_enough_power",
                     "second_vote_before_the_crossing",
                     "second_vote_past_the_crossing")
           for e in ENTRIES}
    assert out["wrong_past_the_light_crossing", "full"][:2] == ("wrong_signature", CROSS_LIGHT + 1)
    assert out["wrong_past_the_light_crossing", "light"] == ("accept",)
    assert out["wrong_at_the_light_crossing", "light"][:2] == ("wrong_signature", CROSS_LIGHT)
    assert out["wrong_at_the_light_crossing", "trusting"] == ("accept",)
    assert out["two_wrong", "full"][:2] == ("wrong_signature", 20)
    assert out["not_enough_power", "full"][:3] == ("not_enough_power", 2600, 2633)
    assert out["not_enough_power", "light"][:3] == ("not_enough_power", 2600, 2633)
    assert out["not_enough_power", "trusting"] == ("accept",)
    assert out["second_vote_before_the_crossing", "trusting"][0] == "value_error"
    assert "double vote" in out["second_vote_before_the_crossing", "trusting"][1]
    assert "(2 and 30)" in out["second_vote_before_the_crossing", "trusting"][1]
    assert out["second_vote_past_the_crossing", "trusting"] == ("accept",)


def test_trusting_over_another_set_takes_each_key_by_address(world, columnar):
    """The trusted set holds 280 of the commit's 300 validators (and 15 the
    commit never heard of), in its own order: candidates are the rows whose
    address it knows, each checked against ITS key for that address."""
    strangers = []
    for i in range(15):
        _, pk = _signer(hashlib.sha256(b"stranger-%d" % i).digest())
        pub = crypto.Ed25519PubKey(pk)
        strangers.append(Validator(pub.address(), pub, 40))
    known = [v.copy() for i, v in enumerate(world.vs.validators) if i % 15 != 4]
    trusted = ValidatorSet(known + strangers)
    assert len(known) == 280
    for wrong in ((), (7,), (4,), (299,)):   # row 4 is no candidate
        commit = _variant(world.base, wrong=wrong)
        got = _call("trusting", trusted, commit)
        assert got == _spec("trusting", trusted, commit)
    assert B.stats["columnar_batches"] == 4
    assert B.stats["columnar_sigs"] == 4 * 280


def _outcome_of(err):
    def throw():
        if err is not None:
            raise err
    return _outcome(throw)


@pytest.mark.parametrize("entry", ["light", "trusting"])
def test_window_functions_match_the_scalar_spec(world, columnar, entry):
    """Both batched window functions (rows from the same builder, many
    commits in one batch) give each commit the answer the scalar loop gives
    it alone."""
    names = ["accept", "wrong_at_the_trusting_crossing",
             "wrong_past_the_light_crossing", "wrong_at_the_light_crossing",
             "two_wrong", "not_enough_power_and_wrong", "nil_votes_mixed",
             "second_vote_before_a_wrong_row"]
    commits = [_variant(world.base, **VARIANTS[n]) for n in names]
    if entry == "light":
        got = VS.verify_commit_light_batched(
            [(world.vs, CHAIN, BID, c.height, c) for c in commits])
    else:
        got = VS.verify_commit_light_trusting_batched(
            [(world.vs, CHAIN, c, TRUST) for c in commits])
    assert [_outcome_of(e) for e in got] == [
        _spec(entry, world.vs, c) for c in commits]
    assert B.stats["device_batches"] == 1 and B.stats["host_sigs"] == 0


# -- every way back to rows ----------------------------------------------------

class OddPubKey(crypto.PubKey):
    """A key type the kernel does not take (ed25519 underneath, so that the
    world's signer serves it)."""

    type_name = "odd"

    def __init__(self, key: bytes):
        self.key = key

    def address(self) -> bytes:
        return crypto.address_hash(self.key)

    def bytes(self) -> bytes:
        return self.key

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        return _host_rule(self.key, msg, sig)


def _open_breaker():
    while device_breaker.allow():
        device_breaker.record_failure()


def _precomputed_scope(world, commit):
    rows = [commit.vote_sign_bytes(CHAIN, i) for i in range(N_VALS)]
    return B.precomputed_verdicts.set({
        (v.pub_key.bytes(), rows[i], cs.signature):
            _host_rule(v.pub_key.bytes(), rows[i], cs.signature)
        for i, (v, cs) in enumerate(zip(world.vs.validators, commit.signatures))})


FALLBACKS = ("non_ed25519_key", "device_error", "open_breaker",
             "precomputed_verdicts", "host_backend")


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("mode", FALLBACKS)
def test_fallback_to_rows_gives_the_same_answer(world, columnar, monkeypatch,
                                                mode, entry):
    """Each way back to rows, on a commit the three rules read differently
    (one wrong row between the trusting and the light crossing, one past
    both): same verdict, same exception fields as the scalar spec."""
    commit = _variant(world.base, wrong=(CROSS_LIGHT + 1, 100))
    vs, token = world.vs, None
    if mode == "non_ed25519_key":
        vs = world.vs.copy()
        v = vs.validators[50]
        vs.validators[50] = Validator(v.address, OddPubKey(v.pub_key.bytes()),
                                      v.voting_power, v.proposer_priority)
        vs._bump_mutations()
    elif mode == "device_error":
        faults.configure("device.batch_verify")
    elif mode == "open_breaker":
        _open_breaker()
    elif mode == "precomputed_verdicts":
        token = _precomputed_scope(world, commit)
    elif mode == "host_backend":
        monkeypatch.setenv("TMTPU_BATCH_BACKEND", "host")
    try:
        got = _call(entry, vs, commit)
    finally:
        if token is not None:
            B.precomputed_verdicts.reset(token)
    want = _spec(entry, world.vs, commit)
    assert got == want
    assert want[0] == ("accept" if entry == "trusting" else "wrong_signature")
    assert B.stats["columnar_batches"] == 0 and B.stats["columnar_sigs"] == 0
    # a set with a foreign key never offers columns; the others came in
    # whole and had their rows built where the route needed them
    assert B.stats["columnar_fallbacks"] == int(mode != "non_ed25519_key")
    if mode == "non_ed25519_key":
        assert B.stats["device_sigs"] > 0 and vs._verify_arrays()[0] is None
    elif mode == "precomputed_verdicts":
        assert B.stats["precomputed_batches"] == 1 and not columnar.calls
    else:
        assert B.stats["host_sigs"] > 0 and not columnar.calls
        assert B.stats["device_errors"] == int(mode == "device_error")
        assert B.stats["breaker_rejections"] == int(mode == "open_breaker")


def test_add_columns_mixed_with_rows_degrades_to_rows(world, columnar):
    """The seam itself: columns into a batch that holds rows, and rows after
    columns, verify as rows, in order; the lists handed in are not written
    to."""
    commit = _variant(world.base, wrong=(3,))
    idxs = np.arange(N_VALS)
    cols = commit.vote_sign_bytes_columns(CHAIN, idxs)
    pks = [v.pub_key.bytes() for v in world.vs.validators]
    sigs = [cs.signature for cs in commit.signatures]
    rows = commit.vote_sign_bytes_all(CHAIN)
    extra = world.vs.validators[0].pub_key

    bv = B.BatchVerifier(backend="jax")
    bv.add(extra, rows[0], sigs[0])
    bv.add_columns(pks, sigs, cols)
    bv.add(extra, rows[0], sigs[1])
    assert len(bv) == N_VALS + 2 and len(pks) == N_VALS == len(sigs)
    ok, per = bv.verify()
    want = np.ones(N_VALS + 2, dtype=bool)
    want[[1 + 3, N_VALS + 1]] = False
    assert not ok and per.tolist() == want.tolist()
    assert B.stats["columnar_batches"] == 0 == B.stats["columnar_fallbacks"]

    bv.add_columns(pks, sigs, cols)       # whole, alone: columnar
    assert bv.verify()[1].tolist() == want[1:-1].tolist()
    assert B.stats["columnar_batches"] == 1
    assert B.stats["columnar_sigs"] == N_VALS
    with pytest.raises(ValueError, match="must align"):
        bv.add_columns(pks[:-1], sigs, cols)

    # a span says which way the batch went
    from tendermint_tpu.libs.trace import tracer

    tracer.clear()
    tracer.enable()
    try:
        bv.add_columns(pks, sigs, cols)
        bv.verify()
        bv.add(extra, rows[0], sigs[0])
        bv.verify()
        spans = [e for e in tracer.events() if e["name"] == "batch_verify"]
    finally:
        tracer.disable()
    assert [e["args"]["columnar"] for e in spans[-2:]] == [True, False]


def test_the_sets_arrays_renew_when_the_set_changes(world, columnar):
    """What a verify call keeps for the next derives from the set alone and
    goes with ``_mutations``: after a change of powers the next call tallies
    the new ones and reads the new order."""
    vs = world.vs.copy()
    commit = world.commit_for(vs)
    assert _call("full", vs, commit) == ("accept",)
    pks0, powers0 = vs._verify_arrays()
    assert vs._verify_arrays()[1] is powers0           # kept between calls
    assert vs.copy()._verify_arrays()[1] is powers0    # and by a copy
    # the five whales fall to 1 each: the order changes, and so does 2/3
    vs.update_with_change_set(
        [Validator(v.address, v.pub_key, 1) for v in vs.validators[:WHALES]])
    pks1, powers1 = vs._verify_arrays()
    assert powers1 is not powers0 and pks1 != pks0
    assert powers1.tolist() == [v.voting_power for v in vs.validators]
    assert pks1 == [v.pub_key.bytes() for v in vs.validators]
    # the old commit's rows no longer line up with the set: refused, as the
    # scalar spec refuses it; one signed in the new order passes
    assert _call("full", vs, commit) == _spec("full", vs, commit)
    assert _call("full", vs, commit)[0] == "wrong_signature"
    renewed = world.commit_for(vs)
    for entry in ENTRIES:
        for c in (renewed, _variant(renewed, absent=tuple(range(60, 170)))):
            assert _call(entry, vs, c) == _spec(entry, vs, c)
    assert _call("full", vs, _variant(renewed, absent=tuple(range(60, 170))))[:3] == (
        "not_enough_power", 1855, 1970)


def test_powers_no_int64_holds_tally_exactly(columnar):
    """A hand-built set with a negative power (no chain can have one) still
    tallies in Python integers."""
    vals = []
    for i in range(60):
        _, pk = _signer(hashlib.sha256(b"neg-%d" % i).digest())
        pub = crypto.Ed25519PubKey(pk)
        vals.append(Validator(pub.address(), pub, 10))
    vs = ValidatorSet(vals)
    vs.validators[59].voting_power = -3
    vs._bump_mutations()
    assert vs._verify_arrays()[1].dtype == object
    commit = Commit(3, 0, BID, [CommitSig(C, v.address, SEC * 10**9 + 5, b"\x00" * 64)
                                for v in vs.validators])
    columnar.rule = lambda pk, msg, sig: True
    vs._total_voting_power = None
    assert _call("full", vs, commit) == ("accept",)
    got = _call("full", vs, _variant(commit, absent=tuple(range(20, 59))))
    assert got[:3] == ("not_enough_power", 197, 391)
    assert _call("light", vs, commit) == ("accept",)


# -- nothing survives a request but the set's arrays ---------------------------

def test_nothing_survives_a_request_but_the_sets_arrays(device_standin,
                                                       monkeypatch):
    """10,000 signatures at the real chunk, twice, each time on a new Commit
    over the SAME CommitSig objects (as benchmarks/objects.as_received makes
    it): every signature of both requests reaches the device seam, both
    come in whole, and no CommitSig carries anything it did not have."""
    n = 10_000
    rng = np.random.default_rng(27)
    vals = [Validator(pub.address(), pub, 30 if i < 2000 else 10)
            for i, pub in enumerate(
                crypto.Ed25519PubKey(rng.bytes(32)) for _ in range(n))]
    vs = ValidatorSet(vals)
    sigs = [CommitSig(C, v.address, 1_700_000_012_500_000_000 + i,
                      rng.bytes(32) + b"\x00" * 32)
            for i, v in enumerate(vs.validators)]
    fields = {i: dict(vars(cs)) for i, cs in enumerate(sigs)}
    set_before = set(vars(vs))
    device_standin.rule = lambda pk, msg, sig: True   # rows are no signatures
    for k in B.stats:
        monkeypatch.setitem(B.stats, k, 0)
    for call in (1, 2):
        commit = Commit(12, 0, BID, sigs)             # a new object, no memo
        vs.verify_commit(CHAIN, BID, 12, commit)
        assert B.stats["device_sigs"] == call * n
        assert B.stats["columnar_sigs"] == call * n
        assert B.stats["columnar_batches"] == call == B.stats["device_batches"]
        assert sum(device_standin.calls) == call * n
    assert B.stats["host_sigs"] == 0 == B.stats["columnar_fallbacks"]
    assert B.stats["precomputed_sigs"] == 0
    assert all(vars(cs) == fields[i] for i, cs in enumerate(sigs))
    assert not hasattr(CommitSig, "__slots__")        # vars() sees it all
    # the set kept its two arrays, and only under its own rule
    assert set(vars(vs)) - set_before == {"_verify_cache"}
    # a tampered verdict for one row is still that row's
    device_standin.rule = lambda pk, msg, sig: sig != sigs[9_000].signature
    with pytest.raises(ErrWrongSignature) as e:
        vs.verify_commit(CHAIN, BID, 12, Commit(12, 0, BID, sigs))
    assert e.value.idx == 9_000
    vs.verify_commit_light(CHAIN, BID, 12, Commit(12, 0, BID, sigs))
