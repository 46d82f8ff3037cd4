"""The coalescer's candidate collection (light/serve.py
VerifyCoalescer._candidates): each distinct (commit, set, chain) object
triple of a flush is collected once, its rows cut from the commit's
vectorised sign-bytes table, and the list that goes into the one device
call is the per-row loop's list, element for element and in its order."""

import copy
import dataclasses

import pytest

from tendermint_tpu.crypto import schemes
from tendermint_tpu.libs.bits import BitArray
from tendermint_tpu.light.serve import VerifyCoalescer, VerifyRequest
from tendermint_tpu.types import ValidatorSet
from tendermint_tpu.types.basic import ZERO_TIME_NS
from tendermint_tpu.types.block import (
    AggregatedCommit,
    BlockIDFlag,
    Commit,
    CommitSig,
)
from tendermint_tpu.types.light_block import SignedHeader

from tests.test_light_client import _keys, _mk_chain, _val_set
from tests.test_light_serve import (
    NOW,
    _assert_verdict_parity,
    _coalesce,
    _req,
    _scalar_verdict,
)

ZERO_TS_CHAIN = "light-zero-ts"


def _reference_candidates(reqs):
    """The per-row loop the table path replaced, verbatim."""
    from tendermint_tpu.types.validator_set import _is_aggregated

    items = []
    seen = set()
    for r in reqs:
        commit = r.untrusted_sh.commit
        if _is_aggregated(commit):
            continue  # BLS aggregates pair inline in the scalar replay
        chain_id = r.trusted_sh.header.chain_id
        nvals = len(r.untrusted_vals.validators)
        for idx, cs in enumerate(commit.signatures):
            # malformed shapes are NOT pre-verified: the replay's
            # structural checks raise the same typed error as the
            # scalar path (its cache misses fall back to host verify)
            if not cs.for_block() or idx >= nvals:
                continue
            pub = r.untrusted_vals.validators[idx].pub_key
            msg = commit.vote_sign_bytes(chain_id, idx)
            k = (pub.bytes(), msg, cs.signature)
            if k in seen:
                continue
            seen.add(k)
            items.append((pub, msg, cs.signature))
    return items


@pytest.fixture(scope="module")
def chain():
    return _mk_chain([_keys(0x50, 8)], 12)


def _with_sigs(blocks, h, edit):
    """Block h's signed header over a new commit object whose rows
    ``edit`` rewrote (nothing memoised on it), the header shared."""
    sh = blocks[h].signed_header
    c = sh.commit
    sigs = copy.deepcopy(c.signatures)
    edit(sigs)
    return SignedHeader(sh.header, Commit(c.height, c.round, c.block_id, sigs))


def _ask(blocks, trusted_h, sh, vals):
    return VerifyRequest(blocks[trusted_h].signed_header,
                         blocks[trusted_h].validator_set, sh, vals,
                         3600.0, NOW, 10.0)


def _shared(blocks):
    sh, vals = blocks[12].signed_header, blocks[12].validator_set
    return [_ask(blocks, 1 + i % 11, sh, vals) for i in range(32)]


def _distinct(blocks):
    return [_req(blocks, 1, h) for h in range(2, 13)]


def _two_sets(blocks):
    sh = blocks[10].signed_header
    other = _val_set(_keys(0x70, 8))
    return [_ask(blocks, 1, sh, blocks[10].validator_set),
            _ask(blocks, 2, sh, other),
            _ask(blocks, 3, sh, blocks[10].validator_set),
            _ask(blocks, 4, sh, other)]


def _nil_and_absent(blocks):
    def edit(sigs):
        sigs[1].block_id_flag = BlockIDFlag.NIL
        sigs[4] = CommitSig(BlockIDFlag.ABSENT, b"", ZERO_TIME_NS, b"")
        sigs[6] = CommitSig(BlockIDFlag.ABSENT, b"", ZERO_TIME_NS, b"")
    sh = _with_sigs(blocks, 9, edit)
    return [_ask(blocks, t, sh, blocks[9].validator_set) for t in (1, 2)]


def _longer_than_set(blocks):
    short = ValidatorSet(blocks[11].validator_set.validators[:5])
    return [_ask(blocks, 1, blocks[11].signed_header, short),
            _req(blocks, 2, 11)]


def _aggregated_in_mix(blocks):
    sh = blocks[8].signed_header
    agg = AggregatedCommit(sh.commit.height, sh.commit.round,
                           sh.commit.block_id, [], BitArray(8),
                           b"\x01" * 48, sh.commit.signatures[0].timestamp_ns)
    return [_req(blocks, 1, 7),
            _ask(blocks, 1, SignedHeader(sh.header, agg),
                 blocks[8].validator_set),
            _req(blocks, 2, 8)]


def _zero_precommit_ts(blocks):
    """The chain's scheme zeroes every precommit timestamp in sign-bytes."""
    def on_zero_ts_chain(h):
        sh = blocks[h].signed_header
        return SignedHeader(
            dataclasses.replace(sh.header, chain_id=ZERO_TS_CHAIN),
            sh.commit)
    trusted = on_zero_ts_chain(1)
    return [VerifyRequest(trusted, blocks[1].validator_set,
                          blocks[h].signed_header, blocks[h].validator_set,
                          3600.0, NOW, 10.0) for h in (5, 6, 5)]


def _equal_content(blocks):
    sh, vals = blocks[12].signed_header, blocks[12].validator_set
    twin = SignedHeader(sh.header, copy.deepcopy(sh.commit))
    return [_ask(blocks, 1, sh, vals), _ask(blocks, 2, twin,
                                            copy.deepcopy(vals)),
            _ask(blocks, 3, sh, vals)]


def _unknown_flags(blocks):
    def seven(sigs):
        sigs[2].block_id_flag = 7  # a byte, no BlockIDFlag
    def past_a_byte(sigs):
        sigs[5].block_id_flag = 300
    return [_ask(blocks, 1, _with_sigs(blocks, 6, seven),
                 blocks[6].validator_set),
            _ask(blocks, 1, _with_sigs(blocks, 7, past_a_byte),
                 blocks[7].validator_set),
            _req(blocks, 1, 7)]


#: case -> (requests of one flush, (commits collected, commits reused))
CASES = {
    "shared_commit_32": (_shared, (1, 31)),
    "distinct_commits": (_distinct, (11, 0)),
    "one_commit_two_sets": (_two_sets, (2, 2)),
    "nil_and_absent_rows": (_nil_and_absent, (1, 1)),
    "commit_longer_than_set": (_longer_than_set, (2, 0)),
    "aggregated_in_mix": (_aggregated_in_mix, (2, 0)),
    "zero_precommit_ts": (_zero_precommit_ts, (2, 1)),
    "equal_content_commits": (_equal_content, (2, 1)),
    "unknown_flag_rows": (_unknown_flags, (3, 0)),
}


@pytest.fixture
def zero_ts_chain():
    schemes.register_chain(ZERO_TS_CHAIN, schemes.Scheme(
        schemes.SCHEME_ED25519, aggregate_commits=True))
    yield
    schemes.register_chain(ZERO_TS_CHAIN, schemes.Scheme())


def _rows(items):
    return [(pub.bytes(), msg, sig) for pub, msg, sig in items]


@pytest.mark.parametrize("case", list(CASES))
def test_candidates_equal_the_per_row_loop(case, chain, zero_ts_chain):
    build, counts = CASES[case]
    reqs = build(chain)
    want = _reference_candidates(reqs)
    items, got_counts = VerifyCoalescer._candidates(reqs)
    assert _rows(items) == _rows(want)
    assert got_counts == counts
    assert want, "every case has rows to verify"
    if case == "zero_precommit_ts":
        assert want != _reference_candidates(_distinct(chain)[3:5])


def test_unknown_flag_commit_keeps_the_flush_s_other_answers(
        chain, device_standin):
    """A commit whose table cannot be built takes the per-row encoder for
    itself alone: the sound request of the same flush is accepted, and the
    other gets exactly what the scalar spec raises for it."""
    def unknown(sigs):
        sigs[3].block_id_flag = 7
    bad = _ask(chain, 1, _with_sigs(chain, 9, unknown),
               chain[9].validator_set)
    good = _req(chain, 2, 9)
    out, nsigs, commits, _, _ = VerifyCoalescer()._verify_many([bad, good])
    want = _scalar_verdict(bad)
    assert want is not None
    assert type(out[0]) is type(want) and str(out[0]) == str(want)
    assert out[1] is None and _scalar_verdict(good) is None
    assert commits == (2, 0) and nsigs == 8


def test_shared_commit_flush_collects_once_and_answers_as_scalar(
        chain, device_standin):
    """32 requests of one flush over one commit, one of its rows tampered:
    one collection, 31 reuses, and each answer the scalar spec's."""
    def tamper(sigs):
        sigs[2].signature = b"\x00" * 64
    sh = _with_sigs(chain, 12, tamper)
    vals = chain[12].validator_set
    reqs = [VerifyRequest(chain[t].signed_header, chain[t].validator_set,
                          sh, vals, 3600.0 if i % 4 else 5.0, NOW, 10.0,
                          (1, 3) if i % 3 else (2, 3))
            for i, t in enumerate(1 + i % 11 for i in range(32))]
    results, co = _coalesce(reqs)
    _assert_verdict_parity(reqs, results)
    assert co.stats["flushes"] == 1
    assert co.stats["commits_collected"] == 1
    assert co.stats["commits_reused"] == 31
    assert co.stats["batched_sigs"] == 8
    verdicts = {type(r).__name__ for r in results}
    assert len(verdicts) > 1, verdicts
