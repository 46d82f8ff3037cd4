"""Commit's encode-once table (types/block.py ``_CommitWire``) against the
row-by-row encoding it replaced: every CommitSig through its own
``encode``, every row framed by a ``pw.Writer``. Byte for byte, so block
hashes, part-set hashes, ``last_commit_hash`` and stored records stay what
they were. jax-free.

``ref_commit_encode`` / ``ref_commit_rows`` are the parent's
``Commit.encode`` / ``Commit.hash`` bodies, kept here as the reference (and
imported by the store-level test in test_block_sync.py).
"""

import pytest

from tendermint_tpu.crypto import merkle
from tendermint_tpu.libs import protowire as pw
from tendermint_tpu.libs.bits import BitArray
from tendermint_tpu.types.basic import (
    ZERO_TIME_NS,
    BlockID,
    BlockIDFlag,
    PartSetHeader,
    encode_stats,
)
from tendermint_tpu.types.block import AggregatedCommit, Commit, CommitSig

BID = BlockID(bytes(range(32)), PartSetHeader(3, bytes(range(32, 64))))
T0 = 1_700_000_000_000_000_000
C, NIL, ABSENT = BlockIDFlag.COMMIT, BlockIDFlag.NIL, BlockIDFlag.ABSENT


# -- the reference: one Writer and one CommitSig.encode per row --------------

def ref_commit_rows(commit):
    return [cs.encode() for cs in commit.signatures]


def ref_commit_encode(commit):
    w = pw.Writer()
    w.varint(1, commit.height)
    w.varint(2, commit.round)
    w.message(3, commit.block_id.encode())
    for row in ref_commit_rows(commit):
        w.message(4, row)
    return w.finish()


def ref_commit_hash(commit):
    return merkle.hash_from_byte_slices(ref_commit_rows(commit))


# -- commits -----------------------------------------------------------------

def _row(i, flag=C, ts=T0 + 1, addr_len=20, sig_len=64):
    return CommitSig(flag, bytes([i % 251]) * addr_len, ts,
                     bytes([(i * 7 + 1) % 256]) * sig_len)


def _ragged_ts(i):
    """One timestamp a validator, every varint width of seconds and nanos:
    no nanos, no seconds, neither, pre-epoch (a 10-byte seconds varint)."""
    return (T0 + i * 997_331, T0 + i * 10**9, i * 7, 0, ZERO_TIME_NS,
            -1 - i, 127 * 10**9 + 127, 128 * 10**9 + 128,
            (1 << 40) * 10**9 + 999_999_999)[i % 9]


def _uniform(n):
    return [_row(i) for i in range(n)]


#: name -> (rows, rows that are not of the regular shape)
CASES = {
    "uniform_1000": (lambda: _uniform(1000), 0),
    "ragged_timestamps_1000":
        (lambda: [_row(i, ts=_ragged_ts(i)) for i in range(1000)], 0),
    "absent_rows":
        (lambda: [CommitSig.new_absent() if i % 5 == 2 else _row(i)
                  for i in range(50)], 10),
    "all_absent": (lambda: [CommitSig.new_absent() for _ in range(7)], 7),
    "nil_votes":
        (lambda: [_row(i, flag=NIL if i % 3 == 0 else C, ts=T0 + i)
                  for i in range(40)], 0),
    "rows_0": (lambda: [], 0),
    "rows_1": (lambda: _uniform(1), 0),
    "rows_4": (lambda: _uniform(4), 0),
    "signature_63": (lambda: _uniform(3) + [_row(3, sig_len=63)], 1),
    "signature_65": (lambda: [_row(0, sig_len=65)] + _uniform(3), 1),
    "signature_empty": (lambda: _uniform(2) + [_row(2, sig_len=0)], 1),
    "address_19_and_21":
        (lambda: [_row(0, addr_len=19), _row(1), _row(2, addr_len=21)], 2),
    "address_19_signature_65": (lambda: [_row(0, addr_len=19, sig_len=65)], 1),
    "flag_unknown_and_wide":
        (lambda: [_row(0, flag=BlockIDFlag.UNKNOWN), _row(1, flag=200),
                  _row(2, flag=2)], 0),
    "rows_10000": (lambda: _uniform(10_000), 0),
}


def _commit(name):
    return Commit(7, 1, BID, CASES[name][0]())


@pytest.fixture(params=sorted(CASES))
def case(request):
    return request.param


def test_encode_equals_row_by_row(case):
    commit = _commit(case)
    assert commit.encode() == ref_commit_encode(commit)
    assert commit.encode() == ref_commit_encode(commit)  # the kept table too


def test_hash_equals_merkle_over_rows(case):
    commit = _commit(case)
    assert commit.hash() == ref_commit_hash(commit)
    assert commit.hash() == ref_commit_hash(commit)


def test_rows_off_the_regular_shape_take_commit_sig_encode(case):
    commit = _commit(case)
    before = encode_stats["commit_rows_by_row"]
    commit.encode()
    commit.hash()
    assert encode_stats["commit_rows_by_row"] - before == CASES[case][1]


@pytest.mark.parametrize("name", [
    n for n in sorted(CASES) if n != "flag_unknown_and_wide"])  # 200: no flag
def test_decode_round_trip(name):
    commit = _commit(name)
    back = Commit.decode(commit.encode())
    assert back == commit
    assert back.encode() == commit.encode()
    assert back.hash() == commit.hash()


@pytest.mark.parametrize("first", ["encode", "hash"])
def test_encode_and_hash_build_one_table(first):
    commit = _commit("uniform_1000")
    before = dict(encode_stats)
    for name in (first, "hash" if first == "encode" else "encode",
                 "encode", "hash"):
        getattr(commit, name)()
    assert encode_stats["commit_tables_built"] - before["commit_tables_built"] == 1
    assert (encode_stats["commit_tables_reused"]
            - before["commit_tables_reused"]) == 3


def test_head_fields_are_written_every_call():
    """height, round and block_id are not in the table: changing one
    changes the bytes and rebuilds nothing."""
    commit = _commit("rows_4")
    commit.encode()
    before = encode_stats["commit_tables_built"]
    commit.height, commit.round = 9, 0
    commit.block_id = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))
    assert commit.encode() == ref_commit_encode(commit)
    assert encode_stats["commit_tables_built"] == before


def _replace_row(commit):
    commit.signatures[1] = _row(99, ts=5)


def _append_row(commit):
    commit.signatures.append(_row(77))


def _drop_row(commit):
    del commit.signatures[0]


def _swap_rows(commit):
    s = commit.signatures
    s[0], s[2] = s[2], s[0]


def _new_list(commit):
    commit.signatures = [_row(i + 10) for i in range(4)]


@pytest.mark.parametrize("change", [_replace_row, _append_row, _drop_row,
                                    _swap_rows, _new_list],
                         ids=lambda f: f.__name__.strip("_"))
def test_another_set_of_rows_rebuilds_the_table(change):
    commit = _commit("rows_4")
    commit.encode()
    commit.hash()
    before = encode_stats["commit_tables_built"]
    change(commit)
    assert commit.encode() == ref_commit_encode(commit)
    assert commit.hash() == ref_commit_hash(commit)
    assert encode_stats["commit_tables_built"] - before == 1


def test_equal_commits_stay_equal_whatever_they_keep():
    a, b = _commit("rows_4"), _commit("rows_4")
    a.encode()
    assert a == b and "_wire_memo" not in repr(a)


def test_aggregated_commit_is_untouched():
    signers = BitArray(10)
    for i in (0, 3, 9):
        signers.set_index(i, True)
    agg = AggregatedCommit(7, 1, BID, [], signers=signers,
                           agg_sig=b"\x5a" * 48, timestamp_ns=T0 + 3)
    w = pw.Writer()
    w.varint(1, 7)
    w.varint(2, 1)
    w.message(3, BID.encode())
    w.message(5, signers.encode())
    w.bytes(6, b"\x5a" * 48)
    w.varint(7, T0 + 3)
    leaf = pw.Writer()
    leaf.message(1, signers.encode())
    leaf.bytes(2, b"\x5a" * 48)
    leaf.varint(3, T0 + 3)
    before = dict(encode_stats)
    assert agg.encode() == w.finish()
    assert agg.hash() == merkle.hash_from_byte_slices([leaf.finish()])
    assert agg.hash() == agg.hash()
    back = Commit.decode(agg.encode())
    assert isinstance(back, AggregatedCommit) and back == agg
    assert encode_stats == before  # no row table for a commit without rows


@pytest.mark.parametrize("lengths", [
    (), (0,), (1, 0, 1), (127, 128, 129), (300, 5, 300, 16_383, 16_384)],
    ids=str)
def test_repeated_message_equals_writer_messages(lengths):
    bodies = [bytes([n % 256]) * n for n in lengths]
    for field in (1, 4, 16):
        w = pw.Writer()
        for body in bodies:
            w.message(field, body)
        assert pw.repeated_message(field, bodies) == w.finish()


# Frozen: written by the row-by-row encoder at commit b970524 (PR 34), so
# builder and node cannot drift together. A regular row, an absent row, a
# nil vote without nanos, a 63-byte signature with a seconds-free timestamp,
# a second regular row sharing the first one's timestamp.
GOLDEN_ENCODED = (
    "080710011a480a20000102030405060708090a0b0c0d0e0f101112131415161718191a"
    "1b1c1d1e1f122408031220202122232425262728292a2b2c2d2e2f3031323334353637"
    "38393a3b3c3d3e3f226408021214a1a1a1a1a1a1a1a1a1a1a1a1a1a1a1a1a1a1a1a11a"
    "080880e2cfaa0610012240111111111111111111111111111111111111111111111111"
    "1111111111111111111111111111111111111111111111111111111111111111111111"
    "1111111111220f08011a0b088092b8c398feffffff01226208031214b2b2b2b2b2b2b2"
    "b2b2b2b2b2b2b2b2b2b2b2b2b21a060880e2cfaa062240222222222222222222222222"
    "2222222222222222222222222222222222222222222222222222222222222222222222"
    "2222222222222222222222222222222222225d08021214c3c3c3c3c3c3c3c3c3c3c3c3"
    "c3c3c3c3c3c3c3c31a021005223f333333333333333333333333333333333333333333"
    "3333333333333333333333333333333333333333333333333333333333333333333333"
    "33333333333333226408021214d4d4d4d4d4d4d4d4d4d4d4d4d4d4d4d4d4d4d4d41a08"
    "0880e2cfaa061001224044444444444444444444444444444444444444444444444444"
    "4444444444444444444444444444444444444444444444444444444444444444444444"
    "44444444")
GOLDEN_HASH = "1c4d70c0b8b9ae4f6cb1324c93607ed05f2c733266b1f17f172c06ae69bc5776"


def test_golden_vector():
    commit = Commit(7, 1, BID, [
        CommitSig(C, b"\xa1" * 20, T0 + 1, b"\x11" * 64),
        CommitSig.new_absent(),
        CommitSig(NIL, b"\xb2" * 20, T0, b"\x22" * 64),
        CommitSig(C, b"\xc3" * 20, 5, b"\x33" * 63),
        CommitSig(C, b"\xd4" * 20, T0 + 1, b"\x44" * 64),
    ])
    assert commit.encode().hex() == GOLDEN_ENCODED
    assert commit.hash().hex() == GOLDEN_HASH
    assert ref_commit_encode(commit).hex() == GOLDEN_ENCODED
