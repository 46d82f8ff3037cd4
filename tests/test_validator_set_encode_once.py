"""ValidatorSet's encode-once rows (types/validator_set.py
``_encoded_rows``) against the row-by-row encoding it replaced: a
``pw.Writer`` per validator, every field through the writer. What is kept
must be dropped by whatever changes a byte of it: a proposer priority (by a
mutator or by hand), the membership, the list. jax-free.

``ref_valset_encode`` is imported by the store-level test in
test_block_sync.py.
"""

import json

import pytest

from tendermint_tpu import crypto
from tendermint_tpu.libs import protowire as pw
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.state import StateStore, state_from_genesis
from tendermint_tpu.state.execution import update_state
from tendermint_tpu.state.store import ABCIResponses
from tendermint_tpu.types import BlockID, GenesisDoc, GenesisValidator
from tendermint_tpu.types.basic import encode_stats
from tendermint_tpu.types.block import Commit
from tendermint_tpu.types.validator import (
    INT64_MAX,
    INT64_MIN,
    Validator,
    pubkey_proto_bytes,
)
from tendermint_tpu.types.validator_set import ValidatorSet


# -- the reference: every field of every row through a Writer ----------------

def ref_validator_encode(v):
    w = pw.Writer()
    w.bytes(1, v.address)
    w.message(2, pubkey_proto_bytes(v.pub_key))
    w.varint(3, v.voting_power)
    w.varint(4, v.proposer_priority)
    return w.finish()


def ref_valset_encode(vs):
    w = pw.Writer()
    for v in vs.validators:
        w.message(1, ref_validator_encode(v))
    if vs.proposer is not None:
        w.message(2, ref_validator_encode(vs.proposer))
    w.varint(3, vs.total_voting_power())
    return w.finish()


# -- sets --------------------------------------------------------------------

def _key(i):
    return crypto.Ed25519PrivKey.generate(bytes([i % 256, i // 256]) * 16) \
        .pub_key()


def _vals(n, power=10):
    return [Validator(k.address(), k, power + i % 3)
            for i, k in enumerate(map(_key, range(n)))]


#: 1- to 10-byte varints, and zero (left out of the row, as proto3 does)
PRIORITIES = [0, 1, -1, 127, 128, -128, 16_383, 16_384, 2**21, 2**28 - 1,
              2**35, 2**42, 2**49, 2**56, 2**62, INT64_MAX, INT64_MIN,
              -(2**40), 0, -12_345]


def _set_with_priorities(prios):
    vs = ValidatorSet(_vals(len(prios)))
    for v, pp in zip(vs.validators, prios):
        v.proposer_priority = pp
    return vs


def _built_reused(fn):
    before = dict(encode_stats)
    out = fn()
    return (out, encode_stats["valset_encodes_built"]
            - before["valset_encodes_built"],
            encode_stats["valset_encodes_reused"]
            - before["valset_encodes_reused"])


@pytest.mark.parametrize("prios", [
    PRIORITIES, [0] * 5, [-7], [INT64_MIN, INT64_MAX],
    [(-1) ** i * (i * 7919) for i in range(1000)]],
    ids=["every_varint_width", "all_zero", "one_row", "extremes",
         "rows_1000"])
def test_encode_equals_row_by_row(prios):
    vs = _set_with_priorities(prios)
    assert vs.encode() == ref_valset_encode(vs)
    out, built, reused = _built_reused(vs.encode)
    assert out == ref_valset_encode(vs) and (built, reused) == (0, 1)


def test_a_key_that_is_not_ed25519():
    bls = crypto.Bls12381PubKey(b"\x07" * 96)
    vs = ValidatorSet(_vals(3) + [Validator(bls.address(), bls, 5)])
    assert any(v.pub_key is bls for v in vs.validators)
    assert vs.encode() == ref_valset_encode(vs)


@pytest.mark.parametrize("proposer", ["set", "unset", "not_of_the_set"])
def test_proposer_field(proposer):
    vs = ValidatorSet(_vals(4))
    if proposer == "unset":
        vs.proposer = None
    elif proposer == "not_of_the_set":
        k = _key(99)
        vs.proposer = Validator(k.address(), k, 3, -42)
    assert vs.encode() == ref_valset_encode(vs)
    vs.proposer = vs.validators[2]  # outside the kept rows: written anew
    out, built, _ = _built_reused(vs.encode)
    assert out == ref_valset_encode(vs) and built == 0


def test_empty_set():
    vs = ValidatorSet()
    assert vs.encode() == ref_valset_encode(vs) == b""


def test_copy_carries_the_rows():
    vs = ValidatorSet(_vals(6))
    vs.encode()
    c = vs.copy()
    out, built, reused = _built_reused(c.encode)
    assert out == ref_valset_encode(c) == vs.encode()
    assert (built, reused) == (0, 1)
    # the copy's rows are its own from there on
    c.increment_proposer_priority(1)
    assert c.encode() == ref_valset_encode(c)
    out, built, _ = _built_reused(vs.encode)
    assert out == ref_valset_encode(vs) and built == 0
    # a copy made after the priorities moved carries rows that no longer
    # fit, and finds that out
    vs.increment_proposer_priority(1)
    assert vs.copy().encode() == ref_valset_encode(vs)


def _increment(vs):
    vs.increment_proposer_priority(1)


def _rescale(vs):
    vs.validators[0].proposer_priority = 10_000
    vs.encode()  # kept with the wide spread, before the mutator runs
    vs.rescale_priorities(10)


def _shift(vs):
    vs._shift_by_avg_proposer_priority()


def _change_set(vs):
    k = _key(50)
    vs.update_with_change_set([Validator(k.address(), k, 7)])


def _change_power(vs):
    v = vs.validators[1]
    vs.update_with_change_set([Validator(v.address, v.pub_key, 99)])


def _remove(vs):
    v = vs.validators[1]
    vs.update_with_change_set([Validator(v.address, v.pub_key, 0)])


def _replace_list(vs):
    vs.validators = [v.copy() for v in vs.validators[:3]]
    vs._total_voting_power = None


def _append(vs):
    k = _key(51)
    vs.validators.append(Validator(k.address(), k, 1, 5))
    vs._total_voting_power = None


def _write_a_priority(vs):
    vs.validators[2].proposer_priority -= 1


@pytest.mark.parametrize("change", [
    _increment, _rescale, _shift, _change_set, _change_power, _remove,
    _replace_list, _append, _write_a_priority],
    ids=lambda f: f.__name__.strip("_"))
def test_what_changes_a_byte_drops_the_rows(change):
    vs = ValidatorSet(_vals(5))
    vs.validators[0].proposer_priority += 50  # so that the average shifts
    kept = vs.encode()
    change(vs)
    out, built, _ = _built_reused(vs.encode)
    assert out == ref_valset_encode(vs)
    assert out != kept and built == 1


def test_decode_round_trip():
    vs = _set_with_priorities(PRIORITIES)
    back = ValidatorSet.decode(vs.encode())
    assert back.validators == vs.validators
    assert back.proposer == vs.proposer
    assert back.encode() == vs.encode() == ref_valset_encode(back)
    assert back.hash() == vs.hash()


def test_state_records_encode_each_distinct_set_once():
    """Three consecutive heights of the state plane (update_state's copies,
    then the store's save, as apply_block runs them): the State of a height
    holds three sets that are copies of one another a height apart, so each
    height builds ONE set's rows (the new next_validators), the record's
    other two find theirs kept, and the record holds the row-by-row
    bytes."""
    genesis = GenesisDoc(
        chain_id="encode-once", genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(_key(i), 10 + i) for i in range(5)])
    state = state_from_genesis(genesis)
    state_store = StateStore(MemDB())
    state_store.save(state)
    per_height = []
    for h in (1, 2, 3):
        block, parts = state.make_block(
            h, [], Commit(h - 1, 0, state.last_block_id, []), [],
            state.validators.get_proposer().address)
        bid = BlockID(block.hash(), parts.header())

        def step():
            new = update_state(state, bid, block, ABCIResponses(), [])
            state_store.save(new)
            return new

        state, built, reused = _built_reused(step)
        per_height.append((built, reused))
    assert per_height == [(1, 2)] * 3
    record = json.loads(state_store._db.get(b"stateKey").decode())
    names = ("next_validators", "validators", "last_validators")
    for name in names:
        assert record[name] == ref_valset_encode(getattr(state, name)).hex()
    assert len({record[name] for name in names}) == 3
    assert state_store.load().validators.encode() == state.validators.encode()
