"""Fast sync over a chain whose validator set changes at every height
(``blockchain/reactor.py``: windows verified across set changes, rows keyed
by guessed keys that the apply-time rules never trust), and the benchmark
cell that drives it (``churn1000.catchup``) at a small size.

Chains come from the cell's own driver (``benchmarks/drivers/
churn_sync_replay.py``) at 16 validators, 4 power moves a block and a
member swapped every 16th block. The windowed reactor runs with its window
precompute engaged at that size (``PRECOMPUTE_MIN_SIGS`` lowered), the
device seam stood in (conftest.py); the per-block path is v0.34's pool
routine written out: ``VerifyCommitLight`` of each block against the
state's set, then ``ApplyBlock``.
"""

import asyncio
import os
import re
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402
from drivers import churn_sync_replay as C  # noqa: E402
from drivers import fast_sync_replay as F  # noqa: E402
from reference import churn_sync_spec  # noqa: E402

import tendermint_tpu.blockchain.reactor as R  # noqa: E402
from tendermint_tpu.crypto import batch as B  # noqa: E402
from tendermint_tpu.types.basic import BlockID  # noqa: E402

SEED = 2 ** 31 + 4040
#: the cell at a small size (keys of its configuration file)
SMALL = {"validators": 16, "blocks": 32}


def _files(**over):
    config = harness.load_json("configs", "churn1000.json")
    traffic = harness.load_json("traffic", "churn.json")
    config.update(over)
    return config, traffic


@pytest.fixture(scope="module")
def cell():
    config, traffic = _files(**SMALL)
    return C.build(config, traffic, SEED)


@pytest.fixture
def windowed(monkeypatch, device_standin):
    """The reactor's window precompute engaged at 16 validators."""
    monkeypatch.setattr(R, "PRECOMPUTE_MIN_SIGS", 2)
    return device_standin


def _error_row(text):
    m = re.search(r"wrong signature \(#(\d+)\)", text or "")
    return int(m.group(1)) if m else None


def _reactor_sync(data, chain):
    """``fast_sync_replay._sync``'s drive loop, keeping what stopped the
    node: -> (reactor, the answer, the error text or None)."""
    from tendermint_tpu.blockchain import BlockchainReactor, BlockPool

    n = chain["n"]
    blocks = [F._as_received(b) for b in chain["blocks"]]
    state, execu, block_store, conns = F._fresh_node(data["genesis"])
    errors = []
    try:
        reactor = BlockchainReactor(state, execu, block_store,
                                    fast_sync=True)
        reactor.pool = BlockPool(1)
        reactor.pool.set_peer_range("src", 1, n + 1)
        punish = reactor._punish

        async def spy(peers, reason):
            errors.append(reason)
            await punish(peers, reason)

        reactor._punish = spy

        async def drive():
            while reactor.blocks_synced < n:
                want = min(33, n + 2 - reactor.pool.height)
                while len(reactor.pool.peek_window(33)) < want:
                    reqs = reactor.pool.schedule_requests()
                    if not reqs:
                        break
                    for pid, h in reqs:
                        reactor.pool.add_block(pid, blocks[h - 1])
                before = reactor.blocks_synced
                try:
                    await reactor._process_window()
                except R.FatalSyncError as e:
                    errors.append(str(e))
                    return
                if reactor.blocks_synced <= before:
                    return

        asyncio.run(drive())
    finally:
        conns.stop()
    return reactor, F._answer(reactor, n), errors[0] if errors else None


def _per_block_sync(data, chain):
    """v0.34's pool routine, one block at a time: -> (answer, error)."""
    n = chain["n"]
    blocks = [F._as_received(b) for b in chain["blocks"]]
    state, execu, _store, conns = F._fresh_node(data["genesis"])
    error = None
    try:
        for h in range(1, n + 1):
            first, second = blocks[h - 1], blocks[h]
            bid = BlockID(first.hash(), first.make_part_set().header())
            try:
                state.validators.verify_commit_light(
                    state.chain_id, bid, h, second.last_commit)
                state, _ = execu.apply_block(state, bid, first)
            except Exception as e:
                error = str(e)
                break
    finally:
        conns.stop()
    st = state
    height = st.last_block_height
    bid = st.last_block_id
    answer = ("synced" if height == n else "stopped", height, st.app_hash,
              bid.hash, bid.part_set_header.total, bid.part_set_header.hash)
    return answer, error


def _both_paths(data, chain):
    reactor, got, err = _reactor_sync(data, chain)
    want, want_err = _per_block_sync(data, chain)
    assert got == want
    assert _error_row(err) == _error_row(want_err)
    return reactor, got, _error_row(err)


def _spec(data, chain):
    return churn_sync_spec.expected(data["vals"], [chain])[0]


def test_power_changes_every_height(cell, windowed):
    """(a) Every height from 3 on has a set of its own, in another order;
    the windowed reactor syncs the whole chain, as the per-block path and
    the reference do, and its windows span the changes."""
    sets = churn_sync_spec.sets_by_height(cell["vals"], cell["sound"]["txs"],
                                          32)
    orders = [tuple(sets[h][0]) for h in range(2, 33)]
    assert all(a != b for a, b in zip(orders, orders[1:]))
    reactor, got, row = _both_paths(cell, cell["sound"])
    assert got == _spec(cell, cell["sound"]) and got[:2] == ("synced", 32)
    assert row is None
    stage = reactor.stage_breakdown()
    assert stage["spanning_windows"] == 2 and stage["spanning_blocks"] == 32
    assert stage["inline_windows"] == 1 and stage["pipelined_windows"] == 1


def test_a_removal_and_a_join(cell, windowed):
    """(b) Block 16 removes a member and adds a new key; from height 18 on
    the new member signs. Its first rows were never guessed (no set stage
    A held knew its key): the apply-time rules verify them then, on the
    device, once each."""
    txs = cell["sound"]["txs"][15]
    assert sum(t.endswith(b"!0") for t in txs) == 1 and len(txs) == 7
    joiner = bytes.fromhex(txs[-2].split(b"!")[0][4:].decode())
    sets = churn_sync_spec.sets_by_height(cell["vals"], cell["sound"]["txs"],
                                          32)
    assert joiner not in sets[17][0] and joiner in sets[18][0]
    assert all(len(sets[h][0]) == 16 for h in sets)
    reactor, got, _ = _both_paths(cell, cell["sound"])
    assert got == _spec(cell, cell["sound"])
    # window 2 (heights 17-32) was prepared before block 16 applied: the
    # joiner's row of each commit 18..32 missed, once (the map learns it)
    assert reactor.stage_breakdown()["verdict_misses"] == 15


@pytest.mark.parametrize("which", [0, 1])
def test_wrong_signature_where_the_set_just_changed(cell, windowed, which):
    """(c) A wrong signature in the commit for height 12 (whose set differs
    from height 11's), inside the 2/3 prefix of height 12's own order: the
    light rule refuses block 12; past it: the full rule refuses block 13.
    Same row, same stop, as the per-block path and the reference."""
    chain = cell["tampered"][which]
    _h, row = chain["tampered"]
    sets = churn_sync_spec.sets_by_height(cell["vals"], chain["txs"], 16)
    assert sets[12] != sets[11]
    past = next(i + 1 for i, c in enumerate(np.cumsum(sets[12][1]))
                if c > sum(sets[12][1]) * 2 // 3)
    assert (row < past) == (which == 0)
    _reactor, got, got_row = _both_paths(cell, chain)
    assert got == _spec(cell, chain)
    assert got[:2] == ("stopped", 11 if which == 0 else 12)
    assert got_row == row


def test_permuted_rows_are_refused_at_the_index_rule(cell, windowed):
    """(d) The commit for height 5 with two rows swapped: each row carries
    the address, timestamp and signature of another validator than the
    set's row i. Keyed by its address (the guess) a row verifies; v0.34
    keys row i by the set's validator i and refuses the first swapped row.
    So does the windowed reactor, at that row: the guessed verdict is never
    served for the index rule's triple."""
    from tendermint_tpu.types.block import Commit

    chain = dict(cell["sound"])
    blocks = list(chain["blocks"])
    carrier = blocks[5]                       # block 6 carries commit 5
    lc = carrier.last_commit
    sigs = list(lc.signatures)
    i, j = 1, 9
    sigs[i], sigs[j] = sigs[j], sigs[i]
    swapped = Commit(lc.height, lc.round, lc.block_id, sigs)
    blocks[5] = F.as_received(carrier, last_commit=swapped,
                              header=F.as_received(carrier.header),
                              data=F.as_received(carrier.data),
                              evidence=list(carrier.evidence))
    chain["blocks"] = blocks
    _reactor, got, row = _both_paths(cell, chain)
    assert got[:2] == ("stopped", 4) and row == i


def test_counters_and_span_attributes(cell, windowed):
    """(e) The window span carries ``set_changes`` and ``guessed_rows``; the
    reactor counts spanning windows, their blocks, candidates and misses,
    and ``stage_breakdown`` reports them."""
    from tendermint_tpu.libs.trace import tracer

    tracer.clear()
    tracer.enable()
    try:
        reactor, got, _ = _reactor_sync(cell, cell["sound"])
    finally:
        tracer.disable()
    assert got[:2] == ("synced", 32)
    spans = [e for e in tracer.events() if e["name"] == "verify_window"]
    assert len(spans) == 2
    args = [e["args"] for e in spans]
    # window 1 (heights 1-16): sets 1 and 2 are genesis; 3..16 each new
    assert args[0]["set_changes"] == 14 and args[1]["set_changes"] == 15
    # stage A of window 1 held the sets of heights 0-2: the light rows of
    # commits 3..16 and the full rows of commits 3..15 are guessed; stage A
    # of window 2 held no set of its heights: all its rows are
    assert args[0]["guessed_rows"] > 0
    assert args[1]["guessed_rows"] > args[0]["guessed_rows"]
    m = reactor.metrics
    assert m.set_spanning_windows_total.value() == 2
    assert m.spanning_window_blocks_total.value() == 32
    assert m.verdict_misses_total.value() == 15
    assert m.verdict_candidates_total.value() > 0
    stage = reactor.stage_breakdown()
    assert stage["candidates"] == m.verdict_candidates_total.value()


def test_static_set_windows_and_batches_are_unchanged(windowed):
    """(f) A chain whose set never changes (the sync1000 cell's, at 16
    validators): two windows, one device batch each with both planes (the
    first window has no commit for height 0), nothing verified at apply
    time, no window counted as spanning and no guess."""
    from tendermint_tpu.libs.trace import tracer

    config = harness.load_json("configs", "sync1000.json")
    traffic = harness.load_json("traffic", "catchup.json")
    config.update(validators=16, blocks=32)
    data = F.build(config, traffic, SEED)
    tracer.clear()
    tracer.enable()
    try:
        reactor, got, err = _reactor_sync(data, data["sound"])
    finally:
        tracer.disable()
    assert got[:2] == ("synced", 32) and err is None
    assert windowed.calls == [(16 + 15) * 16, (16 + 16) * 16]
    stage = reactor.stage_breakdown()
    assert (stage["inline_windows"], stage["pipelined_windows"]) == (1, 1)
    assert stage["spanning_windows"] == stage["spanning_blocks"] == 0
    assert stage["verdict_misses"] == 0
    args = [e["args"] for e in tracer.events()
            if e["name"] == "verify_window"]
    assert [(a["set_changes"], a["guessed_rows"]) for a in args] == [
        (0, 0), (0, 0)]


def test_partial_map_verifies_only_what_it_lacks(device_standin):
    """The routing seam under a verdict map that lacks some triples: the
    map answers the rest, only the lacking ones go to the device, routed
    by the whole batch's size (one signature alone would go to the host),
    and the map learns their verdicts."""
    from tendermint_tpu import crypto

    keys = [crypto.Ed25519PrivKey.generate(bytes([i]) * 32)
            for i in range(20)]
    msgs = [b"m%d" % i for i in range(20)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    sigs[3] = sigs[4]                                  # a wrong one
    triples = [(k.pub_key().bytes(), m, s)
               for k, m, s in zip(keys, msgs, sigs)]
    pre = {t: True for t in triples[5:]}
    for k in B.stats:
        B.stats[k] = 0
    token = B.precomputed_verdicts.set(pre)
    try:
        bv = B.BatchVerifier(plane="light")
        for k, m, s in zip(keys, msgs, sigs):
            bv.add(k.pub_key(), m, s)
        ok, per = bv.verify()
    finally:
        B.precomputed_verdicts.reset(token)
    assert not ok
    assert list(per) == [True, True, True, False, True] + [True] * 15
    assert device_standin.calls == [5]
    assert B.stats["device_sigs"] == 5 and B.stats["host_sigs"] == 0
    assert B.stats["precomputed_misses"] == 5
    assert B.stats["precomputed_sigs"] == 15
    assert pre[triples[3]] is False and pre[triples[0]] is True


def test_cell_data_is_what_its_files_say():
    """(g) The cell's files: 1,000 validators at power 10, 96 blocks, 16
    pairs a window, 4 power moves a block to 5-15, a member swapped every
    16th block; the sizes the traffic names; the transactions the driver
    makes at the files' own rates."""
    import data as D

    config, traffic = _files()
    assert (config["validators"], config["power"], config["blocks"],
            config["verify_window_pairs"]) == (1000, 10, 96, 16)
    assert config["reduced"] == ["blocks"] and config["chips"] == 1
    assert config["reference"] == "churn_sync_spec"
    assert config["churn"] == {"power_moves_per_block": 4,
                               "move_powers": [5, 15],
                               "swap_every_blocks": 16, "joiner_power": 10}
    assert traffic["driver"] == "churn_sync_replay"
    assert [t["row"] for t in traffic["tampered_chains"]] == [
        "inside_two_thirds", "past_two_thirds"]
    assert {t["commit_height"] for t in traffic["tampered_chains"]} == {12}
    vals = D.make_validators(64, SEED, power=10)
    txs, joiners = C.churn_txs(vals, config["churn"], 97, SEED)
    assert len(txs) == 97 and len(joiners) == 6
    power = dict(zip(vals.pubkeys, vals.powers))
    for h, block in enumerate(txs, 1):
        assert block[-1] == b"h%d=v" % h
        ups = churn_sync_spec.updates(block)
        assert len(ups) == (6 if h % 16 == 0 else 4)
        moved = ups[:4]
        assert all(pk in power and 5 <= p <= 15 and p != power[pk]
                   for pk, p in moved)
        for pk, p in ups:
            if p:
                power[pk] = p
            else:
                del power[pk]
        assert len(power) == 64
    assert txs == C.churn_txs(vals, config["churn"], 97, SEED)[0]
    assert txs != C.churn_txs(vals, config["churn"], 97, SEED + 1)[0]


def test_small_cell_runs_correct_and_its_control_does_not(windowed):
    """(g) The harness's whole run at the small size: every answer the
    reference's, every signature an answer relies on counted on the device,
    every block in a spanning window; the control (every signature taken on
    trust) is not correct."""
    out = harness.run_cell("churn1000.catchup", SEED, 0.2, False,
                           time.perf_counter(), overrides=dict(SMALL),
                           control="also")
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 3 and out["failed"] == 0
    ctl = out["compared_control"]
    assert ctl["sync_state_mismatches"]["value"] > 0
    win = out["compared"]
    assert win["host_routed_sigs"]["value"] == 0


def test_parent_side_counts_no_spanning_block(cell, windowed, monkeypatch):
    """Against a reactor whose ``stage_breakdown`` lacks the new counters
    (the parent side of a comparison), the window counts no spanning block
    and no candidate, and the two metrics read 0 and nothing."""
    real = R.BlockchainReactor.stage_breakdown

    def old(self):
        return {k: v for k, v in real(self).items()
                if k not in C.COUNTERS.values()}

    monkeypatch.setattr(R.BlockchainReactor, "stage_breakdown", old)
    data = dict(cell, extras=dict(cell["extras"]))
    requests = C.window(data, 0.0, harness.Probe())
    assert not any(r["failed"] for r in requests)
    win = harness.Window({}, {}, {}, {}, 0.0, 0.0, 1.0, requests, {}, {},
                         [], extras=data["extras"])
    assert data["extras"]["blocks"] > 0
    assert harness.read_metric("spanning_window_share.churn", win) == 0.0
    assert harness.read_metric("verdict_miss_share.churn", win) is None


def test_warm_keeps_the_set_up_heap_out_of_the_collector_until_the_window_ends(
        cell, windowed):
    """The run's own objects are frozen out of the cyclic collector after
    warm-up (set-up) and back in its reach once the window's last request
    has ended; the window still syncs every chain."""
    import gc

    data = dict(cell, extras=dict(cell["extras"]))
    try:
        C.warm(data)
        assert gc.get_freeze_count() > 0
        requests = C.window(data, 0.0, harness.Probe())
        assert gc.get_freeze_count() == 0
    finally:
        gc.unfreeze()
    assert [r["chain"] for r in requests] == [-1, 0, 1]
    assert not any(r["failed"] for r in requests)
