"""Seeded chaos matrix: every fault site × several seeds, pass/fail table.

Each cell runs in a FRESH subprocess (fault plane, breaker, and fail-point
state are process-global by design) and exercises one injection site with a
deterministic seed, asserting the survival property that site promises:

* device.batch_verify — injected device errors: host fallback keeps
  verdicts byte-identical, breaker opens and re-closes
* device.lane         — ONE device label armed (device.lane.<label>): the
  multi-device pool degrades to the healthy lanes, re-shards the sick
  lane's segments with zero dropped signatures, verdicts byte-identical;
  a healed lane rejoins
* device.vote_flush   — same through the vote micro-batcher (futures all
  resolve correctly, no device error ever surfaces)
* wal.fsync           — fsync EIO (policy=raise here): records past the
  last good fsync may be lost, records before it NEVER; replay stays clean
* db.write_batch      — BufferedDB flush fault: staged window preserved,
  retry after disarm lands every record (no handled-but-not-durable)
* net.drop            — 4-node in-proc net commits +3 heights under seeded
  10% loss with identical block hashes (the slow cell, ~30-60s)
* ingest.mempool_full — open-loop tx load (loadtime schedule) into a
  validator with an 8-slot mempool while another validator is partitioned
  away: reason="full" rejections fire, the tx lifecycle ring stays
  bounded, honest 3/4 keep committing hash-identical blocks
* ingest.backpressure — open-loop overload through the ASYNC admission
  pipeline (mempool/ingest.py) against a 16-slot intake queue on a
  sharded-lane mempool, one validator partitioned away: reason-labeled
  sheds fire (queue-full), every shed comes back as an explicit
  rejection (never a stall), the intake queue never exceeds its bound,
  honest 3/4 keep committing hash-identical blocks

Adversarial (content-corruption) cells — the Byzantine chaos suite:

* net.corrupt              — 4-node net stays live and hash-identical while
  a capped 10% of in-flight payloads get a bit flipped (receivers drop the
  corrupting link; persistent-peer-style reconnects re-heal it); injection
  count replays exactly for a seed
* statesync.lying_chunk    — a restore served by honest peers + one liar
  completes anyway: per-chunk verification strikes the liar, bans it after
  K bad chunks, refetches from honest peers
* statesync.lying_snapshot — a snapshot advertised with a bogus hash is
  restored, fails the trusted-app-hash check, its advertiser is struck,
  and re-discovery finds the honest snapshot
* blocksync.bad_block      — a fresh node fast-syncs a chain although its
  providers serve a capped number of tampered block responses (redo +
  scoreboard backoff/ban)
* combo.maverick_corrupt   — double-prevoting validator AND corrupt links
  at once; honest nodes agree (the slow combo cell)

Churn cells — membership change as the fault (tools/churn.py rig):

* churn.flap        — one node leaves and re-joins 3 times (fresh stores:
  every re-entry is a full statesync restore over the wire); survivors
  never redial the departed id, every rejoin reaches caught-up, hashes
  stay identical
* churn.rotate      — the full churn schedule at N=8 under open-loop load:
  one statesync join + one clean leave per interval, the validator set
  rotating via kvstore val: txs across app-driven prune boundaries;
  survivor app-hash agreement, every retained height's validator set
  resolves, AddrBook/peerscore state bounded
* churn.partition32 — the partition cell re-run at scale: a 32-node SPARSE
  net (4 validators + 28 fulls, ring+chords degree 4) has 8 nodes
  blackholed, the majority keeps committing, heal reconverges everyone to
  identical hashes
* churn.corrupt32   — the corruption cell re-run at scale: the 32-node
  sparse net survives capped bit flips on in-flight payloads (receivers
  drop corrupting links, the redial loop re-heals), hashes identical

Degraded-network cells — the hard regimes of partial synchrony
(tools/quorum_loss.py + p2p/inproc.py link profiles):

* net.quorum_loss — a seeded >1/3 isolation window over a live
  4-validator fleet: height halts, zero conflicting commits, zero
  equivocations, the watchdog reports halt_reason="quorum_lost" from
  the blocking stage's vote bitmap, heal recovers to hash-identical
  commits within the bound; run twice to pin the same-seed outcome
  fingerprint
* net.asym        — the seeded ``asym`` profile (one lossy direction per
  pair, the reverse clean): the fleet keeps committing through the
  asymmetry and reconverges hash-identical once cleared
* net.gray        — ``gray`` links (60% loss, traffic still leaks) on
  every link touching one node: quorum keeps committing, the gray node
  is never declared dead and catches up hash-identical after the clear

Execution cells — the parallel-execution plane (state/parallel.py):

* exec.conflict_storm — every tx of every block writes the SAME key while
  the ``exec.conflict`` site scrambles conflict-lane assignments: the
  worst case for optimistic execution (everything conflicts, speculation
  buys nothing, validation + serial re-execution must carry the whole
  block). Commits must stay byte-identical to the serial spec — responses,
  app hash, results hash — across 3 heights

Crash cells — process death as the fault (tools/crashmatrix.py plane):

* crash.torn_wal — seeded torn WAL appends (``wal.torn_write``): replay
  stops at the tear, repair-on-open truncates the undecodable tail, and
  records appended AFTER the repair are never stranded behind garbage
* crash.privval  — a torn last-sign-state write (``privval.torn_state``):
  FilePV.load refuses to start with an actionable error naming the file
  (never a silent height-0 reset — that is the double-sign hazard)
* crash.loop     — the restart supervisor against an instant crasher:
  bounded exponential backoff walks its schedule, give-up fires after
  max_restarts consecutive fast crashes, and the crash-loop debugdump
  bundle records the full exit history

    python tools/chaos_matrix.py                     # full matrix
    python tools/chaos_matrix.py --quick             # skip the net cells
    python tools/chaos_matrix.py --sites statesync.lying_chunk --seeds 1,2
    python tools/chaos_matrix.py --self-test         # CI guard, seconds

Stdlib-only at the top level (argparse/subprocess/time): repo imports
happen inside cells so --help and --self-test's plumbing checks work
anywhere; the cells themselves need the repo on PYTHONPATH (the tool adds
it).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `python tools/chaos_matrix.py` puts tools/ first
    sys.path.insert(0, REPO)

DEFAULT_SEEDS = (1, 2, 3)
#: cell name -> slow?
SITES = {
    "device.batch_verify": False,
    "device.lane": False,
    "device.vote_flush": False,
    "wal.fsync": False,
    "db.write_batch": False,
    "net.drop": True,
    "ingest.mempool_full": True,
    "ingest.backpressure": True,
    # adversarial cells (content corruption / Byzantine peers)
    "net.corrupt": True,
    "statesync.lying_chunk": False,
    "statesync.lying_snapshot": False,
    "blocksync.bad_block": True,
    "lightserve.lying_server": False,
    "combo.maverick_corrupt": True,
    # churn cells (membership change as the fault; tools/churn.py rig)
    "churn.flap": True,
    "churn.rotate": True,
    "churn.partition32": True,
    "churn.corrupt32": True,
    # degraded-network cells (quorum loss + link profiles;
    # tools/quorum_loss.py + p2p/inproc.py LINK_PROFILES)
    "net.quorum_loss": True,
    "net.asym": True,
    "net.gray": True,
    # execution cells (the parallel-execution plane; state/parallel.py)
    "exec.conflict_storm": False,
    # aggregate-signature cells (the BLS commit plane; crypto/bls12381)
    "aggsig.degrade": False,
    # crash cells (process death as the fault; tools/crashmatrix.py plane)
    "crash.torn_wal": False,
    "crash.privval": False,
    "crash.loop": False,
    # game-day cell (the SLO soak plane; tools/soak.py + libs/slo.py)
    "soak.gameday": False,
}


def _pin_cpu_jax() -> None:
    """Mirror tests/conftest.py: pin jax to 8 virtual CPU devices and arm
    the repo's persistent compilation cache — the ed25519 verify kernel
    takes minutes to compile on CPU, and every cell is a fresh process."""
    if os.environ.get("TM_ON_DEVICE") == "1":
        return
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    from tendermint_tpu.libs.compilecache import enable_compile_cache

    enable_compile_cache()


# -- cells (each runs in its own subprocess via --cell) ----------------------

def _signed(n, seed):
    from tendermint_tpu.crypto import Ed25519PrivKey

    out = []
    for i in range(n):
        sk = Ed25519PrivKey.generate(bytes([seed & 0xFF]) * 31 + bytes([i]))
        msg = b"chaos-%d-%d" % (seed, i)
        out.append((sk.pub_key(), msg, sk.sign(msg)))
    return out


def cell_device_batch_verify(seed: int) -> None:
    import numpy as np

    from tendermint_tpu.crypto.batch import BatchVerifier
    from tendermint_tpu.crypto.breaker import CLOSED, device_breaker
    from tendermint_tpu.libs.faults import faults

    device_breaker.failure_threshold = 2
    device_breaker.cooldown_s = 0.05
    faults.configure("device.batch_verify@0.6", seed=seed)
    cases = _signed(6, seed)
    for round_ in range(12):
        bv = BatchVerifier(backend="jax", plane="votes")
        bad = round_ % len(cases)
        for i, (pub, msg, sig) in enumerate(cases):
            bv.add(pub, msg, sig if i != bad
                   else sig[:-1] + bytes([sig[-1] ^ 1]))
        ok, per = bv.verify()
        expect = np.ones(len(cases), dtype=bool)
        expect[bad] = False
        assert not ok and (per == expect).all(), \
            f"round {round_}: verdicts diverged under injection: {per}"
        time.sleep(0.01)  # lets an OPEN breaker reach its half-open probe
    assert faults.fires("device.batch_verify") > 0, "site never fired"
    faults.reset()
    time.sleep(0.06)
    bv = BatchVerifier(backend="jax", plane="votes")
    for pub, msg, sig in cases:
        bv.add(pub, msg, sig)
    ok, _ = bv.verify()  # half-open probe (or already-closed device route)
    assert ok
    assert device_breaker.state == CLOSED, device_breaker.state


def cell_device_lane(seed: int) -> None:
    """One sick chip in the multi-device pool: the per-lane fault site
    (``device.lane.<label>``) is armed against EXACTLY ONE device label,
    its breaker opens, the pool degrades to the healthy peers with
    byte-identical verdicts and zero dropped signatures, and a healed lane
    rejoins. Shape-identical stub kernels (tools/stub_kernels.py) keep this
    off the multi-minute per-ordinal CPU compiles of the real kernel."""
    import os

    import numpy as np

    os.environ["TMTPU_DEVICE_BREAKER_THRESHOLD"] = "2"
    os.environ["TMTPU_DEVICE_BREAKER_COOLDOWN_S"] = "0.05"

    import jax
    import stub_kernels

    from tendermint_tpu.crypto.breaker import (
        CLOSED,
        OPEN,
        lane_breaker,
        reset_lane_breakers,
    )
    from tendermint_tpu.crypto.ed25519_jax import multidevice as MD
    from tendermint_tpu.crypto.ed25519_jax import verify as V
    from tendermint_tpu.libs.faults import faults

    restore = stub_kernels.install_stub_kernels(V)
    try:
        rng = np.random.default_rng(seed)
        n = 1280
        pks = [rng.bytes(32) for _ in range(n)]
        msgs = [rng.bytes(120) for _ in range(n)]
        sigs = [rng.bytes(63) + b"\x00" for _ in range(n)]
        want = V._verify_segmented(pks, msgs, sigs, V.LANE)
        devs = jax.devices()[:4]
        sick = f"{devs[1].platform}:{devs[1].id}"
        faults.configure(f"device.lane.{sick}", seed=seed)  # always fires
        pool = MD.MultiDeviceStream(devices=devs, min_sigs=0)
        for round_ in range(4):
            got = pool.verify(pks, msgs, sigs, chunk=V.LANE)
            assert (got == want).all(), \
                f"round {round_}: verdicts diverged under lane injection"
        assert faults.fires(f"device.lane.{sick}") >= 2, "site never fired"
        assert lane_breaker(sick).state == OPEN, lane_breaker(sick).state
        assert pool.stats["resharded_segments"] >= 1
        # heal: disarm + clear breakers — the lane rejoins and verdicts
        # stay identical
        faults.reset()
        reset_lane_breakers()
        pool2 = MD.MultiDeviceStream(devices=devs, min_sigs=0)
        got = pool2.verify(pks, msgs, sigs, chunk=V.LANE)
        assert (got == want).all()
        assert lane_breaker(sick).state == CLOSED
        pool.shutdown()
        pool2.shutdown()
    finally:
        restore()


def cell_device_vote_flush(seed: int) -> None:
    import asyncio

    from tendermint_tpu.crypto.vote_batcher import BatchVoteVerifier
    from tendermint_tpu.libs.faults import faults

    faults.configure("device.vote_flush@0.5", seed=seed)
    verifier = BatchVoteVerifier(min_device_batch=2, deadline_s=0.005,
                                 device_timeout_s=600.0)

    async def run():
        for round_ in range(8):
            cases = _signed(4, seed * 100 + round_)
            bad = round_ % len(cases)
            results = await asyncio.gather(*(
                verifier.preverify(pub, msg, sig if i != bad
                                   else sig[:-1] + bytes([sig[-1] ^ 1]))
                for i, (pub, msg, sig) in enumerate(cases)))
            expect = [i != bad for i in range(len(cases))]
            assert results == expect, \
                f"round {round_}: {results} != {expect}"

    asyncio.run(run())


def cell_wal_fsync(seed: int) -> None:
    import tempfile

    from tendermint_tpu.consensus.wal import WAL, FsyncError
    from tendermint_tpu.libs.faults import faults

    path = os.path.join(tempfile.mkdtemp(prefix="chaos-wal-"), "cs.wal")
    WAL.fsync_error_policy = "raise"  # in-process harness; nodes use exit
    wal = WAL(path)  # the constructor's boot-marker sync runs un-armed
    k = seed % 5
    faults.configure(f"wal.fsync*1+{k}", seed=seed)  # fail the (k+1)-th
    written = 0
    try:
        for h in range(1, 30):
            wal.write_end_height(h, 1_700_000_000_000_000_000 + h)
            written += 1
        raise AssertionError("fault never fired")
    except FsyncError:
        pass
    wal.close()
    faults.reset()
    replayed = [m.data["height"] for m in WAL(path).iter_messages()
                if m.type == "end_height"]
    # boot marker, then every appended record: the failed-fsync record was
    # appended+flushed BEFORE its fsync, so it replays too — the crash
    # loses durability guarantees, never framing or durable prefixes
    assert replayed == [0] + list(range(1, written + 2)), \
        f"replay mismatch after injected fsync failure: {replayed}"


def cell_db_write_batch(seed: int) -> None:
    from tendermint_tpu.libs.db import BufferedDB, MemDB
    from tendermint_tpu.libs.faults import faults

    base = MemDB()
    buf = BufferedDB(base)
    keys = [b"k%d-%d" % (seed, i) for i in range(20)]
    for k in keys:
        buf.set(k, b"v" + k)
    faults.configure("db.write_batch*1", seed=seed)
    try:
        buf.flush()
        raise AssertionError("injected flush fault never raised")
    except OSError:
        pass
    # handled-but-not-durable guard: the window is still staged and the
    # base untouched; a disarmed retry lands everything
    assert base.get(keys[0]) is None
    assert buf.get(keys[0]) == b"v" + keys[0]
    faults.reset()
    buf.flush()
    for k in keys:
        assert base.get(k) == b"v" + k, f"record lost across retry: {k}"


def cell_net_drop(seed: int) -> None:
    import asyncio

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_consensus_net import make_net, wait_all_height

    from tendermint_tpu.p2p import InProcNetwork

    async def run():
        nodes = make_net(4)
        net = InProcNetwork()
        for nd in nodes:
            net.add_switch(nd.switch)
        for nd in nodes:
            await nd.start()
        await net.connect_all()
        try:
            await wait_all_height(nodes, 2, timeout=60)
            net.set_loss(0.10, seed=seed)
            h0 = min(nd.cs.state.last_block_height for nd in nodes)
            await wait_all_height(nodes, h0 + 3, timeout=120)
            assert net.chaos_stats()["dropped"] > 0
        finally:
            for nd in nodes:
                await nd.stop()
        common = min(nd.cs.state.last_block_height for nd in nodes) - 1
        hashes = {nd.block_store.load_block_meta(common).header.hash()
                  for nd in nodes}
        assert len(hashes) == 1, "divergent block hashes under loss"

    asyncio.run(run())


def cell_ingest_mempool_full(seed: int) -> None:
    """Ingestion-plane overload: open-loop tx load (tools/loadtime.py
    schedule, fixed-rate grid) into ONE validator whose mempool is shrunk
    to 8 slots, while a second validator is partitioned clean away. The
    survival property: rejection counters fire with reason="full", the
    tx lifecycle ring/active map stay bounded under the firehose, and the
    3/4 honest majority keeps committing with identical hashes."""
    import asyncio

    sys.path.insert(0, os.path.join(REPO, "tests"))
    import loadtime as LT
    from test_consensus_net import make_net, wait_all_height

    from tendermint_tpu.libs.metrics import MempoolMetrics, Registry
    from tendermint_tpu.libs.txlife import TxLifecycle
    from tendermint_tpu.mempool.clist_mempool import MempoolError
    from tendermint_tpu.p2p import InProcNetwork

    ring_cap, active_cap = 32, 64
    m = MempoolMetrics(Registry())
    tl = TxLifecycle(sample_rate=1.0, ring_capacity=ring_cap,
                     active_capacity=active_cap)
    tl.metrics = m

    async def run():
        nodes = make_net(4)
        victim = nodes[0].mempool
        victim._max_txs = 8  # 8 slots vs a 400 tx/s firehose: always full
        victim.metrics = m
        victim.txlife = tl
        net = InProcNetwork()
        for nd in nodes:
            net.add_switch(nd.switch)
        for nd in nodes:
            await nd.start()
        await net.connect_all()
        try:
            await wait_all_height(nodes, 2, timeout=60)
            # one node partitioned clean away: 3/4 voting power remains
            net.partition({"node0", "node1", "node2"}, {"node3"})
            honest = nodes[:3]
            h0 = min(nd.cs.state.last_block_height for nd in honest)
            loop = asyncio.get_running_loop()
            sched = LT.plan_schedule(400.0, 240, t0=loop.time() + 0.05)
            rejected = 0
            for i, target in enumerate(sched):
                now = loop.time()
                if target > now:
                    await asyncio.sleep(target - now)
                tx = b"ingest-%d-%d=" % (seed, i) + b"x" * 64
                try:
                    victim.check_tx(tx)
                except MempoolError:
                    rejected += 1
            assert rejected > 0, "mempool never filled under open-loop load"
            # honest majority commits +2 heights DURING/after the overload
            await wait_all_height(honest, h0 + 2, timeout=120)
        finally:
            for nd in nodes:
                await nd.stop()
        common = min(nd.cs.state.last_block_height for nd in nodes[:3]) - 1
        hashes = {nd.block_store.load_block_meta(common).header.hash()
                  for nd in nodes[:3]}
        assert len(hashes) == 1, "divergent hashes among honest nodes"

    asyncio.run(run())
    # rejection counters fired with the right taxonomy...
    assert m.failed_txs.value("full") > 0, "full-mempool counter never fired"
    # ...and the lifecycle plane stayed bounded under the firehose
    snap = tl.snapshot(10 ** 6)
    assert len(snap["records"]) <= ring_cap, len(snap["records"])
    assert snap["active"] <= active_cap, snap["active"]
    assert snap["sealed_total"] > 0
    # depth gauges were maintained on every mutation path: the final value
    # is the real (small) post-run depth, never a stale high-water mark
    assert m.size.value() <= 8, m.size.value()


def cell_ingest_backpressure(seed: int) -> None:
    """Admission-control overload: an open-loop firehose (400 tx/s on the
    loadtime fixed-rate grid) through the ASYNC ingest pipeline into a
    sharded-lane mempool whose intake queue holds 16 slots, while one of
    4 validators is partitioned away. Survival properties: reason-labeled
    sheds fire (queue-full) and come back as explicit rejections — never
    a stall —, the intake queue never exceeds its bound, admitted txs
    flow through the lanes into blocks, and the honest 3/4 keep
    committing identical hashes."""
    import asyncio

    sys.path.insert(0, os.path.join(REPO, "tests"))
    import loadtime as LT
    from test_consensus_net import make_net, wait_all_height

    from tendermint_tpu.libs.metrics import MempoolMetrics, Registry
    from tendermint_tpu.mempool.ingest import IngestPipeline, ShardedMempool
    from tendermint_tpu.p2p import InProcNetwork

    queue_limit = 16
    m = MempoolMetrics(Registry())

    async def run():
        nodes = make_net(4)
        # node0 runs the production fast path: sharded lanes behind the
        # same surface, rewired everywhere its CList was
        sm = ShardedMempool(nodes[0].conns.mempool, lanes=4)
        sm.metrics = m
        nodes[0].mempool = sm
        nodes[0].block_exec.mempool = sm
        nodes[0].mp_reactor.mempool = sm
        sm.tx_available_callbacks.append(nodes[0].cs.notify_txs_available)
        # deadline-paced flushes (batch_max above the bound): a 400 tx/s
        # firehose fills 16 slots in 40 ms, well inside the 100 ms flush
        # cadence — the front door MUST shed, and only the front door
        pipe = IngestPipeline(sm, batch_max=256, batch_deadline_s=0.1,
                              queue_limit=queue_limit)
        pipe.metrics = m
        net = InProcNetwork()
        for nd in nodes:
            net.add_switch(nd.switch)
        for nd in nodes:
            await nd.start()
        await net.connect_all()
        max_depth = 0
        try:
            await wait_all_height(nodes, 2, timeout=60)
            net.partition({"node0", "node1", "node2"}, {"node3"})
            honest = nodes[:3]
            h0 = min(nd.cs.state.last_block_height for nd in honest)
            loop = asyncio.get_running_loop()
            sched = LT.plan_schedule(400.0, 240, t0=loop.time() + 0.05)
            accepted = 0
            for i, target in enumerate(sched):
                now = loop.time()
                if target > now:
                    await asyncio.sleep(target - now)
                tx = b"bp-%d-%d=" % (seed, i) + b"x" * 64
                if pipe.submit_nowait(tx):
                    accepted += 1
                max_depth = max(max_depth, pipe.queue_depth())
            await pipe.flush_now()
            assert accepted > 0, "pipeline admitted nothing"
            # overload DID shed, with the right reason, as explicit
            # (awaitable) rejections — the submit path never raises/stalls
            shed = await pipe.submit(b"bp-probe" + b"y" * 64) \
                if pipe.queue_depth() >= queue_limit else None
            assert pipe.stats["shed_queue-full"] > 0, dict(pipe.stats)
            if shed is not None:
                assert shed.code == 1 and "queue-full" in shed.log
            # honest majority commits +2 heights during/after the storm
            await wait_all_height(honest, h0 + 2, timeout=120)
        finally:
            await pipe.stop()
            for nd in nodes:
                await nd.stop()
        assert max_depth <= queue_limit, \
            f"intake queue exceeded its bound: {max_depth}"
        common = min(nd.cs.state.last_block_height for nd in nodes[:3]) - 1
        hashes = {nd.block_store.load_block_meta(common).header.hash()
                  for nd in nodes[:3]}
        assert len(hashes) == 1, "divergent hashes among honest nodes"

    asyncio.run(run())
    assert m.shed_txs_total.value("queue-full") > 0, \
        "queue-full shed counter never fired"
    # no other shed reason applies to this cell's knobs
    assert m.shed_txs_total.value("sender-rate") == 0
    assert m.shed_txs_total.value("fee-floor") == 0


async def _live_net_under(site_spec: str, seed: int, extra_heights: int = 3,
                          mavericks=None, post_wait=None):
    """Shared adversarial-net driver: 4 in-proc validators, the given fault
    spec armed mid-run, a persistent-peer-style reconnect loop (corrupted
    payloads make receivers drop links), +N heights, identical hashes.
    ``post_wait`` (async) runs while the net is still live — e.g. to wait
    for an injection cap to be reached."""
    import asyncio

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_consensus_net import make_net, wait_all_height

    from tendermint_tpu.libs.faults import faults
    from tendermint_tpu.p2p import InProcNetwork

    nodes = make_net(4)
    for idx, height_map in (mavericks or {}).items():
        nodes[idx].cs.misbehaviors = dict(height_map)
    net = InProcNetwork()
    for nd in nodes:
        net.add_switch(nd.switch)
    for nd in nodes:
        await nd.start()
    await net.connect_all()

    async def rewire():
        while True:
            await asyncio.sleep(0.3)
            await net.reconnect_missing()

    rewire_task = asyncio.create_task(rewire())
    try:
        await wait_all_height(nodes, 2, timeout=60)
        faults.configure(site_spec, seed=seed)
        h0 = min(nd.cs.state.last_block_height for nd in nodes)
        await wait_all_height(nodes, h0 + extra_heights, timeout=180)
        if post_wait is not None:
            await post_wait()
        # disarm BEFORE teardown so shutdown traffic doesn't tail-fire
        faults.reset()
    finally:
        rewire_task.cancel()
        for nd in nodes:
            await nd.stop()
    common = min(nd.cs.state.last_block_height for nd in nodes) - 1
    hashes = {nd.block_store.load_block_meta(common).header.hash()
              for nd in nodes}
    assert len(hashes) == 1, "divergent block hashes under corruption"


def cell_net_corrupt(seed: int) -> None:
    import asyncio

    from tendermint_tpu.libs.faults import faults

    cap = 30
    observed = []

    async def until_cap():
        # the armed net keeps committing (empty blocks) so traffic keeps
        # evaluating the site; the cap WILL be reached — wait for it so the
        # injection count is exactly reproducible across seeds/runs
        deadline = asyncio.get_running_loop().time() + 60
        while faults.fires("net.corrupt") < cap:
            if asyncio.get_running_loop().time() > deadline:
                break
            await asyncio.sleep(0.25)
        observed.append(faults.fires("net.corrupt"))

    asyncio.run(_live_net_under(f"net.corrupt@0.1*{cap}", seed,
                                post_wait=until_cap))
    assert observed and observed[0] == cap, \
        f"expected {cap} injections, saw {observed}"


def cell_combo_maverick_corrupt(seed: int) -> None:
    """The Byzantine combo: a double-prevoting validator AND corrupt links
    at once — honest nodes must keep committing and agree."""
    import asyncio

    from tendermint_tpu.libs.faults import faults

    observed = []

    async def snap_fires():
        observed.append(faults.fires("net.corrupt"))

    asyncio.run(_live_net_under("net.corrupt@0.1*10", seed,
                                extra_heights=4,
                                mavericks={3: {3: "double-prevote"}},
                                post_wait=snap_fires))
    assert observed and observed[0] > 0, "site never fired"


def _statesync_harness():
    """Server app with a multi-chunk snapshot + fresh client app + stub
    state provider — the in-proc Byzantine statesync rig."""
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.example.kvstore import SnapshotKVStoreApplication
    from tendermint_tpu.statesync.stateprovider import StateProvider

    server = SnapshotKVStoreApplication(interval=1)
    for i in range(40):
        server.deliver_tx(abci.RequestDeliverTx(
            tx=f"key{i:03d}={'v' * 150}".encode()))
    server.commit()  # height 1: snapshot with ~7 chunks
    client = SnapshotKVStoreApplication(interval=1)

    class StubProvider(StateProvider):
        async def app_hash(self, height):
            return server.app_hash

        async def commit(self, height):
            return "commit"

        async def state(self, height):
            return "state"

    return server, client, StubProvider()


def _run_lying_chunk_restore(seed: int):
    """One full restore against 2 honest peers + 1 always-lying chunk
    server; returns (syncer, injected fire count)."""
    import asyncio
    import random as _random

    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.libs.faults import faults
    from tendermint_tpu.libs.peerscore import PeerScoreboard
    from tendermint_tpu.statesync.msgs import ChunkResponse
    from tendermint_tpu.statesync.syncer import Syncer

    server, client, provider = _statesync_harness()
    faults.configure("statesync.lying_chunk", seed=seed)

    async def run():
        async def request_chunk(peer_id, height, fmt, idx):
            resp = server.load_snapshot_chunk(
                abci.RequestLoadSnapshotChunk(height, fmt, idx))
            chunk = resp.chunk
            if peer_id == "liar":  # the serving reactor's fault seam
                chunk = faults.mutate("statesync.lying_chunk", chunk)
            syncer.add_chunk(
                ChunkResponse(height, fmt, idx, chunk, not resp.chunk),
                peer_id)

        syncer = Syncer(client, client, provider, request_chunk,
                        chunk_timeout=2.0,
                        rng=_random.Random(seed),
                        scoreboard=PeerScoreboard(ban_threshold=2, seed=seed))
        snaps = server.list_snapshots(abci.RequestListSnapshots()).snapshots
        for s in snaps:
            for pid in ("honest-a", "honest-b", "liar"):
                syncer.add_snapshot(pid, s)
        state, commit = await syncer.sync_any(discovery_time=0.01)
        assert (state, commit) == ("state", "commit")
        return syncer

    syncer = asyncio.run(run())
    assert client.state == server.state, "restored state diverged"
    return syncer, faults.fires("statesync.lying_chunk")


def cell_statesync_lying_chunk(seed: int) -> None:
    from tendermint_tpu.libs.faults import faults

    syncer, fires1 = _run_lying_chunk_restore(seed)
    assert fires1 > 0, "liar was never asked for a chunk"
    assert syncer.scoreboard.banned("liar"), \
        f"liar not banned: {syncer.scoreboard.snapshot()}"
    assert not syncer.scoreboard.banned("honest-a")
    assert not syncer.scoreboard.banned("honest-b")
    # replayability: same seed, fresh plane -> identical injection count
    faults.reset()
    syncer2, fires2 = _run_lying_chunk_restore(seed)
    assert fires2 == fires1, f"injection count diverged: {fires1} != {fires2}"
    assert syncer2.scoreboard.banned("liar")


def cell_statesync_lying_snapshot(seed: int) -> None:
    import asyncio

    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.libs.faults import faults
    from tendermint_tpu.libs.peerscore import PeerScoreboard
    from tendermint_tpu.statesync.msgs import ChunkResponse
    from tendermint_tpu.statesync.syncer import Syncer

    server, client, provider = _statesync_harness()
    faults.configure("statesync.lying_snapshot*1", seed=seed)

    async def run():
        async def request_chunk(peer_id, height, fmt, idx):
            resp = server.load_snapshot_chunk(
                abci.RequestLoadSnapshotChunk(height, fmt, idx))
            syncer.add_chunk(
                ChunkResponse(height, fmt, idx, resp.chunk, not resp.chunk),
                peer_id)

        syncer = Syncer(client, client, provider, request_chunk,
                        chunk_timeout=2.0,
                        scoreboard=PeerScoreboard(ban_threshold=1, seed=seed))
        snaps = server.list_snapshots(abci.RequestListSnapshots()).snapshots

        def rediscover():
            # honest advertisers answer the re-ask after the lie collapses
            for s in snaps:
                for pid in ("honest-a", "honest-b"):
                    syncer.add_snapshot(pid, s)

        # initially only the liar has been heard from — with a bogus hash
        # (the serving reactor's statesync.lying_snapshot seam); tampered
        # COPIES so the honest re-advertisements above stay honest
        for s in snaps:
            syncer.add_snapshot("liar", abci.Snapshot(
                s.height, s.format, s.chunks,
                faults.mutate("statesync.lying_snapshot", s.hash),
                s.metadata))
        state, commit = await syncer.sync_any(discovery_time=0.05,
                                              rediscover=rediscover)
        assert (state, commit) == ("state", "commit")
        return syncer

    syncer = asyncio.run(run())
    assert client.state == server.state
    assert syncer.scoreboard.banned("liar"), \
        f"lying advertiser not banned: {syncer.scoreboard.snapshot()}"
    assert faults.fires("statesync.lying_snapshot") == 1


def cell_blocksync_bad_block(seed: int) -> None:
    """A fresh node fast-syncs although providers serve a capped number of
    tampered block responses: redo + scoreboard strikes, never a wedge."""
    import asyncio

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_block_sync import SyncNode, build_chain
    from tendermint_tpu import crypto
    from tendermint_tpu.libs.faults import faults
    from tendermint_tpu.p2p import InProcNetwork
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

    os.environ.setdefault("TMTPU_BATCH_BACKEND", "host")
    pv = MockPV(crypto.Ed25519PrivKey.generate(b"\x42" * 32))
    genesis = GenesisDoc(
        chain_id="sync-chain", genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(pv.get_pub_key(), 10)])

    async def run():
        from dataclasses import replace

        from tendermint_tpu.consensus.config import test_consensus_config

        quiet = replace(test_consensus_config(), create_empty_blocks=False)
        chain = build_chain(40, pv, genesis)
        src_a = SyncNode("src_a", genesis, pv=pv, fast_sync=False,
                         chain=chain, config=quiet)
        src_b = SyncNode("src_b", genesis, pv=None, fast_sync=True,
                         config=quiet)
        fresh = SyncNode("fresh", genesis, pv=None, fast_sync=True,
                         config=quiet)
        net = InProcNetwork()
        for nd in (src_a, src_b, fresh):
            net.add_switch(nd.switch)
        await src_a.start()
        await src_b.start()
        await net.connect("src_a", "src_b")
        # second source catches up honestly first, then serves too
        await asyncio.wait_for(src_b.bc_reactor.synced.wait(), timeout=120)
        # arm AFTER the honest warm-up: the very next served block response
        # is tampered (*1 => exactly one injection, every seed, every run)
        faults.configure("blocksync.bad_block*1", seed=seed)

        async def rewire():
            # a corrupted response that fails decode drops the link; the
            # in-proc analog of persistent-peer redial keeps serving alive
            while True:
                await asyncio.sleep(0.3)
                await net.reconnect_missing()

        rewire_task = asyncio.create_task(rewire())
        await fresh.start()
        await net.connect("src_a", "fresh")
        await net.connect("src_b", "fresh")
        try:
            await asyncio.wait_for(fresh.bc_reactor.synced.wait(), timeout=120)
            assert fresh.state_store.load().last_block_height >= 39
        finally:
            rewire_task.cancel()
            for nd in (fresh, src_b, src_a):
                await nd.stop()
        return fresh

    fresh = asyncio.run(run())
    fires = faults.fires("blocksync.bad_block")
    assert fires == 1, f"expected exactly 1 injection, saw {fires}"
    strikes = sum(s["total_failures"]
                  for s in fresh.bc_reactor.scoreboard.snapshot().values())
    assert strikes > 0, "victim never struck a lying provider"


def cell_lightserve_lying_server(seed: int) -> None:
    """A serving node armed with ``lightserve.lying_server`` swaps served
    headers for a re-signed equivocation fork (same keys, different
    app_hash — it VERIFIES); a bisecting light-client fleet sharing one
    scoreboard catches the lie by witness cross-check, strikes the liar
    severely (instant ban), and honest serving continues for the rest of
    the fleet. Replay: same seed => identical injection count."""
    import asyncio
    import copy

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_light_client import CHAIN, T0, _keys, _mk_chain, _resign
    from tendermint_tpu.libs.faults import faults
    from tendermint_tpu.libs.peerscore import PeerScoreboard
    from tendermint_tpu.light import LightClient, TrustOptions
    from tendermint_tpu.light.client import DivergenceError
    from tendermint_tpu.light.serve import TAMPER_SITE, ServeProvider

    os.environ.setdefault("TMTPU_BATCH_BACKEND", "host")
    # validator rotation at height 5 forces the fleet to bisect: many
    # heights served, many chances for the armed site to lie
    a, b = _keys(0x50, 4), _keys(0x60, 4)
    key_sets = [a, a, a, a, b, b, b, b, b, b]
    honest = _mk_chain(key_sets, 10)
    forged = copy.deepcopy(honest)
    for h in forged:
        forged[h].signed_header.header.app_hash = b"\xee" * 32
    # _resign needs one key list per height: rebuild per rotated set
    lo = _resign({h: forged[h] for h in range(1, 5)}, a)
    hi = _resign({h: forged[h] for h in range(5, 11)}, b)
    forged = {**lo, **hi}
    now = T0 + 100 * 1_000_000_000

    def run_fleet():
        primary = ServeProvider(CHAIN, honest, name="primary")
        liar = ServeProvider(CHAIN, honest,
                             forged={h: forged[h] for h in range(2, 11)},
                             name="liar")
        witnesses = [liar, ServeProvider(CHAIN, honest, name="honest-a"),
                     ServeProvider(CHAIN, honest, name="honest-b")]
        sb = PeerScoreboard(name="light", seed=seed)
        trust = TrustOptions(3600.0, 1,
                             honest[1].signed_header.header.hash())

        async def run():
            caught = 0
            for _ in range(3):  # the fleet: one scoreboard, fresh clients
                client = LightClient(CHAIN, trust, primary, witnesses,
                                     scoreboard=sb)
                try:
                    lb = await client.verify_light_block_at_height(
                        10, now_ns=now)
                    assert lb.signed_header.header.height == 10
                except DivergenceError as e:
                    assert e.witness_id == "liar", e
                    caught += 1
            return caught

        caught = asyncio.run(run())
        return caught, sb, liar

    faults.configure(f"{TAMPER_SITE}@0.75", seed=seed)
    caught1, sb, liar = run_fleet()
    fires1 = faults.fires(TAMPER_SITE)
    assert fires1 > 0, "lying site never fired"
    assert caught1 >= 1, "no client ever caught the liar"
    assert sb.banned("liar"), f"liar not banned: {sb.snapshot()}"
    assert not sb.banned("honest-a") and not sb.banned("honest-b")
    assert liar.evidence, "divergence evidence never reported"
    # honest serving continued: with the liar banned (skipped on
    # cross-check) at least one later client completed the bisection
    assert caught1 < 3, "serving never recovered after the ban"
    # replayability: same seed, fresh plane -> identical injection count
    faults.reset()
    faults.configure(f"{TAMPER_SITE}@0.75", seed=seed)
    caught2, sb2, _ = run_fleet()
    fires2 = faults.fires(TAMPER_SITE)
    assert (fires2, caught2) == (fires1, caught1), \
        f"replay diverged: {(fires1, caught1)} != {(fires2, caught2)}"
    assert sb2.banned("liar")
    faults.reset()


def _churn_mod():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import churn

    return churn


def cell_churn_flap(seed: int) -> None:
    """A flapping node: 3 leave/rejoin cycles, every rejoin a full
    statesync restore; survivors never redial the departed id, hashes
    identical (all asserted inside run_flap)."""
    churn = _churn_mod()

    report = churn.run_flap(cycles=3, seed=seed)
    assert len(report["rejoin_caughtup_s"]) == 3, report
    assert all(s < 60 for s in report["rejoin_caughtup_s"]), report


def cell_churn_rotate(seed: int) -> None:
    """The full N=8 churn schedule: joins + leaves + validator rotation
    across prune boundaries under open-loop load. run_churn asserts
    liveness, survivor app-hash agreement, prune-floor resolution, and
    bounded book/scoreboard state; the cell checks the schedule shape."""
    churn = _churn_mod()

    report = churn.run_churn(n_nodes=8, intervals=2, seed=seed)
    assert report["rotations"] == 2, report
    assert len(report["join_caughtup_s"]) == 2, report
    actions = [a for a, _ in report["executed"]]
    assert actions.count("leave") == 2 and actions.count("join") == 2


def _net32(seed: int, drive):
    """Shared 32-node sparse-fleet driver: build, run `drive(net, nodes)`,
    assert all 32 agree on a common block hash, tear down."""
    import asyncio

    churn = _churn_mod()

    async def run():
        net, nodes, _pvs, _genesis = await churn.build_fleet(
            32, topology="sparse", degree=4, seed=seed)
        try:
            await churn._wait_heights(list(nodes.values()), 3, timeout=240)
            await drive(net, nodes, churn)
        finally:
            for nd in nodes.values():
                try:
                    await nd.stop()
                except Exception:
                    pass
        common = min(nd.height for nd in nodes.values()) - 1
        hashes = {nd.block_store.load_block_meta(common).header.app_hash
                  for nd in nodes.values()}
        assert len(hashes) == 1, "divergent hashes across the 32-node net"

    asyncio.run(run())


def cell_churn_partition32(seed: int) -> None:
    """Partition at scale: 8 of 32 sparse-topology nodes blackholed; the
    majority keeps committing, heal reconverges everyone."""
    async def drive(net, nodes, churn):
        minority = {f"full{i}" for i in range(20, 28)}
        net.partition(set(nodes) - minority, minority)
        majority = [nd for n, nd in nodes.items() if n not in minority]
        h0 = max(nd.height for nd in majority)
        await churn._wait_heights(majority, h0 + 2, timeout=180)
        net.heal()
        h1 = max(nd.height for nd in majority)
        await churn._wait_heights(list(nodes.values()), h1 + 1, timeout=240)

    _net32(seed, drive)


def cell_churn_corrupt32(seed: int) -> None:
    """Content corruption at scale: capped bit flips on the 32-node sparse
    net's in-flight payloads; receivers drop corrupting links, the redial
    loop re-heals, commits continue."""
    import asyncio

    from tendermint_tpu.libs.faults import faults

    cap = 20

    async def drive(net, nodes, churn):
        rewire_task = asyncio.create_task(churn.rewire_loop(net))
        try:
            faults.configure(f"net.corrupt@0.02*{cap}", seed=seed)
            h0 = max(nd.height for nd in nodes.values())
            await churn._wait_heights(list(nodes.values()), h0 + 3,
                                      timeout=300)
            assert faults.fires("net.corrupt") > 0, "site never fired"
        finally:
            # disarm on EVERY exit — 32 nodes tearing down under live bit
            # flips would bury the real failure in link-drop noise
            faults.reset()
            rewire_task.cancel()

    _net32(seed, drive)


def cell_crash_torn_wal(seed: int) -> None:
    """Torn WAL tail, repaired on open: arm the byte-emit tear site so the
    LAST append lands partial, prove replay stops at the tear, and prove a
    reopen truncates the garbage so new appends are replayable (the
    stranded-records regression the repair exists for)."""
    import tempfile

    from tendermint_tpu.consensus.wal import WAL
    from tendermint_tpu.libs.faults import faults

    path = os.path.join(tempfile.mkdtemp(prefix="chaos-torn-"), "cs.wal")
    wal = WAL(path)
    for h in range(1, 6):
        wal.write_end_height(h, 1_700_000_000_000_000_000 + h)
    # tear exactly the NEXT append (the tail record a crash would tear)
    faults.configure("wal.torn_write*1", seed=seed)
    wal.write_end_height(6, 1_700_000_000_000_000_006)
    assert faults.fires("wal.torn_write") == 1, "tear site never fired"
    faults.reset()
    wal.close()
    # replay stops cleanly at (or before) the torn record
    replayed = [m.data["height"] for m in WAL(path, repair=False)
                .iter_messages() if m.type == "end_height"]
    assert replayed[:6] == [0, 1, 2, 3, 4, 5], replayed
    assert 6 not in replayed, "a torn record must never replay whole"
    # repair-on-open: append after the tear, the new record must replay
    wal2 = WAL(path)
    size_after_repair = os.path.getsize(path)
    assert WAL._decodable_prefix_len(
        open(path, "rb").read()) == size_after_repair, \
        "repair left undecodable bytes in the head"
    wal2.write_end_height(7, 1_700_000_000_000_000_007)
    wal2.close()
    replayed = [m.data["height"] for m in WAL(path).iter_messages()
                if m.type == "end_height"]
    assert replayed[-1] == 7, \
        f"record appended after repair was stranded: {replayed}"
    # determinism: the same seed tears the same bytes
    fp1 = faults.configure("wal.torn_write*1", seed=seed).tear(
        "wal.torn_write", b"A" * 64)
    faults.reset()
    fp2 = faults.configure("wal.torn_write*1", seed=seed).tear(
        "wal.torn_write", b"A" * 64)
    faults.reset()
    assert fp1 == fp2, "tear schedule not deterministic per seed"


def cell_crash_privval(seed: int) -> None:
    """Torn last-sign-state: the atomic write emits a partial file, and
    the next startup REFUSES with an error naming the file — never a
    silent height-0 reset (the double-sign hazard)."""
    import tempfile

    from tendermint_tpu.libs.faults import faults
    from tendermint_tpu.privval.file_pv import CorruptSignStateError, FilePV
    from tendermint_tpu.types import (BlockID, PartSetHeader, SignedMsgType,
                                      Vote)

    d = tempfile.mkdtemp(prefix="chaos-pv-")
    key, state = os.path.join(d, "pv_key.json"), os.path.join(d, "pv_state.json")
    pv = FilePV.generate(key, state, seed=bytes([seed & 0xFF]) * 32)
    pv.save()
    bid = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))

    def vote(h):
        return Vote(SignedMsgType.PREVOTE, h, 0, bid,
                    1_700_000_000_000_000_000, b"\xaa" * 20, 0)

    pv.sign_vote("chaos-chain", vote(1))          # clean sign + save
    faults.configure("privval.torn_state*1", seed=seed)
    pv.sign_vote("chaos-chain", vote(2))          # state write torn
    assert faults.fires("privval.torn_state") == 1, "tear site never fired"
    faults.reset()
    try:
        FilePV.load(key, state)
        raise AssertionError("corrupt sign state silently accepted")
    except CorruptSignStateError as e:
        assert state in str(e), f"error does not name the file: {e}"
        assert "double-sign" in str(e), e
    # after the operator restores the file, startup works again
    pv.last_sign_state.save()                     # un-torn rewrite
    pv2 = FilePV.load(key, state)
    assert pv2.last_sign_state.height == 2


def cell_crash_loop(seed: int) -> None:
    """Crash-loop give-up: an instant crasher walks the bounded backoff
    schedule, exhausts max_restarts, and the supervisor gives up with a
    debugdump bundle holding the exit history."""
    import json
    import tempfile

    from tendermint_tpu.libs.supervisor import (RestartPolicy,
                                                RestartSupervisor,
                                                write_crashloop_bundle)

    clock = [0.0]
    policy = RestartPolicy(policy="on-failure", max_restarts=3,
                           backoff_s=0.5, backoff_max_s=4.0,
                           healthy_uptime_s=10.0)
    sup = RestartSupervisor(policy, name=f"crasher{seed}",
                            time_fn=lambda: clock[0])
    delays = []
    for _ in range(10):
        sup.on_launch()
        clock[0] += 0.01            # dies instantly every time
        delay = sup.on_exit(1)
        if delay is None:
            break
        delays.append(delay)
    assert sup.gave_up, "supervisor never gave up on an instant crasher"
    assert delays == [0.5, 1.0, 2.0], delays   # bounded doubling
    assert sup.restarts == policy.max_restarts
    # a healthy run re-earns the budget (not a crash loop)
    sup2 = RestartSupervisor(policy, name="occasional",
                             time_fn=lambda: clock[0])
    for _ in range(6):
        sup2.on_launch()
        clock[0] += 60.0            # an hour of uptime per life
        assert sup2.on_exit(1) == 0.5
    assert not sup2.gave_up
    # the give-up artifact records the whole history
    out = tempfile.mkdtemp(prefix="chaos-loop-")
    bundle = write_crashloop_bundle(out, sup, extras={"seed": str(seed)})
    with open(bundle) as f:
        doc = json.load(f)
    assert doc["crashloop"]["gave_up"] is True
    assert len(doc["crashloop"]["history"]) == policy.max_restarts + 1
    assert doc["crashloop"]["history"][-1]["action"] == "give-up"


def cell_exec_conflict_storm(seed: int) -> None:
    """All-same-key blocks under parallel execution with the
    exec.conflict chaos site scrambling lane assignments: the serial and
    parallel executors must commit byte-identical results at every
    height."""
    from tendermint_tpu import crypto
    from tendermint_tpu.abci.example.kvstore import MerkleKVStoreApplication
    from tendermint_tpu.config import ExecutionConfig
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.libs.faults import faults
    from tendermint_tpu.proxy import AppConns, local_client_creator
    from tendermint_tpu.state import (BlockExecutor, StateStore,
                                      state_from_genesis)
    from tendermint_tpu.state.execution import (EmptyEvidencePool,
                                                NoOpMempool)
    from tendermint_tpu.store import BlockStore
    from tendermint_tpu.types import (BlockID, GenesisDoc, GenesisValidator,
                                      MockPV, SignedMsgType, Vote, VoteSet)
    from tendermint_tpu.types.block import Commit

    import random

    def run(version, arm):
        if arm:
            faults.configure("exec.conflict", seed=seed)
        try:
            pv = MockPV(crypto.Ed25519PrivKey.generate(b"\x21" * 32))
            genesis = GenesisDoc(
                chain_id=f"storm-{seed}",
                genesis_time_ns=1_700_000_000_000_000_000,
                validators=[GenesisValidator(pv.get_pub_key(), 10)])
            state = state_from_genesis(genesis)
            app = MerkleKVStoreApplication()
            conns = AppConns(local_client_creator(app))
            conns.start()
            ss = StateStore(MemDB())
            ss.save(state)
            ex = BlockExecutor(ss, conns.consensus, NoOpMempool(),
                               EmptyEvidencePool(), BlockStore(MemDB()),
                               exec_config=ExecutionConfig(version=version))
            wl_rng = random.Random(seed)  # identical workload both runs
            last_commit = Commit(0, 0, BlockID(), [])
            out = []
            for h in range(1, 4):
                txs = [b"storm=%d.%d.%08x" % (h, i, wl_rng.getrandbits(32))
                       for i in range(30)]
                proposer = state.validators.get_proposer().address
                block, parts = state.make_block(h, txs, last_commit, [],
                                                proposer)
                bid = BlockID(block.hash(), parts.header())
                state, _ = ex.apply_block(state, bid, block)
                vs = VoteSet(state.chain_id, h, 0, SignedMsgType.PRECOMMIT,
                             state.validators)
                v = Vote(SignedMsgType.PRECOMMIT, h, 0, bid,
                         block.header.time_ns + 1,
                         state.validators.validators[0].address, 0)
                pv.sign_vote(state.chain_id, v)
                vs.add_vote(v)
                last_commit = vs.make_commit()
                out.append((ss.load_abci_responses(h).to_json(),
                            state.app_hash, state.last_results_hash))
            # storm property: the whole block is ONE conflict group (or,
            # with the chaos site scrambling, re-executed serially)
            if version == "v1":
                assert ex._parallel.last_groups >= 1
            return out, dict(app.state), app.tx_count
        finally:
            if arm:
                faults.reset()

    serial = run("v0", arm=False)
    parallel = run("v1", arm=True)
    assert serial == parallel, "conflict storm diverged from serial spec"


def cell_aggsig_degrade(seed: int) -> None:
    """BLS aggregate-verify under device strikes: the armed
    ``crypto.bls_verify`` site fails EVERY jax apk aggregation, the device
    breaker opens, and every single verify still returns the host-scalar
    verdict — zero dropped commits, accept AND reject parity throughout
    the degradation. After disarm + cooldown, a single-key aggregate (the
    n==1 device-evidence probe in aggregate_pubkeys_vec) re-closes the
    breaker."""
    from tendermint_tpu.crypto import bls12381 as bls
    from tendermint_tpu.crypto.bls12381 import vec
    from tendermint_tpu.crypto.breaker import CLOSED, OPEN, device_breaker
    from tendermint_tpu.libs.faults import faults

    device_breaker.failure_threshold = 2
    # long cooldown while armed: the scalar-fallback pairing (~100 ms)
    # must not outlast the OPEN window, or every call would be a fresh
    # half-open probe and no breaker rejection would ever be observed
    device_breaker.cooldown_s = 30.0
    vec.reset_stats()
    bls.reset()

    sks = [bls.sk_from_seed(bytes([seed & 0xFF, i])) for i in range(4)]
    pks = [bls.sk_to_pk(sk) for sk in sks]
    msg = b"aggsig-degrade-%d" % seed
    good = bls.aggregate([bls.sign(sk, msg) for sk in sks])
    bad = bytes([good[0] ^ 0x01]) + good[1:]

    faults.configure("crypto.bls_verify@1.0", seed=seed)
    try:
        for round_ in range(6):
            # every call lands a verdict (fallback, never a drop), and the
            # verdict matches the scalar spec for valid AND tampered input
            assert vec.fast_aggregate_verify_routed(pks, msg, good,
                                                    backend="jax"), \
                f"round {round_}: valid aggregate rejected under injection"
            assert not vec.fast_aggregate_verify_routed(pks, msg, bad,
                                                        backend="jax"), \
                f"round {round_}: tampered aggregate accepted under injection"
        assert faults.fires("crypto.bls_verify") > 0, "site never fired"
        assert vec.stats["device_errors"] >= 2, vec.stats
        assert vec.stats["breaker_rejections"] > 0, \
            "breaker never opened under 100% strikes"
        assert device_breaker.state == OPEN, device_breaker.state
    finally:
        faults.reset()
    device_breaker.cooldown_s = 0.05
    time.sleep(0.06)
    # half-open probe with REAL device evidence: the single-key aggregate
    # runs the Montgomery limb roundtrip on the jax backend
    assert vec.fast_aggregate_verify_routed(
        [pks[0]], pks[0], bls.pop_prove(sks[0]), dst=bls.DST_POP,
        backend="jax")
    assert device_breaker.state == CLOSED, device_breaker.state
    assert vec.stats["device_calls"] >= 1, vec.stats


def _soak_mod():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import soak

    return soak


def cell_soak_gameday(seed: int) -> None:
    """A compressed game day through the SLO soak plane: the chaos
    schedule must be a pure function of the seed, the live fleet must
    make height progress under the armed corrupt+churn windows, and
    every SLO breach the engine raises must leave with an attribution —
    a named plane or the loud ``unattributed``, never silence."""
    import tempfile

    soak = _soak_mod()

    plan_a = soak.plan_gameday(seed, n_nodes=5, duration_s=22.0)
    plan_b = soak.plan_gameday(seed, n_nodes=5, duration_s=22.0)
    assert plan_a == plan_b, "gameday plan is not seed-deterministic"
    assert soak.schedule_fingerprint(plan_a) == \
        soak.schedule_fingerprint(plan_b)
    planes = [ev["plane"] for ev in plan_a["events"]]
    # 5 nodes: one spare full (churn) + the always-on corrupt plane +
    # the quorum-loss window a full 4-validator quorum always gets
    assert planes == ["churn", "corrupt", "quorum_loss"], planes

    out = os.path.join(tempfile.mkdtemp(prefix="chaos_soak_"),
                       "soak_report.json")
    rep = soak.run_soak(n_nodes=5, seed=seed, duration_s=22.0, out=out)
    assert rep["schedule_fingerprint"] == soak.schedule_fingerprint(plan_a), \
        "live run drifted from the pure plan"
    assert rep["heights"]["final"] > rep["heights"]["initial"], rep["heights"]
    assert sorted(p for p, _ in rep["executed"]) == sorted(planes), \
        rep["executed"]
    assert not rep["event_errors"], rep["event_errors"]
    for b in rep["slo"]["breaches"]:
        att = b.get("attribution")
        assert att and att.get("plane"), f"silent breach: {b}"
    assert os.path.exists(out), "report never written"


def _quorum_loss_mod():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import quorum_loss

    return quorum_loss


def cell_net_quorum_loss(seed: int) -> None:
    """The partially-synchronous contract under >1/3 isolation: a seeded
    quorum-loss window over a live 4-validator fleet halts height advance
    with zero conflicting commits and zero equivocations, the survivor's
    watchdog classifies the halt ``quorum_lost`` from the blocking
    stage's vote bitmap, and post-heal the fleet recovers to
    hash-identical commits — run TWICE to pin the same-seed outcome
    fingerprint (all asserted inside run_quorum_loss)."""
    ql = _quorum_loss_mod()

    assert ql.plan_quorum_loss(seed, 1) == ql.plan_quorum_loss(seed, 1)
    vd = ql.verify_determinism(seed=seed, windows=1)
    assert vd["ok"], f"same-seed outcomes diverged: {vd}"
    assert all(s < ql.RECOVER_BOUND_S for s in vd["recover_s"]), vd


def cell_net_asym(seed: int) -> None:
    """Asymmetric degradation: the seeded ``asym`` profile makes one
    direction of every pair lossy while the reverse stays clean — the
    regime TCP-ish failure detectors misread. The 5-node fleet must keep
    committing through it and reconverge hash-identical once cleared."""
    import asyncio

    from tendermint_tpu.p2p.inproc import plan_link_profiles

    churn = _churn_mod()

    ids = [f"n{i}" for i in range(5)]
    plan = plan_link_profiles(ids, "asym", seed=seed)
    assert plan == plan_link_profiles(ids, "asym", seed=seed)
    # one degraded direction per pair, never both
    for (src, dst) in plan:
        assert (dst, src) not in plan, f"both directions degraded: {src},{dst}"

    async def run():
        net, nodes, _pvs, _genesis = await churn.build_fleet(5, seed=seed)
        try:
            for nd in nodes.values():
                nd.cs.config.gossip_stall_refresh_s = 1.0
            applied = net.apply_profile("asym", seed=seed)
            assert applied == len(net.links) // 2, applied
            await churn._wait_heights(list(nodes.values()), 2, timeout=120)
            h0 = max(nd.height for nd in nodes.values())
            await churn._wait_heights(list(nodes.values()), h0 + 3,
                                      timeout=300)
            net.clear_policies()
            h1 = max(nd.height for nd in nodes.values())
            await churn._wait_heights(list(nodes.values()), h1 + 1,
                                      timeout=120)
            common = min(nd.height for nd in nodes.values()) - 1
            hashes = {nd.block_store.load_block_meta(common).header.app_hash
                      for nd in nodes.values()}
            assert len(hashes) == 1, "hashes diverged under asym links"
        finally:
            for nd in nodes.values():
                try:
                    await nd.stop()
                except Exception:
                    pass

    asyncio.run(run())


def cell_net_gray(seed: int) -> None:
    """Gray failure: every link touching one full node runs the ``gray``
    profile (60% loss — traffic leaks, so nothing declares the node
    dead). The quorum must keep committing, the gray node must stay a
    peer (never treated as departed) and keep making progress through
    the leak, and once the links clear it must catch up hash-identical."""
    import asyncio

    churn = _churn_mod()

    async def run():
        net, nodes, _pvs, _genesis = await churn.build_fleet(5, seed=seed)
        gray = "full0"
        try:
            for nd in nodes.values():
                nd.cs.config.gossip_stall_refresh_s = 1.0
            from tendermint_tpu.p2p.inproc import plan_link_profiles

            plan = plan_link_profiles(sorted(nodes), "gray", seed=seed)
            plan = {lk: kw for lk, kw in plan.items() if gray in lk}
            applied = net.apply_link_plan(plan, seed=seed)
            assert applied == 8, applied  # 4 peers x 2 directions
            await churn._wait_heights(list(nodes.values()), 2, timeout=120)
            majority = [nd for n, nd in nodes.items() if n != gray]
            h0 = max(nd.height for nd in majority)
            await churn._wait_heights(majority, h0 + 3, timeout=300)
            # gray is a leak, not a blackhole: the node is still a peer
            # of every survivor and still advancing through the loss
            assert gray not in net.departed
            for nd in majority:
                assert gray in nd.switch.peers, \
                    f"{nd.name} dropped the gray node"
            assert nodes[gray].height > 0
            net.clear_policies()
            h1 = max(nd.height for nd in majority)
            await churn._wait_heights(list(nodes.values()), h1 + 1,
                                      timeout=180)
            common = min(nd.height for nd in nodes.values()) - 1
            hashes = {nd.block_store.load_block_meta(common).header.app_hash
                      for nd in nodes.values()}
            assert len(hashes) == 1, "hashes diverged across the gray link"
        finally:
            for nd in nodes.values():
                try:
                    await nd.stop()
                except Exception:
                    pass

    asyncio.run(run())


CELLS = {
    "device.batch_verify": cell_device_batch_verify,
    "device.lane": cell_device_lane,
    "device.vote_flush": cell_device_vote_flush,
    "wal.fsync": cell_wal_fsync,
    "db.write_batch": cell_db_write_batch,
    "net.drop": cell_net_drop,
    "ingest.backpressure": cell_ingest_backpressure,
    "ingest.mempool_full": cell_ingest_mempool_full,
    "net.corrupt": cell_net_corrupt,
    "statesync.lying_chunk": cell_statesync_lying_chunk,
    "statesync.lying_snapshot": cell_statesync_lying_snapshot,
    "blocksync.bad_block": cell_blocksync_bad_block,
    "lightserve.lying_server": cell_lightserve_lying_server,
    "combo.maverick_corrupt": cell_combo_maverick_corrupt,
    "churn.flap": cell_churn_flap,
    "churn.rotate": cell_churn_rotate,
    "churn.partition32": cell_churn_partition32,
    "churn.corrupt32": cell_churn_corrupt32,
    "net.quorum_loss": cell_net_quorum_loss,
    "net.asym": cell_net_asym,
    "net.gray": cell_net_gray,
    "exec.conflict_storm": cell_exec_conflict_storm,
    "aggsig.degrade": cell_aggsig_degrade,
    "crash.torn_wal": cell_crash_torn_wal,
    "crash.privval": cell_crash_privval,
    "crash.loop": cell_crash_loop,
    "soak.gameday": cell_soak_gameday,
}
assert set(CELLS) == set(SITES)


# -- matrix driver -----------------------------------------------------------

def run_cell_subprocess(site: str, seed: int, timeout: float = 300.0):
    """One cell in a fresh interpreter; returns (passed, seconds, detail)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("TMTPU_FAULTS", None)  # the cell arms its own sites
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--cell", site, "--seed", str(seed)],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired:
        return False, time.perf_counter() - t0, "timeout"
    dt = time.perf_counter() - t0
    if proc.returncode == 0:
        return True, dt, ""
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    return False, dt, tail[-1] if tail else f"exit {proc.returncode}"


def format_table(rows) -> str:
    """rows: (site, seed, passed, seconds, detail)."""
    header = ("site", "seed", "result", "secs", "detail")
    table = [header] + [(site, str(seed), "PASS" if ok else "FAIL",
                         f"{secs:.1f}", detail[:60])
                        for site, seed, ok, secs, detail in rows]
    widths = [max(len(r[i]) for r in table) for i in range(len(header))]
    lines = []
    for i, r in enumerate(table):
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def self_test() -> None:
    # table plumbing
    rows = [("wal.fsync", 1, True, 0.51, ""),
            ("net.drop", 2, False, 61.0, "divergent block hashes")]
    txt = format_table(rows)
    assert "PASS" in txt and "FAIL" in txt and "wal.fsync" in txt, txt
    assert txt.splitlines()[0].startswith("site"), txt
    # registry closed under CELLS/SITES (module asserts at import too)
    assert all(s in CELLS for s in SITES)
    # the cheapest cells in-process: the injection seams really work
    from tendermint_tpu.libs.faults import faults

    cell_db_write_batch(seed=1)
    faults.reset()
    cell_wal_fsync(seed=1)
    faults.reset()
    # the Byzantine statesync cells are jax-free and fast: run them too
    cell_statesync_lying_chunk(seed=1)
    faults.reset()
    cell_statesync_lying_snapshot(seed=1)
    faults.reset()
    # the lying light-server cell is jax-free (host-path ed25519): run it
    cell_lightserve_lying_server(seed=1)
    faults.reset()
    # churn plumbing: the plan the churn cells execute is deterministic
    churn = _churn_mod()
    assert churn.plan_churn(3, 2, 8) == churn.plan_churn(3, 2, 8)
    # degraded-net plumbing, 2 seeds each: the quorum-loss plan and the
    # link-profile plans the net.* cells execute are seed-deterministic
    # (the live fleets themselves run via the matrix — they are the slow
    # cells) and the planner invariants hold
    ql = _quorum_loss_mod()
    from tendermint_tpu.p2p.inproc import LINK_PROFILES, plan_link_profiles

    ids = [f"n{i}" for i in range(5)]
    for seed in (1, 2):
        plan = ql.plan_quorum_loss(seed, windows=2)
        assert plan == ql.plan_quorum_loss(seed, windows=2)
        assert ql.plan_fingerprint(plan) == ql.plan_fingerprint(
            ql.plan_quorum_loss(seed, windows=2))
        for ev in plan["events"]:
            assert ev["isolated_power"] * 3 > ev["total_power"], ev
            assert 0 < len(ev["isolate"]) < plan["n_validators"], ev
        for profile in LINK_PROFILES:
            lp = plan_link_profiles(ids, profile, seed=seed)
            assert lp == plan_link_profiles(ids, profile, seed=seed)
            assert all(kw["profile"] == profile for kw in lp.values())
        asym = plan_link_profiles(ids, "asym", seed=seed)
        assert all((dst, src) not in asym for (src, dst) in asym)
    assert ql.plan_quorum_loss(1, windows=2) != ql.plan_quorum_loss(
        2, windows=2)
    # the crash cells are jax-free and fast: run them in-process too
    cell_crash_torn_wal(seed=1)
    faults.reset()
    cell_crash_privval(seed=1)
    faults.reset()
    cell_crash_loop(seed=1)
    print("chaos_matrix self-test OK")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sites", default=",".join(SITES),
                    help="comma-separated subset of: " + ", ".join(SITES))
    ap.add_argument("--seeds", default=",".join(map(str, DEFAULT_SEEDS)))
    ap.add_argument("--quick", action="store_true",
                    help="skip slow cells (the in-proc consensus net)")
    ap.add_argument("--cell", help="(internal) run one cell in-process")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)

    if args.self_test:
        self_test()
        return 0
    if args.cell:
        if args.cell not in CELLS:
            ap.error(f"unknown cell {args.cell!r}")
        _pin_cpu_jax()
        CELLS[args.cell](args.seed)
        return 0

    sites = [s.strip() for s in args.sites.split(",") if s.strip()]
    unknown = [s for s in sites if s not in SITES]
    if unknown:
        ap.error(f"unknown sites: {unknown}")
    if args.quick:
        sites = [s for s in sites if not SITES[s]]
    seeds = [int(s) for s in args.seeds.split(",") if s.strip()]

    rows = []
    for site in sites:
        for seed in seeds:
            ok, secs, detail = run_cell_subprocess(site, seed)
            rows.append((site, seed, ok, secs, detail))
            print(f"{'PASS' if ok else 'FAIL'}  {site} seed={seed} "
                  f"({secs:.1f}s)", flush=True)
    print()
    print(format_table(rows))
    failed = [r for r in rows if not r[2]]
    print(f"\n{len(rows) - len(failed)}/{len(rows)} cells passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
