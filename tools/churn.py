"""Churn orchestrator: live join/leave + validator rotation under load.

Every net-level claim in this repo used to rest on static full meshes; this
driver makes membership change the steady state. It runs an N-node in-proc
net (4 validators + N-4 full nodes over ``InProcNetwork``, full-mesh or
sparse ring+chords topology) under open-loop tx load (the loadtime
fixed-rate grid) and executes a SEEDED, DETERMINISTIC churn plan:

* each interval, ONE node leaves cleanly (``InProcNetwork.remove_node`` —
  departed switches drained, survivors' link policies untouched, the
  redial loop never re-adds it) and ONE fresh node joins — via a real
  snapshot restore over the statesync wire channels (the *normal* entry
  path: block stores are pruned, so replay-from-genesis is impossible by
  construction), then fast-syncs to the tip and follows live consensus;
* each interval, the validator set ROTATES via kvstore ``val:`` update
  txs — one full node's key in, the longest-serving rotatable validator
  out — so the prune-checkpointed validator storage (state/store.py prune
  floor + change pointers) is stressed by continuous set changes across
  prune boundaries (the app sets ``retain_height``, so the REAL consensus
  prune path runs at every commit on every node).

Assertions after the run: liveness (the net kept committing through every
event), app-hash agreement among survivors, every joiner reached
caught-up (join-to-caught-up seconds reported), ``load_validators``
resolves at every retained height, and AddrBook/peerscore state stays
bounded by the number of nodes that ever existed.

Determinism: the plan is a PURE function of (seed, n_nodes, intervals) —
``plan_churn`` — and the run executes it in plan order, so two same-seed
runs produce the identical join/leave event sequence and the identical
validator-set composition sequence (``--verify-determinism`` runs twice
and diffs both).

    python tools/churn.py --nodes 8 --intervals 2 --seed 1
    python tools/churn.py --nodes 8 --seed 1 --verify-determinism
    python tools/churn.py --nodes 16 --topology sparse --degree 3
    python tools/churn.py --self-test        # stdlib-only, seconds

Stdlib-only at the top level; repo imports happen inside the run (the
pattern chaos_matrix.py uses) so --help/--self-test work anywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TOOLS_DIR)
for p in (REPO, TOOLS_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

#: how many blocks between churn events — long enough for a statesync
#: join (snapshot every SNAPSHOT_INTERVAL heights) to land inside it
BLOCKS_PER_INTERVAL = 5
SNAPSHOT_INTERVAL = 3
#: app-driven retain window (ResponseCommit.retain_height = h - RETAIN):
#: must cover at least one snapshot so joiners can restore + fast-sync
RETAIN_BLOCKS = 12
N_VALIDATORS = 4


# -- the deterministic plan (pure) -------------------------------------------

def node_names(n_nodes: int, n_validators: int = N_VALIDATORS):
    """Initial roster: val0..val{V-1} are genesis validators, full{i} the
    genesis full nodes."""
    n_validators = min(n_validators, n_nodes)
    vals = [f"val{i}" for i in range(n_validators)]
    fulls = [f"full{i}" for i in range(n_nodes - n_validators)]
    return vals, fulls


def plan_churn(seed: int, intervals: int, n_nodes: int,
               n_validators: int = N_VALIDATORS):
    """The churn schedule as a pure function of its inputs: a list of
    per-interval event dicts, plus the validator-set composition after
    each rotation. Two same-seed calls are byte-identical — the property
    --verify-determinism checks end-to-end against two real runs.

    Membership simulation: each interval leaves one running full node
    (never a current validator, never the anchor val0's peers), joins one
    fresh statesync node, and rotates (in: the longest-running full node
    outside the set; out: the longest-serving validator except val0, the
    anchor/donor)."""
    import random
    import zlib

    rng = random.Random(zlib.crc32(f"churn|{seed}|{n_nodes}".encode()))
    vals, fulls = node_names(n_nodes, n_validators)
    vset = list(vals)              # current validator composition
    running_fulls = list(fulls)    # non-validator nodes currently up
    # seniority: genesis validators in roster order, rotated-in members by
    # the interval they entered the set — "longest-serving" is its min
    seniority = {v: (-1, i) for i, v in enumerate(vals)}
    events, compositions = [], [list(vset)]
    for i in range(intervals):
        ev = {"interval": i}
        # leave: a running full node outside the current set (quorum-safe)
        leavable = sorted(set(running_fulls) - set(vset))
        if leavable:
            ev["leave"] = rng.choice(leavable)
            running_fulls.remove(ev["leave"])
        # join: a fresh node, statesync entry
        joiner = f"join{i}"
        ev["join"] = joiner
        # rotate: in = longest-running full not in the set (joined BEFORE
        # this interval), out = longest-serving rotatable validator
        rotatable_in = [f for f in running_fulls if f not in vset]
        if rotatable_in:
            rot_in = rotatable_in[0]
            rot_out = min((v for v in vset if v != "val0"),
                          key=lambda v: seniority[v])
            ev["rotate_in"], ev["rotate_out"] = rot_in, rot_out
            seniority[rot_in] = (i, 0)
            vset[vset.index(rot_out)] = rot_in
            compositions.append(list(vset))
        running_fulls.append(joiner)  # caught-up by the interval's end
        events.append(ev)
    return {"events": events, "compositions": compositions}


# -- the in-proc rig ---------------------------------------------------------

_RIG = None


def _rig():
    """Import-heavy rig pieces, built lazily (keeps --help/--self-test
    stdlib-fast) and memoized (one ChurnNode class per process)."""
    global _RIG
    if _RIG is not None:
        return _RIG
    import asyncio  # noqa: F401  (re-exported pattern guard)

    from tendermint_tpu import crypto
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.example.kvstore import SnapshotKVStoreApplication
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.consensus import ConsensusState
    from tendermint_tpu.consensus.config import test_consensus_config
    from tendermint_tpu.consensus.reactor import ConsensusReactor
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.libs.metrics import NodeMetrics
    from tendermint_tpu.mempool import CListMempool
    from tendermint_tpu.mempool.reactor import MempoolReactor
    from tendermint_tpu.p2p import Switch
    from tendermint_tpu.p2p.pex import AddrBook, NetAddress
    from tendermint_tpu.proxy import AppConns, local_client_creator
    from tendermint_tpu.state import (BlockExecutor, StateStore,
                                      state_from_genesis)
    from tendermint_tpu.state.execution import EmptyEvidencePool
    from tendermint_tpu.statesync.reactor import StateSyncReactor
    from tendermint_tpu.statesync.stateprovider import StateProvider
    from tendermint_tpu.store import BlockStore
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

    class ChurnApp(SnapshotKVStoreApplication):
        """Snapshot-taking kvstore whose commit also declares a retain
        height — so the REAL consensus prune path (block store + state
        store) runs on every node at every commit, and validator-change
        pointers keep crossing the moving prune floor."""

        def __init__(self, interval: int, retain: int):
            super().__init__(interval=interval)
            self.retain = retain

        def commit(self):
            resp = super().commit()
            if self.retain:
                resp.retain_height = max(0, self.height - self.retain)
            return resp

    class ChurnNode:
        """One in-proc node: snapshot app, consensus + blocksync +
        statesync + mempool reactors, per-node metric registry (gossip
        wakeups), an AddrBook sharing the blocksync scoreboard."""

        def __init__(self, name, genesis, pv, fast_sync=False):
            self.name = name
            self.pv = pv
            self.app = ChurnApp(SNAPSHOT_INTERVAL, RETAIN_BLOCKS)
            self.conns = AppConns(local_client_creator(self.app))
            self.conns.start()
            self.state_store = StateStore(MemDB())
            self.block_store = BlockStore(MemDB())
            state = state_from_genesis(genesis)
            state = Handshaker(self.state_store, state, self.block_store,
                               genesis).handshake(self.conns.consensus,
                                                  self.conns.query)
            self.state_store.save(state)
            self.mempool = CListMempool(self.conns.mempool)
            self.block_exec = BlockExecutor(self.state_store,
                                            self.conns.consensus,
                                            self.mempool, EmptyEvidencePool(),
                                            self.block_store)
            self.cs = ConsensusState(test_consensus_config(), state,
                                     self.block_exec, self.block_store)
            self.cs.set_priv_validator(pv)
            self.mempool.tx_available_callbacks.append(
                self.cs.notify_txs_available)
            self.switch = Switch(name)
            self.metrics = NodeMetrics(f"churn_{name}_{time.monotonic_ns()}")
            # wakeup/poll counters read through cs.metrics (the reactor's
            # _gossip_idle), encode-cache counters through set_metrics
            self.cs.metrics = self.metrics.consensus
            self.cs_reactor = ConsensusReactor(self.cs, wait_sync=fast_sync)
            self.cs_reactor.set_metrics(self.metrics.consensus)
            self.switch.add_reactor("CONSENSUS", self.cs_reactor)
            self.bc_reactor = BlockchainReactor(
                state, self.block_exec, self.block_store, fast_sync=False,
                consensus_reactor=self.cs_reactor)
            self.switch.add_reactor("BLOCKCHAIN", self.bc_reactor)
            self.mp_reactor = MempoolReactor(self.mempool, gossip_sleep=0.01)
            self.switch.add_reactor("MEMPOOL", self.mp_reactor)
            self.ss_reactor = StateSyncReactor(self.app, self.app)
            self.switch.add_reactor("STATESYNC", self.ss_reactor)
            self.addr_book = AddrBook(strict=False,
                                      scoreboard=self.bc_reactor.scoreboard)
            self.fast_sync = fast_sync
            self._started = False

        @property
        def height(self):
            return self.cs.state.last_block_height

        async def start(self):
            self._started = True
            await self.switch.start()
            if not self.fast_sync:
                await self.cs.start()

        async def stop(self):
            if not self._started:
                return
            self._started = False
            await self.cs.stop()
            await self.switch.stop()
            self.conns.stop()

        def wakeups(self):
            m = self.metrics.consensus.gossip_wakeups_total
            return sum(m.value(r) for r in ("data", "votes"))

        def encode_cache(self):
            """(hits, misses) summed across kinds — the wire-encode cache
            is what keeps per-link gossip cost flat as peers multiply."""
            c = self.metrics.consensus
            return (sum(c.encode_cache_hits_total._values.values()),
                    sum(c.encode_cache_misses_total._values.values()))

    class DirectStateProvider(StateProvider):
        """Orchestrator-trusted provider for in-proc joins: reads headers,
        commits and validator sets straight from a live survivor's stores
        (the wire-level chunk fetch + per-chunk verification still runs;
        PR 7's adversarial suite covers UNTRUSTED providers — churn
        measures membership mechanics)."""

        def __init__(self, donor, timeout=90.0):
            self.donor = donor
            self.timeout = timeout

        async def _meta(self, height):
            import asyncio

            deadline = time.monotonic() + self.timeout
            while time.monotonic() < deadline:
                meta = self.donor.block_store.load_block_meta(height)
                if meta is not None:
                    return meta
                await asyncio.sleep(0.05)
            raise TimeoutError(f"donor never reached height {height}")

        async def app_hash(self, height):
            return (await self._meta(height + 1)).header.app_hash

        async def commit(self, height):
            import asyncio

            deadline = time.monotonic() + self.timeout
            while time.monotonic() < deadline:
                blk = self.donor.block_store.load_block(height + 1)
                if blk is not None:
                    return blk.last_commit
                await asyncio.sleep(0.05)
            raise TimeoutError(f"donor never served block {height + 1}")

        async def state(self, height):
            from tendermint_tpu.state.state import State
            from tendermint_tpu.types.params import ConsensusParams

            last = (await self._meta(height)).header
            cur = (await self._meta(height + 1)).header
            await self._meta(height + 2)  # h+2's vals = next of h+1
            ss = self.donor.state_store
            return State(
                chain_id=cur.chain_id,
                initial_height=1,
                last_block_height=height,
                last_block_id=cur.last_block_id,
                last_block_time_ns=last.time_ns,
                last_validators=ss.load_validators(height),
                validators=ss.load_validators(height + 1),
                next_validators=ss.load_validators(height + 2),
                last_height_validators_changed=height + 1,
                consensus_params=self.donor.cs.state.consensus_params
                or ConsensusParams(),
                last_height_consensus_params_changed=1,
                app_hash=cur.app_hash,
                last_results_hash=cur.last_results_hash,
            )

    def make_genesis(pvs, powers):
        return GenesisDoc(
            chain_id="churn-chain",
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=[GenesisValidator(pv.get_pub_key(), p)
                        for pv, p in zip(pvs, powers)])

    def make_pv(tag: str):
        seed = (tag.encode() * 32)[:32]
        return MockPV(crypto.Ed25519PrivKey.generate(seed))

    _RIG = {
        "ChurnNode": ChurnNode,
        "DirectStateProvider": DirectStateProvider,
        "make_genesis": make_genesis,
        "make_pv": make_pv,
        "NetAddress": NetAddress,
        "abci": abci,
    }
    return _RIG


# -- the run ------------------------------------------------------------------

async def join_statesync(net, jn, donor, neighbors, seed: int,
                         timeout: float = 120.0) -> float:
    """The statesync entry path, end to end: wait for a donor snapshot,
    wire the started node into the live net, restore over the wire
    channels, bootstrap stores, fast-sync to the tip, switch to live
    consensus. Returns join-to-caught-up seconds (clock starts when the
    node enters the net). Shared by run_churn and the chaos flap cell."""
    import asyncio

    rig = _rig()
    deadline = time.monotonic() + 60
    while not donor.app._snapshots and time.monotonic() < deadline:
        await asyncio.sleep(0.1)
    assert donor.app._snapshots, "donor never produced a snapshot"
    t0 = time.monotonic()
    catch_target = donor.height
    await jn.start()
    await net.add_node(jn.switch, connect_to=neighbors)
    provider = rig["DirectStateProvider"](donor)
    state, commit = await asyncio.wait_for(
        jn.ss_reactor.sync(provider, discovery_time=0.3, chunk_timeout=5.0,
                           seed=seed, discovery_rounds=20),
        timeout=timeout)
    jn.state_store.bootstrap(state)
    jn.block_store.save_seen_commit(state.last_block_height, commit)
    await jn.bc_reactor.switch_to_fast_sync(state)
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if jn.bc_reactor.synced.is_set() and jn.height >= catch_target:
            break
        await asyncio.sleep(0.1)
    else:
        raise TimeoutError(f"{jn.name} never caught up")
    jn.fast_sync = False  # now a live follower
    return round(time.monotonic() - t0, 3)


async def _wait_heights(nodes, target, timeout=150.0):
    import asyncio

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(nd.height >= target for nd in nodes):
            return
        await asyncio.sleep(0.1)
    raise TimeoutError(
        f"height {target} not reached: "
        f"{ {nd.name: nd.height for nd in nodes} }")


async def rewire_loop(net, interval: float = 0.3) -> None:
    """Persistent-peer redial loop: re-heal real link failures forever
    (reconnect_missing never touches departed nodes). Run as a task,
    cancel at teardown — shared by the churn/flap drivers and the chaos
    corruption cells."""
    import asyncio

    while True:
        await asyncio.sleep(interval)
        await net.reconnect_missing()


async def _run_async(n_nodes: int, intervals: int, seed: int,
                     topology: str, degree: int, rate: float) -> dict:
    import asyncio

    from tendermint_tpu.p2p import InProcNetwork

    rig = _rig()
    ChurnNode = rig["ChurnNode"]
    plan = plan_churn(seed, intervals, n_nodes)
    vals, fulls = node_names(n_nodes)
    pvs = {name: rig["make_pv"](name) for name in vals + fulls}
    genesis = rig["make_genesis"]([pvs[v] for v in vals], [10] * len(vals))

    nodes = {name: ChurnNode(name, genesis, pvs[name]) for name in vals + fulls}
    all_ever = dict(nodes)          # every node that ever existed
    net = InProcNetwork()
    for nd in nodes.values():
        net.add_switch(nd.switch)
    for nd in nodes.values():
        await nd.start()
    await net.connect_topology(topology, degree=degree, seed=seed)

    # survivors' address books learn everyone at wiring time (the in-proc
    # analog of PEX discovery) — the bounded-state assertion's subject
    def book_learns(name):
        port = 20000 + len(all_ever)
        for nd in nodes.values():
            if nd.name != name:
                nd.addr_book.add_address(
                    rig["NetAddress"](name, "127.0.0.1", port), src_id="churn")
    for name in list(nodes):
        book_learns(name)

    executed = []       # the run's own (action, node) event log
    join_stats = {}     # joiner -> seconds to caught-up
    rotations_done = []

    rewire_task = asyncio.create_task(rewire_loop(net))

    # open-loop tx load for the whole run (the loadtime harness
    # discipline: the i-th send fires at t0 + i/rate no matter how slow
    # the net answers — computed lazily, the run uses a few hundred slots)
    async def load():
        import itertools

        loop = asyncio.get_running_loop()
        t0 = loop.time() + 0.1
        for i in itertools.count():
            target = t0 + i / rate
            now = loop.time()
            if target > now:
                await asyncio.sleep(target - now)
            survivors = [nd for nd in nodes.values()
                         if nd.name not in net.departed and not nd.fast_sync]
            if not survivors:
                continue
            nd = survivors[i % len(survivors)]
            try:
                nd.mempool.check_tx(b"churn-%d-%d=x" % (seed, i))
            except Exception:
                pass  # full mempool under churn is load, not failure

    load_task = asyncio.create_task(load())

    t_run0 = time.monotonic()
    try:
        await _wait_heights(list(nodes.values()), 2)
        h0 = max(nd.height for nd in nodes.values())
        wak0 = {name: nd.wakeups() for name, nd in nodes.items()}

        for ev in plan["events"]:
            i = ev["interval"]
            target_h = h0 + (i + 1) * BLOCKS_PER_INTERVAL

            # -- leave: clean departure, survivors must not redial it
            leaver = ev.get("leave")
            if leaver and leaver in nodes:
                nd = nodes.pop(leaver)
                await net.remove_node(leaver)
                await nd.stop()
                for s in nodes.values():   # book sees the departure
                    s.addr_book.mark_attempt(
                        rig["NetAddress"](leaver, "127.0.0.1", 1))
                executed.append(("leave", leaver))

            # -- join: statesync restore over the wire, then fast sync
            joiner = ev["join"]
            jpv = rig["make_pv"](joiner)
            pvs[joiner] = jpv
            jn = ChurnNode(joiner, genesis, jpv, fast_sync=True)
            nodes[joiner] = jn
            all_ever[joiner] = jn
            donor = nodes["val0"]
            # sparse entry: connect to a few neighbors only; mesh: everyone
            neighbors = sorted(n for n in nodes if n != joiner)
            if topology == "sparse":
                neighbors = neighbors[:max(2, degree)]
            join_stats[joiner] = await join_statesync(
                net, jn, donor, neighbors, seed)
            book_learns(joiner)
            executed.append(("join", joiner))

            # -- rotate: val: txs flip the set across a prune boundary
            if "rotate_in" in ev:
                rin, rout = ev["rotate_in"], ev["rotate_out"]
                in_hex = pvs[rin].get_pub_key().bytes().hex()
                out_hex = pvs[rout].get_pub_key().bytes().hex()
                donor.mempool.check_tx(f"val:{in_hex}!10".encode())
                donor.mempool.check_tx(f"val:{out_hex}!0".encode())
                executed.append(("rotate", f"{rin}>{rout}"))
                rotations_done.append((rin, rout))

            await _wait_heights(
                [nd for nd in nodes.values() if not nd.fast_sync], target_h)

        # settle: everyone (joiners included) reaches a common height
        final_target = max(nd.height for nd in nodes.values()) + 2
        await _wait_heights(list(nodes.values()), final_target)
    except BaseException:
        # failed runs must still tear the net down (leaked consensus tasks
        # wedge asyncio.run's cleanup) — stop everything, then re-raise
        rewire_task.cancel()
        load_task.cancel()
        for nd in nodes.values():
            try:
                await nd.stop()
            except Exception:
                pass
        raise
    finally:
        rewire_task.cancel()
        load_task.cancel()

    elapsed = time.monotonic() - t_run0
    survivors = list(nodes.values())
    try:
        h_final = min(nd.height for nd in survivors)

        # -- invariants ------------------------------------------------------
        # survivor app-hash agreement at a common height
        common = h_final - 1
        hashes = {nd.name:
                  nd.block_store.load_block_meta(common).header.app_hash
                  for nd in survivors}
        assert len(set(hashes.values())) == 1, \
            f"survivor app hashes diverged at {common}: {hashes}"
        # the rotation actually took: the final set differs from genesis
        # when the plan rotated, and matches the plan's final composition
        if rotations_done:
            set_keys = {v.pub_key.bytes()
                        for v in survivors[0].cs.state.validators.validators}
            final_names = {name for name, pv in pvs.items()
                           if pv.get_pub_key().bytes() in set_keys}
            assert final_names == set(plan["compositions"][-1]), \
                (sorted(final_names), plan["compositions"][-1])
        # every retained height's validator set resolves (the
        # prune-checkpoint path under continuous churn)
        anchor = nodes["val0"]
        floor = max(1, anchor.app.height - RETAIN_BLOCKS)
        unresolved = [h for h in range(floor, anchor.height + 1)
                      if anchor.state_store.load_validators(h) is None]
        assert not unresolved, f"unresolvable retained heights: {unresolved}"
        # bounded AddrBook / peerscore state: no growth beyond the roster
        for nd in survivors:
            assert nd.addr_book.size() <= len(all_ever), \
                (nd.name, nd.addr_book.size(), len(all_ever))
            assert len(nd.bc_reactor.scoreboard.snapshot()) <= len(all_ever)

        # -- wakeup accounting (sublinearity evidence) ----------------------
        wak_delta = sum(nd.wakeups() - wak0.get(nd.name, 0.0)
                        for nd in survivors)
        links = max(1, len(net.links))
        blocks = max(1, h_final - h0)
    finally:
        # a FAILED invariant must still tear the net down (leaked
        # consensus tasks wedge asyncio.run's cleanup and the caller
        # never sees the diagnostic)
        for nd in survivors:
            try:
                await nd.stop()
            except Exception:
                pass

    return {
        "n_nodes": n_nodes, "seed": seed, "intervals": intervals,
        "topology": topology, "degree": degree,
        "plan": plan, "executed": executed,
        "compositions": plan["compositions"],
        "height_initial": h0, "height_final": h_final,
        "blocks_per_min": round(blocks / elapsed * 60.0, 2),
        "join_caughtup_s": join_stats,
        "wakeups_per_link_per_block": round(wak_delta / links / blocks, 3),
        "directed_links": links,
        "rotations": len(rotations_done),
        "prune_floor": floor,
        "survivor_app_hash": next(iter(hashes.values())).hex(),
        "elapsed_s": round(elapsed, 2),
    }


async def build_fleet(n_nodes: int, topology: str = "full_mesh",
                      degree: int = 3, seed: int = 0,
                      n_validators: int = N_VALIDATORS):
    """A started static fleet (4 validators + fulls) wired per topology:
    (net, nodes dict, pvs, genesis). Chaos cells build on this."""
    from tendermint_tpu.p2p import InProcNetwork

    rig = _rig()
    vals, fulls = node_names(n_nodes, n_validators)
    pvs = {name: rig["make_pv"](name) for name in vals + fulls}
    genesis = rig["make_genesis"]([pvs[v] for v in vals], [10] * len(vals))
    nodes = {name: rig["ChurnNode"](name, genesis, pvs[name])
             for name in vals + fulls}
    net = InProcNetwork()
    for nd in nodes.values():
        net.add_switch(nd.switch)
    for nd in nodes.values():
        await nd.start()
    await net.connect_topology(topology, degree=degree, seed=seed)
    return net, nodes, pvs, genesis


async def _flap_async(cycles: int, seed: int) -> dict:
    """One node repeatedly leaving and re-joining (fresh stores each time,
    so every re-entry is a full statesync restore) while 4 validators + a
    stable full node keep committing. Asserts per cycle: the survivors
    never hold a peer object for the departed node (reconnect_missing must
    skip it), the rejoin catches up, and hashes stay identical."""
    import asyncio

    rig = _rig()
    net, nodes, pvs, genesis = await build_fleet(6, seed=seed)
    flapper = "full1"
    rejoin_s = []

    rewire_task = asyncio.create_task(rewire_loop(net, interval=0.2))
    try:
        await _wait_heights(list(nodes.values()), 2)
        for cycle in range(cycles):
            nd = nodes.pop(flapper)
            await net.remove_node(flapper)
            await nd.stop()
            survivors = list(nodes.values())
            h0 = max(s.height for s in survivors)
            await _wait_heights(survivors, h0 + 2)
            # several rewire passes ran while the flapper was away: no
            # survivor may have re-acquired it, and its id is marked
            assert flapper in net.departed
            for s in survivors:
                assert flapper not in s.switch.peers, \
                    f"{s.name} redialed departed {flapper} (cycle {cycle})"
            fresh = rig["ChurnNode"](flapper, genesis, pvs[flapper],
                                     fast_sync=True)
            nodes[flapper] = fresh
            rejoin_s.append(await join_statesync(
                net, fresh, nodes["val0"],
                [n for n in nodes if n != flapper], seed))
            assert flapper not in net.departed
        final = max(nd.height for nd in nodes.values()) + 2
        await _wait_heights(list(nodes.values()), final)
        h_common = min(nd.height for nd in nodes.values()) - 1
        hashes = {nd.block_store.load_block_meta(h_common).header.app_hash
                  for nd in nodes.values()}
        assert len(hashes) == 1, "hashes diverged under flapping"
        for nd in nodes.values():
            # the flapper's comings and goings must not bloat peer state
            assert len(nd.bc_reactor.scoreboard.snapshot()) <= len(nodes)
    finally:
        # one teardown for run AND invariant failures alike — leaked
        # consensus tasks would wedge asyncio.run's cleanup
        rewire_task.cancel()
        for nd in nodes.values():
            try:
                await nd.stop()
            except Exception:
                pass
    return {"cycles": cycles, "rejoin_caughtup_s": rejoin_s,
            "final_height": h_common + 1}


def run_flap(cycles: int = 3, seed: int = 1) -> dict:
    """The churn.flap scenario; returns its report (asserts on failure)."""
    import asyncio

    os.environ.setdefault("TMTPU_BATCH_BACKEND", "host")
    return asyncio.run(_flap_async(cycles, seed))


def run_churn(n_nodes: int = 8, intervals: int = 2, seed: int = 1,
              topology: str = "full_mesh", degree: int = 3,
              rate: float = 10.0) -> dict:
    """One full churn run; returns the report dict (asserts on failure).
    Pure-python ed25519 keeps the rig independent of device kernels (and
    a join/leave per interval is mempool/gossip-bound, not verify-bound)."""
    import asyncio

    os.environ.setdefault("TMTPU_BATCH_BACKEND", "host")
    if n_nodes < N_VALIDATORS + 1:
        raise ValueError(f"need at least {N_VALIDATORS + 1} nodes")
    return asyncio.run(_run_async(n_nodes, intervals, seed, topology,
                                  degree, rate))


def schedule_fingerprint(report: dict) -> dict:
    """The deterministic slice of a report: the executed join/leave/rotate
    event order and the validator-set composition sequence (wall-clock
    fields excluded) — what two same-seed runs must agree on."""
    return {"executed": [list(e) for e in report["executed"]],
            "compositions": report["compositions"],
            "plan": report["plan"]}


# -- self-test (stdlib-only: plan + schema, the net runs live in chaos) ------

def self_test() -> int:
    # plan determinism + shape
    p1 = plan_churn(7, 3, 8)
    p2 = plan_churn(7, 3, 8)
    assert p1 == p2, "same-seed plans diverged"
    assert plan_churn(8, 3, 8) != p1, "seed does not vary the plan"
    assert len(p1["events"]) == 3
    for ev in p1["events"]:
        assert ev["join"].startswith("join")
        assert ev.get("leave", "full").startswith(("full", "join"))
        if "rotate_in" in ev:
            assert ev["rotate_out"] != "val0", "anchor must never rotate out"
    # compositions: constant size, change only on rotation
    sizes = {len(c) for c in p1["compositions"]}
    assert sizes == {N_VALIDATORS}, sizes
    n_rot = sum(1 for ev in p1["events"] if "rotate_in" in ev)
    assert len(p1["compositions"]) == 1 + n_rot
    # quorum safety: a leave never names a current validator
    vset = set(p1["compositions"][0])
    for ev, comp in zip(p1["events"],
                        p1["compositions"][1:] + [p1["compositions"][-1]]):
        assert ev.get("leave") not in vset, ev
        vset = set(comp)
    # roster helper
    vals, fulls = node_names(8)
    assert len(vals) == N_VALIDATORS and len(fulls) == 4
    vals, fulls = node_names(3)
    assert len(vals) == 3 and fulls == []
    # fingerprint strips wall-clock fields
    fake = {"executed": [("join", "join0")], "compositions": [["a"]],
            "plan": {"events": []}, "elapsed_s": 1.23,
            "join_caughtup_s": {"join0": 4.5}}
    fp = schedule_fingerprint(fake)
    assert "elapsed_s" not in json.dumps(fp)
    assert fp["executed"] == [["join", "join0"]]
    # the retain window must cover a snapshot (joiners depend on it)
    assert RETAIN_BLOCKS > 2 * SNAPSHOT_INTERVAL
    print("churn self-test OK (plan determinism, quorum safety, schema)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--intervals", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--topology", choices=("full_mesh", "sparse"),
                    default="full_mesh")
    ap.add_argument("--degree", type=int, default=3)
    ap.add_argument("--rate", type=float, default=10.0,
                    help="open-loop tx rate during the run")
    ap.add_argument("--verify-determinism", action="store_true",
                    help="run TWICE with the same seed and assert identical "
                         "join/leave/commit schedules")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()

    r1 = run_churn(args.nodes, args.intervals, args.seed, args.topology,
                   args.degree, args.rate)
    if args.verify_determinism:
        r2 = run_churn(args.nodes, args.intervals, args.seed, args.topology,
                       args.degree, args.rate)
        f1, f2 = schedule_fingerprint(r1), schedule_fingerprint(r2)
        if f1 != f2:
            print("DETERMINISM FAIL:\n" + json.dumps(f1, indent=2)
                  + "\nvs\n" + json.dumps(f2, indent=2), file=sys.stderr)
            return 1
        r1["determinism_verified"] = True
    if args.json:
        print(json.dumps(r1, indent=2))
    else:
        print(f"churn OK: N={r1['n_nodes']} seed={r1['seed']} "
              f"{r1['topology']} h {r1['height_initial']}→"
              f"{r1['height_final']} "
              f"({r1['blocks_per_min']} blocks/min) "
              f"joins={r1['join_caughtup_s']} rotations={r1['rotations']} "
              f"wakeups/link/block={r1['wakeups_per_link_per_block']}"
              + (" [determinism verified]"
                 if r1.get("determinism_verified") else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
