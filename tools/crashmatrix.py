"""Crash matrix: SIGKILL one node at EVERY durability boundary, prove it
comes back correct — and never equivocates.

Every durability mechanism in this repo (CRC-framed WAL + repair-on-open,
atomic-rename privval state, transactional KV batches, MempooWAL replay)
exists for exactly one moment: the instant the process dies. This driver
manufactures that moment at every enumerated boundary, deterministically:

* a 4-validator in-proc fleet (3 in-memory survivors + ONE fully
  persistent victim: SQLite block/state stores, file WAL, FilePV sign
  state, MempoolWAL, a durable kvstore app that prunes its own block
  store) commits under open-loop tx load;
* per boundary in a SEEDED order, the victim is killed AT the boundary
  via ``libs.fail.arm_raise`` — the in-proc analog of SIGKILL: a
  BaseException no defensive handler can swallow, scoped (``fail.scope``)
  so boundaries living in shared code paths (execution, commit) kill
  only the victim's tasks. At kill time the victim's buffered file
  bytes are DISCARDED (fds dup2'd onto /dev/null — what the kernel does
  to unflushed buffers on a real SIGKILL) and its sqlite transactions
  roll back (what losing the fd does);
* a ``libs.supervisor.RestartSupervisor`` (policy "on-failure", bounded
  exponential backoff) restarts it: rebuild from the home dir — WAL
  repair-on-open, ABCI handshake replay, WAL catchup replay, FilePV
  reload — rejoin the live net, catch back up via consensus catchup
  gossip;
* the ``statesync.mid_chunk_apply`` boundary kills a fresh statesync
  JOINER mid-restore instead; the retry restores from scratch;
* the ``net.during_quorum_loss`` boundary is a timing WINDOW, not a code
  site: >1/3 of voting power is isolated until consensus halts
  fleet-wide (watchdog ``quorum_lost``), and the victim is then killed
  at its next WAL fsync INSIDE the halted window — proving WAL repair +
  handshake replay across a halt-spanning WAL after the heal.

Invariants per kill: the boundary actually fired; the victim recovers to
a height >= the net's tip at restart; app hashes agree with survivors at
a common height; the sign state never regresses and NO double-sign
evidence appears anywhere (pending or committed) — the restarted
validator re-emits at most timestamp-equivalent votes; and afterwards
the victim's MempoolWAL replay is idempotent (a second replay re-admits
nothing).

Determinism: the kill schedule is a pure function of the seed
(``plan_crashes``), and ``--verify-determinism`` runs the whole matrix
twice, diffing schedule + per-kill outcome fingerprints (wall-clock
fields excluded).

    python tools/crashmatrix.py --seed 1
    python tools/crashmatrix.py --seed 1 --verify-determinism
    python tools/crashmatrix.py --boundaries wal.after_fsync,prune.mid_blocks
    python tools/crashmatrix.py --self-test      # stdlib-only, seconds

Stdlib-only at the top level; repo imports happen inside the run (the
churn.py/chaos_matrix discipline) so --help/--self-test work anywhere —
including slim containers without ``cryptography``, which is the point:
the subprocess-TCP variant of this matrix (e2e manifests with
``fail_point`` + ``restart_policy = "on-failure"``) needs that package,
the in-proc matrix does not.
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

#: boundaries killed on the persistent VICTIM validator, in catalog order
#: (the seeded plan shuffles execution order). Every name must be in
#: libs.fail.KNOWN_FAIL_POINTS — --self-test enforces the subset.
VICTIM_BOUNDARIES = (
    "wal.before_fsync",
    "wal.after_fsync",
    "wal.mid_group_commit",
    "db.mid_window_flush",
    "privval.between_sign_and_save",
    "consensus.commit.before_end_height",
    "execution.before_exec_block",
    "execution.after_state_save",
    "prune.mid_blocks",
)
#: boundaries killed on a fresh statesync JOINER mid-restore
JOINER_BOUNDARIES = ("statesync.mid_chunk_apply",)
#: the degraded-network boundary: NOT a code fail point but a timing
#: window — >1/3 of voting power is isolated until consensus halts
#: fleet-wide (watchdog classifies quorum_lost), and only THEN is the
#: victim killed, at its next WAL fsync (QUORUM_KILL_SITE; gossip
#: stall-refresh re-sends keep peer records flowing through the wedged
#: victim's WAL, so the armed site fires inside the halted window).
#: Proves WAL repair + handshake replay across a halt-spanning WAL.
QUORUM_BOUNDARIES = ("net.during_quorum_loss",)
QUORUM_KILL_SITE = "wal.before_fsync"
ALL_BOUNDARIES = VICTIM_BOUNDARIES + QUORUM_BOUNDARIES + JOINER_BOUNDARIES

VICTIM = "crash"        # the persistent victim's node name
N_SURVIVORS = 3         # val0..val2, in-memory
SNAPSHOT_INTERVAL = 3   # donor snapshots for the joiner boundary
RETAIN_BLOCKS = 6       # victim app's prune window (prune.mid_blocks)

#: scheduling/wall-clock field names stripped from determinism
#: fingerprints (wal_repaired depends on where the io buffer happened to
#: spill mid-frame at kill time — real, but not part of the schedule)
_CLOCK_FIELDS = ("kill_to_caughtup_s", "join_caughtup_s", "backoff_s",
                 "elapsed_s", "recovery_records_replayed",
                 "wal_repaired", "wal_repaired_bytes")


# -- the deterministic plan (pure) -------------------------------------------

def plan_crashes(seed: int, boundaries=None) -> dict:
    """The kill schedule as a pure function of its inputs: a seeded order
    over the requested boundaries (victim kills shuffled, joiner kills
    last — a mid-restore kill needs donors with settled snapshots), each
    with its target node. Two same-seed calls are byte-identical; the
    property --verify-determinism checks end-to-end against two runs."""
    import random
    import zlib

    boundaries = list(boundaries or ALL_BOUNDARIES)
    unknown = [b for b in boundaries if b not in ALL_BOUNDARIES]
    if unknown:
        raise ValueError(f"unknown boundaries {unknown}; "
                         f"known: {list(ALL_BOUNDARIES)}")
    rng = random.Random(zlib.crc32(f"crash|{seed}".encode()))
    victim_kills = [b for b in boundaries if b in VICTIM_BOUNDARIES]
    quorum_kills = [b for b in boundaries if b in QUORUM_BOUNDARIES]
    joiner_kills = [b for b in boundaries if b in JOINER_BOUNDARIES]
    rng.shuffle(victim_kills)
    # the quorum-loss window halts the whole fleet for seconds — run it
    # after the plain victim kills, before the joiner (whose statesync
    # catchup wants an already-healed, committing net)
    kills = ([{"boundary": b, "target": VICTIM} for b in victim_kills]
             + [{"boundary": b, "target": VICTIM} for b in quorum_kills]
             + [{"boundary": b, "target": "joiner"} for b in joiner_kills])
    return {"seed": seed, "kills": kills}


def outcome_fingerprint(report: dict) -> dict:
    """The deterministic slice of a report: the executed kill schedule and
    each kill's boolean outcomes, wall-clock fields excluded — what two
    same-seed runs must agree on."""
    kills = []
    for k in report["kills"]:
        kills.append({key: v for key, v in k.items()
                      if key not in _CLOCK_FIELDS})
    return {"plan": report["plan"], "kills": kills}


# -- the in-proc rig ---------------------------------------------------------

_RIG = None


def _rig():
    """Import-heavy rig pieces, built lazily and memoized (one node class
    per process) — keeps --help/--self-test stdlib-fast."""
    global _RIG
    if _RIG is not None:
        return _RIG

    from tendermint_tpu import crypto
    from tendermint_tpu.abci.example.kvstore import SnapshotKVStoreApplication
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.consensus import ConsensusState
    from tendermint_tpu.consensus.config import test_consensus_config
    from tendermint_tpu.consensus.replay import Handshaker, catchup_replay
    from tendermint_tpu.consensus.wal import WAL
    from tendermint_tpu.evidence.pool import EvidencePool
    from tendermint_tpu.libs import fail
    from tendermint_tpu.libs.db import MemDB, SQLiteDB
    from tendermint_tpu.libs.fail import KilledAtFailPoint
    from tendermint_tpu.mempool import CListMempool
    from tendermint_tpu.mempool.clist_mempool import init_mempool_wal
    from tendermint_tpu.mempool.reactor import MempoolReactor
    from tendermint_tpu.p2p import Switch
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.proxy import AppConns, local_client_creator
    from tendermint_tpu.state import (BlockExecutor, StateStore,
                                      state_from_genesis)
    from tendermint_tpu.statesync.reactor import StateSyncReactor
    from tendermint_tpu.store import BlockStore
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

    class DurableCrashApp(SnapshotKVStoreApplication):
        """Snapshot kvstore whose state survives process death: every
        commit atomically persists {state, height, ...} so the restarted
        victim's ABCI handshake replays only the block-store suffix —
        which is what lets the victim PRUNE its own block store (the
        prune.mid_blocks boundary) and still restart without
        replay-from-genesis."""

        def __init__(self, path: str, interval: int, retain: int):
            super().__init__(interval=interval)
            self.path = path
            self.retain = retain
            if os.path.exists(path):
                with open(path) as f:
                    doc = json.load(f)
                self.state = dict(doc["state"])
                self.tx_count = doc["tx_count"]
                self.height = doc["height"]
                self.validators = dict(doc["validators"])
                self.app_hash = bytes.fromhex(doc["app_hash"])

        def commit(self):
            resp = super().commit()
            if self.retain:
                resp.retain_height = max(0, self.height - self.retain)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"state": self.state, "tx_count": self.tx_count,
                           "height": self.height,
                           "validators": self.validators,
                           "app_hash": self.app_hash.hex()}, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
            return resp

    class CrashRigNode:
        """One in-proc node. home=None → in-memory survivor (MockPV,
        NilWAL, MemDB); home=<dir> → the persistent victim (file WAL with
        repair-on-open, SQLite stores, FilePV, MempoolWAL, durable
        pruning app). Every node carries a REAL EvidencePool so a
        double-signing restarted validator would surface as committed
        evidence, not pass silently."""

        def __init__(self, name, genesis, home=None, pv=None,
                     fast_sync=False):
            self.name = name
            self.home = home
            self.killed_at = None
            self.killed_evt = None  # created at start (needs a loop)
            if home is not None:
                os.makedirs(home, exist_ok=True)
                self.pv = _victim_pv(home)
                self.app = DurableCrashApp(os.path.join(home, "app.json"),
                                           SNAPSHOT_INTERVAL, RETAIN_BLOCKS)
                self._state_db = SQLiteDB(os.path.join(home, "state.db"))
                self._blocks_db = SQLiteDB(os.path.join(home, "blocks.db"))
                wal = WAL(os.path.join(home, "cs.wal"))
            else:
                self.pv = pv
                self.app = SnapshotKVStoreApplication(
                    interval=SNAPSHOT_INTERVAL)
                self._state_db = MemDB()
                self._blocks_db = MemDB()
                wal = None
            self.wal_repairs = getattr(wal, "repairs", 0)
            self.wal_repaired_bytes = getattr(wal, "repaired_bytes", 0)
            self.conns = AppConns(local_client_creator(self.app))
            self.conns.start()
            self.state_store = StateStore(self._state_db)
            self.block_store = BlockStore(self._blocks_db)
            state = state_from_genesis(genesis)
            loaded = self.state_store.load()
            if loaded is not None:
                state = loaded
            state = Handshaker(self.state_store, state, self.block_store,
                               genesis).handshake(self.conns.consensus,
                                                  self.conns.query)
            self.state_store.save(state)
            self.mempool = CListMempool(self.conns.mempool)
            if home is not None:
                init_mempool_wal(self.mempool,
                                 os.path.join(home, "mempool_wal"))
            self.evpool = EvidencePool(MemDB(), self.state_store,
                                       self.block_store)
            self.block_exec = BlockExecutor(self.state_store,
                                            self.conns.consensus,
                                            self.mempool, self.evpool,
                                            self.block_store)
            self.cs = ConsensusState(test_consensus_config(), state,
                                     self.block_exec, self.block_store,
                                     evpool=self.evpool, wal=wal)
            self.cs.set_priv_validator(self.pv)
            self.mempool.tx_available_callbacks.append(
                self.cs.notify_txs_available)
            self.switch = Switch(name)
            from tendermint_tpu.consensus.reactor import ConsensusReactor

            self.cs_reactor = ConsensusReactor(self.cs, wait_sync=fast_sync)
            self.switch.add_reactor("CONSENSUS", self.cs_reactor)
            self.bc_reactor = BlockchainReactor(
                state, self.block_exec, self.block_store, fast_sync=False,
                consensus_reactor=self.cs_reactor)
            self.switch.add_reactor("BLOCKCHAIN", self.bc_reactor)
            self.mp_reactor = MempoolReactor(self.mempool, gossip_sleep=0.01)
            self.switch.add_reactor("MEMPOOL", self.mp_reactor)
            self.ss_reactor = StateSyncReactor(self.app, self.app)
            self.switch.add_reactor("STATESYNC", self.ss_reactor)
            self.fast_sync = fast_sync
            self._started = False
            self.recovery_records_replayed = 0
            # kill guard: a BaseException at a boundary ends the receive
            # loop; record WHERE so the rig can react — the same shape
            # subprocess supervision sees (a dead process + its log line)
            orig = self.cs.receive_routine

            async def guarded():
                try:
                    await orig()
                except KilledAtFailPoint as e:
                    self.killed_at = e.site
                    if self.killed_evt is not None:
                        self.killed_evt.set()

            self.cs.receive_routine = guarded

        @property
        def height(self):
            return self.cs.state.last_block_height

        async def start(self):
            import asyncio

            self._started = True
            self.killed_evt = asyncio.Event()
            # every task the node creates below inherits this scope, so
            # armed boundaries in SHARED code kill only this node's tasks
            token = fail.scope.set(self.name)
            try:
                await self.switch.start()
                if not self.fast_sync:
                    # the node.py non-fast-sync boot path: WAL catchup for
                    # the in-flight height BEFORE the machine runs
                    self.recovery_records_replayed = catchup_replay(
                        self.cs, self.cs.rs.height)
                    await self.cs.start()
            finally:
                fail.scope.reset(token)

        def freeze(self):
            """SIGKILL semantics for an in-proc node: unflushed buffered
            bytes die with the process (dup2 the fds onto /dev/null so
            later GC/close flushes land nowhere) and open sqlite
            transactions roll back (what losing the fd does)."""
            self._devnull = open(os.devnull, "wb")
            wal_f = getattr(self.cs.wal, "_f", None)
            mwal = getattr(self.mempool, "_wal", None)
            for fobj in (wal_f, getattr(mwal, "_f", None)):
                if fobj is None:
                    continue
                try:
                    os.dup2(self._devnull.fileno(), fobj.fileno())
                except (OSError, ValueError):
                    pass
            for db in (self._state_db, self._blocks_db):
                conn = getattr(db, "_conn", None)
                if conn is not None:
                    try:
                        conn.rollback()
                        conn.close()
                    except Exception:
                        pass

        async def stop(self):
            if not self._started:
                return
            self._started = False
            await self.cs.stop()
            await self.switch.stop()
            self.conns.stop()

    def _victim_pv(home):
        key = os.path.join(home, "pv_key.json")
        state = os.path.join(home, "pv_state.json")
        if os.path.exists(key):
            # a corrupt sign state raises here — startup refused, exactly
            # like a real node boot (privval satellite)
            return FilePV.load(key, state)
        pv = FilePV.generate(key, state, seed=(VICTIM.encode() * 32)[:32])
        pv.save()
        return pv

    def make_pv(tag: str):
        return MockPV(crypto.Ed25519PrivKey.generate((tag.encode() * 32)[:32]))

    def make_genesis(pubkeys):
        return GenesisDoc(
            chain_id="crash-chain",
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=[GenesisValidator(pk, 10) for pk in pubkeys])

    _RIG = {"CrashRigNode": CrashRigNode, "make_pv": make_pv,
            "make_genesis": make_genesis, "fail": fail,
            "KilledAtFailPoint": KilledAtFailPoint, "FilePV": FilePV}
    return _RIG


# -- the run ------------------------------------------------------------------

async def _bounded(coro, secs: float, what: str, fatal: bool = True):
    """Every await in the rig is BOUNDED: a wedged stop/teardown must
    surface as a loud failure (fatal) or a logged note (cleanup paths),
    never as a silently hung matrix."""
    import asyncio

    try:
        return await asyncio.wait_for(coro, timeout=secs)
    except asyncio.TimeoutError:
        if fatal:
            raise AssertionError(f"{what} wedged past {secs}s")
        print(f"crashmatrix: {what} wedged past {secs}s (cleanup path, "
              f"continuing)", file=sys.stderr, flush=True)
        return None


async def _run_async(seed: int, boundaries, home_root: str) -> dict:
    import asyncio

    from tendermint_tpu.libs.supervisor import RestartPolicy, RestartSupervisor
    from tendermint_tpu.libs.toolbox import load_tool
    from tendermint_tpu.p2p import InProcNetwork

    # via the toolbox helper, not a bare import: callers that loaded THIS
    # module through load_tool (chaos_matrix, the tests) have already popped
    # tools/ back off sys.path by the time the run executes
    churn = load_tool("churn")

    rig = _rig()
    fail = rig["fail"]
    CrashRigNode = rig["CrashRigNode"]
    plan = plan_crashes(seed, boundaries)
    victim_home = os.path.join(home_root, VICTIM)

    survivor_names = [f"val{i}" for i in range(N_SURVIVORS)]
    pvs = {n: rig["make_pv"](n) for n in survivor_names}
    # the victim's FilePV key is deterministic (seeded) so genesis can name
    # it before the node object exists
    victim_pub = rig["FilePV"].generate(
        "", "", seed=(VICTIM.encode() * 32)[:32]).get_pub_key()
    genesis = rig["make_genesis"](
        [pvs[n].get_pub_key() for n in survivor_names] + [victim_pub])

    nodes = {n: CrashRigNode(n, genesis, pv=pvs[n]) for n in survivor_names}
    nodes[VICTIM] = CrashRigNode(VICTIM, genesis, home=victim_home)
    net = InProcNetwork()
    for nd in nodes.values():
        net.add_switch(nd.switch)
    for nd in nodes.values():
        await nd.start()
    await net.connect_all()

    rewire_task = asyncio.create_task(churn.rewire_loop(net, interval=0.2))

    async def load():
        import itertools

        loop = asyncio.get_running_loop()
        t0 = loop.time() + 0.1
        for i in itertools.count():
            target = t0 + i / 10.0
            now = loop.time()
            if target > now:
                await asyncio.sleep(target - now)
            live = [nd for n, nd in nodes.items()
                    if nd._started and not nd.fast_sync]
            if not live:
                continue
            try:
                # fat values: the app blob must span several snapshot
                # chunks quickly, or the mid-chunk-apply boundary (which
                # needs >=1 chunk already applied) can never fire
                live[i % len(live)].mempool.check_tx(
                    b"crash-%d-%d=" % (seed, i) + b"x" * 120)
            except Exception:
                pass  # a full mempool under kills is load, not failure

    load_task = asyncio.create_task(load())
    t_run0 = time.monotonic()
    kills = []
    try:
        await churn._wait_heights(list(nodes.values()), 2, timeout=120)

        for kill in plan["kills"]:
            boundary = kill["boundary"]
            print(f"crashmatrix: arming {boundary} "
                  f"(h={max(nd.height for nd in nodes.values())}, "
                  f"t+{time.monotonic() - t_run0:.0f}s)",
                  file=sys.stderr, flush=True)
            if kill["target"] == "joiner":
                kills.append(await _joiner_kill(net, nodes, genesis, seed,
                                                boundary, churn, rig))
                continue
            if boundary in QUORUM_BOUNDARIES:
                kills.append(await _quorum_loss_kill(
                    net, nodes, genesis, survivor_names, victim_home,
                    churn, rig))
                continue

            victim = nodes[VICTIM]
            sup = RestartSupervisor(
                RestartPolicy(policy="on-failure", max_restarts=3,
                              backoff_s=0.2, backoff_max_s=2.0,
                              healthy_uptime_s=5.0), name=VICTIM,
                time_fn=time.monotonic)
            sup.on_launch()
            lss_before = victim.pv.last_sign_state.height
            fail.arm_raise(boundary, scope_token=VICTIM)
            t_kill0 = time.monotonic()
            try:
                await asyncio.wait_for(victim.killed_evt.wait(), timeout=150)
            except asyncio.TimeoutError:
                raise AssertionError(
                    f"boundary {boundary!r} never fired on {VICTIM} "
                    f"(heights={ {n: nd.height for n, nd in nodes.items()} })")
            assert victim.killed_at == boundary, (victim.killed_at, boundary)
            assert fail.killed_at() == boundary
            # freeze disk state the way a SIGKILL would, then tear the
            # carcass down (task/switch cleanup is rig hygiene — the
            # durable state is already frozen)
            victim.freeze()
            await _bounded(net.remove_node(VICTIM), 30, "remove_node(victim)")
            await _bounded(victim.stop(), 30, "dead victim stop",
                           fatal=False)
            del nodes[VICTIM]

            backoff = sup.on_exit(1)
            assert backoff is not None and not sup.gave_up
            await asyncio.sleep(backoff)

            # survivors must have kept committing while the victim was down
            live = [nodes[n] for n in survivor_names]
            h_down = max(nd.height for nd in live)
            await churn._wait_heights(live, h_down + 1, timeout=60)

            # restart: rebuild from the home dir (WAL repair-on-open +
            # handshake replay + WAL catchup replay + FilePV reload)
            restarted = CrashRigNode(VICTIM, genesis, home=victim_home)
            nodes[VICTIM] = restarted
            sup.on_launch()
            tip = max(nd.height for nd in live)
            await _bounded(restarted.start(), 60, "restarted victim start")
            await _bounded(net.add_node(restarted.switch,
                                        connect_to=survivor_names),
                           30, "add_node(restarted victim)")
            await churn._wait_heights([restarted], tip + 1, timeout=120)
            kill_to_caughtup = time.monotonic() - t_kill0

            # -- per-kill invariants ------------------------------------
            common = min(nd.height for nd in nodes.values()) - 1
            hashes = {n: nd.block_store.load_block_meta(common).header.app_hash
                      for n, nd in nodes.items()}
            assert len(set(hashes.values())) == 1, \
                f"app hashes diverged after {boundary}: {hashes}"
            lss_after = restarted.pv.last_sign_state.height
            assert lss_after >= lss_before, \
                f"sign state regressed after {boundary}: " \
                f"{lss_before} -> {lss_after}"
            double_sign = _evidence_observed(nodes.values(), common)
            assert not double_sign, \
                f"double-sign evidence after {boundary}: {double_sign}"
            kills.append({
                "boundary": boundary, "target": VICTIM, "killed": True,
                "recovered": True, "restarts": sup.restarts,
                "evidence": 0, "double_sign_observed": False,
                "wal_repaired": bool(restarted.wal_repairs),
                "wal_repaired_bytes": restarted.wal_repaired_bytes,
                "recovery_records_replayed":
                    restarted.recovery_records_replayed,
                "kill_to_caughtup_s": round(kill_to_caughtup, 3),
                "backoff_s": backoff,
            })
    except BaseException:
        rewire_task.cancel()
        load_task.cancel()
        for nd in nodes.values():
            try:
                await _bounded(nd.stop(), 20, f"{nd.name} stop",
                               fatal=False)
            except Exception:
                pass
        raise
    finally:
        rewire_task.cancel()
        load_task.cancel()

    # settle + final teardown
    try:
        final = max(nd.height for nd in nodes.values()) + 1
        await churn._wait_heights(list(nodes.values()), final, timeout=120)
        victim = nodes.get(VICTIM)
        mempool_wal_idempotent = None
        if victim is not None:
            await _bounded(victim.stop(), 30, "final victim stop",
                           fatal=False)
            nodes.pop(VICTIM)
            mempool_wal_idempotent = _check_mempool_wal_idempotent(
                os.path.join(victim_home, "mempool_wal"))
    finally:
        for nd in nodes.values():
            try:
                await _bounded(nd.stop(), 20, f"{nd.name} stop",
                               fatal=False)
            except Exception:
                pass

    return {
        "seed": seed, "plan": plan, "kills": kills,
        "boundaries_killed": [k["boundary"] for k in kills],
        "mempool_wal_idempotent": mempool_wal_idempotent,
        "elapsed_s": round(time.monotonic() - t_run0, 2),
    }


async def _joiner_kill(net, nodes, genesis, seed, boundary, churn, rig):
    """The statesync boundary: a fresh joiner dies mid-chunk-apply, the
    supervised retry restores from scratch and catches up."""
    import asyncio

    from tendermint_tpu.libs.supervisor import RestartPolicy, RestartSupervisor

    fail = rig["fail"]
    CrashRigNode = rig["CrashRigNode"]
    donor = nodes["val0"]
    sup = RestartSupervisor(
        RestartPolicy(policy="on-failure", max_restarts=3, backoff_s=0.2,
                      backoff_max_s=2.0, healthy_uptime_s=5.0),
        name="joiner", time_fn=time.monotonic)
    neighbors = sorted(nodes)
    # a mid-apply kill needs a MULTI-chunk snapshot (>=1 chunk applied,
    # restore incomplete); the fat load txs get the donor there quickly
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        if any(len(c) >= 2 for c in donor.app._snapshots.values()):
            break
        await asyncio.sleep(0.2)
    else:
        raise AssertionError("donor never produced a multi-chunk snapshot")
    t0 = time.monotonic()

    print(f"crashmatrix: joiner restoring from donor snapshots "
          f"{sorted(donor.app._snapshots)} (armed {boundary})",
          file=sys.stderr, flush=True)
    jn = CrashRigNode("joiner", genesis, pv=rig["make_pv"]("joiner"),
                      fast_sync=True)
    sup.on_launch()
    fail.arm_raise(boundary, scope_token="joiner")
    token = fail.scope.set("joiner")
    killed = False
    try:
        # join_statesync bounds its phases internally; the outer bound
        # catches any wedge in its switch wiring / reactor teardown
        await _bounded(churn.join_statesync(net, jn, donor, neighbors, seed),
                       300, "armed joiner statesync")
    except rig["KilledAtFailPoint"] as e:
        assert e.site == boundary
        killed = True
    finally:
        fail.scope.reset(token)
    print(f"crashmatrix: joiner killed at {boundary}: {killed}",
          file=sys.stderr, flush=True)
    assert killed, f"boundary {boundary!r} never fired on the joiner"
    await _bounded(net.remove_node("joiner"), 30, "remove_node(joiner)")
    try:
        await _bounded(jn.stop(), 20, "killed joiner stop", fatal=False)
    except Exception:
        pass
    nodes.pop("joiner", None)

    backoff = sup.on_exit(1)
    assert backoff is not None
    await asyncio.sleep(backoff)

    # the retry: a FRESH node (a half-restored app is untrusted torso —
    # the app restore machinery re-derives everything from chunk 0)
    retry = CrashRigNode("joiner", genesis, pv=rig["make_pv"]("joiner"),
                         fast_sync=True)
    nodes["joiner"] = retry
    sup.on_launch()
    caught = await _bounded(
        churn.join_statesync(net, retry, donor, neighbors, seed),
        300, "joiner retry statesync")
    common = min(nd.height for nd in nodes.values()) - 1
    hashes = {nd.block_store.load_block_meta(common).header.app_hash
              for nd in nodes.values()
              if nd.block_store.load_block_meta(common) is not None}
    assert len(hashes) == 1, "joiner diverged from the fleet"
    return {"boundary": boundary, "target": "joiner", "killed": True,
            "recovered": True, "restarts": sup.restarts, "evidence": 0,
            "double_sign_observed": False,
            "kill_to_caughtup_s": round(time.monotonic() - t0, 3),
            "backoff_s": backoff, "join_caughtup_s": caught}


async def _quorum_loss_kill(net, nodes, genesis, survivor_names,
                            victim_home, churn, rig):
    """The net.during_quorum_loss boundary: WAL + handshake replay across
    a quorum-loss halt. Two survivor validators (>1/3 of voting power)
    are isolated until consensus halts fleet-wide and a survivor's
    watchdog classifies the episode ``quorum_lost``; the victim — wedged
    in the MAJORITY partition — is then killed at its next WAL fsync
    (gossip stall-refresh re-sends keep peer records flowing through its
    WAL, so the armed site fires while the window is still halted). The
    partition heals and the victim rebuilds from its home dir: WAL
    repair-on-open + handshake replay spanning the halted window, rejoin,
    and the full fleet commits past the halt height — never
    double-signing."""
    import asyncio

    from tendermint_tpu.consensus.watchdog import ConsensusWatchdog
    from tendermint_tpu.libs.supervisor import RestartPolicy, RestartSupervisor

    fail = rig["fail"]
    CrashRigNode = rig["CrashRigNode"]
    victim = nodes[VICTIM]
    isolate = ["val1", "val2"]  # 20/40 power: >1/3, victim stays majority
    # the recovery clock: bitmap refresh -> vote re-send (see
    # tools/quorum_loss.py) — also what keeps peer records flowing
    # through the wedged victim's WAL so the armed kill site fires
    for nd in nodes.values():
        nd.cs.config.gossip_stall_refresh_s = 1.0
    observer = nodes["val0"]
    wd = ConsensusWatchdog(observer.cs, stall_timeout_s=1.2,
                           check_interval_s=0.3,
                           height_fn=lambda: observer.height)
    await wd.start()
    sup = RestartSupervisor(
        RestartPolicy(policy="on-failure", max_restarts=3, backoff_s=0.2,
                      backoff_max_s=2.0, healthy_uptime_s=5.0), name=VICTIM,
        time_fn=time.monotonic)
    sup.on_launch()
    lss_before = victim.pv.last_sign_state.height
    t_kill0 = time.monotonic()
    try:
        net.partition(isolate)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not wd.stalls:
            await asyncio.sleep(0.1)
        assert wd.stalls, "fleet never halted under >1/3 isolation"
        assert wd.last_halt_reason == "quorum_lost", \
            f"halt misclassified: {wd.last_halt_reason!r}"
        h_halt = max(nd.height for nd in nodes.values())

        fail.arm_raise(QUORUM_KILL_SITE, scope_token=VICTIM)
        # the armed site needs WAL traffic on the wedged victim. A PREVOTE
        # wedge (no polka) gets it from gossip stall-refresh re-sends; a
        # PRECOMMIT wedge keeps every link chatty with maj23 queries —
        # never silent, so nothing is re-sent and nothing is WAL'd.
        # Re-deliver one duplicate vote into the victim's queue (exactly
        # what a stall-refresh re-send is): receive_routine WALs every
        # peer record before applying it, and the group commit's deadline
        # fsync fires the kill regardless of which step the wedge hit.
        from tendermint_tpu.consensus.state import VoteMessage
        deadline = time.monotonic() + 60
        while (not victim.killed_evt.is_set()
               and time.monotonic() < deadline):
            rs = victim.cs.rs
            vs = rs.votes.prevotes(rs.round) if rs.votes else None
            votes = vs.list_votes() if vs is not None else []
            if votes:
                await victim.cs.add_peer_msg(VoteMessage(votes[0]), "val0")
            await asyncio.sleep(0.1)
        assert victim.killed_evt.is_set(), \
            f"{QUORUM_KILL_SITE!r} never fired on {VICTIM} during the " \
            f"halted window"
        assert victim.killed_at == QUORUM_KILL_SITE
        # the kill landed INSIDE the window: no commit since halt detection
        h_at_kill = max(nd.height for nd in nodes.values())
        assert h_at_kill == h_halt, \
            f"height advanced during the halt: {h_halt} -> {h_at_kill}"
        victim.freeze()
        await _bounded(net.remove_node(VICTIM), 30, "remove_node(victim)")
        await _bounded(victim.stop(), 30, "dead victim stop", fatal=False)
        del nodes[VICTIM]

        backoff = sup.on_exit(1)
        assert backoff is not None and not sup.gave_up
        await asyncio.sleep(backoff)

        # heal and restart the victim immediately: the property under
        # test is the restart replaying a halt-spanning WAL and
        # rejoining, not 3-of-4 progress (the plain victim boundaries
        # prove survivors commit while one validator is down) — and a
        # full 40/40 post-heal fleet recovers exactly like the proven
        # tools/quorum_loss.py window, where 30/40 with a dead proposer
        # in the rotation can wedge on rare post-heal vote states
        net.heal(group_a=isolate)
        restarted = CrashRigNode(VICTIM, genesis, home=victim_home)
        nodes[VICTIM] = restarted
        sup.on_launch()
        await _bounded(restarted.start(), 60, "restarted victim start")
        await _bounded(net.add_node(restarted.switch,
                                    connect_to=survivor_names),
                       30, "add_node(restarted victim)")
        await churn._wait_heights(list(nodes.values()), h_halt + 1,
                                  timeout=120)
    finally:
        await wd.stop()
    kill_to_caughtup = time.monotonic() - t_kill0

    common = min(nd.height for nd in nodes.values()) - 1
    hashes = {n: nd.block_store.load_block_meta(common).header.app_hash
              for n, nd in nodes.items()}
    assert len(set(hashes.values())) == 1, \
        f"app hashes diverged after the quorum-loss kill: {hashes}"
    lss_after = nodes[VICTIM].pv.last_sign_state.height
    assert lss_after >= lss_before, \
        f"sign state regressed across the halt: {lss_before} -> {lss_after}"
    double_sign = _evidence_observed(nodes.values(), common)
    assert not double_sign, \
        f"double-sign evidence after the quorum-loss kill: {double_sign}"
    return {
        "boundary": QUORUM_BOUNDARIES[0], "target": VICTIM,
        "kill_site": QUORUM_KILL_SITE, "killed": True, "halted": True,
        "halt_reason": wd.last_halt_reason, "recovered": True,
        "restarts": sup.restarts, "evidence": 0,
        "double_sign_observed": False,
        "wal_repaired": bool(nodes[VICTIM].wal_repairs),
        "wal_repaired_bytes": nodes[VICTIM].wal_repaired_bytes,
        "recovery_records_replayed":
            nodes[VICTIM].recovery_records_replayed,
        "kill_to_caughtup_s": round(kill_to_caughtup, 3),
        "backoff_s": backoff,
    }


def _evidence_observed(nodes, up_to_height: int):
    """Any pending or committed DuplicateVoteEvidence anywhere — the
    on-the-wire observable of a double-sign."""
    found = []
    for nd in nodes:
        pending, _ = nd.evpool.pending_evidence(1 << 20)
        found.extend((nd.name, "pending", type(e).__name__) for e in pending)
        for h in range(max(1, up_to_height - 20), up_to_height + 1):
            blk = nd.block_store.load_block(h)
            ev = getattr(getattr(blk, "evidence", None), "evidence", None) \
                if blk is not None else None
            if ev:
                found.extend((nd.name, f"committed@{h}",
                              type(e).__name__) for e in ev)
    return found


def _check_mempool_wal_idempotent(wal_dir: str) -> bool:
    """Replay the victim's MempoolWAL TWICE into one fresh mempool: the
    second pass must re-admit nothing (every line a cache dup/skip)."""
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication
    from tendermint_tpu.mempool import CListMempool
    from tendermint_tpu.mempool.ingest import replay_mempool_wal
    from tendermint_tpu.proxy import AppConns, local_client_creator

    conns = AppConns(local_client_creator(KVStoreApplication()))
    conns.start()
    try:
        mp = CListMempool(conns.mempool, max_txs=100000)
        replayed1, _ = replay_mempool_wal(mp, wal_dir)
        replayed2, skipped2 = replay_mempool_wal(mp, wal_dir)
        assert replayed2 == 0, \
            f"MempoolWAL replay not idempotent: 2nd pass admitted {replayed2}"
        assert replayed1 == 0 or skipped2 >= replayed1
        return True
    finally:
        conns.stop()


def run_matrix(seed: int = 1, boundaries=None) -> dict:
    """One full matrix run; returns the report dict (asserts on failure).
    Pure-python ed25519 keeps the rig independent of device kernels."""
    import asyncio
    import tempfile

    os.environ.setdefault("TMTPU_BATCH_BACKEND", "host")
    home_root = tempfile.mkdtemp(prefix=f"crashmatrix-{seed}-")
    from tendermint_tpu.libs import fail

    fail.reset()
    try:
        return asyncio.run(_run_async(seed, boundaries, home_root))
    finally:
        fail.reset()


# -- self-test (stdlib + cheap libs: plan, schema, catalog, supervisor) ------

def self_test() -> int:
    from tendermint_tpu.libs.fail import KNOWN_FAIL_POINTS
    from tendermint_tpu.libs.supervisor import RestartPolicy

    # the code-site boundary catalog is a subset of the production fail
    # points — a drifting name would make that cell pass vacuously. The
    # quorum-loss boundary is a timing WINDOW, not a code site; the site
    # it arms inside the window must itself be real
    assert (set(VICTIM_BOUNDARIES + JOINER_BOUNDARIES)
            <= set(KNOWN_FAIL_POINTS)), \
        sorted(set(VICTIM_BOUNDARIES + JOINER_BOUNDARIES)
               - set(KNOWN_FAIL_POINTS))
    assert QUORUM_KILL_SITE in KNOWN_FAIL_POINTS
    assert not set(QUORUM_BOUNDARIES) & set(KNOWN_FAIL_POINTS)
    # plan determinism + shape
    p1 = plan_crashes(7)
    p2 = plan_crashes(7)
    assert p1 == p2, "same-seed plans diverged"
    assert plan_crashes(8) != p1, "seed does not vary the plan"
    assert len(p1["kills"]) == len(ALL_BOUNDARIES)
    assert {k["boundary"] for k in p1["kills"]} == set(ALL_BOUNDARIES)
    # joiner boundaries always run last (donors need settled snapshots),
    # the quorum-loss window just before them (it halts the whole fleet)
    targets = [k["target"] for k in p1["kills"]]
    assert targets[-len(JOINER_BOUNDARIES):] == ["joiner"] * len(
        JOINER_BOUNDARIES)
    assert all(t == VICTIM for t in targets[:-len(JOINER_BOUNDARIES)])
    pre_joiner = [k["boundary"] for k in p1["kills"]][:-len(JOINER_BOUNDARIES)]
    assert pre_joiner[-len(QUORUM_BOUNDARIES):] == list(QUORUM_BOUNDARIES)
    # subset + unknown rejection
    sub = plan_crashes(1, ["wal.after_fsync"])
    assert [k["boundary"] for k in sub["kills"]] == ["wal.after_fsync"]
    try:
        plan_crashes(1, ["no.such.boundary"])
        raise AssertionError("unknown boundary accepted")
    except ValueError:
        pass
    # fingerprint strips wall-clock fields but keeps the invariant schema
    fake = {"plan": p1, "kills": [{
        "boundary": "wal.after_fsync", "target": VICTIM, "killed": True,
        "recovered": True, "restarts": 1, "evidence": 0,
        "double_sign_observed": False, "wal_repaired": False,
        "wal_repaired_bytes": 0, "recovery_records_replayed": 3,
        "kill_to_caughtup_s": 4.5, "backoff_s": 0.2}],
        "elapsed_s": 9.9}
    fp = outcome_fingerprint(fake)
    s = json.dumps(fp)
    assert "kill_to_caughtup_s" not in s and "backoff_s" not in s
    for key in ("killed", "recovered", "evidence", "double_sign_observed"):
        assert key in fp["kills"][0], key
    # the supervisor's backoff schedule is the bounded doubling the
    # README documents
    assert RestartPolicy(policy="on-failure", max_restarts=3,
                         backoff_s=0.5).schedule() == [0.5, 1.0, 2.0]
    print("crashmatrix self-test OK (catalog, plan determinism, schema)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--boundaries", default="",
                    help="comma-separated subset of: "
                         + ", ".join(ALL_BOUNDARIES))
    ap.add_argument("--verify-determinism", action="store_true",
                    help="run TWICE with the same seed and assert identical "
                         "kill schedules + recovery outcomes")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    if os.environ.get("TMTPU_CRASHMATRIX_DUMP_S"):
        # debug aid for a wedged matrix: dump every thread's stack to
        # stderr on an interval (a blocked event loop shows the same
        # synchronous frame dump after dump)
        import faulthandler

        faulthandler.dump_traceback_later(
            float(os.environ["TMTPU_CRASHMATRIX_DUMP_S"]), repeat=True)

    boundaries = [b.strip() for b in args.boundaries.split(",")
                  if b.strip()] or None
    r1 = run_matrix(args.seed, boundaries)
    if args.verify_determinism:
        r2 = run_matrix(args.seed, boundaries)
        f1, f2 = outcome_fingerprint(r1), outcome_fingerprint(r2)
        if f1 != f2:
            print("DETERMINISM FAIL:\n" + json.dumps(f1, indent=2)
                  + "\nvs\n" + json.dumps(f2, indent=2), file=sys.stderr)
            return 1
        r1["determinism_verified"] = True
    if args.json:
        print(json.dumps(r1, indent=2))
    else:
        worst = max((k["kill_to_caughtup_s"] for k in r1["kills"]),
                    default=0.0)
        print(f"crashmatrix OK: seed={r1['seed']} "
              f"{len(r1['kills'])}/{len(r1['plan']['kills'])} boundaries "
              f"killed+recovered, worst kill→caught-up {worst}s, "
              f"mempool WAL idempotent={r1['mempool_wal_idempotent']}"
              + (" [determinism verified]"
                 if r1.get("determinism_verified") else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
