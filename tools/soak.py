"""Game-day soak: every chaos plane at once, judged by SLOs.

Each chaos cell arms ONE hostile condition for ~2 minutes and asserts
invariants. This driver is the "game day" the ROADMAP calls for: an
8-16 node in-proc fleet (churn.py's rig) under continuous open-loop
SIGNED load at a measured fraction of admission capacity, with FIVE
planes armed concurrently from ONE seed:

* churn    — a full node leaves, a fresh one statesync-joins (plan_churn);
* crash    — a victim is killed AT a durability boundary (libs/fail
             arm_raise, crashmatrix's kill machinery), then rebuilt and
             rejoined, kill-to-caught-up on the clock;
* corrupt  — seeded bit flips on in-flight payloads (faults net.corrupt);
* partition— a node black-holed from the fleet for a window, then healed;
* quorum_loss — >1/3 of validator power isolated for a bounded window
             (tools/quorum_loss.py's planner, the deferred ROADMAP
             cell): commits halt BY DESIGN, and any SLO breach inside
             the window attributes to this plane, not to a mystery.

The run is judged by a declarative SLOSpec (libs/slo.py): p99 commit
latency, kill/join-to-caught-up, zero queue-full sheds under capacity,
bounded RSS/WAL/sealed-ring growth slopes, bounded metric-series
cardinality — evaluated over sliding windows from streams the repo
already emits (txlife sealed records, ProcessMetrics watermarks,
FleetScraper rollups over in-proc registries, consensus stage
timelines). Every breach is ATTRIBUTED by intersecting its window with
the armed chaos windows plus the slowest-stage timeline: each SLO miss
names a plane, a node and a stage — with ``unattributed`` as a loud
first-class outcome (that's how slow leaks surface).

Determinism: the schedule is a PURE function of (seed, n_nodes,
duration) — ``plan_gameday`` — and ``--verify-determinism`` replays the
pure half (plan + seeded synthetic streams through the SLO engine) twice
per seed, diffing chaos-schedule AND breach fingerprints.

    python tools/soak.py --nodes 8 --duration 120 --seed 1
    python tools/soak.py --ci                  # 5-minute CI shape
    python tools/soak.py --verify-determinism --seeds 1,2
    python tools/soak.py --self-test           # stdlib-only, seconds

Stdlib-only at the top level; repo imports happen inside the run (the
churn.py/chaos_matrix.py pattern) so --help/--self-test work anywhere.
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

#: load rate = min(RATE_CAP, PER_FLEET_BUDGET/n, max(RATE_FLOOR,
#: fraction * measured capacity / n)) — all n nodes, their gossip, their
#: consensus AND the load generator share one event loop, so the
#: sustainable whole-fleet rate shrinks as the fleet grows
DEFAULT_RATE_FRACTION = 0.2
DEFAULT_RATE_CAP = 50.0
PER_FLEET_BUDGET = 100.0
RATE_FLOOR = 5.0

#: boundaries a MemDB in-proc node reaches every block (subset of
#: libs/fail.KNOWN_FAIL_POINTS — WAL/db boundaries need file stores,
#: which the soak fleet trades away for scale)
CRASH_BOUNDARIES = ("execution.before_exec_block",
                    "consensus.commit.before_end_height")


def _churn_mod():
    # toolbox.load_tool() pops TOOLS_DIR from sys.path after importing
    # this module; sibling imports must re-assert it
    if TOOLS_DIR not in sys.path:
        sys.path.insert(0, TOOLS_DIR)
    import churn
    return churn


def _slo_mod():
    from tendermint_tpu.libs import slo
    return slo


def _quorum_loss_mod():
    if TOOLS_DIR not in sys.path:
        sys.path.insert(0, TOOLS_DIR)
    import quorum_loss
    return quorum_loss


# -- the deterministic plan (pure) -------------------------------------------

def plan_gameday(seed: int, n_nodes: int, duration_s: float,
                 n_validators: int = 4) -> dict:
    """The multi-plane chaos schedule as a pure function of its inputs:
    offset-timestamped armed windows, one per plane. Victims of the
    churn/crash/partition planes are drawn only from full nodes; the
    quorum_loss plane is the ONE deliberate exception — it exists to
    isolate >1/3 of validator power (quorum_loss.plan_quorum_loss picks
    the seeded subset) and arms only when the fleet carries a full
    4-validator quorum. Small fleets degrade gracefully: with no spare
    fulls only the corruption plane arms — which is exactly the tier-1
    smoke shape (2 nodes, one armed site)."""
    import random
    import zlib

    churn = _churn_mod()
    rng = random.Random(zlib.crc32(
        f"soak|{seed}|{n_nodes}|{int(duration_s)}".encode()))
    d = float(duration_s)
    vals, fulls = churn.node_names(n_nodes, n_validators)
    events = []

    def window(frac0, frac1):
        return round(d * frac0, 3), round(d * frac1, 3)

    # corruption: always armed (every fleet size has links to corrupt)
    c0, c1 = window(0.25, 0.55)
    events.append({"t0": c0, "t1": c1, "plane": "corrupt",
                   "kind": "net.corrupt", "node": None,
                   "detail": f"net.corrupt@0.05 seed={seed}"})

    spares = list(fulls)
    # churn: plan_churn picks the leaver/joiner (same namespace as the
    # churn plane everywhere else); rotations stay out of the soak
    if spares:
        cev = churn.plan_churn(seed, 1, n_nodes, n_validators)["events"][0]
        leaver, joiner = cev.get("leave"), cev["join"]
        if leaver in spares:
            spares.remove(leaver)
        t0, t1 = window(0.12, 0.62)
        events.append({"t0": t0, "t1": t1, "plane": "churn",
                       "kind": "leave_join", "node": leaver,
                       "join": joiner,
                       "detail": f"leave {leaver}, statesync-join {joiner}"})
    # crash: kill a spare full AT a boundary, supervised rebuild + rejoin
    if spares:
        victim = spares.pop(rng.randrange(len(spares)))
        boundary = rng.choice(CRASH_BOUNDARIES)
        t0, t1 = window(0.45, 0.9)
        events.append({"t0": t0, "t1": t1, "plane": "crash",
                       "kind": "kill_restart", "node": victim,
                       "boundary": boundary,
                       "detail": f"arm_raise {boundary} on {victim}, "
                                 f"rebuild + statesync rejoin"})
    # partition: black-hole one remaining spare full for a window
    if spares:
        iso = spares[rng.randrange(len(spares))]
        t0, t1 = window(0.65, 0.85)
        events.append({"t0": t0, "t1": t1, "plane": "partition",
                       "kind": "blackhole", "node": iso,
                       "detail": f"partition {iso} from the fleet, "
                                 f"heal at window end"})
    # quorum loss: isolate >1/3 of validator power for a bounded window
    # (the seeded subset from quorum_loss.plan_quorum_loss) — kept clear
    # of the corrupt window so a commit-latency breach inside the halt
    # attributes to THIS plane, never smeared onto the bit flips
    if min(n_validators, n_nodes) >= 4:
        ql = _quorum_loss_mod()
        qev = ql.plan_quorum_loss(
            seed, 1, n_validators=min(n_validators, n_nodes))["events"][0]
        t0, t1 = window(0.68, 0.8)
        events.append({"t0": t0, "t1": t1, "plane": "quorum_loss",
                       "kind": "net.quorum_loss", "node": None,
                       "isolate": qev["isolate"],
                       "isolated_power": qev["isolated_power"],
                       "total_power": qev["total_power"],
                       "detail": f"isolate {'+'.join(qev['isolate'])} "
                                 f"({qev['isolated_power']}/"
                                 f"{qev['total_power']} power, >1/3), "
                                 f"heal at window end"})
    events.sort(key=lambda e: (e["t0"], e["plane"]))
    return {"seed": seed, "n_nodes": n_nodes,
            "duration_s": round(d, 3),
            "n_validators": min(n_validators, n_nodes),
            "events": events}


def schedule_fingerprint(plan: dict) -> str:
    return _slo_mod().schedule_fingerprint(plan["events"])


# -- the pure half: synthetic streams through the engine ----------------------

def synthetic_gameday(seed: int, n_nodes: int = 8, duration_s: float = 120.0,
                      inject: bool = True, leak: bool = True,
                      spec_text=None) -> dict:
    """Seeded synthetic streams derived from the plan, pushed through the
    real SLO engine: commit latency spikes INSIDE the corruption window
    on one node and inside the quorum-loss window on another (each
    injected regression must attribute to ITS armed plane — the windows
    are disjoint by construction) and a monotone RSS ramp spanning the
    whole run (the slow leak — must stay loudly unattributed). The
    backbone of --verify-determinism and the attribution self-test."""
    import random
    import zlib

    slo = _slo_mod()
    churn = _churn_mod()
    plan = plan_gameday(seed, n_nodes, duration_s)
    rng = random.Random(zlib.crc32(f"soak-synth|{seed}".encode()))
    spec = slo.SLOSpec.parse(spec_text) if spec_text else slo.SLOSpec.default()
    engine = slo.SLOEngine(spec)
    corrupt = [ev for ev in plan["events"] if ev["plane"] == "corrupt"]
    qloss = [ev for ev in plan["events"] if ev["plane"] == "quorum_loss"]
    vals = churn.node_names(n_nodes)[0]
    node, qnode = vals[0], vals[-1]
    t = 0.0
    while t < duration_s:
        lat = 0.3 + 0.2 * rng.random()
        if inject and any(ev["t0"] <= t <= ev["t1"] for ev in corrupt):
            lat = 30.0 + rng.random()
        engine.feed("commit_latency", t, lat, node=node)
        if qloss:
            # the halted quorum: commits stop inside the window, which a
            # sliding p99 reads as a latency wall on the observing node
            qlat = 0.3 + 0.2 * rng.random()
            if inject and any(ev["t0"] <= t <= ev["t1"] for ev in qloss):
                qlat = 30.0 + rng.random()
            engine.feed("commit_latency", t, qlat, node=qnode)
        if leak:
            # 64 MB/s against an 8 MB/s bound: unmistakably a leak
            engine.feed("rss_bytes", t, 1e8 + t * 64e6, node=node)
        else:
            engine.feed("rss_bytes", t, 1e8, node=node)
        t += 1.0
    breaches = slo.attribute_all(engine.evaluate(), plan["events"],
                                 total_span=duration_s)
    return {
        "plan": plan,
        "breaches": breaches,
        "unattributed": sum(1 for b in breaches
                            if b["attribution"]["plane"] == "unattributed"),
        "schedule_fingerprint": slo.schedule_fingerprint(plan["events"]),
        "breach_fingerprint": slo.breach_fingerprint(breaches),
    }


def verify_determinism(seeds=(1, 2), n_nodes: int = 8,
                       duration_s: float = 120.0) -> dict:
    """Per seed, run the pure half TWICE and diff chaos-schedule and
    breach fingerprints. Returns {"ok": bool, "seeds": {...}}."""
    out = {"ok": True, "seeds": {}}
    for seed in seeds:
        a = synthetic_gameday(seed, n_nodes, duration_s)
        b = synthetic_gameday(seed, n_nodes, duration_s)
        ok = (a["schedule_fingerprint"] == b["schedule_fingerprint"]
              and a["breach_fingerprint"] == b["breach_fingerprint"])
        out["seeds"][str(seed)] = {
            "ok": ok,
            "schedule_fingerprint": a["schedule_fingerprint"],
            "breach_fingerprint": a["breach_fingerprint"],
            "breaches": len(a["breaches"]),
        }
        out["ok"] = out["ok"] and ok
    return out


# -- the in-proc rig ----------------------------------------------------------

_SOAK_RIG = None


def _soak_rig():
    """churn's ChurnNode grown the soak extras: the crashmatrix kill
    guard (scoped arm_raise + killed_evt), ingest-plane txlife wiring,
    and the watermark sampler — memoized, one class per process."""
    global _SOAK_RIG
    if _SOAK_RIG is not None:
        return _SOAK_RIG
    churn = _churn_mod()
    rig = churn._rig()
    Base = rig["ChurnNode"]
    from tendermint_tpu.libs import fail
    from tendermint_tpu.libs.fail import KilledAtFailPoint
    from tendermint_tpu.libs.txlife import TxLifecycle
    from tendermint_tpu.libs.watermark import ResourceWatermarks

    class SoakNode(Base):
        def __init__(self, name, genesis, pv, fast_sync=False):
            super().__init__(name, genesis, pv, fast_sync=fast_sync)
            # ChurnNode wires only the consensus metric set; the soak
            # judges ingest + resource streams too, and reads the
            # slowest-stage timeline out of stage_seconds (the timeline
            # seals into the histogram only when its metrics are wired)
            self.cs.timeline.metrics = self.metrics.consensus
            self.mempool.metrics = self.metrics.mempool
            self.txlife = TxLifecycle()
            self.txlife.metrics = self.metrics.mempool
            self.mempool.txlife = self.txlife
            self.watermarks = ResourceWatermarks(
                self.metrics.process, txlife=self.txlife,
                registry=self.metrics.registry)
            self.killed_at = None
            self.killed_evt = None  # created at start (needs a loop)
            # kill guard (crashmatrix pattern): a BaseException at an
            # armed boundary ends the receive loop; record WHERE
            orig = self.cs.receive_routine

            async def guarded():
                try:
                    await orig()
                except KilledAtFailPoint as e:
                    self.killed_at = e.site
                    if self.killed_evt is not None:
                        self.killed_evt.set()

            self.cs.receive_routine = guarded

        async def start(self):
            import asyncio

            self.killed_evt = asyncio.Event()
            # tasks created below inherit this scope: armed boundaries in
            # SHARED code (execution, commit) kill only this node's tasks
            token = fail.scope.set(self.name)
            try:
                await super().start()
            finally:
                fail.scope.reset(token)

        def render_metrics(self) -> str:
            """Callable /metrics endpoint for the in-proc FleetScraper:
            sample watermarks, then render — same order as node.py's
            HTTP handler."""
            try:
                self.watermarks.sample()
            except Exception:
                pass
            return self.metrics.registry.render()

    _SOAK_RIG = {"SoakNode": SoakNode, "fail": fail,
                 "KilledAtFailPoint": KilledAtFailPoint}
    return _SOAK_RIG


def _queue_full_count(nd) -> float:
    """Cumulative queue-full sheds on one node: failed_txs{reason~full}
    plus every admission-control shed."""
    total = 0.0
    try:
        for lv, v in nd.metrics.mempool.failed_txs._values.items():
            if any("full" in part for part in lv):
                total += v
    except Exception:
        pass
    try:
        total += sum(nd.metrics.mempool.shed_txs_total._values.values())
    except Exception:
        pass
    return total


# -- the live run -------------------------------------------------------------

async def _run_async(n_nodes: int, seed: int, duration_s: float,
                     rate_fraction: float, rate_cap: float,
                     spec_text, out_path, sample_interval: float,
                     topology: str, degree: int) -> dict:
    import asyncio

    # re-assert the tools dir: toolbox.load_tool() pops it from sys.path
    # after importing THIS module, so sibling imports deferred to run time
    # must put it back
    if TOOLS_DIR not in sys.path:
        sys.path.insert(0, TOOLS_DIR)
    import loadtime

    from tendermint_tpu.libs.faults import faults
    from tendermint_tpu.p2p import InProcNetwork

    from fleet_scrape import FleetScraper

    churn = _churn_mod()
    slo = _slo_mod()
    srig = _soak_rig()
    crig = churn._rig()
    SoakNode = srig["SoakNode"]
    fail = srig["fail"]

    plan = plan_gameday(seed, n_nodes, duration_s)
    spec = (slo.SLOSpec.parse(spec_text) if spec_text
            else slo.SLOSpec.default())
    engine = slo.SLOEngine(spec)

    vals, fulls = churn.node_names(n_nodes)
    pvs = {name: crig["make_pv"](name) for name in vals + fulls}
    genesis = crig["make_genesis"]([pvs[v] for v in vals], [10] * len(vals))
    nodes = {name: SoakNode(name, genesis, pvs[name])
             for name in vals + fulls}
    net = InProcNetwork()
    for nd in nodes.values():
        net.add_switch(nd.switch)
    for nd in nodes.values():
        await nd.start()
        # a healed quorum-loss window recovers through the gossip
        # self-heal (bitmap refresh -> vote re-send); the default 10s
        # refresh would dominate every recovery inside a short soak
        nd.cs.config.gossip_stall_refresh_s = 2.0
    await net.connect_topology(topology, degree=degree, seed=seed)

    scraper = FleetScraper(
        {name: nd.render_metrics for name, nd in nodes.items()},
        interval_s=max(1.0, sample_interval))

    armed_windows = []   # ACTUAL armed chaos windows (wall clock)
    stage_windows = []   # slowest-stage per sample interval (wall clock)
    joins, kills, event_errors, executed = [], [], {}, []
    done = asyncio.Event()
    loop = asyncio.get_running_loop()

    await churn._wait_heights(list(nodes.values()), 2)

    # capacity probe BEFORE chaos arms: open-loop rate is a fraction of
    # what admission measured, so "zero sheds while under capacity" is an
    # honest objective rather than a tautology. The probe uses SIGNED txs
    # (admission pays a host ed25519 verify each) and the measured
    # per-node rate is divided by fleet size: mempool gossip re-verifies
    # every admitted tx on every peer, so fleet capacity is per-node
    # capacity over n, not per-node capacity
    probe_txs = loadtime.make_signed_txs(
        96, [time.time_ns()] * 50, n_keys=4)
    t0p = time.perf_counter()
    for tx in probe_txs:
        try:
            nodes[vals[0]].mempool.check_tx(tx)
        except Exception:
            pass
    capacity = len(probe_txs) / max(time.perf_counter() - t0p, 1e-6)
    n = max(1, len(nodes))
    rate = min(rate_cap, PER_FLEET_BUDGET / n,
               max(RATE_FLOOR, capacity * rate_fraction / n))

    t_start_wall = time.time()
    t_start = loop.time()
    t_end = t_start + duration_s

    def survivors():
        return [nd for nd in nodes.values()
                if nd.name not in net.departed and not nd.fast_sync]

    # -- continuous open-loop signed load (loadtime discipline) ----------
    async def load_task():
        import itertools

        sent = 0
        chunk = []
        t0 = loop.time() + 0.1
        for i in itertools.count():
            if loop.time() >= t_end:
                break
            target = t0 + i / rate
            delay = target - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            if not chunk:
                # pre-sign in a worker thread, small batches: pure-python
                # ed25519 is ~2 ms/tx and holds the GIL, so a big batch
                # would starve consensus and read back as node latency
                scheds = [time.time_ns() + int(1e9 * j / rate)
                          for j in range(50)]
                chunk = await loop.run_in_executor(
                    None, lambda: loadtime.make_signed_txs(
                        96, scheds, n_keys=16))
                chunk.reverse()
            tx = chunk.pop()
            live = survivors()
            if not live:
                continue
            try:
                live[i % len(live)].mempool.check_tx(tx)
                sent += 1
            except Exception:
                pass
        return sent

    # -- the SLO sampler: streams out of the running fleet ---------------
    async def sampler():
        consumed = {}      # node -> sealed records already consumed
        shed_seen = {}     # node -> cumulative shed count
        stage_sums = {}    # stage -> cumulative sum across nodes
        prev_t = time.time()
        tick = 0
        while not done.is_set():
            try:
                await asyncio.wait_for(done.wait(), timeout=sample_interval)
            except asyncio.TimeoutError:
                pass
            now = time.time()
            for name, nd in list(nodes.items()):
                try:
                    tl = nd.txlife
                    new = tl.sealed_total - consumed.get(name, 0)
                    if new > 0:
                        consumed[name] = tl.sealed_total
                        for rec in tl.tail(min(new, tl.ring_capacity)):
                            if (rec.get("terminal") == "committed"
                                    and rec.get("total_s") is not None):
                                engine.feed(
                                    "commit_latency",
                                    rec["t0_wall"] + rec["total_s"],
                                    rec["total_s"], node=name)
                    shed = _queue_full_count(nd)
                    d = shed - shed_seen.get(name, 0.0)
                    shed_seen[name] = shed
                    if d > 0:
                        engine.feed("queue_full_sheds", now, d, node=name)
                    w = nd.watermarks.sample()
                    engine.feed("rss_bytes", now, w["rss_bytes"], node=name)
                    engine.feed("wal_bytes", now, w["wal_bytes"], node=name)
                    engine.feed("ring_depth", now, w["ring_depth"],
                                node=name)
                    engine.feed("metric_series", now, w["metric_series"],
                                node=name)
                except Exception:
                    continue
            # slowest consensus stage this interval, summed across nodes
            try:
                sums = {}
                for nd in list(nodes.values()):
                    for lv, s in nd.metrics.consensus.stage_seconds. \
                            _sums.items():
                        sums[lv[0]] = sums.get(lv[0], 0.0) + s
                deltas = {st: v - stage_sums.get(st, 0.0)
                          for st, v in sums.items()}
                stage_sums = sums
                pos = {st: d for st, d in deltas.items() if d > 1e-9}
                if pos:
                    slowest = max(sorted(pos), key=lambda st: pos[st])
                    stage_windows.append(
                        {"t0": prev_t, "t1": now, "stage": slowest})
            except Exception:
                pass
            prev_t = now
            tick += 1
            if out_path and tick % 10 == 0:
                _write_report(out_path, {
                    "in_flight": True, "seed": seed, "plan": plan,
                    "armed_windows": armed_windows,
                    "elapsed_s": round(now - t_start_wall, 1)})

    # -- plane executors --------------------------------------------------
    async def do_corrupt(ev):
        cap = 400
        t0 = time.time()
        faults.configure(f"net.corrupt@0.05*{cap}", seed=seed)
        try:
            await asyncio.sleep(max(0.0, ev["t1"] - ev["t0"]))
        finally:
            faults.reset()
        armed_windows.append({"t0": t0, "t1": time.time(),
                              "plane": "corrupt", "node": None,
                              "detail": ev["detail"],
                              "fires": faults.fires("net.corrupt")})

    async def do_partition(ev):
        iso = ev["node"]
        t0 = time.time()
        net.partition([iso])
        try:
            await asyncio.sleep(max(0.0, ev["t1"] - ev["t0"]))
        finally:
            # heal exactly THIS cut: a global heal() would also erase a
            # concurrently armed quorum-loss window
            net.heal(group_a=[iso])
        armed_windows.append({"t0": t0, "t1": time.time(),
                              "plane": "partition", "node": iso,
                              "detail": ev["detail"]})

    async def do_quorum_loss(ev):
        isolate = list(ev["isolate"])
        t0 = time.time()
        h_cut = max((nd.height for nd in survivors()), default=0)
        net.partition(isolate)
        try:
            await asyncio.sleep(max(0.0, ev["t1"] - ev["t0"]))
        finally:
            net.heal(group_a=isolate)
        armed_windows.append({"t0": t0, "t1": time.time(),
                              "plane": "quorum_loss", "node": None,
                              "detail": ev["detail"],
                              "height_at_cut": h_cut,
                              "height_at_heal": max(
                                  (nd.height for nd in survivors()),
                                  default=0)})

    async def do_churn(ev):
        leaver, joiner = ev.get("node"), ev["join"]
        t0 = time.time()
        if leaver and leaver in nodes:
            nd = nodes.pop(leaver)
            scraper.remove_endpoint(leaver)
            await net.remove_node(leaver)
            await asyncio.wait_for(nd.stop(), timeout=30)
        jn = SoakNode(joiner, genesis, crig["make_pv"](joiner),
                      fast_sync=True)
        pvs[joiner] = jn.pv
        nodes[joiner] = jn
        secs = await asyncio.wait_for(
            churn.join_statesync(net, jn, nodes[vals[0]],
                                 [n for n in nodes if n != joiner], seed),
            timeout=150)
        scraper.add_endpoint(joiner, jn.render_metrics)
        engine.feed("caughtup", time.time(), secs, node=joiner)
        joins.append({"leave": leaver, "join": joiner, "caughtup_s": secs})
        armed_windows.append({"t0": t0, "t1": time.time(),
                              "plane": "churn", "node": leaver or joiner,
                              "detail": ev["detail"]})

    async def do_crash(ev):
        victim, boundary = ev["node"], ev["boundary"]
        nd = nodes.get(victim)
        if nd is None or nd.fast_sync:
            return
        t0 = time.time()
        fail.arm_raise(boundary, scope_token=victim)
        try:
            await asyncio.wait_for(nd.killed_evt.wait(), timeout=60)
        except asyncio.TimeoutError:
            fail.reset()
            kills.append({"node": victim, "boundary": boundary,
                          "fired": False})
            return
        t_kill = time.monotonic()
        rec = {"node": victim, "boundary": boundary, "fired": True,
               "killed_at": nd.killed_at}
        kills.append(rec)  # the kill is on the record even if rejoin fails
        nodes.pop(victim, None)
        scraper.remove_endpoint(victim)
        await net.remove_node(victim)
        try:
            await asyncio.wait_for(nd.stop(), timeout=20)
        except Exception:
            pass
        await asyncio.sleep(0.25)  # supervised-restart backoff (bounded)
        # two rejoin attempts, each with a freshly built node: the first
        # can race a concurrently armed partition window and time out
        last_err = None
        for attempt in range(2):
            fresh = SoakNode(victim, genesis, pvs[victim], fast_sync=True)
            nodes[victim] = fresh
            try:
                await asyncio.wait_for(
                    churn.join_statesync(
                        net, fresh, nodes[vals[0]],
                        [n for n in nodes if n != victim], seed),
                    timeout=150)
                break
            except Exception as e:
                last_err = e
                rec["rejoin_retries"] = attempt + 1
                nodes.pop(victim, None)
                await net.remove_node(victim)
                try:
                    await asyncio.wait_for(fresh.stop(), timeout=10)
                except Exception:
                    pass
                await asyncio.sleep(2.0)
        else:
            raise last_err
        scraper.add_endpoint(victim, fresh.render_metrics)
        caught = round(time.monotonic() - t_kill, 3)
        engine.feed("caughtup", time.time(), caught, node=victim)
        rec["kill_to_caughtup_s"] = caught
        armed_windows.append({"t0": t0, "t1": time.time(),
                              "plane": "crash", "node": victim,
                              "detail": ev["detail"]})

    EXEC = {"corrupt": do_corrupt, "partition": do_partition,
            "churn": do_churn, "crash": do_crash,
            "quorum_loss": do_quorum_loss}

    async def run_event(ev):
        delay = ev["t0"] - (loop.time() - t_start)
        if delay > 0:
            await asyncio.sleep(delay)
        executed.append([ev["plane"], ev.get("node")])
        try:
            await EXEC[ev["plane"]](ev)
        except Exception as e:  # an executor failure is data, not a wedge
            event_errors[f"{ev['plane']}:{ev.get('node')}"] = repr(e)

    h_initial = max(nd.height for nd in nodes.values())
    rewire_task = asyncio.create_task(churn.rewire_loop(net))
    sampler_task = asyncio.create_task(sampler())
    load_fut = asyncio.create_task(load_task())
    scraper.start()
    event_tasks = [asyncio.create_task(run_event(ev))
                   for ev in plan["events"]]
    try:
        sent = await load_fut
        # events normally end inside the run; the bound only exists so a
        # wedged rejoin (worst case: kill wait + two statesync attempts)
        # cannot hang the report
        await asyncio.wait_for(
            asyncio.gather(*event_tasks, return_exceptions=True),
            timeout=duration_s + 420.0)
    finally:
        done.set()
        faults.reset()
        fail.reset()
        net.heal()
        rewire_task.cancel()
        for t in event_tasks:
            t.cancel()
        try:
            await asyncio.wait_for(sampler_task, timeout=10)
        except Exception:
            pass
        rollup = scraper.stop()
        h_final = max((nd.height for nd in survivors()), default=0)
        for nd in list(nodes.values()):
            try:
                await asyncio.wait_for(nd.stop(), timeout=20)
            except Exception:
                pass

    breaches = slo.attribute_all(engine.evaluate(), armed_windows,
                                 stage_windows, total_span=duration_s)
    # headline observations for the report: one number each, derived from
    # the same streams the SLO engine judged (not a parallel measurement)
    lat_vals = [v for _, v, _ in engine._streams.get("commit_latency", [])]
    caught_vals = [v for _, v, _ in engine._streams.get("caughtup", [])]
    observed = {
        "commit_p99_s": (round(slo._percentile(lat_vals, 99.0), 4)
                         if lat_vals else None),
        "commit_samples": len(lat_vals),
        "caughtup_max_s": (round(max(caught_vals), 2)
                           if caught_vals else None),
    }
    report = {
        "seed": seed, "n_nodes": n_nodes,
        "duration_s": round(duration_s, 3), "topology": topology,
        "plan": plan,
        "schedule_fingerprint": slo.schedule_fingerprint(plan["events"]),
        "executed": executed,
        "armed_windows": armed_windows,
        "event_errors": event_errors,
        "load": {"capacity_probe_txs_per_s": round(capacity, 1),
                 "rate_txs_per_s": round(rate, 2),
                 "rate_fraction": rate_fraction, "sent": sent},
        "heights": {"initial": h_initial, "final": h_final},
        "joins": joins, "kills": kills,
        "observed": observed,
        "slo": {
            "objectives": spec.as_dicts(),
            "sample_counts": engine.sample_counts(),
            "breaches": breaches,
            "unattributed": sum(
                1 for b in breaches
                if b["attribution"]["plane"] == "unattributed"),
        },
        "breach_fingerprint": slo.breach_fingerprint(breaches),
        "fleet_rollup": {k: rollup.get(k) for k in
                         ("n_nodes", "cluster_height",
                          "cluster_blocks_per_min", "txs_admitted_delta",
                          "process")},
        "elapsed_s": round(time.time() - t_start_wall, 2),
    }
    return report


def _write_report(path: str, doc: dict) -> str:
    import tempfile

    fd, tmp = tempfile.mkstemp(prefix=os.path.basename(path) + ".",
                               dir=os.path.dirname(path) or ".")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1, default=str)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def run_soak(n_nodes: int = 8, seed: int = 1, duration_s: float = 120.0,
             rate_fraction: float = DEFAULT_RATE_FRACTION,
             rate_cap: float = DEFAULT_RATE_CAP,
             spec_text=None, out=None, sample_interval: float = 1.0,
             topology: str = "full_mesh", degree: int = 3) -> dict:
    """One game day; returns the attributed report (and writes it to
    ``out``, default ``soak_report.json`` in the cwd, exporting
    TMTPU_SOAK_REPORT so in-proc debugdump bundles pick it up)."""
    import asyncio

    os.environ.setdefault("TMTPU_BATCH_BACKEND", "host")
    out = out or os.path.abspath("soak_report.json")
    os.environ["TMTPU_SOAK_REPORT"] = out
    report = asyncio.run(_run_async(
        n_nodes, seed, duration_s, rate_fraction, rate_cap, spec_text,
        out, sample_interval, topology, degree))
    report["report_path"] = _write_report(out, report)
    return report


# -- self-test (stdlib-only: spec grammar, window math, attribution) ----------

def self_test() -> int:
    slo = _slo_mod()

    # spec grammar: parse, defaults, loud rejects
    spec = slo.SLOSpec.parse(
        "commit_latency p99 <= 2.5 window=30\ncaughtup max <= 60\n")
    assert [o.name for o in spec.objectives] == [
        "commit_latency_p99", "caughtup_max"]
    assert spec.objectives[0].window_s == 30.0
    assert len(slo.SLOSpec.default().objectives) == 7
    for bad in ("x p99 <=\n", "x p42 <= 1\n", "x p99 ~ 1\n",
                "x p99 <= one\n", "x p99 <= 1 win=3\n"):
        try:
            slo.SLOSpec.parse(bad)
        except ValueError:
            pass
        else:
            raise AssertionError(f"spec {bad!r} parsed")

    # window math: p99 over sliding windows trips only where the spike is
    eng = slo.SLOEngine(slo.SLOSpec.parse(
        "lat p99 <= 1.0 window=10\nevents count <= 0\n"))
    for t in range(60):
        eng.feed("lat", float(t), 5.0 if 20 <= t < 30 else 0.2, node="n0")
    b = eng.evaluate()
    assert len(b) == 1 and b[0]["objective"] == "lat_p99", b
    assert b[0]["node"] == "n0" and b[0]["observed"] >= 5.0
    w0, w1 = b[0]["window"]
    assert w0 <= 20 <= w1 and w1 < 45, b  # merged run hugs the spike
    # count: no samples -> no breach; one event -> breach
    eng.feed("events", 10.0, 1.0, node="n1")
    b2 = eng.evaluate()
    assert any(x["objective"] == "events_count" and x["node"] == "n1"
               for x in b2), b2

    # slope: monotone ramp trips, flat line doesn't, dips clamp to zero
    eng = slo.SLOEngine(slo.SLOSpec.parse("rss slope <= 100.0\n"))
    for t in range(30):
        eng.feed("rss", float(t), 1000.0 + 500.0 * t, node="leaky")
        eng.feed("rss", float(t), 5000.0 - 10.0 * t, node="fine")
    b = eng.evaluate()
    assert [x["node"] for x in b] == ["leaky"], b

    # attribution: injected breach -> its armed plane/node/stage; a
    # barely-overlapping event does NOT claim a long breach
    sched = [{"t0": 20.0, "t1": 35.0, "plane": "corrupt", "node": None,
              "detail": "bitflips"},
             {"t0": 0.0, "t1": 2.0, "plane": "churn", "node": "full0",
              "detail": "早"}]
    stages = [{"t0": 18.0, "t1": 36.0, "stage": "commit_finalized"}]
    att = slo.attribute({"window": [22.0, 33.0], "node": "val1"},
                        sched, stages)
    assert att == {"plane": "corrupt", "node": "val1",
                   "stage": "commit_finalized", "detail": "bitflips"}, att
    # whole-run leak window: corrupt covers <50% of it -> unattributed
    att2 = slo.attribute({"window": [0.0, 120.0], "node": "val1"}, sched)
    assert att2["plane"] == "unattributed", att2
    # point breach (caughtup event) inside a crash window -> attributed
    att3 = slo.attribute(
        {"window": [25.0, 25.0], "node": "full1"},
        [{"t0": 20.0, "t1": 40.0, "plane": "crash", "node": "full1"}])
    assert att3["plane"] == "crash" and att3["node"] == "full1", att3
    # concurrent planes: the nested, more specific window wins the broad
    # one armed across it
    att4 = slo.attribute(
        {"window": [28.0, 40.0], "node": "val0"},
        [{"t0": 0.0, "t1": 60.0, "plane": "churn", "node": "full0"},
         {"t0": 27.0, "t1": 41.0, "plane": "corrupt", "node": None}])
    assert att4["plane"] == "corrupt", att4

    # plan: pure, seeded, quorum-safe (except the one plane built to
    # take the quorum)
    p1 = plan_gameday(7, 8, 120)
    assert p1 == plan_gameday(7, 8, 120), "same-seed plans diverged"
    assert p1 != plan_gameday(8, 8, 120), "seed does not vary the plan"
    planes = {ev["plane"] for ev in p1["events"]}
    assert planes == {"corrupt", "churn", "crash", "partition",
                      "quorum_loss"}, planes
    vals = {f"val{i}" for i in range(4)}
    for ev in p1["events"]:
        assert ev.get("node") not in vals, f"quorum touched: {ev}"
        assert 0 <= ev["t0"] <= ev["t1"] <= 120
    # the quorum-loss window round-trips the quorum_loss planner: same
    # seeded isolation subset, >1/3 of the power, never every validator
    ql = _quorum_loss_mod()
    qev = next(ev for ev in p1["events"] if ev["plane"] == "quorum_loss")
    qplan = ql.plan_quorum_loss(7, 1, n_validators=4)["events"][0]
    assert qev["isolate"] == qplan["isolate"], (qev, qplan)
    assert qev["isolated_power"] == qplan["isolated_power"]
    assert qev["isolated_power"] * 3 > qev["total_power"], qev
    assert set(qev["isolate"]) < vals, qev
    # ...and stays clear of the corrupt window (attribution clarity)
    cev = next(ev for ev in p1["events"] if ev["plane"] == "corrupt")
    assert qev["t0"] >= cev["t1"] or qev["t1"] <= cev["t0"], (qev, cev)
    # small fleets degrade to the corrupt-only smoke shape; a full
    # quorum (>= 4 validators) always gets its loss window
    assert [ev["plane"] for ev in plan_gameday(1, 2, 30)["events"]] \
        == ["corrupt"]
    assert {ev["plane"] for ev in plan_gameday(1, 5, 30)["events"]} \
        == {"corrupt", "churn", "quorum_loss"}

    # the pure half: each injected regression attributes to ITS armed
    # plane, the leak stays loudly unattributed, fingerprints replay.
    # The latency objective runs a tighter sliding window here: the
    # default 30s window smears a breach well past the short quorum-loss
    # window, dropping the true cause below the attribution cover floor
    g = synthetic_gameday(
        3, 8, 120,
        spec_text="commit_latency p99 <= 20.0 window=10\n"
                  "rss_bytes slope <= 8388608\n")
    lat = [b for b in g["breaches"]
           if b["objective"] == "commit_latency_p99"]
    lat_planes = {b["attribution"]["plane"] for b in lat}
    assert lat and lat_planes == {"corrupt", "quorum_loss"}, lat
    leaks = [b for b in g["breaches"] if b["objective"] == "rss_bytes_slope"]
    assert leaks and all(b["attribution"]["plane"] == "unattributed"
                         for b in leaks), leaks
    assert g["unattributed"] == len(leaks)
    clean = synthetic_gameday(3, 8, 120, inject=False, leak=False)
    assert clean["breaches"] == [], clean["breaches"]
    assert clean["schedule_fingerprint"] == g["schedule_fingerprint"]
    assert clean["breach_fingerprint"] != g["breach_fingerprint"]
    vd = verify_determinism(seeds=(1, 2), duration_s=90)
    assert vd["ok"], vd

    # fingerprints strip wall-clock: observed/window never enter
    b1 = [{"objective": "o", "node": "n", "window": [1.0, 2.0],
           "observed": 9.9, "attribution": {"plane": "p", "stage": "s"}}]
    b2 = [{"objective": "o", "node": "n", "window": [50.0, 60.0],
           "observed": 1.1, "attribution": {"plane": "p", "stage": "s"}}]
    assert slo.breach_fingerprint(b1) == slo.breach_fingerprint(b2)

    print("soak self-test OK (spec grammar, window math, attribution, "
          "plan determinism, injected-regression + leak outcomes)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--duration", type=float, default=120.0)
    ap.add_argument("--ci", action="store_true",
                    help="the CI shape: 8 nodes, 300 s")
    ap.add_argument("--topology", choices=("full_mesh", "sparse"),
                    default="full_mesh")
    ap.add_argument("--degree", type=int, default=3)
    ap.add_argument("--rate-fraction", type=float,
                    default=DEFAULT_RATE_FRACTION,
                    help="open-loop rate as a fraction of probed capacity")
    ap.add_argument("--rate-cap", type=float, default=DEFAULT_RATE_CAP)
    ap.add_argument("--sample-interval", type=float, default=1.0)
    ap.add_argument("--spec", default=None, metavar="PATH",
                    help="SLO spec file (default: libs/slo.py DEFAULT_SPEC)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="report path (default ./soak_report.json)")
    ap.add_argument("--seeds", default="1,2",
                    help="seeds for --verify-determinism")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--self-test", action="store_true")
    ap.add_argument("--verify-determinism", action="store_true",
                    help="replay the pure half twice per seed and diff "
                         "chaos-schedule + breach fingerprints")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    if args.verify_determinism:
        seeds = tuple(int(s) for s in args.seeds.split(",") if s)
        vd = verify_determinism(seeds=seeds, n_nodes=args.nodes,
                                duration_s=args.duration)
        print(json.dumps(vd, indent=2))
        print("determinism " + ("OK" if vd["ok"] else "FAIL")
              + f" over seeds {seeds}")
        return 0 if vd["ok"] else 1

    if args.ci:
        args.nodes, args.duration = max(args.nodes, 8), 300.0
    spec_text = None
    if args.spec:
        with open(args.spec) as f:
            spec_text = f.read()
    report = run_soak(
        n_nodes=args.nodes, seed=args.seed, duration_s=args.duration,
        rate_fraction=args.rate_fraction, rate_cap=args.rate_cap,
        spec_text=spec_text, out=args.out,
        sample_interval=args.sample_interval, topology=args.topology,
        degree=args.degree)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        s = report["slo"]
        print(f"soak OK: N={report['n_nodes']} seed={report['seed']} "
              f"{report['duration_s']}s h {report['heights']['initial']}→"
              f"{report['heights']['final']} "
              f"load {report['load']['rate_txs_per_s']}/s "
              f"({report['load']['sent']} sent) "
              f"breaches={len(s['breaches'])} "
              f"unattributed={s['unattributed']} "
              f"joins={len(report['joins'])} kills={len(report['kills'])} "
              f"-> {report['report_path']}")
        for b in s["breaches"]:
            a = b["attribution"]
            print(f"  BREACH {b['objective']} node={b['node']} "
                  f"observed={b['observed']} (bound {b['op']} "
                  f"{b['threshold']}) -> plane={a['plane']} "
                  f"node={a['node']} stage={a['stage']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
