"""Quorum-loss windows: seeded >1/3 isolation over a live fleet.

Tendermint's liveness argument concedes exactly one regime: when more
than 1/3 of voting power is unreachable, height advance MUST halt — and
nothing else may go wrong. Safety (no conflicting commits, no
double-sign evidence) has to hold through the window, the watchdog has
to attribute the halt to the missing power (``halt_reason =
"quorum_lost"``, not a generic stall), and once the power returns the
fleet has to re-form a quorum and commit within a bound. This driver
makes that whole contract a seeded, asserted, gated scenario:

* ``plan_quorum_loss`` — a PURE function of (seed, windows,
  n_validators, powers): each window shuffles the validator set with a
  seeded RNG and isolates the shortest prefix whose power exceeds 1/3
  of the total (falling back to the single >2/3 whale when only the
  full set would qualify — survivors must exist to observe the halt),
  plus a seeded hold duration;
* the executor runs each planned window over a live 4-validator in-proc
  fleet (churn.py's rig): partition the isolated set, assert the height
  freezes, assert a survivor's ConsensusWatchdog classifies the episode
  ``quorum_lost`` with the isolated validators absent from the round's
  vote bitmaps, assert zero equivocations observed anywhere, then
  ``heal()`` exactly the cut and clock heal→next-commit (the report's
  ``recover_max_s`` is the worst window);
* ``run_wan`` — the same fleet under the ``wan`` link profile
  (seeded base+jitter latency, light loss, reorder on every directed
  link), commit throughput on the clock (the gated
  ``inproc_wan4_commits_per_min`` row);
* ``outcome_fingerprint`` strips wall-clock so two same-seed runs can
  be diffed structurally (``--verify-determinism``).

    python tools/quorum_loss.py --seed 1 --windows 2
    python tools/quorum_loss.py --wan --blocks 12
    python tools/quorum_loss.py --verify-determinism
    python tools/quorum_loss.py --self-test   # stdlib-only, instant

Stdlib-only at the top level; repo imports happen inside the run (the
churn.py/chaos_matrix.py pattern) so --help/--self-test work anywhere.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TOOLS_DIR)
for p in (REPO, TOOLS_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

N_VALIDATORS = 4
#: heal -> next committed height, worst window (the gated bound)
RECOVER_BOUND_S = 30.0
#: the executor tightens the gossip self-heal interval: the post-heal
#: recovery path is bitmap refresh -> vote re-send, so the refresh
#: interval IS the recovery clock's dominant term (default 10s would
#: make every recover_s sample mostly measure an idle timer)
GOSSIP_REFRESH_S = 1.0


def _churn_mod():
    if TOOLS_DIR not in sys.path:
        sys.path.insert(0, TOOLS_DIR)
    import churn
    return churn


# -- the deterministic plan (pure) -------------------------------------------

def plan_quorum_loss(seed: int, windows: int = 1,
                     n_validators: int = N_VALIDATORS,
                     powers=None) -> dict:
    """Seeded isolation windows as a pure function of the inputs. Each
    event names the isolated validators (>1/3 of total power, never the
    whole set), the isolated/total power, and a seeded hold duration."""
    import random
    import zlib

    powers = list(powers) if powers is not None else [10] * n_validators
    if len(powers) != n_validators:
        raise ValueError("one power per validator")
    total = sum(powers)
    rng = random.Random(zlib.crc32(
        ("quorumloss|%d|%d|%d|%s" % (
            seed, windows, n_validators,
            ",".join(map(str, powers)))).encode()))
    events = []
    for w in range(windows):
        order = list(range(n_validators))
        rng.shuffle(order)
        isolate, power = [], 0
        for i in order:
            isolate.append(i)
            power += powers[i]
            if power * 3 > total:
                break
        if len(isolate) == n_validators:
            # only reachable when the last-shuffled validator alone holds
            # >2/3 (every proper prefix summed <=1/3): isolating just the
            # whale already kills quorum AND leaves survivors to observe
            isolate, power = [order[-1]], powers[order[-1]]
        isolate.sort()
        events.append({
            "window": w,
            "isolate": ["val%d" % i for i in isolate],
            "isolated_power": power,
            "total_power": total,
            "hold_s": round(rng.uniform(2.5, 4.0), 3),
        })
    return {"seed": seed, "windows": windows,
            "n_validators": n_validators, "powers": powers,
            "events": events}


def plan_fingerprint(plan: dict) -> str:
    return hashlib.sha256(
        json.dumps(plan, sort_keys=True).encode()).hexdigest()[:16]


def outcome_fingerprint(report: dict) -> str:
    """Structural outcome only — wall-clock fields (recover_s, heights
    reached, elapsed) never enter, so two same-seed runs fingerprint
    identically whenever the CONTRACT held the same way."""
    core = {
        "plan": report["plan"],
        "windows": [
            {k: w[k] for k in ("window", "isolate", "halted",
                               "halt_reason", "recovered")}
            for w in report["windows_run"]],
        "hash_identical": report["hash_identical"],
        "equivocations": report["equivocations"],
    }
    return hashlib.sha256(
        json.dumps(core, sort_keys=True).encode()).hexdigest()[:16]


# -- the live executor -------------------------------------------------------

async def _run_async(seed: int, windows: int,
                     stall_timeout_s: float = 1.2,
                     recover_bound_s: float = RECOVER_BOUND_S) -> dict:
    import asyncio

    from tendermint_tpu.consensus.watchdog import ConsensusWatchdog

    churn = _churn_mod()
    plan = plan_quorum_loss(seed, windows)
    net, nodes, pvs, genesis = await churn.build_fleet(
        N_VALIDATORS, seed=seed)
    equivocations = {name: 0 for name in nodes}
    addr_of = {name: pvs[name].get_pub_key().address().hex()
               for name in nodes}
    for name, nd in nodes.items():
        nd.cs.config.gossip_stall_refresh_s = GOSSIP_REFRESH_S

        def _on_equivocation(_vote, _n=name):
            equivocations[_n] += 1

        nd.cs.equivocation_listeners.append(_on_equivocation)
    windows_run = []
    t0_run = time.monotonic()
    try:
        await churn._wait_heights(list(nodes.values()), 2)
        for ev in plan["events"]:
            isolate = ev["isolate"]
            survivors = [nd for n, nd in nodes.items() if n not in isolate]
            observer = survivors[0]
            wd = ConsensusWatchdog(
                observer.cs, stall_timeout_s,
                check_interval_s=stall_timeout_s / 4,
                height_fn=lambda o=observer: o.height)
            await wd.start()
            net.partition(isolate)
            t_cut = time.monotonic()
            # settle: messages already in flight at the cut may finish the
            # current height — the freeze assertion starts after them
            await asyncio.sleep(min(1.0, ev["hold_s"] / 3.0))
            h_frozen = max(nd.height for nd in nodes.values())
            remain = ev["hold_s"] - (time.monotonic() - t_cut)
            if remain > 0:
                await asyncio.sleep(remain)
            # the watchdog must have fired by the window's end (its stall
            # timeout is well inside hold_s); give a bounded grace so a
            # slow CI box never flips the verdict
            deadline = time.monotonic() + 4 * stall_timeout_s
            while wd.stalls == 0 and time.monotonic() < deadline:
                await asyncio.sleep(stall_timeout_s / 4)
            h_end = max(nd.height for nd in nodes.values())
            halted = (h_end == h_frozen)
            assert halted, (
                f"height advanced {h_frozen}->{h_end} with "
                f"{ev['isolated_power']}/{ev['total_power']} power isolated")
            assert wd.stalls > 0, "watchdog never noticed the halt"
            reason, detail = wd.last_halt_reason, wd.last_halt_detail
            assert reason == "quorum_lost", (
                f"halt misclassified as {reason!r}: {detail}")
            assert detail["missing_power"] * 3 > detail["total_power"], detail
            # the isolated validators must be the ones absent from the
            # blocking stage's vote bitmap (matched by address:
            # validator-set order is not name order) — a cut landing
            # between the quorums legitimately leaves their PREVOTES in
            # the round, but never their precommits
            stage = detail["blocking_stage"]
            absent = {row["address"] for row in detail["validators"]
                      if not row[stage]}
            for name in isolate:
                assert addr_of[name] in absent, (
                    f"{name} {stage}d during its own isolation window: "
                    f"{detail}")
            assert sum(equivocations.values()) == 0, equivocations
            t_heal = time.monotonic()
            net.heal(group_a=isolate)
            await churn._wait_heights(list(nodes.values()), h_end + 1,
                                      timeout=recover_bound_s)
            recover_s = round(time.monotonic() - t_heal, 3)
            await wd.stop()
            windows_run.append({
                "window": ev["window"], "isolate": isolate,
                "hold_s": ev["hold_s"], "halted": True,
                "halt_height": h_end, "halt_reason": reason,
                "missing_power": detail["missing_power"],
                "total_power": detail["total_power"],
                "recovered": True, "recover_s": recover_s,
            })
        # post-run settle + whole-history agreement among all nodes
        final = max(nd.height for nd in nodes.values()) + 1
        await churn._wait_heights(list(nodes.values()), final)
        common = min(nd.height for nd in nodes.values()) - 1
        base = max(nd.block_store.base() for nd in nodes.values())
        hash_identical = True
        for h in range(max(1, base), common + 1):
            hashes = {nd.block_store.load_block_meta(h).header.app_hash
                      for nd in nodes.values()}
            assert len(hashes) == 1, f"conflicting commits at height {h}"
        assert sum(equivocations.values()) == 0, equivocations
        for nd in nodes.values():
            evpool = getattr(nd.block_exec, "evpool", None)
            if evpool is not None and hasattr(evpool, "pending_evidence"):
                evs, _ = evpool.pending_evidence(1 << 20)
                assert not evs, f"double-sign evidence on {nd.name}: {evs}"
    finally:
        for nd in nodes.values():
            try:
                await nd.stop()
            except Exception:
                pass
    report = {
        "seed": seed, "windows": windows, "plan": plan,
        "plan_fingerprint": plan_fingerprint(plan),
        "windows_run": windows_run,
        "recover_max_s": max(w["recover_s"] for w in windows_run),
        "final_height": common,
        "hash_identical": hash_identical,
        "equivocations": sum(equivocations.values()),
        "elapsed_s": round(time.monotonic() - t0_run, 2),
    }
    report["outcome_fingerprint"] = outcome_fingerprint(report)
    return report


def run_quorum_loss(seed: int = 1, windows: int = 1,
                    recover_bound_s: float = RECOVER_BOUND_S) -> dict:
    """The net.quorum_loss scenario; returns its report (asserts on
    failure). Host signing backend: the scenario measures consensus
    mechanics, not signature throughput."""
    import asyncio

    os.environ.setdefault("TMTPU_BATCH_BACKEND", "host")
    return asyncio.run(_run_async(seed, windows,
                                  recover_bound_s=recover_bound_s))


# -- WAN throughput (the other gated row) ------------------------------------

async def _wan_async(seed: int, blocks: int) -> dict:
    churn = _churn_mod()
    net, nodes, _pvs, _genesis = await churn.build_fleet(
        N_VALIDATORS, seed=seed)
    try:
        applied = net.apply_profile("wan", seed=seed)
        await churn._wait_heights(list(nodes.values()), 2, timeout=120)
        h0 = max(nd.height for nd in nodes.values())
        t0 = time.monotonic()
        await churn._wait_heights(list(nodes.values()), h0 + blocks,
                                  timeout=600)
        dt = time.monotonic() - t0
        common = min(nd.height for nd in nodes.values()) - 1
        hashes = {nd.block_store.load_block_meta(common).header.app_hash
                  for nd in nodes.values()}
        assert len(hashes) == 1, "hashes diverged under the wan profile"
    finally:
        for nd in nodes.values():
            try:
                await nd.stop()
            except Exception:
                pass
    return {"seed": seed, "blocks": blocks,
            "applied_links": applied,
            "elapsed_s": round(dt, 3),
            "commits_per_min": round(blocks * 60.0 / dt, 2)}


def run_wan(seed: int = 1, blocks: int = 12) -> dict:
    """4 validators under the ``wan`` link profile, commit throughput on
    the clock — feeds ``inproc_wan4_commits_per_min``."""
    import asyncio

    os.environ.setdefault("TMTPU_BATCH_BACKEND", "host")
    return asyncio.run(_wan_async(seed, blocks))


def verify_determinism(seed: int = 1, windows: int = 1) -> dict:
    """Two live same-seed runs must agree on the structural outcome."""
    a = run_quorum_loss(seed, windows)
    b = run_quorum_loss(seed, windows)
    return {"ok": a["outcome_fingerprint"] == b["outcome_fingerprint"],
            "fingerprints": [a["outcome_fingerprint"],
                             b["outcome_fingerprint"]],
            "recover_s": [a["recover_max_s"], b["recover_max_s"]]}


# -- self-test (stdlib-only, instant) ----------------------------------------

def self_test() -> int:
    # the planner is pure and seed-sensitive
    p1 = plan_quorum_loss(7, windows=3)
    assert p1 == plan_quorum_loss(7, windows=3), "same-seed plans diverged"
    assert p1 != plan_quorum_loss(8, windows=3), "seed does not vary plan"
    assert plan_fingerprint(p1) == plan_fingerprint(
        plan_quorum_loss(7, windows=3))
    # every window isolates >1/3 but never everyone, across power shapes
    for powers in (None, [10, 10, 10, 10], [1, 1, 1, 97], [30, 5, 5, 5],
                   [7, 11, 13, 17]):
        for seed in range(1, 9):
            plan = plan_quorum_loss(seed, windows=2, powers=powers)
            total = sum(plan["powers"])
            for ev in plan["events"]:
                assert 0 < len(ev["isolate"]) < plan["n_validators"], ev
                assert ev["isolated_power"] * 3 > total, ev
                assert ev["total_power"] == total
                assert all(n.startswith("val") for n in ev["isolate"])
                assert 2.5 <= ev["hold_s"] <= 4.0
    try:
        plan_quorum_loss(1, powers=[10, 10])
    except ValueError:
        pass
    else:
        raise AssertionError("power/validator length mismatch accepted")
    # the outcome fingerprint strips wall-clock
    base = {"plan": plan_quorum_loss(3),
            "windows_run": [{"window": 0, "isolate": ["val1", "val3"],
                             "halted": True, "halt_reason": "quorum_lost",
                             "recovered": True, "recover_s": 1.5}],
            "hash_identical": True, "equivocations": 0}
    slower = dict(base, windows_run=[
        dict(base["windows_run"][0], recover_s=9.9, halt_height=42)])
    assert outcome_fingerprint(base) == outcome_fingerprint(slower)
    worse = dict(base, windows_run=[
        dict(base["windows_run"][0], halt_reason="stalled")])
    assert outcome_fingerprint(base) != outcome_fingerprint(worse)
    print("quorum_loss self-test OK (planner determinism, >1/3 floor, "
          "never-total isolation, fingerprint wall-clock independence)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--windows", type=int, default=1)
    ap.add_argument("--wan", action="store_true",
                    help="run the wan-profile throughput scenario instead")
    ap.add_argument("--blocks", type=int, default=12,
                    help="blocks on the clock for --wan")
    ap.add_argument("--verify-determinism", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    if args.verify_determinism:
        vd = verify_determinism(args.seed, args.windows)
        print(json.dumps(vd, indent=1))
        return 0 if vd["ok"] else 1
    if args.wan:
        rep = run_wan(args.seed, args.blocks)
    else:
        rep = run_quorum_loss(args.seed, args.windows)
    if args.json:
        print(json.dumps(rep, indent=1))
    elif args.wan:
        print(f"wan4: {rep['commits_per_min']} commits/min over "
              f"{rep['blocks']} blocks ({rep['elapsed_s']}s, "
              f"{rep['applied_links']} degraded links)")
    else:
        print(f"quorum_loss: {len(rep['windows_run'])} window(s), "
              f"worst recover {rep['recover_max_s']}s, "
              f"outcome {rep['outcome_fingerprint']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
