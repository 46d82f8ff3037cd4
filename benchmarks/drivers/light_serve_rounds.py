"""Driver: a full node answers a crowd of light clients as each header lands.

A request is one ROUND: ``clients`` light clients call
``LightServePlane.serve_verify(height=tip, trusted_height=tip - gap)`` at
once, on one asyncio loop, through the plane's normal admission,
``_build_request`` (the node's own BlockStore and StateStore), the
``VerifyCoalescer`` and its executor. The round ends when the last answer
is back, and the next round starts then: closed loop, one round at a time.

The node serves a kvstore chain of ``chain_blocks`` blocks that it built
itself through ``BlockExecutor.apply_block`` (``fast_sync_replay``'s node),
every validator signing every commit over the benchmark's own sign-bytes
(data.py). The tip advances one height a round over the pool of the
``pool_heights`` heights below the last block, cycled; the plane sees the
store as it stood when that height was its newest (``height()`` answers the
tip, every load answers from the full store). A tip request carries no
verdict-cache key, so every round goes through one device call: the
coalescer deduplicates the candidate rows of the 32 requests, which all ask
about the same header, to that commit's signatures.

Each client's trusted gap is drawn per round from the seed, by class
(``gap_classes``: adjacent followers, near skippers, returning clients).
``tampered_rows`` puts one wrong signature into the SEEN commit of as many
seeded pool heights, one row region each (the next block's LastCommit
stays sound), so the answers of those rounds depend on which check reads
which row. The heights are drawn among the whole pool. A round of 1,000
validators takes seconds of host work, so the deadline may come before
the cycle reaches the last of them: the window then runs on until every
tampered height has been a tip.

Time: the chain's header times lie a day or two behind the wall clock at
build (genesis at the start of the previous UTC day), inside the trusting
period and not from the future at any instant of a run; the reference
reads the same chain with the build's clock. Keys, gaps and tampered rows
come from the seed; header times from the day of the run.

Traffic parameters: ``chain_id``, ``clients``, ``pool_heights``,
``gap_classes`` ([{"clients", "gaps": [lo, hi]}], uniform in [lo, hi]),
``tampered_rows`` (region names, below), ``trace_after_ticks``,
``trace_ticks`` (a tick between rounds).
"""

from __future__ import annotations

import asyncio

import numpy as np

import data as D
from drivers.fast_sync_replay import _fresh_node

#: where in the set order a tampered row lies, by the power before it:
#: region -> (num, den) of the prefix it starts after, and of the one it
#: ends inside
REGIONS = {"inside_one_third": ((0, 1), (1, 3)),
           "one_third_to_two_thirds": ((1, 3), (2, 3)),
           "past_two_thirds": ((2, 3), (1, 1))}

DAY_NS = 86_400 * 1_000_000_000

#: the program's new cumulative seconds (light/serve.py), by where they live
COUNTERS = {"build_s": ("plane", "build_s"),
            "collect_s": ("coalescer", "collect_s"),
            "replay_s": ("coalescer", "replay_s")}


class NodeAt:
    """The serving node's BlockStore as it stood when ``tip`` was its
    newest height: ``height()`` answers the tip, every load the store."""

    def __init__(self, store):
        self._store = store
        self.tip = store.height()

    def height(self) -> int:
        return self.tip

    def __getattr__(self, name):
        return getattr(self._store, name)


def _row_bound(vals: D.PlainValidators, num: int, den: int) -> int:
    """The first row past the prefix (in set order) that holds num/den of
    the power: 0 for none of it, every row for all of it."""
    if num == 0:
        return 0
    if num == den:
        return len(vals)
    return D.first_row_past(vals, num, den)


def _row_in(vals: D.PlainValidators, region: str, rng) -> int:
    lo, hi = (_row_bound(vals, *f) for f in REGIONS[region])
    if hi <= lo:
        raise ValueError(f"no row lies {region}")
    return int(rng.integers(lo, hi))


def _plain_header(hd) -> dict:
    """A header's fields as plain values, for the reference to hash anew."""
    lb, psh = hd.last_block_id, hd.last_block_id.part_set_header
    return {"version": (hd.version.block, hd.version.app),
            "chain_id": hd.chain_id, "height": hd.height,
            "time_ns": hd.time_ns,
            "last_block_id": D.PlainBlockID(lb.hash, psh.total, psh.hash),
            **{k: getattr(hd, k) for k in (
                "last_commit_hash", "data_hash", "validators_hash",
                "next_validators_hash", "consensus_hash", "app_hash",
                "last_results_hash", "evidence_hash", "proposer_address")}}


def _build_node(genesis, vals: D.PlainValidators, chain_id: str, n: int,
                tampered: dict) -> dict:
    """``n`` blocks applied through BlockExecutor into the node's own
    stores, each saved with its seen commit. ``tampered`` = {height: row}:
    that height's seen commit carries one flipped signature bit at ``row``;
    the next block's LastCommit is signed sound. Set-up only: apply_block's
    LastCommit re-check reads the verdicts of the rows as signed."""
    from tendermint_tpu.crypto.batch import precomputed_verdicts
    from tendermint_tpu.types.basic import BlockID, BlockIDFlag
    from tendermint_tpu.types.block import Commit, CommitSig

    n_vals = len(vals)
    signer_of = dict(zip(vals.pubkeys, vals.signers))
    signed_sound: dict = {}
    headers, seen = {}, {}

    def program_commit(in_order, h, bid, plain):
        return Commit(h, 0, bid, [
            CommitSig(BlockIDFlag.COMMIT, v.address, ts, sig)
            for v, ts, sig in zip(in_order, plain.timestamps_ns,
                                  plain.signatures)])

    state, execu, block_store, conns = _fresh_node(genesis)
    last_commit = Commit(0, 0, BlockID(), [])
    token = precomputed_verdicts.set(signed_sound)
    try:
        for h in range(1, n + 1):
            proposer = state.validators.get_proposer().address
            block, parts = state.make_block(h, [f"h{h}=v".encode()],
                                            last_commit, [], proposer)
            bid = BlockID(block.hash(), parts.header())
            in_order = state.validators.validators
            if [v.pub_key.bytes() for v in in_order] != vals.pubkeys:
                raise RuntimeError("the program orders the validator set "
                                   "otherwise than the benchmark's data")
            ts = block.header.time_ns + 1
            plain_bid = D.PlainBlockID(bid.hash, bid.part_set_header.total,
                                       bid.part_set_header.hash)
            sbs = D.vote_sign_bytes(chain_id, h, 0, plain_bid, [ts] * n_vals)
            sound = D.PlainCommit(chain_id, h, 0, plain_bid, [ts] * n_vals,
                                  [signer_of[pk].sign(sb) for pk, sb
                                   in zip(vals.pubkeys, sbs)])
            for pk, sb, sig in zip(vals.pubkeys, sbs, sound.signatures):
                signed_sound[(pk, sb, sig)] = True
            seen[h] = (D.tamper(sound, tampered[h]) if h in tampered
                       else sound)
            block_store.save_block(block, parts, program_commit(
                in_order, h, bid, seen[h]))
            state, _ = execu.apply_block(state, bid, block)
            headers[h] = _plain_header(block.header)
            last_commit = program_commit(in_order, h, bid, sound)
    finally:
        precomputed_verdicts.reset(token)
        conns.stop()
    return {"block_store": block_store, "state_store": execu.state_store,
            "headers": headers, "seen": seen}


def draw_tampered(vals: D.PlainValidators, pool: list, rows: list,
                  seed: int) -> tuple:
    """As many distinct pool heights as ``rows`` names regions, drawn from
    the seed among the whole pool, and a row of its region for each:
    ({height: region}, {height: row})."""
    rng = np.random.default_rng([seed, 11])
    heights = rng.choice(pool, size=len(rows), replace=False)
    regions = {int(h): region for h, region in zip(heights, rows)}
    return regions, {h: _row_in(vals, region, rng)
                     for h, region in regions.items()}


def build(config: dict, traffic: dict, seed: int) -> dict:
    import time

    from tendermint_tpu import crypto
    from tendermint_tpu.config import LightServeConfig
    from tendermint_tpu.light.serve import LightServePlane
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    chain_id = traffic["chain_id"]
    n_blocks, n_pool = config["chain_blocks"], traffic["pool_heights"]
    max_gap = max(c["gaps"][1] for c in traffic["gap_classes"])
    if sum(c["clients"] for c in traffic["gap_classes"]) != traffic["clients"]:
        raise ValueError("the gap classes do not hold every client")
    if n_blocks - 1 - n_pool < max_gap:
        raise ValueError("the chain is too short for the pool and its gaps")
    vals = D.make_validators(config["validators"], seed,
                             power=config["power"])
    now_ns = time.time_ns()
    genesis = GenesisDoc(
        chain_id=chain_id, genesis_time_ns=(now_ns // DAY_NS - 1) * DAY_NS,
        validators=[GenesisValidator(crypto.Ed25519PubKey(pk), power)
                    for pk, power in zip(vals.pubkeys, vals.powers)])
    pool = list(range(n_blocks - n_pool, n_blocks))
    regions, tampered = draw_tampered(vals, pool, traffic["tampered_rows"],
                                      seed)
    node = _build_node(genesis, vals, chain_id, n_blocks, tampered)
    serving = config["serving"]
    cfg = LightServeConfig(
        flush_deadline_ms=serving["flush_deadline_ms"],
        flush_max=serving["flush_max"],
        trusting_period_s=serving["trusting_period_s"])
    view = NodeAt(node["block_store"])
    plane = LightServePlane(block_store=view,
                            state_store=node["state_store"],
                            chain_id=chain_id, config=cfg)
    return {"seed": seed, "traffic": traffic, "vals": vals,
            "plane": plane, "view": view, "pool": pool,
            "tampered": tampered, "regions": regions,
            "trust_level": tuple(serving["trust_level"]),
            "chain": {"chain_id": chain_id, "headers": node["headers"],
                      "seen": node["seen"], "now_ns": now_ns,
                      "trusting_period_s": cfg.trusting_period_s,
                      "max_clock_drift_s": cfg.max_clock_drift_s,
                      "trust_level": tuple(serving["trust_level"])},
            "extras": {"device_sigs": 0}}


def gaps_of_round(data: dict, k: int) -> list:
    """Each client's trusted gap in round ``k``, from the seed."""
    rng = np.random.default_rng([data["seed"], 13, k])
    out = []
    for c in data["traffic"]["gap_classes"]:
        lo, hi = c["gaps"]
        out += [int(g) for g in rng.integers(lo, hi + 1, size=c["clients"])]
    return out


def answer_of(res) -> tuple:
    """A client's answer: ``("accept",)``, or the exception's type and the
    row of the wrong signature it rests on (None where it rests on none)."""
    if res is None:
        return ("accept",)
    e = res
    while e is not None and not hasattr(e, "idx"):
        e = e.__cause__
    return (type(res).__name__, None if e is None else e.idx)


async def _round(data: dict, tip: int, gaps: list) -> tuple:
    plane = data["plane"]
    data["view"].tip = tip

    async def ask(i, gap):
        try:
            res = await plane.serve_verify(tip, tip - gap,
                                           trust_level=data["trust_level"],
                                           client_id=f"client-{i}")
            return answer_of(res), False
        except Exception as e:  # recorded: an operation failed
            return ("error", type(e).__name__, str(e)), True

    got = await asyncio.gather(*[ask(i, g) for i, g in enumerate(gaps)])
    return [a for a, _ in got], any(f for _, f in got)


def warm(data: dict) -> None:
    """Two whole rounds: one at a sound tip, one at a tampered tip. The
    1,024-lane one-call program (every round verifies one commit), the
    routing probe, and both ways out of the replay."""
    sound = next(h for h in data["pool"] if h not in data["tampered"])
    bad = min(data["tampered"])

    async def run():
        for tip in (sound, bad):
            await _round(data, tip, gaps_of_round(data, 0))

    asyncio.run(run())


def program_counters(plane) -> dict:
    """The program's cumulative seconds of the serving plane's three host
    stages; a key the program does not keep reads None."""
    where = {"plane": plane.stats, "coalescer": plane.coalescer.stats}
    return {k: where[w].get(key) for k, (w, key) in COUNTERS.items()}


def window(data: dict, seconds: float, probe) -> list:
    from tendermint_tpu.crypto import batch

    plane, extras = data["plane"], data["extras"]
    n_sigs, pool = len(data["vals"]), data["pool"]
    requests = []
    before = program_counters(plane)
    device_sigs0 = batch.stats["device_sigs"]
    last_tampered = max(pool.index(h) for h in data["tampered"]) + 1

    async def run():
        k = 0
        t_end = probe.clock() + seconds
        while True:
            # the tip advances one height a round, the pool cycled
            tip, gaps = pool[k % len(pool)], gaps_of_round(data, k)
            with probe.span("round") as sp:
                answers, failed = await _round(data, tip, gaps)
            requests.append({"t0": sp["t0"], "t1": sp["t1"], "tip": tip,
                             "gaps": gaps, "answers": answers,
                             "units": {"sigs": n_sigs, "headers": len(gaps)},
                             "failed": failed})
            k += 1
            probe.tick()
            # the deadline, once every tampered height has been a tip
            if probe.clock() >= t_end and k >= last_tampered:
                return

    try:
        asyncio.run(run())
    finally:
        plane.stop()
    after = program_counters(plane)
    extras["device_sigs"] = batch.stats["device_sigs"] - device_sigs0
    for k in COUNTERS:   # a program without the counter reads None
        extras[k] = None if after[k] is None else after[k] - before[k]
    return requests


def compare(data: dict, requests: list, reference, control: bool = False
            ) -> dict:
    """Every answer of every round against the plain reference's answer
    for its tip and gap; the tampered heights the window reached; and the
    signatures the answers rely on (one commit a round) against what the
    routing seam gave to the device."""
    spec = reference.Spec(data["vals"], data["chain"])
    ctl = (reference.Spec(data["vals"], data["chain"], control=True)
           if control else None)
    mismatches = 0
    for r in requests:
        for gap, got in zip(r["gaps"], r["answers"]):
            if control:
                got = ctl.answer(r["tip"], gap)
            mismatches += got != spec.answer(r["tip"], gap)
    tips = {r["tip"] for r in requests}
    relied_on = len(requests) * len(data["vals"])
    return {"answer_mismatches": (mismatches, 0),
            "tampered_heights_unseen": (
                sum(1 for h in data["tampered"] if h not in tips), 0),
            "signatures_not_on_device": (
                max(0, relied_on - data["extras"]["device_sigs"]), 0)}
