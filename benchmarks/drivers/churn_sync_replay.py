"""Driver: fresh nodes fast-sync a chain whose validator set changes at
every height, back to back.

The catch-up of ``fast_sync_replay`` (one request is one whole sync of a
fresh node through the real ``BlockchainReactor`` window loop and
``BlockExecutor.apply_block``; after the first sound sync one sync of each
tampered chain), over another chain. Every block carries validator updates
that the kvstore application returns from EndBlock: ``power_moves_per_block``
seeded members move to a new power drawn from ``move_powers``, and every
``swap_every_blocks``-th block one member leaves (power 0) and a new seeded
key joins at ``joiner_power``, so the set keeps its size. By v0.34's H+2 rule
the set, and with it the order of every commit's rows, changes at every
height from height 3 on. Each seen commit is signed by the set of its own
height, in that set's order, over the benchmark's own sign-bytes.

A tampered chain is one verify window long, as in ``fast_sync_replay``; its
wrong row is drawn inside or past the 2/3-power prefix of the set of the
tampered commit's own height (after the churn up to there).

Besides ``fast_sync_replay``'s sums, ``extras`` keeps the reactor's own
counters over the window: ``blocks`` applied, ``blocks_in_spanning_windows``
(blocks from windows whose headers claim more than one set),
``candidates`` (signatures verified ahead for the windows applied) and
``verdict_misses`` (those the apply-time rules had to verify again). A
reactor without these counters (one that gates every window on one set)
counts no block as spanning and no candidate.

Traffic parameters: as ``fast_sync_replay``'s.
"""

from __future__ import annotations

import gc

import numpy as np
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
)

import data as D
from drivers.fast_sync_replay import STAGES, _answer, _fresh_node, _sync

#: the reactor's counters (``stage_breakdown()`` keys) the window keeps
COUNTERS = {"blocks_in_spanning_windows": "spanning_blocks",
            "candidates": "candidates", "verdict_misses": "verdict_misses"}


def churn_txs(vals: D.PlainValidators, churn: dict, n_blocks: int,
              seed: int) -> tuple:
    """``(transactions of each block 1..n_blocks, signer of each joiner's
    key)``: the validator updates as the configuration's ``churn`` says,
    then one key=value transaction. The members moved or removed are drawn
    among those of the set the updates apply to (every update so far)."""
    rng = np.random.default_rng([seed, 5])
    lo, hi = churn["move_powers"]
    moves, every = churn["power_moves_per_block"], churn["swap_every_blocks"]
    power_of = dict(zip(vals.pubkeys, vals.powers))
    joiners = {}
    blocks = []
    for h in range(1, n_blocks + 1):
        members = sorted(power_of)
        swap = h % every == 0
        picked = rng.choice(len(members), size=moves + swap, replace=False)
        txs = []
        for i in picked[:moves]:
            pk = members[int(i)]
            choices = [p for p in range(lo, hi + 1) if p != power_of[pk]]
            power_of[pk] = choices[int(rng.integers(len(choices)))]
            txs.append(b"val:%s!%d" % (pk.hex().encode(), power_of[pk]))
        if swap:
            gone = members[int(picked[moves])]
            del power_of[gone]
            txs.append(b"val:%s!0" % gone.hex().encode())
            sk = Ed25519PrivateKey.from_private_bytes(rng.bytes(32))
            pk = sk.public_key().public_bytes_raw()
            joiners[pk] = sk
            power_of[pk] = churn["joiner_power"]
            txs.append(b"val:%s!%d" % (pk.hex().encode(), power_of[pk]))
        txs.append(b"h%d=v" % h)
        blocks.append(txs)
    return blocks, joiners


def _build_chain(genesis, signer_of: dict, chain_id: str, txs: list, n: int,
                 signed: dict, tamper=None) -> dict:
    """``n + 1`` real blocks over the churning set, each applied through
    BlockExecutor against a kvstore app (which returns the updates). The
    seen commit for height h is signed by the set of height h, in its
    order. ``tamper`` = (height, region, rng) flips one bit of one
    signature of the seen commit for that height, the row drawn from the
    rng inside that region of that height's set; the chain goes on on top
    of it. Set-up only: apply_block's LastCommit re-check runs against the
    builder's own verdicts (fast_sync_replay._build_chain). ``signed`` keeps
    every signature made, by (key, sign-bytes): the chains share their
    blocks up to the tampered height."""
    from tendermint_tpu.crypto.batch import precomputed_verdicts
    from tendermint_tpu.types.basic import BlockID, BlockIDFlag
    from tendermint_tpu.types.block import Commit, CommitSig

    builder_says: dict = {}
    plain_commits: dict = {}
    tampered_row = None

    def sign_seen_commit(vset, block, bid):
        nonlocal tampered_row
        in_order = vset.validators
        h = block.header.height
        ts = block.header.time_ns + 1
        plain_bid = D.PlainBlockID(bid.hash, bid.part_set_header.total,
                                   bid.part_set_header.hash)
        sbs = D.vote_sign_bytes(chain_id, h, 0, plain_bid, [ts] * len(in_order))
        sigs = []
        for v, sb in zip(in_order, sbs):
            pk = v.pub_key.bytes()
            sig = signed.get((pk, sb))
            if sig is None:
                sig = signed[pk, sb] = signer_of[pk].sign(sb)
            sigs.append(sig)
        plain = D.PlainCommit(chain_id, h, 0, plain_bid,
                              [ts] * len(in_order), sigs)
        if tamper is not None and tamper[0] == h:
            powers = [v.voting_power for v in in_order]
            past = D.first_row_past(D.PlainValidators([], powers, []), 2, 3)
            lo, hi = {"inside_two_thirds": (0, past),
                      "past_two_thirds": (past, len(in_order))}[tamper[1]]
            if hi <= lo:
                raise ValueError(f"no row lies {tamper[1]}")
            tampered_row = int(tamper[2].integers(lo, hi))
            plain = D.tamper(plain, tampered_row)
        plain_commits[h] = plain
        sigs = []
        for v, sb, sig in zip(in_order, sbs, plain.signatures):
            sigs.append(CommitSig(BlockIDFlag.COMMIT, v.address, ts, sig))
            builder_says[(v.pub_key.bytes(), sb, sig)] = True
        return Commit(h, 0, bid, sigs)

    state, execu, _bs, conns = _fresh_node(genesis)
    blocks = []
    last_commit = Commit(0, 0, BlockID(), [])
    token = precomputed_verdicts.set(builder_says)
    try:
        for h in range(1, n + 2):
            proposer = state.validators.get_proposer().address
            block, parts = state.make_block(h, txs[h - 1], last_commit, [],
                                            proposer)
            bid = BlockID(block.hash(), parts.header())
            blocks.append(block)
            state, _ = execu.apply_block(state, bid, block)
            # after block h the state's last set is the set of height h
            last_commit = sign_seen_commit(state.last_validators, block, bid)
    finally:
        precomputed_verdicts.reset(token)
        conns.stop()
    return {"n": n, "blocks": blocks, "txs": txs[:n + 1],
            "txs_per_block": [len(t) for t in txs[:n + 1]],
            "commits": plain_commits,
            "tampered": None if tamper is None else (tamper[0], tampered_row)}


def build(config: dict, traffic: dict, seed: int) -> dict:
    from tendermint_tpu import crypto
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    chain_id = traffic["chain_id"]
    vals = D.make_validators(config["validators"], seed,
                             power=config["power"])
    genesis = GenesisDoc(
        chain_id=chain_id, genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(crypto.Ed25519PubKey(pk), power)
                    for pk, power in zip(vals.pubkeys, vals.powers)])
    txs, joiners = churn_txs(vals, config["churn"], config["blocks"] + 1,
                             seed)
    signer_of = {**dict(zip(vals.pubkeys, vals.signers)), **joiners}
    signed: dict = {}
    sound = _build_chain(genesis, signer_of, chain_id, txs, config["blocks"],
                         signed)
    rng = np.random.default_rng([seed, 3])
    tampered = []
    for spec in traffic["tampered_chains"]:
        if not 0 < spec["commit_height"] <= config["verify_window_pairs"]:
            raise ValueError("a tampered chain is one verify window long")
        tampered.append(_build_chain(
            genesis, signer_of, chain_id, txs, config["verify_window_pairs"],
            signed, tamper=(spec["commit_height"], spec["row"], rng)))
    return {"seed": seed, "traffic": traffic, "vals": vals,
            "genesis": genesis, "sound": sound, "tampered": tampered,
            "extras": {"syncs": 0, "sync_wall_s": 0.0,
                       **{s: 0.0 for s in STAGES},
                       "pipelined_windows": 0, "inline_windows": 0,
                       "device_sigs": 0, "blocks": 0,
                       **dict.fromkeys(COUNTERS, 0)}}


def warm(data: dict) -> None:
    """One whole sync of the sound chain: the window program (K=8 at the
    96-column bucket at 1,000 validators), the one-call program the
    verdict misses of a joiner's first rows are verified in, and every host
    path of a sync; then the tampered chains, for the paths on which a node
    stops.

    Last, the run's own objects (the three chains, every signature made, the
    modules and programs loaded) leave the cyclic collector's reach until
    the window ends. A node holds none of the benchmark's chains, and each
    height's new 1,000-member set makes garbage enough for full collections
    all through a sync: left in reach, every one of them walks the whole
    set-up heap too, a share of the window that grows with the heap and
    moves with the host's memory traffic from run to run."""
    _sync(data, data["sound"])
    for chain in data["tampered"]:
        _sync(data, chain)
    gc.collect()
    gc.freeze()


def window(data: dict, seconds: float, probe) -> list:
    from tendermint_tpu.crypto import batch

    extras = data["extras"]
    requests = []
    device_sigs0 = batch.stats["device_sigs"]
    # the sound chain; after its first sync each tampered chain once
    queue = [(-1, data["sound"])] + list(enumerate(data["tampered"]))
    t_end = probe.clock() + seconds
    while True:
        which, chain = queue.pop(0) if queue else (-1, data["sound"])
        sound = which < 0
        stalled = probe.overhead_s
        with probe.span("sync") as sp:
            try:
                reactor = _sync(data, chain, probe if sound else None)
                answer, failed = _answer(reactor, chain["n"]), False
            except Exception as e:  # recorded: an operation failed
                reactor = None
                answer, failed = ("error", type(e).__name__, str(e)), True
        applied = reactor.blocks_synced if reactor else 0
        requests.append({"t0": sp["t0"], "t1": sp["t1"],
                         "units": {"blocks": applied}, "chain": which,
                         "answer": answer, "failed": failed})
        if reactor is not None:
            stage = reactor.stage_breakdown()
            for k in STAGES + ("pipelined_windows", "inline_windows"):
                extras[k] += stage[k]
            for k, counter in COUNTERS.items():
                extras[k] += stage.get(counter, 0)
            extras["blocks"] += applied
            extras["syncs"] += 1
            # less the profiler's own stop inside a traced sync
            extras["sync_wall_s"] += (sp["t1"] - sp["t0"]
                                      - (probe.overhead_s - stalled))
        if probe.clock() >= t_end and not queue:
            extras["device_sigs"] = batch.stats["device_sigs"] - device_sigs0
            gc.unfreeze()  # after the last request: out of the window
            return requests


def compare(data: dict, requests: list, reference, control: bool = False
            ) -> dict:
    """Every sync of the window against what the plain reference says a
    node holds after it was offered that chain; and the signatures the
    routing seam gave to the device over the window against those the
    reference's answers rely on."""
    chains = [data["sound"]] + data["tampered"]
    genesis = data["vals"]
    want = reference.expected(genesis, chains)
    got_control = (reference.expected(genesis, chains, control=True)
                   if control else None)
    mismatches = relied_on = 0
    seen = set()
    for r in requests:
        i = r["chain"] + 1
        seen.add(i)
        got = got_control[i] if control else r["answer"]
        mismatches += got != want[i]
        relied_on += reference.signatures_relied_on(genesis, chains[i],
                                                    want[i])
    return {"sync_state_mismatches": (mismatches, 0),
            "chains_not_synced": (len(chains) - len(seen), 0),
            "sound_chain_refused": (int(want[0][0] != "synced"), 0),
            "tampered_chains_followed": (
                sum(w[0] == "synced" for w in want[1:]), 0),
            "signatures_not_on_device": (
                max(0, relied_on - data["extras"]["device_sigs"]), 0)}
