"""Driver: fresh nodes fast-sync seeded source chains, back to back.

A request is one whole sync: a fresh node (kvstore ABCI over the local
client, StateStore and BlockStore in memory) catches up through the real
``BlockchainReactor`` window loop and ``BlockExecutor.apply_block``. The
window starts syncs until ``--seconds`` have passed and lets the one in
progress finish; blocks applied count over the time to that end.

Most syncs are of the sound chain (``blocks`` of the configuration). After
the first of them, every window also holds one sync of each TAMPERED chain:
a chain of one verify window (``verify_window_pairs`` + 1 blocks) in which
one signature of one seen commit is wrong, the later blocks built on top of
it. A node has to stop there, and where it stops says which of the two
signature planes refused it: a row inside the 2/3-power prefix is the light
check's to find (the node stops below that height), a row past it only the
full LastCommit check's (it stops at that height). ``compare`` holds every
sync to the plain reference's answer for its chain, and the signatures the
routing seam counted on the device to the signatures the reference says
those answers rely on.

Chain building and the replay loop are copied from ``bench.py``
(``build_sync_chain``, ``_sync_fresh_node``, ``replay_sync_chain``), which
the chip smoke ran; the seen commits are signed here over the benchmark's
own sign-bytes (data.py), so the plain reference reads them independently.

Traffic parameters: ``chain_id``; ``tampered_chains``: a list of
{"commit_height", "row": "inside_two_thirds" | "past_two_thirds"} (the row
itself is drawn from the seed inside that region); ``trace_after_ticks``,
``trace_ticks``: the driver ticks at the start of every sync of the sound
chain and after each of its window calls of the reactor (16 block pairs;
seven ticks a 96-block sync), and a traced run starts the profiler at that
tick and stops it that many ticks later (the whole second sound sync). The
tampered syncs do not tick.
"""

from __future__ import annotations

import asyncio

import numpy as np

import data as D
from objects import as_received

STAGES = ("hash_s", "verify_s", "store_s", "abci_s")


def _fresh_node(genesis):
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.proxy import AppConns, local_client_creator
    from tendermint_tpu.state import (
        BlockExecutor,
        StateStore,
        state_from_genesis,
    )
    from tendermint_tpu.state.execution import EmptyEvidencePool, NoOpMempool
    from tendermint_tpu.store import BlockStore

    conns = AppConns(local_client_creator(KVStoreApplication()))
    conns.start()
    state = state_from_genesis(genesis)
    state_store = StateStore(MemDB())
    state_store.save(state)
    block_store = BlockStore(MemDB())
    execu = BlockExecutor(state_store, conns.consensus, NoOpMempool(),
                          EmptyEvidencePool(), block_store)
    return state, execu, block_store, conns


def _build_chain(genesis, vals: D.PlainValidators, chain_id: str, n: int,
                 tamper=None) -> dict:
    """``n + 1`` real blocks (every block needs a successor carrying its
    seen commit) over the validator set, each applied through BlockExecutor
    against a kvstore app. ``tamper`` = (height, row) flips one bit of that
    row's signature in the seen commit for that height; the chain goes on
    on top of it. Set-up only: the signatures are this builder's own, so
    apply_block's LastCommit re-check runs against verdicts given here
    (true for the tampered one too: the builder has to get past it) instead
    of a device dispatch per block."""
    from tendermint_tpu.crypto.batch import precomputed_verdicts
    from tendermint_tpu.types.basic import BlockID, BlockIDFlag
    from tendermint_tpu.types.block import Commit, CommitSig

    n_vals = len(vals)
    signer_of = dict(zip(vals.pubkeys, vals.signers))
    builder_says: dict = {}
    plain_commits: dict = {}

    def sign_seen_commit(state, block, bid):
        in_order = state.validators.validators
        if [v.pub_key.bytes() for v in in_order] != vals.pubkeys:
            raise RuntimeError("the program orders the validator set "
                               "otherwise than the benchmark's data")
        h = block.header.height
        ts = block.header.time_ns + 1
        plain_bid = D.PlainBlockID(bid.hash, bid.part_set_header.total,
                                   bid.part_set_header.hash)
        sbs = D.vote_sign_bytes(chain_id, h, 0, plain_bid, [ts] * n_vals)
        plain = D.PlainCommit(
            chain_id, h, 0, plain_bid, [ts] * n_vals,
            [signer_of[v.pub_key.bytes()].sign(sb)
             for v, sb in zip(in_order, sbs)])
        if tamper is not None and tamper[0] == h:
            plain = D.tamper(plain, tamper[1])
        plain_commits[h] = plain
        sigs = []
        for v, sb, sig in zip(in_order, sbs, plain.signatures):
            sigs.append(CommitSig(BlockIDFlag.COMMIT, v.address, ts, sig))
            builder_says[(v.pub_key.bytes(), sb, sig)] = True
        return Commit(h, 0, bid, sigs)

    state, execu, _bs, conns = _fresh_node(genesis)
    blocks, txs_per_block = [], []
    last_commit = Commit(0, 0, BlockID(), [])
    token = precomputed_verdicts.set(builder_says)
    try:
        for h in range(1, n + 2):
            proposer = state.validators.get_proposer().address
            txs = [f"h{h}=v".encode()]
            block, parts = state.make_block(h, txs, last_commit, [], proposer)
            bid = BlockID(block.hash(), parts.header())
            blocks.append(block)
            txs_per_block.append(len(txs))
            state, _ = execu.apply_block(state, bid, block)
            last_commit = sign_seen_commit(state, block, bid)
    finally:
        precomputed_verdicts.reset(token)
        conns.stop()
    return {"n": n, "blocks": blocks, "txs_per_block": txs_per_block,
            "commits": plain_commits, "tampered": tamper}


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
    sound = _build_chain(genesis, vals, chain_id, config["blocks"])
    rng = np.random.default_rng([seed, 3])
    past = D.first_row_past(vals, 2, 3)
    tampered = []
    for spec in traffic["tampered_chains"]:
        lo, hi = {"inside_two_thirds": (0, past),
                  "past_two_thirds": (past, len(vals))}[spec["row"]]
        if not 0 < spec["commit_height"] <= config["verify_window_pairs"]:
            raise ValueError("a tampered chain is one verify window long")
        if hi <= lo:
            raise ValueError(f"no row lies {spec['row']}")
        tampered.append(_build_chain(
            genesis, vals, chain_id, config["verify_window_pairs"],
            tamper=(spec["commit_height"], int(rng.integers(lo, hi)))))
    return {"seed": seed, "traffic": traffic, "vals": vals,
            "genesis": genesis, "sound": sound, "tampered": tampered,
            "extras": {"syncs": 0, "sync_wall_s": 0.0,
                       **{s: 0.0 for s in STAGES},
                       "pipelined_windows": 0, "inline_windows": 0,
                       "device_sigs": 0}}


def _as_received(block):
    """The block as a node gets it from a peer: new objects all the way
    down to the signatures (objects.py)."""
    lc = block.last_commit
    return as_received(
        block, header=as_received(block.header),
        data=as_received(block.data), evidence=list(block.evidence),
        last_commit=None if lc is None else as_received(lc))


def _sync(data: dict, chain: dict, probe=None):
    """One fresh node, offered the chain's ``n + 1`` blocks by one peer,
    until it holds ``n`` of them or stops. -> the reactor."""
    from tendermint_tpu.blockchain import BlockchainReactor, BlockPool
    from tendermint_tpu.blockchain.reactor import FatalSyncError

    n = chain["n"]
    if probe is not None:
        probe.tick()
    blocks = [_as_received(b) for b in chain["blocks"]]
    state, execu, block_store, conns = _fresh_node(data["genesis"])
    try:
        reactor = BlockchainReactor(state, execu, block_store,
                                    fast_sync=True)
        reactor.pool = BlockPool(1)
        reactor.pool.set_peer_range("src", 1, n + 1)

        async def drive():
            # keep TWO full verify windows downloaded before each process
            # call: the apply pipeline prepares window N+1 on a worker
            # thread while window N applies, and needs N+1's blocks present
            # at spawn time (n is a multiple of the reactor's window of 16)
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
                except FatalSyncError:
                    return      # the node halts on a block it cannot apply
                if reactor.blocks_synced <= before:
                    return      # the peer was dropped: nobody left to ask
                if probe is not None:
                    probe.tick()

        asyncio.run(drive())
        return reactor
    finally:
        conns.stop()


def _answer(reactor, n: int) -> tuple:
    st = reactor.state
    bid = st.last_block_id
    return ("synced" if st.last_block_height == n else "stopped",
            st.last_block_height, st.app_hash, bid.hash,
            bid.part_set_header.total, bid.part_set_header.hash)


def warm(data: dict) -> None:
    """One whole sync of the sound chain: the window program (K=8 at the
    96-column bucket at 1,000 validators) and every host path of a sync;
    then the tampered chains, for the paths on which a node stops."""
    _sync(data, data["sound"])
    for chain in data["tampered"]:
        _sync(data, chain)


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
            extras["syncs"] += 1
            # less the profiler's own stop inside a traced sync
            extras["sync_wall_s"] += (sp["t1"] - sp["t0"]
                                      - (probe.overhead_s - stalled))
        if probe.clock() >= t_end and not queue:
            extras["device_sigs"] = batch.stats["device_sigs"] - device_sigs0
            return requests


def compare(data: dict, requests: list, reference, control: bool = False
            ) -> dict:
    """Every sync of the window against what the plain reference says a
    node holds after it was offered that chain; and the signatures the
    routing seam gave to the device over the window against those the
    reference's answers rely on."""
    chains = [data["sound"]] + data["tampered"]
    want = reference.expected(data["vals"], chains)
    got_control = (reference.expected(data["vals"], chains, control=True)
                   if control else None)
    n_vals = len(data["vals"])
    mismatches = relied_on = 0
    seen = set()
    for r in requests:
        i = r["chain"] + 1
        seen.add(i)
        got = got_control[i] if control else r["answer"]
        mismatches += got != want[i]
        relied_on += reference.signatures_relied_on(want[i], n_vals)
    return {"sync_state_mismatches": (mismatches, 0),
            "chains_not_synced": (len(chains) - len(seen), 0),
            "sound_chain_refused": (int(want[0][0] != "synced"), 0),
            "tampered_chains_followed": (
                sum(w[0] == "synced" for w in want[1:]), 0),
            "signatures_not_on_device": (
                max(0, relied_on - data["extras"]["device_sigs"]), 0)}
