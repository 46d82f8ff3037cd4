"""Benchmarks: every BASELINE.md config, one JSON line each.

Output contract: each line is {"metric", "value", "unit", "vs_baseline"}.
The FLAGSHIP metric — sustained VerifyCommit throughput at 10,240
validators (the north star scale, reference types/validator_set.go:667) —
prints LAST so the driver records it.

Configs (BASELINE.json):
  1  Ed25519 batched stream, CHUNK-sig chunks scanned in one execution
  2  ValidatorSet.VerifyCommit over a 150-validator commit (one-shot)
  3  VerifyCommitLight+Trusting over a 1000-validator header chain
  4  4-node localnet (kvstore), consensus end-to-end blocks/min
  5  fast-sync windowed replay @ 1000 validators
  ingest  open-loop broadcast_tx load on the 4-node localnet: sustained
       committed txs/s + p99 broadcast->commit latency + p99 admission
       latency through the ingest fast path (tools/loadtime.py)
  multichip  devices x chunk scaling table (device_profile scale)
  10k  sustained VerifyCommit @ 10,240 validators (flagship, last) plus
       the multichip flagship through the multi-device dispatcher

Baselines: configs 1/2/3/5/10k measure the host scalar loop (OpenSSL-backed
PubKey.verify_signature — the stand-in for the reference's Go x/crypto
ed25519.Verify hot call, crypto/ed25519/ed25519.go:148-155) in the same
process. Config 4's baseline is the reference QA testnet's 19.5 blocks/min
(docs/qa/v034/README.md:141-142; 200-node WAN vs 4-node localhost — an
anchor, not an equal-hardware comparison).

The device path is charged end-to-end: host packing + transfer + kernel +
verdict fetch, exactly what the consensus/blocksync callers pay.
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

N_STREAM = 32768
CHUNK = 2048
N_BASE = 2048


def _enable_compile_cache():
    from tendermint_tpu.libs.compilecache import enable_compile_cache

    warn = enable_compile_cache()
    if warn:  # stderr: stdout is the driver-parsed JSONL stream
        print(warn, file=sys.stderr)


@functools.cache
def _device_fields() -> dict:
    """platform / device_kind / device count as jax reports them, read
    once. Reading them initializes the backend, so this process then owns
    the chip: no bench child may need it (localnet nodes and host-mesh
    scale cells are CPU-pinned; the chip's scaling cells run in this
    process)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "device_count": len(devs)}


def _emit(metric: str, value: float, unit: str, vs_baseline: float, **extra):
    line = {"metric": metric, "value": round(value, 3), "unit": unit,
            "vs_baseline": round(vs_baseline, 3)}
    line.update(_device_fields())
    line.update(extra)
    print(json.dumps(line), flush=True)


def build_batch(n: int):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    from tendermint_tpu import crypto
    from tendermint_tpu.types import BlockID, PartSetHeader, SignedMsgType
    from tendermint_tpu.types.canonical import vote_sign_bytes

    bid = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))
    rng = np.random.default_rng(7)
    pks, msgs, sigs, pubs = [], [], [], []
    for i in range(n):
        priv = Ed25519PrivateKey.from_private_bytes(
            bytes(rng.integers(0, 256, 32, dtype=np.uint8)))
        pub_bytes = priv.public_key().public_bytes_raw()
        # realistic vote sign-bytes (unique timestamp per validator)
        msg = vote_sign_bytes("bench-chain", SignedMsgType.PRECOMMIT, 100, 0,
                              bid, 1_700_000_000_000_000_000 + i)
        pks.append(pub_bytes)
        msgs.append(msg)
        sigs.append(priv.sign(msg))
        pubs.append(crypto.Ed25519PubKey(pub_bytes))
    return pks, msgs, sigs, pubs


def _host_rate(pubs, msgs, sigs, n: int) -> float:
    """Host scalar loop sigs/s on an n-item subset."""
    t0 = time.perf_counter()
    ok = all(pub.verify_signature(m, s)
             for pub, m, s in zip(pubs[:n], msgs[:n], sigs[:n]))
    elapsed = time.perf_counter() - t0
    assert ok
    return n / elapsed


def bench_stream():
    """Config #1: sustained batched-verifier throughput on vote sign-bytes."""
    pks, msgs, sigs, pubs = build_batch(N_STREAM)

    from tendermint_tpu.crypto.ed25519_jax import batch_verify_stream

    out = batch_verify_stream(pks, msgs, sigs, chunk=CHUNK)  # compile
    assert np.asarray(out).all(), "warmup stream rejected valid sigs"
    times = []
    for _ in range(4):
        t0 = time.perf_counter()
        out = batch_verify_stream(pks, msgs, sigs, chunk=CHUNK)
        times.append(time.perf_counter() - t0)
    assert np.asarray(out).all()
    dev = N_STREAM / min(times)
    host = _host_rate(pubs, msgs, sigs, N_BASE)
    _emit(f"verify_commit_sigs_per_sec_stream{CHUNK}", dev, "sigs/s",
          dev / host, chunk=CHUNK)


# --- commit helpers ---------------------------------------------------------

def _mk_val_set(n_vals: int, seed: int = 7):
    """A validator set + its signing keys (OpenSSL), reusable across heights."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    from tendermint_tpu import crypto
    from tendermint_tpu.types import Validator, ValidatorSet

    rng = np.random.default_rng(seed)
    keys = {}
    vals = []
    for _ in range(n_vals):
        sk = Ed25519PrivateKey.from_private_bytes(
            bytes(rng.integers(0, 256, 32, dtype=np.uint8)))
        pub = crypto.Ed25519PubKey(sk.public_key().public_bytes_raw())
        keys[pub.address()] = sk
        vals.append(Validator(pub.address(), pub, 10))
    return ValidatorSet(vals), keys


def _sign_commit(vs, keys, height: int, chain_id: str):
    """A canonical commit for `height` signed by every validator, in
    validator-set order."""
    from tendermint_tpu.types.basic import (
        BlockID,
        BlockIDFlag,
        PartSetHeader,
        SignedMsgType,
    )
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.canonical import vote_sign_bytes

    bid = BlockID(hash(("bench", height)).to_bytes(8, "big", signed=True) * 4,
                  PartSetHeader(1, b"\x02" * 32))
    sigs = []
    for i, v in enumerate(vs.validators):
        ts = 1_700_000_000_000_000_000 + height * 1_000_000 + i
        msg = vote_sign_bytes(chain_id, SignedMsgType.PRECOMMIT, height, 0,
                              bid, ts)
        sigs.append(CommitSig(BlockIDFlag.COMMIT, v.address, ts,
                              keys[v.address].sign(msg)))
    return Commit(height, 0, bid, sigs), bid


def _timed(fn, warm: int = 1, runs: int = 3) -> float:
    for _ in range(warm):
        fn()
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_verify_commit_150():
    """Config #2: ValidatorSet.VerifyCommit over a 150-validator commit
    (reference types/validator_set.go:667) — the live consensus hot loop.

    Two regimes:
    * seam cost: the auto backend vs the pinned host backend, interleaved
      A/B to cancel CPU drift — proves the routing seam costs nothing;
    * routing honesty: the auto router measured as-is. The calibrated
      break-even (crypto/batch.py device_threshold, payload-bearing probe)
      must keep a sub-threshold commit on the host path, so the routed
      number may never be slower than scalar — asserted, not just
      reported. (A forced 16-sig threshold once pushed this commit onto
      a device path several times slower than scalar.)
    """
    vs, keys = _mk_val_set(150)
    commit, bid = _sign_commit(vs, keys, 100, "bench-150")

    def run():
        vs.verify_commit("bench-150", bid, 100, commit)

    run()  # warm (sign-bytes memo, threshold calibration)
    dev_ts, host_ts = [], []

    def _one(pinned: bool) -> None:
        if pinned:
            os.environ["TMTPU_BATCH_BACKEND"] = "host"
        try:
            t0 = time.perf_counter()
            run()
            (host_ts if pinned else dev_ts).append(time.perf_counter() - t0)
        finally:
            if pinned:
                del os.environ["TMTPU_BATCH_BACKEND"]

    for i in range(9):  # interleaved A/B with alternating order: cache
        # warmth systematically favors whichever runs second in a pair
        _one(pinned=bool(i % 2))
        _one(pinned=not bool(i % 2))
    dev, host = min(dev_ts), min(host_ts)
    _emit("verify_commit_150_vals_sigs_per_sec", 150 / dev, "sigs/s",
          host / dev)

    # routed regime: the interleaved auto-backend measurement above IS the
    # calibrated router's decision (150 sigs below the break-even stays on
    # host; on locally-attached silicon, threshold ~16, the same call
    # routes to the device and must win there). Reusing the drift-cancelled
    # A/B numbers keeps the never-slower assertion symmetric — no separate
    # un-interleaved timing, no fudge factor.
    from tendermint_tpu.crypto.batch import device_threshold

    thr = device_threshold()
    not_slower = dev <= host * 1.05  # interleaved min-of-9 each; 5% jitter
    _emit("verify_commit_150_vals_device_routed_sigs_per_sec",
          150 / dev, "sigs/s", host / dev,
          calibrated_threshold=thr,
          routed_backend="jax" if 150 >= thr else "host",
          routing_not_slower_than_scalar=bool(not_slower))
    assert not_slower, (
        f"device routing slower than scalar: routed {150 / dev:.0f} "
        f"sigs/s vs host {150 / host:.0f} sigs/s (threshold {thr})")


def bench_light_chain_1000():
    """Config #3: light-client VerifyCommitLight+Trusting over a
    1000-validator header chain (reference validator_set.go:722,775,
    light/verifier.go:32). Device path: ONE segmented (pipelined) device
    call verifies every unique candidate signature across the 32-header
    range; both verification kinds then replay their scalar precedence
    semantics against the shared precomputed verdicts (the same dual-plane
    dedup the fast-sync reactor applies per window). Sign-bytes are built
    once per commit via the shared-field batch encoder. The metric's sig
    count is the UNIQUE signatures verified (n_headers x n_vals).

    vs_baseline is EQUAL WORK: the host baseline runs the identical dedup
    structure (one pass over unique signatures, scalar backend, then both
    replays) — a scalar implementation could memoize the same way, so the
    headline ratio credits only the crypto plane. This also approximates
    the reference's TRUE scalar cost: its early-exiting loops verify ~1001
    sigs/header (1/3 tally for trusting + 2/3 for light,
    validator_set.go:722,775) vs the 1000 unique here. The extra field
    vs_undeduped_scalar keeps round-over-round continuity with the r1-r4
    methodology, whose baseline pushed ALL candidates through the seam once
    per verification kind (~2x the unique set). (The helpers' own internal
    dispatch path is exercised by config #5's plane metric and the test
    suite.)"""
    from tendermint_tpu.types.validator_set import (
        verify_commit_light_batched,
        verify_commit_light_trusting_batched,
    )

    n_vals, n_headers = 1000, 32
    vs, keys = _mk_val_set(n_vals)
    commits = [_sign_commit(vs, keys, h, "bench-light")[0]
               for h in range(2, n_headers + 2)]
    trust = (1, 3)

    def _fresh_commits():
        # a real light client sees each commit once: drop the sign-bytes
        # memo so every timed pass pays construction, on both backends
        for c in commits:
            c.__dict__.pop("_sb_cache", None)

    def verify_chain_deduped(backend: str):
        from tendermint_tpu.crypto import batch as crypto_batch
        from tendermint_tpu.crypto.batch import (
            BatchVerifier,
            precomputed_verdicts,
        )

        _fresh_commits()
        # both verification kinds check the SAME candidate signatures, so
        # one verification pass serves trusting AND light (the same
        # dual-plane pattern the fast-sync reactor uses per window)
        bv = BatchVerifier(backend=backend)
        verdict_keys = []
        for c in commits:
            sb = c.vote_sign_bytes_all("bench-light")
            for idx, cs in enumerate(c.signatures):
                if cs.for_block():
                    pk = vs.validators[idx].pub_key
                    bv.add(pk, sb[idx], cs.signature)
                    verdict_keys.append((pk.bytes(), sb[idx], cs.signature))
        _, verdicts = bv.verify()
        token = precomputed_verdicts.set(
            {k: bool(v) for k, v in zip(verdict_keys, verdicts)})
        pre_before = crypto_batch.stats["precomputed_batches"]
        try:
            errs = verify_commit_light_trusting_batched(
                [(vs, "bench-light", c, trust) for c in commits])
            assert all(e is None for e in errs), errs
            errs = verify_commit_light_batched(
                [(vs, "bench-light", c.block_id, c.height, c)
                 for c in commits])
            assert all(e is None for e in errs), errs
        finally:
            precomputed_verdicts.reset(token)
        # guard the metric: a key mismatch would silently re-dispatch the
        # whole batch inside the timed region instead of replaying verdicts
        assert crypto_batch.stats["precomputed_batches"] == pre_before + 2, \
            "precomputed verdicts missed: bench would measure re-dispatch"

    def verify_chain_undeduped_host():
        _fresh_commits()
        for c in commits:
            vs.verify_commit_light_trusting("bench-light", c, trust)
            vs.verify_commit_light("bench-light", c.block_id, c.height, c)

    dev = _timed(lambda: verify_chain_deduped("jax"))
    os.environ["TMTPU_BATCH_BACKEND"] = "host"
    try:
        # equal work: the SAME dedup structure on the scalar backend
        host = _timed(lambda: verify_chain_deduped("host"), warm=0, runs=1)
        # the reference-shaped seam: each kind verifies its candidates
        host2x = _timed(verify_chain_undeduped_host, warm=0, runs=1)
    finally:
        del os.environ["TMTPU_BATCH_BACKEND"]
    # unique candidate signatures verified per pass (the honest numerator:
    # both verification kinds share the same signatures, verified once)
    sigs = n_headers * n_vals
    _emit("light_chain_1000_vals_sigs_per_sec", sigs / dev, "sigs/s",
          host / dev, vs_undeduped_scalar=round(host2x / dev, 3))


def bench_fast_sync_replay():
    """Config #5 (scaled): the block-sync engine's windowed batched commit
    verification over a 1000-validator chain (reference
    blockchain/v0/reactor.go:255; our blockchain/reactor.py). Measures
    the verification plane, which is the reference's fast-sync bottleneck."""
    from tendermint_tpu.types.validator_set import verify_commit_light_batched

    n_vals, n_blocks, window = 1000, 64, 16
    vs, keys = _mk_val_set(n_vals)
    entries = []
    for h in range(1, n_blocks + 1):
        commit, bid = _sign_commit(vs, keys, h, "bench-sync")
        entries.append((vs, "bench-sync", bid, h, commit))

    def replay():
        for i in range(0, n_blocks, window):
            errs = verify_commit_light_batched(entries[i:i + window])
            assert all(e is None for e in errs), errs

    dev = _timed(replay)
    os.environ["TMTPU_BATCH_BACKEND"] = "host"
    try:
        host = _timed(replay, warm=0, runs=1)
    finally:
        del os.environ["TMTPU_BATCH_BACKEND"]
    _emit("fast_sync_1000_vals_blocks_per_sec", n_blocks / dev, "blocks/s",
          host / dev)
    bench_fast_sync_pipeline()


def build_sync_chain(n_vals: int, n_blocks: int, chain_id: str,
                     seed: int = 7):
    """A source chain for fast-sync replays: ``n_blocks + 1`` real blocks
    (every block needs a successor carrying its seen commit) over an
    ``n_vals`` validator set, each applied through BlockExecutor against a
    kvstore app. Returns ``(genesis, blocks, marks)`` with
    ``marks[h] = (app_hash, last_block_id)`` of the source state after
    height ``h``. Setup only: our own fresh signatures are known-valid, so
    apply_block's LastCommit re-check runs against precomputed verdicts
    instead of a per-block device dispatch."""
    from tendermint_tpu.crypto.batch import precomputed_verdicts
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.basic import BlockID, BlockIDFlag, SignedMsgType
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.canonical import vote_sign_bytes_batch

    vs, keys = _mk_val_set(n_vals, seed)
    genesis = GenesisDoc(
        chain_id=chain_id, genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(v.pub_key, v.voting_power)
                    for v in vs.validators])

    build_verdicts: dict = {}  # (pk, sb, sig) -> True, for setup-time skip

    def sign_seen_commit(state, block, bid):
        ts = block.header.time_ns + 1
        sbs = vote_sign_bytes_batch(
            chain_id, SignedMsgType.PRECOMMIT, block.header.height, 0,
            [bid] * n_vals, [ts] * n_vals)
        sigs = []
        for v, sb in zip(state.validators.validators, sbs):
            sig = keys[v.address].sign(sb)
            sigs.append(CommitSig(BlockIDFlag.COMMIT, v.address, ts, sig))
            build_verdicts[(v.pub_key.bytes(), sb, sig)] = True
        return Commit(block.header.height, 0, bid, sigs)

    state, execu, _bs, conns = _sync_fresh_node(genesis)
    blocks, marks = [], {}
    last_commit = Commit(0, 0, BlockID(), [])
    token = precomputed_verdicts.set(build_verdicts)
    try:
        for h in range(1, n_blocks + 2):
            proposer = state.validators.get_proposer().address
            block, parts = state.make_block(h, [f"h{h}=v".encode()],
                                            last_commit, [], proposer)
            bid = BlockID(block.hash(), parts.header())
            blocks.append(block)
            state, _ = execu.apply_block(state, bid, block)
            marks[h] = (state.app_hash, state.last_block_id)
            last_commit = sign_seen_commit(state, block, bid)
    finally:
        precomputed_verdicts.reset(token)
        conns.stop()
    return genesis, blocks, marks


def _sync_fresh_node(genesis):
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.proxy import AppConns, local_client_creator
    from tendermint_tpu.state import BlockExecutor, StateStore, state_from_genesis
    from tendermint_tpu.state.execution import EmptyEvidencePool, NoOpMempool
    from tendermint_tpu.store import BlockStore

    app = KVStoreApplication()
    conns = AppConns(local_client_creator(app))
    conns.start()
    state = state_from_genesis(genesis)
    state_store = StateStore(MemDB())
    state_store.save(state)
    block_store = BlockStore(MemDB())
    execu = BlockExecutor(state_store, conns.consensus, NoOpMempool(),
                          EmptyEvidencePool(), block_store)
    return state, execu, block_store, conns


def replay_sync_chain(genesis, blocks, n: int):
    """A fresh node (kvstore ABCI over the local client, StateStore,
    BlockStore) fast-syncs the first ``n`` blocks of a
    :func:`build_sync_chain` chain through the real BlockchainReactor
    window loop and BlockExecutor.apply_block. Returns the reactor."""
    import asyncio

    from tendermint_tpu.blockchain import BlockchainReactor, BlockPool

    state, execu, block_store, conns = _sync_fresh_node(genesis)
    try:
        for b in blocks:  # fresh node: none of the per-instance memos a
            # previous replay populated (sign-bytes, part sets, header
            # hashes) may leak into this pass — the host baseline must
            # pay the same hashing work the timed run paid
            b.last_commit.__dict__.pop("_sb_cache", None)
            b.__dict__.pop("_part_set_cache", None)
            b.header.__dict__.pop("_hash_memo", None)
        reactor = BlockchainReactor(state, execu, block_store,
                                    fast_sync=True)
        reactor.pool = BlockPool(1)
        reactor.pool.set_peer_range("src", 1, n + 1)

        async def drive():
            # keep TWO full verify windows downloaded before each
            # process call: the apply pipeline prepares window N+1 on a
            # worker thread while window N applies, and needs N+1's
            # blocks present at spawn time (n is a multiple of the
            # reactor's VERIFY_WINDOW=16, so no ragged tail window)
            while reactor.blocks_synced < n:
                want = min(33, n + 2 - reactor.pool.height)
                while len(reactor.pool.peek_window(33)) < want:
                    reqs = reactor.pool.schedule_requests()
                    if not reqs:
                        break
                    for pid, h in reqs:
                        reactor.pool.add_block(pid, blocks[h - 1])
                before = reactor.blocks_synced
                await reactor._process_window()
                assert reactor.blocks_synced > before, \
                    f"sync stalled at {before}"
            assert reactor.state.last_block_height >= n

        asyncio.run(drive())
        assert block_store.height() >= n
        return reactor
    finally:
        conns.stop()


def bench_fast_sync_pipeline():
    """Config #5 (pipeline): END-TO-END fast-sync replay — real blocks
    through the real BlockchainReactor window loop (verify both signature
    planes in one batched device scope) + BlockExecutor.ApplyBlock (kvstore
    ABCI app, local client) + BlockStore/StateStore writes. 256 blocks @
    1000 validators, measured as a fresh node syncing the chain; the host
    baseline replays a 64-block prefix through the identical loop with the
    scalar backend. Reference blockchain/v0/reactor.go:255 + BASELINE.md
    config #5."""
    n_blocks = 256
    genesis, blocks, _marks = build_sync_chain(1000, n_blocks,
                                               "bench-sync-pipe")

    def replay(n):
        return replay_sync_chain(genesis, blocks, n)

    replay(32)  # warm: compile shapes, device pk cache
    t0 = time.perf_counter()
    reactor = replay(n_blocks)
    dev = time.perf_counter() - t0
    os.environ["TMTPU_BATCH_BACKEND"] = "host"
    try:
        t0 = time.perf_counter()
        replay(64)
        host_rate = 64 / (time.perf_counter() - t0)
    finally:
        del os.environ["TMTPU_BATCH_BACKEND"]
    rate = n_blocks / dev
    st = reactor.stage_breakdown()  # derived from BlocksyncMetrics histograms
    assert st["pipelined_windows"] > 0, \
        "apply pipeline never engaged: every window was prepared inline"
    # hash+store share of end-to-end pipeline wall-clock: the two apply-plane
    # costs this round attacked directly (iterative merkle + hash
    # memoization; per-window write batches). verify_s runs on the worker
    # thread overlapped with apply, so stage shares can sum past 1.0.
    _emit("fast_sync_pipeline_breakdown_hash_store_share",
          (st["hash_s"] + st["store_s"]) / dev, "ratio", 0.0,
          hash_seconds=round(st["hash_s"], 3),
          store_seconds=round(st["store_s"], 3),
          verify_seconds=round(st["verify_s"], 3),
          abci_seconds=round(st["abci_s"], 3),
          wall_seconds=round(dev, 3),
          pipelined_windows=st["pipelined_windows"],
          inline_windows=st["inline_windows"])
    _emit("fast_sync_1000_vals_pipeline_blocks_per_sec", rate, "blocks/s",
          rate / host_rate)


#: round 5's localnet p50 commit latency (CPU-pinned nodes on that round's
#: host; its record file is gone) — the anchor the live-plane work
#: (event-driven gossip + WAL group commit) was measured against
R05_LOCALNET_P50_S = 1.121


def _prom_sum(text: str, name: str) -> float:
    """Sum a Prometheus series across its label sets (text exposition)."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        rest = line[len(name):]
        if rest[:1] not in ("{", " "):
            continue  # e.g. foo_sum when asked for foo
        try:
            total += float(line.rsplit(" ", 1)[1])
        except ValueError:
            pass
    return total


def _prom_by_label(text: str, name: str, label: str) -> dict:
    """{label value: series value} for one labeled series (exposition
    text), e.g. per-stage sums of the consensus stage histogram."""
    out = {}
    needle = f'{label}="'
    for line in text.splitlines():
        if not line.startswith(name + "{"):
            continue
        rest = line[len(name):]
        i = rest.find(needle)
        if i < 0:
            continue
        val = rest[i + len(needle):]
        val = val[:val.index('"')]
        try:
            out[val] = out.get(val, 0.0) + float(line.rsplit(" ", 1)[1])
        except ValueError:
            pass
    return out


def _tools_mod(name: str):
    """Import a stdlib-only module out of tools/ (trace_summary,
    fleet_scrape, trace_merge) without making tools a package."""
    from tendermint_tpu.libs.toolbox import load_tool

    return load_tool(name)


def bench_localnet():
    """Config #4: 4-node localnet over TCP (kvstore app), consensus reactor
    end-to-end. Measures blocks/min across the net and broadcast_tx_commit
    latency, plus the live-plane breakdown (gossip wakeups vs polls,
    encode-cache hit rate, WAL records-per-fsync) scraped from /metrics and
    a per-height span breakdown from the nodes' shutdown traces. Baseline
    anchors: reference 200-node QA testnet 19.5 blocks/min
    (docs/qa/v034/README.md:141-142); p50 latency vs round 5's 1.121 s."""
    import shutil
    import signal
    import subprocess
    import sys
    import tempfile
    import urllib.request

    root = tempfile.mkdtemp(prefix="bench-localnet-")
    port0 = 28656

    def rpc(port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/{path}", timeout=10) as r:
            return json.loads(r.read())

    procs = []
    per_height = None
    fleet = None
    try:
        # the nodes of a multi-process localnet (init included) are
        # CPU-pinned: a chip has one owner, and this bench process is it
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # each node runs under the span tracer and writes a Chrome trace on
        # graceful shutdown — the per-height live-plane attribution input
        env["TMTPU_TRACE_OUT"] = os.path.join(root, "trace")
        # a watchdog debugdump during the run snapshots the fleet rollup
        env["TMTPU_FLEET_JSON"] = os.path.join(root, "fleet.json")
        subprocess.run(
            ["python", "-m", "tendermint_tpu.cmd", "testnet", "--v", "4",
             "--output-dir", root, "--chain-id", "bench-e2e",
             "--starting-port", str(port0), "--prometheus"],
            check=True, capture_output=True, timeout=120, env=env)
        for i in range(4):
            procs.append(subprocess.Popen(
                ["python", "-m", "tendermint_tpu.cmd", "--home",
                 f"{root}/node{i}", "start", "--log-level", "error"],
                env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        # wait for liveness
        deadline = time.time() + 120
        h0 = None
        while time.time() < deadline:
            try:
                h0 = int(rpc(port0 + 1, "status")
                         ["result"]["sync_info"]["latest_block_height"])
                if h0 >= 2:
                    break
            except Exception:
                pass
            time.sleep(1.0)
        assert h0 is not None and h0 >= 2, "localnet failed to start"

        # fleet metrics aggregator (tools/fleet_scrape.py): poll all four
        # nodes' /metrics during the measurement window so the reported
        # numbers are cluster truth, not node-0's view
        try:
            fs = _tools_mod("fleet_scrape")
            fleet = fs.FleetScraper(
                {f"node{i}": f"http://127.0.0.1:{port0 + 8 + i}/metrics"
                 for i in range(4)},
                interval_s=2.0,
                out_path=os.path.join(root, "fleet.json")).start()
        except Exception:
            fleet = None

        # measure block rate over a fixed window + tx commit latency
        t0 = time.time()
        start_h = int(rpc(port0 + 1, "status")
                      ["result"]["sync_info"]["latest_block_height"])
        tx_lat = []
        n_txs = 5
        for i in range(n_txs):
            body = json.dumps({
                "jsonrpc": "2.0", "id": 1, "method": "broadcast_tx_commit",
                "params": {"tx": __import__("base64").b64encode(
                    f"bench{i}=v{i}".encode()).decode()}}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port0 + 1}/", data=body,
                headers={"Content-Type": "application/json"})
            t1 = time.time()
            with urllib.request.urlopen(req, timeout=30) as r:
                resp = json.loads(r.read())
            tx_lat.append(time.time() - t1)
            assert resp["result"]["deliver_tx"].get("code", 0) == 0
        elapsed = time.time() - t0
        end_h = int(rpc(port0 + 1, "status")
                    ["result"]["sync_info"]["latest_block_height"])
        blocks_per_min = (end_h - start_h) / elapsed * 60.0
        p50 = float(np.median(tx_lat))
        _emit("localnet_4node_tx_commit_latency_p50", p50, "s",
              R05_LOCALNET_P50_S / p50, r05_p50_s=R05_LOCALNET_P50_S)
        _emit("localnet_4node_blocks_per_min", blocks_per_min, "blocks/min",
              blocks_per_min / 19.5)

        # live-plane breakdown from the RPC node's (node0's) /metrics —
        # testnet --prometheus serves node i on starting_port+2v+i (past the
        # p2p/rpc port block), and every rpc()/tx call above hit node0 (rpc
        # port port0+1)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port0 + 8}/metrics", timeout=10) as r:
                mtext = r.read().decode()
            pre = "tendermint_consensus_"
            wakeups = _prom_sum(mtext, pre + "gossip_wakeups_total")
            polls = _prom_sum(mtext, pre + "gossip_polls_total")
            ehits = _prom_sum(mtext, pre + "encode_cache_hits_total")
            emiss = _prom_sum(mtext, pre + "encode_cache_misses_total")
            fsyncs = _prom_sum(mtext, pre + "wal_fsyncs_total")
            rec_sum = _prom_sum(mtext, pre + "wal_records_per_fsync_sum")
            rec_cnt = _prom_sum(mtext, pre + "wal_records_per_fsync_count")
            fsync_s = _prom_sum(mtext, pre + "wal_fsync_seconds_sum")
            _emit("localnet_4node_live_plane_breakdown",
                  wakeups / max(1.0, wakeups + polls), "ratio", 0.0,
                  gossip_wakeups=int(wakeups), gossip_polls=int(polls),
                  encode_cache_hits=int(ehits),
                  encode_cache_misses=int(emiss),
                  encode_cache_hit_ratio=round(
                      ehits / max(1.0, ehits + emiss), 3),
                  wal_fsyncs=int(fsyncs),
                  wal_records_per_fsync_avg=round(
                      rec_sum / max(1.0, rec_cnt), 2),
                  wal_fsync_seconds_total=round(fsync_s, 4))
            # per-stage consensus latency decomposition from the stage
            # timeline histograms (consensus/timeline.py): mean seconds per
            # stage interval at this node — the bench row the ROADMAP scale
            # items will attribute regressions through
            s_sum = _prom_by_label(mtext, pre + "stage_seconds_sum", "stage")
            s_cnt = _prom_by_label(mtext, pre + "stage_seconds_count",
                                   "stage")
            stage_mean_ms = {
                s: round(s_sum[s] / s_cnt[s] * 1000.0, 3)
                for s in sorted(s_sum) if s_cnt.get(s)}
            if stage_mean_ms:
                _emit("localnet_4node_stage_breakdown",
                      sum(stage_mean_ms.values()) / 1000.0, "s", 0.0,
                      stage_mean_ms=stage_mean_ms,
                      heights_observed=int(max(s_cnt.values())))
        except Exception as e:
            _emit("localnet_4node_live_plane_breakdown", 0.0, "error", 0.0,
                  error=f"{type(e).__name__}: {e}")

        # cluster rollup: blocks/min as the CLUSTER saw it (max committed
        # height across nodes), gossip wakeups per peer link, and the
        # cross-node spread of committed heights at the last scrape
        if fleet is not None:
            try:
                roll = fleet.stop()
                fleet = None
                hs = roll["series"].get(
                    "tendermint_consensus_committed_height", {})
                _emit("localnet_4node_cluster_rollup",
                      roll.get("cluster_blocks_per_min", 0.0), "blocks/min",
                      roll.get("cluster_blocks_per_min", 0.0) / 19.5,
                      n_nodes=roll["n_nodes"],
                      scrapes=roll["scrapes"],
                      scrape_errors=roll["scrape_errors"],
                      height_min=hs.get("min"), height_max=hs.get("max"),
                      wakeups_per_peer_link=roll.get(
                          "wakeups_per_peer_link", 0.0))
            except Exception as e:
                _emit("localnet_4node_cluster_rollup", 0.0, "error", 0.0,
                      error=f"{type(e).__name__}: {e}")
    finally:
        if fleet is not None:  # a failed run must not leak the scraper
            try:
                fleet.stop()
            except Exception:
                pass
        for p in procs:
            try:
                p.send_signal(signal.SIGTERM)
            except Exception:
                pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        # per-height live-plane attribution from the nodes' shutdown traces
        # (gossip wait vs WAL sync vs apply vs consensus stage_* spans per
        # height) — best-effort
        skew = None
        trace_paths = []
        try:
            trace_summary = _tools_mod("trace_summary")
            by_height = trace_summary.by_height
            load_events = trace_summary.load_events
            merged = {}
            trace_paths = [os.path.join(root, name)
                           for name in sorted(os.listdir(root))
                           if name.startswith("trace-")
                           and name.endswith(".json")]
            for path in trace_paths:
                for h, per in by_height(load_events(path)).items():
                    tgt = merged.setdefault(h, {})
                    for span, us in per.items():
                        tgt[span] = tgt.get(span, 0.0) + us
            if merged:
                spans = sorted({s for per in merged.values() for s in per})
                n_h = len(merged)
                mean_ms = {s: round(sum(per.get(s, 0.0)
                                        for per in merged.values())
                                    / n_h / 1000.0, 3) for s in spans}
                per_height = {"n_heights": n_h, "mean_ms_per_height": mean_ms}
        except Exception:
            per_height = None
        # cross-node correlation: merge the four traces onto one wall
        # clock (tools/trace_merge.py) and report the commit skew —
        # first-to-last commit spread per height across nodes. Own
        # try/except: a torn trace from a SIGKILLed node must not wipe
        # the per-height breakdown computed above.
        try:
            if len(trace_paths) >= 2:
                tm = _tools_mod("trace_merge")
                docs = []
                for p in trace_paths:
                    doc = tm.load_trace(p)
                    docs.append((tm.node_label(doc, p), doc))
                report = tm.skew_report(docs)
                if report["heights"]:
                    skew = {"heights": report["heights"],
                            "mean_spread_ms": report["mean_spread_ms"],
                            "max_spread_ms": report["max_spread_ms"],
                            "slowest_stage_per_node": {
                                n: s["slowest_stage"] for n, s in
                                report["slowest_stage_per_node"].items()}}
        except Exception:
            skew = None
        shutil.rmtree(root, ignore_errors=True)
    if per_height is not None:
        _emit("localnet_4node_per_height_breakdown",
              per_height["mean_ms_per_height"].get("gossip_idle", 0.0),
              "ms/height", 0.0, **per_height)
    if skew is not None:
        _emit("localnet_4node_commit_skew", skew["mean_spread_ms"],
              "ms/height", 0.0, **skew)


def bench_ingest():
    """Config ingest: open-loop broadcast_tx load against the 4-node
    localnet (tools/loadtime.py) — the ROADMAP ingestion plane's gate.
    Send times are pre-planned on a fixed-rate grid (coordinated omission
    cannot hide stalls); per-tx latency is recovered from committed blocks
    via the embedded planned-send timestamp, cross-checked against the
    nodes' own /tx_timeline lifecycle records; mempool/RPC ingestion
    series ride along from node0's /metrics. Emits three gated rows:
    localnet_4node_ingest_txs_per_sec (higher-better),
    localnet_4node_ingest_commit_latency_p99_s (lower-better), and
    localnet_4node_ingest_checktx_p99_s (lower-better admission latency,
    rpc_received→mempool_admitted measured in-node by txlife)."""
    import asyncio
    import shutil
    import signal
    import subprocess
    import tempfile
    import urllib.request

    root = tempfile.mkdtemp(prefix="bench-ingest-")
    port0 = 28856  # clear of config 4's 28656 block when running "all"
    # 150 tx/s: 6x the PR 11 smoke rate — a load the pre-lane scalar
    # admission path was never shown to sustain; the sharded-lane +
    # async-admission fast path must hold it with p99 commit latency no
    # worse (both rows gated in bench_compare, plus admission p99 below)
    rate, duration, size, clients = 150.0, 12.0, 96, 8
    endpoint = f"http://127.0.0.1:{port0 + 1}"
    metrics_endpoint = f"http://127.0.0.1:{port0 + 8}/metrics"

    def rpc(port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/{path}", timeout=10) as r:
            return json.loads(r.read())

    def emit_error(err: str) -> None:
        # the crashed-config unit convention: both gated rows must read
        # as ERRORED in bench_compare, never as silent absence
        for metric in ("localnet_4node_ingest_txs_per_sec",
                       "localnet_4node_ingest_commit_latency_p99_s",
                       "localnet_4node_ingest_checktx_p99_s"):
            _emit(metric, 0.0, "error", 0.0, error=err)

    procs = []
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        subprocess.run(
            ["python", "-m", "tendermint_tpu.cmd", "testnet", "--v", "4",
             "--output-dir", root, "--chain-id", "bench-ingest",
             "--starting-port", str(port0), "--prometheus"],
            check=True, capture_output=True, timeout=120, env=env)
        for i in range(4):
            procs.append(subprocess.Popen(
                ["python", "-m", "tendermint_tpu.cmd", "--home",
                 f"{root}/node{i}", "start", "--log-level", "error"],
                env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        deadline = time.time() + 120
        h0 = None
        while time.time() < deadline:
            try:
                h0 = int(rpc(port0 + 1, "status")
                         ["result"]["sync_info"]["latest_block_height"])
                if h0 >= 2:
                    break
            except Exception:
                pass
            time.sleep(1.0)
        assert h0 is not None and h0 >= 2, "localnet failed to start"

        lt = _tools_mod("loadtime")
        load_stats = asyncio.run(lt.open_loop_load(
            endpoint, rate=rate, duration=duration, size=size,
            clients=clients))
        # settle: let the tail of the offered load commit before reading
        # the chain back (bounded — a wedged net must not hang the bench)
        settle_deadline = time.time() + 30
        while time.time() < settle_deadline:
            try:
                pending = int(rpc(port0 + 1, "num_unconfirmed_txs")
                              ["result"]["n_txs"])
                if pending == 0:
                    break
            except Exception:
                pass
            time.sleep(1.0)
        doc = lt.report_doc(endpoint, metrics_endpoint=metrics_endpoint)
        if not doc.get("txs"):
            emit_error("no harness txs found in committed blocks")
            return
        tlr = doc.get("tx_timeline", {})
        mtx = doc.get("metrics", {})
        _emit("localnet_4node_ingest_txs_per_sec", doc["txs_per_sec"],
              "txs/s", doc["txs_per_sec"] / rate,
              offered_rate=rate, duration_s=duration, clients=clients,
              planned=load_stats["planned"],
              accepted=load_stats["accepted"],
              rejected=load_stats["rejected"],
              send_errors=load_stats["errors"],
              committed=doc["txs"],
              max_sched_lag_s=round(load_stats["max_sched_lag_s"], 4),
              mempool_admitted=mtx.get(
                  "tendermint_mempool_admitted_txs_total"),
              rpc_broadcast_ok=mtx.get(
                  'tendermint_rpc_request_seconds_count'
                  '{endpoint="broadcast_tx_sync",outcome="ok"}'))
        # the acceptance probe: at least one sampled tx's timeline record
        # must carry the full rpc_received → committed stage chain
        _emit("localnet_4node_ingest_commit_latency_p99_s",
              doc["latency_s"]["p99"], "s", 0.0,
              latency_s=doc["latency_s"],
              node_commit_latency_s=tlr.get("node_commit_latency_s"),
              timeline_complete_records=tlr.get(
                  "complete_rpc_to_commit_records"),
              timeline_stage_counts=tlr.get("stage_counts"),
              timeline_sampled_sealed=tlr.get("sealed_total"))
        # admission latency (rpc_received → mempool_admitted measured IN
        # node0 by txlife): the async admission path's own cost, gated
        # lower-better so intake-queue/batching regressions trip loudly
        adm = tlr.get("admission_latency_s") or {}
        if "p99" not in adm:
            _emit("localnet_4node_ingest_checktx_p99_s", 0.0, "error", 0.0,
                  error="no timeline records carried "
                        "rpc_received+mempool_admitted marks")
        else:
            _emit("localnet_4node_ingest_checktx_p99_s", adm["p99"],
                  "s", 0.0, admission_latency_s=adm,
                  rejections=doc.get("rejections"))
    except Exception as e:
        emit_error(f"{type(e).__name__}: {e}")
    finally:
        for p in procs:
            try:
                p.send_signal(signal.SIGTERM)
            except Exception:
                pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        shutil.rmtree(root, ignore_errors=True)


def bench_churn():
    """Config churn: the membership-churn plane, measured (tools/churn.py
    in-proc rig — no subprocess fleet, so it runs in slim containers).

    Gated rows, from a seeded N=8 run (one statesync join + one clean
    leave per interval under open-loop load, the validator set rotating
    across app-driven prune boundaries):
    * inproc_churn8_blocks_per_min   — liveness under churn (higher better)
    * inproc_churn8_join_caughtup_s  — worst join-to-caught-up (lower
      better): launch → snapshot restore over the wire → fast-sync →
      caught up to the net's height at entry

    Informational scaling row: gossip wakeups per directed peer-link per
    block on static SPARSE fleets at N=8/16/32 — per-link wakeups staying
    flat as the fleet quadruples is the evidence that the wire-encode
    cache + event-driven gossip keep cost sublinear in peer count (each
    node pays for its degree, not the fleet)."""
    churn = _tools_mod("churn")

    try:
        rep = churn.run_churn(n_nodes=8, intervals=2, seed=1)
        joins = rep["join_caughtup_s"]
        _emit("inproc_churn8_blocks_per_min", rep["blocks_per_min"],
              "blocks/min", rep["blocks_per_min"] / 19.5,
              height_span=[rep["height_initial"], rep["height_final"]],
              rotations=rep["rotations"],
              executed=[list(e) for e in rep["executed"]],
              topology=rep["topology"])
        _emit("inproc_churn8_join_caughtup_s",
              max(joins.values()), "s", 0.0, per_join=joins,
              prune_floor=rep["prune_floor"])
    except Exception as e:
        err = f"{type(e).__name__}: {e}"
        _emit("inproc_churn8_blocks_per_min", 0.0, "error", 0.0, error=err)
        _emit("inproc_churn8_join_caughtup_s", 0.0, "error", 0.0, error=err)

    try:
        cells = {}
        for n in (8, 16, 32):
            cells[str(n)] = churn.measure_gossip(n=n, blocks=3,
                                                 topology="sparse",
                                                 degree=4, seed=1)
        w8 = cells["8"]["wakeups_per_link_per_s"]
        w32 = cells["32"]["wakeups_per_link_per_s"]
        # sublinear: the per-link wakeup RATE may wobble but must not
        # scale with the 4x fleet growth (2x headroom for scheduler
        # noise); per-BLOCK numbers are in the cells for context but
        # don't gate — block cadence itself slows with N
        _emit("inproc_churn_gossip_scaling_breakdown",
              w32 / max(0.001, w8), "ratio", 0.0,
              cells=cells, sublinear=bool(w32 <= 2.0 * max(0.001, w8)))
    except Exception as e:
        _emit("inproc_churn_gossip_scaling_breakdown", 0.0, "error", 0.0,
              error=f"{type(e).__name__}: {e}")


def bench_crash():
    """Config crash: crash recovery, measured (tools/crashmatrix.py in-proc
    rig — no subprocess fleet, so it runs in slim containers).

    Gated row, from a seeded 4-validator run where the persistent victim
    is SIGKILL-equivalently killed at two representative durability
    boundaries (post-WAL-fsync and mid-window-flush) and supervisor-
    restarted from its home dir (WAL repair-on-open + handshake replay +
    WAL catchup replay + FilePV reload + consensus catchup):

    * inproc_crash4_kill_caughtup_s — WORST kill→caught-up seconds (lower
      better): arm boundary → victim dies at it → bounded backoff →
      rebuild → height >= the net's tip. The recovery-time budget the
      ROADMAP's real-fleet milestones inherit.

    The full boundary matrix (10 boundaries, double-sign/evidence/
    mempool-WAL invariants, --verify-determinism) runs as the crashmatrix
    tool + the slow test tier; the bench keeps the fast, gateable core."""
    cm = _tools_mod("crashmatrix")

    try:
        rep = cm.run_matrix(seed=1, boundaries=["wal.after_fsync",
                                                "db.mid_window_flush"])
        per = {k["boundary"]: k["kill_to_caughtup_s"] for k in rep["kills"]}
        _emit("inproc_crash4_kill_caughtup_s",
              max(per.values()), "s", 0.0, per_boundary=per,
              restarts=sum(k["restarts"] for k in rep["kills"]),
              wal_repaired=[k["boundary"] for k in rep["kills"]
                            if k.get("wal_repaired")],
              mempool_wal_idempotent=rep["mempool_wal_idempotent"],
              boundaries_killed=rep["boundaries_killed"])
    except Exception as e:
        # the crashed-config unit convention: the gated row must read
        # "errored", never silently vanish
        _emit("inproc_crash4_kill_caughtup_s", 0.0, "error", 0.0,
              error=f"{type(e).__name__}: {e}")


def bench_verify_commit_10k():
    """FLAGSHIP (north star): VerifyCommit at 10,240 validators — the scale
    BASELINE.json names (≥15x target vs the host scalar loop, reference
    types/validator_set.go:667, docs/qa/v034). Two numbers:

    * sustained: a fast-sync-shaped stream of full commits in ONE
      batch_verify_stream call — internally segmented into ~10-chunk
      dispatches double-buffered on a worker thread, so segment i+1's host
      packing and host->device transfer overlap segment i's device compute
      (one thread's dispatches run back to back, a second thread's
      dispatch overlaps an in-flight one; the gain on a locally attached
      chip: not measured);
    * one-shot: a single cold commit in one call, paying full dispatch
      latency.

    Also prints a stage breakdown (pack / device+transfer) so regressions
    are attributable.
    """
    from tendermint_tpu import crypto
    from tendermint_tpu.crypto.ed25519_jax import verify as V

    n_vals, n_commits, window = 10240, 12, 12
    repeats = 5
    vs, keys = _mk_val_set(n_vals)
    chain = "bench-10k"
    pks_row = [v.pub_key.bytes() for v in vs.validators]

    def build_slice(base_h):
        """A fresh n_commits batch signed at disjoint heights: every repeat
        gets distinct sign-bytes AND signatures, so no cache of identical
        computations anywhere in the path can serve a previous repeat's
        run and inflate the min-of-N."""
        per_commit = []
        for h in range(base_h, base_h + n_commits):
            c = _sign_commit(vs, keys, h, chain)[0]
            per_commit.append((pks_row, c.vote_sign_bytes_all(chain),
                               [cs.signature for cs in c.signatures]))
        return per_commit

    def verify_window(cs):
        pks = [p for c in cs for p in c[0]]
        msgs = [m for c in cs for m in c[1]]
        sigs = [s for c in cs for s in c[2]]
        out = V.batch_verify_stream(pks, msgs, sigs, chunk=CHUNK)
        assert out.all()

    def sustained(per_commit):
        for i in range(0, n_commits, window):
            verify_window(per_commit[i:i + window])

    from tendermint_tpu.crypto import phases

    warm_pc = build_slice(1)
    sustained(warm_pc)  # compile + warm the pk device cache
    # min-of-5 with FRESH inputs per repeat: a repeat must never be a
    # no-op served from a cache; per-repeat values land in the JSON for
    # auditability
    repeat_times, repeat_marks = [], []
    for rep in range(repeats):
        pc = build_slice(1000 + rep * n_commits)  # untimed setup
        t0 = time.perf_counter()
        sustained(pc)
        t1 = time.perf_counter()
        repeat_times.append(t1 - t0)
        repeat_marks.append((t0, t1))
        del pc
    best_i = int(np.argmin(repeat_times))
    best = repeat_times[best_i]
    total_sigs = n_commits * n_vals
    dev_rate = total_sigs / best

    # host scalar baseline on a subset
    pubs = [crypto.Ed25519PubKey(p) for p in warm_pc[0][0][:N_BASE]]
    host_rate = _host_rate(pubs, warm_pc[0][1], warm_pc[0][2], N_BASE)

    # stage breakdown from the dispatcher's OWN phase telemetry
    # (crypto/phases.py): the per-segment pack/dispatch/fetch stamps
    # recorded during the best timed repeat, decomposed by interval union —
    # no more hand-placed perf_counter pair re-packing outside the run
    w0, w1 = repeat_marks[best_i]
    recs = [r for r in phases.recent_segments()
            if r["t0"] >= w0 and r["t_end"] <= w1 + 1e-6]
    bd = phases.phase_breakdown(recs, w0, w1) if recs else None

    # one-shot: single cold commit, one call — three DISTINCT commits so
    # no cache can serve run 2 and 3 from run 1
    oneshot_pc = build_slice(5000)[:3]
    one = min(_timed(lambda c=c: verify_window([c]), warm=0, runs=1)
              for c in oneshot_pc)
    _emit("verify_commit_10k_oneshot_sigs_per_sec", n_vals / one, "sigs/s",
          (n_vals / one) / host_rate)
    if bd is not None:
        # gated lower-is-better by tools/bench_compare.py (a 7% -> 11.1%
        # packing creep once ran ungated): total pack seconds across
        # all pipeline threads over the best repeat's wall
        _emit("verify_commit_10k_breakdown_pack_share",
              bd["pack_share_total"], "ratio", 0.0,
              pack_seconds=round(bd["pack_s"], 3),
              total_seconds=round(best, 3),
              segments=bd["segments"], source="phase_telemetry")
        # per-phase wall decomposition: exposed pack + exposed dispatch +
        # device-in-flight union tile the wall, so their sum (the accounted
        # share) must come within 10% of end-to-end wall time — the
        # telemetry indicting itself if a phase goes missing
        acc = bd["accounted_share"]
        # an accounting shortfall (>10% of wall unattributed) means a
        # dispatch phase is going unrecorded — flag it with the crashed-
        # config unit convention so bench_compare surfaces it, but never
        # abort the run over an environment-dependent accounting gap
        _emit("verify_commit_10k_phase_shares", acc,
              "ratio" if acc >= 0.90 else "error", 0.0,
              pack_share=round(bd["pack_share_exposed"], 3),
              dispatch_share=round(bd["dispatch_share_exposed"], 3),
              device_share=round(bd["device_share"], 3),
              pack_share_total=round(bd["pack_share_total"], 3),
              overlap_ratio=round(bd["overlap_ratio"], 3),
              fetch_wait_seconds=round(bd["wait_s"], 3),
              segments=bd["segments"],
              accounted_within_10pct=bool(acc >= 0.90))
    else:
        _emit("verify_commit_10k_breakdown_pack_share", 0.0, "error", 0.0,
              error="no phase records captured during the timed repeats")
    # multichip flagship: the same windows through the multi-device
    # dispatcher (which the routed flagship above already rides when >1
    # device is visible), plus a FORCED single-device reference repeat so
    # the in-JSON speedup is attributable. Target: >3x the single-device
    # flagship (single-device rate on today's code: not measured).
    from tendermint_tpu.crypto.ed25519_jax import multidevice as MD

    md = MD.pool()
    if md is not None and len(md.eligible_lanes()) >= 2:
        # min-of-2 like-for-like: a single noisy reference pass must not
        # inflate the multichip speedup ratio
        single_times = []
        with MD.disabled():
            for rep in range(2):
                pc = build_slice(20000 + rep * n_commits)
                t0 = time.perf_counter()
                sustained(pc)
                single_times.append(time.perf_counter() - t0)
                del pc
        single_rate = total_sigs / min(single_times)
        md_times = []
        for rep in range(repeats):
            pc = build_slice(30000 + rep * n_commits)
            t0 = time.perf_counter()
            sustained(pc)
            md_times.append(time.perf_counter() - t0)
            del pc
        md_rate = total_sigs / min(md_times)
        _emit("verify_commit_10k_multichip_sigs_per_sec", md_rate,
              "sigs/s", md_rate / host_rate,
              devices=len(md.eligible_lanes()),
              seg_chunks=md.seg_chunks,
              vs_single_device=round(md_rate / single_rate, 3),
              single_device_sigs_per_sec=round(single_rate, 1),
              target="3x the single-device flagship",
              per_repeat_sigs_per_sec=[round(total_sigs / t, 1)
                                       for t in md_times])
    else:
        # the crashed-config unit convention: a vanished pool must read
        # as ERRORED in bench_compare, never as silent absence
        n_lanes = 0 if md is None else len(md.eligible_lanes())
        _emit("verify_commit_10k_multichip_sigs_per_sec", 0.0, "error",
              0.0, error=f"multi-device pool unavailable "
                         f"({n_lanes} healthy lanes); see "
                         f"TMTPU_VERIFY_DEVICES in README")
    _emit("verify_commit_10k_sigs_per_sec", dev_rate, "sigs/s",
          dev_rate / host_rate,
          per_repeat_seconds=[round(t, 3) for t in repeat_times],
          per_repeat_sigs_per_sec=[round(total_sigs / t, 1)
                                   for t in repeat_times])


def bench_multichip_scale():
    """Config multichip: the devices x chunk scaling table through
    ``tools/device_profile.py scale``, all three modes (sharded psum / raw
    threads x devices / the production MultiDeviceStream dispatcher).

    On a machine with a chip every cell runs in THIS process, over the
    device counts the machine has: the chip belongs to one process, and a
    bench that has emitted a row already holds it. On a CPU box the forced
    host mesh + shape-identical stub kernels exercise the dispatch
    topology, one CPU-pinned subprocess per device count, one after
    another; that run prints counts, never a rate. A cell that errors
    fails the config."""
    dp = _tools_mod("device_profile")
    workload = dp.resolve_workload("auto")
    host_mesh = workload == "synthetic"
    devices = [d for d in (1, 2, 4, 8)
               if host_mesh or d <= _device_fields()["device_count"]]
    # 40960 sigs: at 8 lanes every lane still gets >=2 segments, so the
    # per-lane double-buffering the dispatcher is built on is measured
    res = dp.run_scale(devices, chunks=[CHUNK], sigs=40960,
                       workload=workload, host_mesh=host_mesh, runs=2,
                       threads=None)
    if res.get("cell_errors"):
        raise RuntimeError(f"scaling cells failed: {res['cell_errors']}")
    md_rows = sorted((r for r in res["table"] if r["mode"] == "multidev"),
                     key=lambda r: r["devices"])
    if host_mesh:
        # stub kernels on a forced host mesh: which cells ran, not how fast
        _emit("verify_commit_10k_multichip_scaling", float(len(md_rows)),
              "rows", 0.0, workload=workload, host_mesh=True,
              cells=[{k: r[k] for k in ("devices", "mode", "chunk",
                                        "threads", "sigs")}
                     for r in res["table"]])
        return
    by_dev = {r["devices"]: r["sigs_per_sec"] for r in md_rows}
    mono = bool(by_dev) and all(
        by_dev[a] <= by_dev[b] * 1.05  # 5% noise allowance
        for a, b in zip(sorted(by_dev), sorted(by_dev)[1:]))
    _emit("verify_commit_10k_multichip_scaling", float(len(md_rows)),
          "rows", 0.0, workload=workload, host_mesh=False,
          monotone_through_max_devices=mono,
          multidev_sigs_per_sec_by_devices={str(d): by_dev[d]
                                            for d in sorted(by_dev)},
          table=res["table"])


def bench_exec():
    """Config exec: the parallel-execution plane, measured (tools/
    execbench.py in-proc rig — no subprocess fleet, so it runs in slim
    containers).

    Gated row, from a seeded 4-validator in-proc fleet under an open-loop
    firehose of large-value disjoint-key txs (the payload where block
    execution dominates block time and speculation has maximum
    parallelism):

    * inproc_exec4_committed_txs_per_sec — committed txs/sec with
      execution.version=v1 (higher better). The A/B payload carries the
      matching SERIAL (v0) rate and the speedup: on a multi-core host the
      serial run visibly saturates first; on a 1-core host the executor
      caps its workers and the two rates converge (n_cpus says which
      world the row came from). Both fleets must land on the same app
      hash — the byte-parity invariant observed end-to-end.

    Informational row: inproc_exec4_phase_breakdown — the exec-plane
    phase decomposition of the parallel run's measured window (the
    per-block plane="exec" segments: validate=pack, tx execution=
    in-flight, commit+persist=fetch), same interval-union accounting as
    the device-plane profiles."""
    eb = _tools_mod("execbench")

    try:
        rep = eb.run_exec_ab(seed=1)
        par, ser = rep["parallel"], rep["serial"]
        _emit("inproc_exec4_committed_txs_per_sec", par["txs_per_sec"],
              "txs/s", rep["speedup"],
              serial_txs_per_sec=round(ser["txs_per_sec"], 3),
              speedup=round(rep["speedup"], 3), n_cpus=rep["n_cpus"],
              n_txs=rep["n_txs"], value_size=rep["value_size"],
              groups=par["parallel"]["groups"],
              conflicted=par["parallel"]["conflicted"],
              heights=par["heights"], app_hash=par["app_hash"])
        bd = par["exec_phase"]
        _emit("inproc_exec4_phase_breakdown",
              bd.get("device_share", 0.0), "ratio", 0.0,
              parallel=bd, serial=ser["exec_phase"])
    except Exception as e:
        _emit("inproc_exec4_committed_txs_per_sec", 0.0, "error", 0.0,
              error=f"{type(e).__name__}: {e}")
        _emit("inproc_exec4_phase_breakdown", 0.0, "error", 0.0,
              error=f"{type(e).__name__}: {e}")


def _mk_ed25519_commit_local(n_vals: int, chain_id: str, height: int = 100):
    """Ed25519 validator set + fully-signed commit built with the package's
    own keys (the aggsig A/B must run on hosts without OpenSSL bindings)."""
    import hashlib

    from tendermint_tpu import crypto
    from tendermint_tpu.types import Validator, ValidatorSet
    from tendermint_tpu.types.basic import (
        BlockID,
        BlockIDFlag,
        PartSetHeader,
        SignedMsgType,
    )
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.canonical import vote_sign_bytes

    privs = [crypto.Ed25519PrivKey.generate(
        hashlib.sha256(f"aggsig-ed-{chain_id}-{i}".encode()).digest())
        for i in range(n_vals)]
    vs = ValidatorSet([Validator(p.pub_key().address(), p.pub_key(), 10)
                       for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    sigs = []
    for i, v in enumerate(vs.validators):
        ts = 1_700_000_000_000_000_000 + i
        msg = vote_sign_bytes(chain_id, SignedMsgType.PRECOMMIT, height, 0,
                              bid, ts)
        sigs.append(CommitSig(BlockIDFlag.COMMIT, v.address, ts,
                              by_addr[v.address].sign(msg)))
    return vs, Commit(height, 0, bid, sigs), bid


def _mk_bls_aggregated_commit(n_vals: int, chain_id: str, height: int = 100):
    """BLS validator set + one aggregated commit on a registered
    aggregate-commits chain: every validator signs the SAME zero-timestamp
    precommit payload; the signatures fold into one 48-byte G1 point."""
    import hashlib

    from tendermint_tpu import crypto
    from tendermint_tpu.crypto import bls12381 as bls
    from tendermint_tpu.crypto import schemes
    from tendermint_tpu.libs.bits import BitArray
    from tendermint_tpu.types import Validator, ValidatorSet
    from tendermint_tpu.types.basic import (
        BlockID,
        PartSetHeader,
        SignedMsgType,
    )
    from tendermint_tpu.types.block import AggregatedCommit
    from tendermint_tpu.types.canonical import vote_sign_bytes
    from tendermint_tpu.types.params import SignatureParams

    schemes.register_chain(chain_id, SignatureParams("bls12381", True))
    privs = [crypto.Bls12381PrivKey.generate(
        hashlib.sha256(f"aggsig-bls-{chain_id}-{i}".encode()).digest())
        for i in range(n_vals)]
    vs = ValidatorSet([Validator(p.pub_key().address(), p.pub_key(), 10)
                       for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    msg = vote_sign_bytes(chain_id, SignedMsgType.PRECOMMIT, height, 0,
                          bid, schemes.AGG_ZERO_TS_NS)
    agg = bls.aggregate([by_addr[v.address].sign(msg)
                         for v in vs.validators])
    signers = BitArray(n_vals)
    for i in range(n_vals):
        signers.set_index(i, True)
    commit = AggregatedCommit(height, 0, bid, [], signers=signers,
                              agg_sig=agg,
                              timestamp_ns=1_700_000_000_000_000_000)
    return vs, commit, bid


def bench_aggsig():
    """Config aggsig: commit verification A/B — ed25519 CommitSig lists
    through the batched verifier vs ONE BLS fast-aggregate-verify pairing —
    at 150 and 1000 validators, plus the informational commit-size row.
    Steady-state regime on both sides: the same commit re-verified (warm
    sign-bytes memo for ed25519, warm decompression/apk caches for BLS),
    which is what the consensus hot loop and light client replay pay per
    height once a validator set is live.  vs_baseline on the BLS rows is
    the A/B ratio against the ed25519-batched rate at the same scale.

    Two extensions ride along: the blocksync fast-sync replay A/B (a
    window of contiguous commits through verify_commit_light_batched per
    scheme — the stage-A dispatch path fast sync actually runs, closing
    the ROADMAP aggsig edge) and the BLS plane telemetry captured through
    a run-local DeviceMetrics (pairing wall time, aggregate-verify counts
    by mode, aggregated-commit wire-size observations)."""
    from tendermint_tpu.crypto import phases, schemes
    from tendermint_tpu.libs.metrics import DeviceMetrics, Registry
    from tendermint_tpu.types.validator_set import verify_commit_light_batched

    dm = DeviceMetrics(Registry("bench_aggsig"))
    prev_metrics = phases.metrics
    phases.set_device_metrics(dm)
    sizes = {}
    replay_window = 8
    try:
        for n_vals in (150, 1000):
            ed_chain = f"aggsig-ed-{n_vals}"
            vs_ed, commit_ed, bid_ed = _mk_ed25519_commit_local(
                n_vals, ed_chain)
            best_ed = _timed(lambda: vs_ed.verify_commit(
                ed_chain, bid_ed, 100, commit_ed), warm=2, runs=3)
            ed_rate = 1.0 / best_ed
            _emit(f"verify_commit_{n_vals}val_ed25519_batched_commits_per_sec",
                  ed_rate, "commits/s", 1.0, n_vals=n_vals)

            bls_chain = f"aggsig-bls-{n_vals}"
            vs_bls, commit_bls, bid_bls = _mk_bls_aggregated_commit(
                n_vals, bls_chain)
            best_bls = _timed(lambda: vs_bls.verify_commit(
                bls_chain, bid_bls, 100, commit_bls), warm=2, runs=3)
            bls_rate = 1.0 / best_bls
            _emit(f"verify_commit_{n_vals}val_bls_aggregated_commits_per_sec",
                  bls_rate, "commits/s", bls_rate / ed_rate, n_vals=n_vals)
            sizes[n_vals] = (len(commit_ed.encode()),
                             len(commit_bls.encode()))

        # -- blocksync fast-sync replay A/B (ROADMAP aggsig edge) ---------
        # the replay regime: a window of contiguous commits verified in
        # ONE verify_commit_light_batched call, exactly what the blocksync
        # reactor's stage-A dispatch pays per window. Ed25519 entries fold
        # into one device batch; aggregated commits verify inline, one
        # pairing each.
        def _replay(entries):
            errs = [e for e in verify_commit_light_batched(entries)
                    if e is not None]
            if errs:
                raise errs[0]

        bs_ed_chain = "aggsig-replay-ed-150"
        vs_e, commit_e, bid_e = _mk_ed25519_commit_local(150, bs_ed_chain)
        ed_entries = [(vs_e, bs_ed_chain, bid_e, 100, commit_e)
                      for _ in range(replay_window)]
        best = _timed(lambda: _replay(ed_entries), warm=2, runs=3)
        ed_replay_rate = replay_window / best
        _emit("blocksync_replay_150val_ed25519_commits_per_sec",
              ed_replay_rate, "commits/s", 1.0, window=replay_window)

        bs_bls_chain = "aggsig-replay-bls-150"
        vs_b, commit_b, bid_b = _mk_bls_aggregated_commit(150, bs_bls_chain)
        bls_entries = [(vs_b, bs_bls_chain, bid_b, 100, commit_b)
                       for _ in range(replay_window)]
        best = _timed(lambda: _replay(bls_entries), warm=2, runs=3)
        bls_replay_rate = replay_window / best
        _emit("blocksync_replay_150val_bls_commits_per_sec",
              bls_replay_rate, "commits/s", bls_replay_rate / ed_replay_rate,
              window=replay_window)
    finally:
        phases.set_device_metrics(prev_metrics)
        schemes.reset()
    # informational: the wire-size collapse (48 B sig + signer bitmap +
    # fixed header vs n_vals CommitSig entries) — never gated
    ed_b, agg_b = sizes[1000]
    _emit("aggregated_commit_1000val_bytes", float(agg_b), "bytes", 0.0,
          ed25519_commit_bytes=ed_b,
          agg_sig_bytes=48,
          compression_ratio=round(ed_b / agg_b, 1))
    # informational: the BLS plane telemetry the run just exercised,
    # read back through the run-local DeviceMetrics — pairing wall cost,
    # verify counts split by mode (full from the A/B, light from the
    # replay), and how many wire-size observations landed. Never gated.
    pair_calls = sum(dm.pairing_seconds._totals.values())
    pair_wall = sum(dm.pairing_seconds._sums.values())
    verify_by_mode = {"|".join(k): int(v) for k, v in
                      sorted(dm.aggregate_verify_total._values.items())}
    _emit("aggsig_pairing_telemetry", float(pair_calls), "calls", 0.0,
          pairing_wall_s_total=round(pair_wall, 6),
          pairing_wall_s_mean=round(pair_wall / pair_calls, 6)
          if pair_calls else 0.0,
          aggregate_verify_total=verify_by_mode,
          wire_size_observations=sum(
              dm.aggregated_commit_bytes._totals.values()))


def bench_soak():
    """Config soak: compressed in-proc game day (tools/soak.py). A 6-node
    fleet (4 validators + 2 fulls) under continuous open-loop signed load
    with corruption, churn and a crash-kill armed concurrently from one
    seed, judged against the default SLOSpec. Gated rows: SLO breach
    count (lower-better "breaches" unit), commit p99, and kill->caught-up
    recovery. Roughly a minute of chaos plus fleet spin-up/teardown; the
    full 8-node / 5-minute game day stays in tools/soak.py --ci."""
    import tempfile

    soak = _tools_mod("soak")
    try:
        out = os.path.join(tempfile.mkdtemp(prefix="bench_soak_"),
                           "soak_report.json")
        rep = soak.run_soak(n_nodes=6, seed=1, duration_s=60.0, out=out)
        sl = rep["slo"]
        _emit("inproc_soak_slo_breaches", float(len(sl["breaches"])),
              "breaches", 0.0, seed=rep["seed"], n_nodes=rep["n_nodes"],
              duration_s=rep["duration_s"],
              unattributed=sl["unattributed"],
              breach_planes=sorted({b["attribution"]["plane"]
                                    for b in sl["breaches"]}),
              schedule_fingerprint=rep["schedule_fingerprint"],
              breach_fingerprint=rep["breach_fingerprint"],
              heights=rep["heights"], event_errors=rep["event_errors"],
              report_path=out)
        obs = rep["observed"]
        if obs["commit_samples"]:
            _emit("inproc_soak_commit_p99_s", float(obs["commit_p99_s"]),
                  "s", 0.0, commit_samples=obs["commit_samples"],
                  rate_txs_per_s=rep["load"]["rate_txs_per_s"],
                  sent=rep["load"]["sent"])
        else:
            _emit("inproc_soak_commit_p99_s", 0.0, "error", 0.0,
                  error="no commit latency samples observed")
        recoveries = [k["kill_to_caughtup_s"] for k in rep["kills"]
                      if k.get("kill_to_caughtup_s") is not None]
        if recoveries:
            _emit("inproc_soak_kill_caughtup_s", float(max(recoveries)),
                  "s", 0.0, kills=len(rep["kills"]),
                  churn_caughtup_s=[round(j["caughtup_s"], 2)
                                    for j in rep["joins"]])
        else:
            # a kill that armed but never fired (or never rejoined) is a
            # regression the gate must see, not a silently missing row
            _emit("inproc_soak_kill_caughtup_s", 0.0, "error", 0.0,
                  error="no completed kill->rejoin cycle",
                  kills=rep["kills"], event_errors=rep["event_errors"])
    except Exception as e:
        for m in ("inproc_soak_slo_breaches", "inproc_soak_commit_p99_s",
                  "inproc_soak_kill_caughtup_s"):
            _emit(m, 0.0, "error", 0.0, error=f"{type(e).__name__}: {e}")


def bench_wan():
    """Config wan: the degraded-network plane (tools/quorum_loss.py). Two
    gated rows: 4-validator commit throughput under the seeded ``wan``
    link profile (80-160ms asymmetric latency + jitter on every link;
    higher-better "commits/min"), and worst-case quorum-loss recovery —
    >1/3 of voting power isolated until the fleet halts with
    ``halt_reason="quorum_lost"``, then healed; the row is the worst
    heal->next-commit time across windows (lower-better "s"). Both runs
    also assert the safety half (no conflicting commits, no double-sign
    evidence, hash-identical history), so a regression that trades
    safety for speed errors the row instead of improving it."""
    ql = _tools_mod("quorum_loss")
    try:
        rep = ql.run_wan(seed=1, blocks=12)
        _emit("inproc_wan4_commits_per_min", float(rep["commits_per_min"]),
              "commits/min", 0.0, seed=rep["seed"], blocks=rep["blocks"],
              applied_links=rep["applied_links"],
              elapsed_s=rep["elapsed_s"])
    except Exception as e:
        _emit("inproc_wan4_commits_per_min", 0.0, "error", 0.0,
              error=f"{type(e).__name__}: {e}")
    try:
        rep = ql.run_quorum_loss(seed=1, windows=2)
        _emit("inproc_quorumloss_recover_s", float(rep["recover_max_s"]),
              "s", 0.0, seed=rep["seed"], windows=rep["windows"],
              recover_s=[w["recover_s"] for w in rep["windows_run"]],
              halt_heights=[w["halt_height"] for w in rep["windows_run"]],
              hash_identical=rep["hash_identical"],
              equivocations=rep["equivocations"],
              outcome_fingerprint=rep["outcome_fingerprint"])
    except Exception as e:
        _emit("inproc_quorumloss_recover_s", 0.0, "error", 0.0,
              error=f"{type(e).__name__}: {e}")


def _mk_light_serve_chain(n_vals: int, n_heights: int, chain_id: str,
                          scheme: str = "ed25519"):
    """Signed LightBlock chain for the serving-plane A/B: real headers
    (hash-linked, valset hashes bound) with fully-signed commits — the
    CommitSig list per height for ed25519, ONE aggregate per height on a
    registered BLS chain."""
    import hashlib

    from tendermint_tpu import crypto
    from tendermint_tpu.types import Validator, ValidatorSet
    from tendermint_tpu.types.basic import (
        BlockID,
        BlockIDFlag,
        PartSetHeader,
        SignedMsgType,
    )
    from tendermint_tpu.types.block import Commit, CommitSig, Consensus, Header
    from tendermint_tpu.types.canonical import vote_sign_bytes
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    t0_ns = 1_700_000_000_000_000_000
    if scheme == "bls12381":
        from tendermint_tpu.crypto import bls12381 as bls
        from tendermint_tpu.crypto import schemes
        from tendermint_tpu.libs.bits import BitArray
        from tendermint_tpu.types.block import AggregatedCommit
        from tendermint_tpu.types.params import SignatureParams

        schemes.register_chain(chain_id, SignatureParams("bls12381", True))
        privs = [crypto.Bls12381PrivKey.generate(
            hashlib.sha256(f"lsrv-{chain_id}-{i}".encode()).digest())
            for i in range(n_vals)]
    else:
        privs = [crypto.Ed25519PrivKey.generate(
            hashlib.sha256(f"lsrv-{chain_id}-{i}".encode()).digest())
            for i in range(n_vals)]
    vs = ValidatorSet([Validator(p.pub_key().address(), p.pub_key(), 10)
                       for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    blocks = {}
    last_bid = BlockID(b"", PartSetHeader())
    for h in range(1, n_heights + 1):
        header = Header(
            version=Consensus(), chain_id=chain_id, height=h,
            time_ns=t0_ns + h * 1_000_000_000, last_block_id=last_bid,
            last_commit_hash=b"\x01" * 32, data_hash=b"\x02" * 32,
            validators_hash=vs.hash(), next_validators_hash=vs.hash(),
            consensus_hash=b"\x03" * 32, app_hash=b"\x04" * 32,
            last_results_hash=b"\x05" * 32, evidence_hash=b"\x06" * 32,
            proposer_address=vs.validators[0].address)
        bid = BlockID(header.hash(), PartSetHeader(1, b"\x07" * 32))
        if scheme == "bls12381":
            from tendermint_tpu.crypto import schemes

            msg = vote_sign_bytes(chain_id, SignedMsgType.PRECOMMIT, h, 0,
                                  bid, schemes.AGG_ZERO_TS_NS)
            agg = bls.aggregate([by_addr[v.address].sign(msg)
                                 for v in vs.validators])
            signers = BitArray(n_vals)
            for i in range(n_vals):
                signers.set_index(i, True)
            commit = AggregatedCommit(h, 0, bid, [], signers=signers,
                                      agg_sig=agg,
                                      timestamp_ns=header.time_ns)
        else:
            sigs = []
            for i, v in enumerate(vs.validators):
                ts = header.time_ns + i
                msg = vote_sign_bytes(chain_id, SignedMsgType.PRECOMMIT,
                                      h, 0, bid, ts)
                sigs.append(CommitSig(BlockIDFlag.COMMIT, v.address, ts,
                                      by_addr[v.address].sign(msg)))
            commit = Commit(h, 0, bid, sigs)
        blocks[h] = LightBlock(SignedHeader(header, commit), vs)
        last_bid = bid
    return blocks


def _lightserve_requests(blocks, spans, per_span: int, now_ns: int):
    """The fleet's ask: ``per_span`` clients per (trusted, target) span —
    the steady-state where thousands of clients bisect the same heights."""
    from tendermint_tpu.light.serve import VerifyRequest

    reqs = []
    for i in range(per_span):
        for t, h in spans:
            reqs.append(VerifyRequest(
                blocks[t].signed_header, blocks[t].validator_set,
                blocks[h].signed_header, blocks[h].validator_set,
                3600.0, now_ns, 10.0, (1, 3), cache_key=(t, h)))
    return reqs


def _lightserve_run_coalesced(reqs, flush_max: int = 64,
                              deadline_s: float = 0.002):
    """One fleet burst through a FRESH coalescer; returns (wall, per-client
    sojourn latencies, coalescer stats)."""
    import asyncio

    from tendermint_tpu.light.serve import VerifyCoalescer

    lat = []

    async def run():
        co = VerifyCoalescer(flush_deadline_s=deadline_s,
                             flush_max=flush_max)
        try:
            async def one(r):
                t0 = time.perf_counter()
                res = await co.submit(r)
                lat.append(time.perf_counter() - t0)
                return res

            t0 = time.perf_counter()
            results = await asyncio.gather(*[one(r) for r in reqs])
            wall = time.perf_counter() - t0
            bad = [r for r in results if r is not None]
            assert not bad, f"coalesced serving rejected honest spans: {bad[:2]}"
            return wall, dict(co.stats)
        finally:
            co.stop()

    wall, stats = asyncio.run(run())
    return wall, lat, stats


def _lightserve_run_scalar(reqs):
    """The pre-coalescer serving plane: one scalar verifier.verify per
    request, FIFO. Latencies are sojourn times for a burst arriving at t0 —
    what a concurrent client actually waits on a one-at-a-time server."""
    from tendermint_tpu.light import verifier

    lat = []
    t0 = time.perf_counter()
    for r in reqs:
        verifier.verify(r.trusted_sh, r.trusted_vals, r.untrusted_sh,
                        r.untrusted_vals, r.trusting_period_s, r.now_ns,
                        r.max_clock_drift_s, r.trust_level)
        lat.append(time.perf_counter() - t0)
    return time.perf_counter() - t0, lat


def _p99(lat):
    s = sorted(lat)
    return s[min(len(s) - 1, int(0.99 * (len(s) - 1) + 0.999))]


def bench_lightserve():
    """Config lightserve: the light-client serving plane A/B. A 96-client
    fleet trusting-verifies a handful of spans over a 16-validator chain:
    scalar = one verifier.verify per request FIFO (the pre-plane serving
    path); coalesced = the same requests through VerifyCoalescer (ONE
    batched precompute + scalar-spec replay, dedup + verdict cache).
    Gated rows: fleet headers/s (higher-better; vs_baseline is the A/B
    ratio over scalar) and p99 client sojourn (lower-better). The BLS
    aggregated plane rides along at 8 validators — a flush there is a
    handful of pairings."""
    from tendermint_tpu.crypto import schemes

    t0_ns = 1_700_000_000_000_000_000
    now_ns = t0_ns + 100 * 1_000_000_000
    try:
        blocks = _mk_light_serve_chain(16, 12, "lightserve-bench-ed")
        spans = [(1, 12), (2, 12), (1, 8), (3, 10), (2, 9), (4, 11)]
        reqs = _lightserve_requests(blocks, spans, 16, now_ns)  # 96 clients

        _lightserve_run_scalar(reqs)  # warm (sign-bytes memos, jit)
        _lightserve_run_coalesced(reqs)
        sc_wall = sc_lat = None
        for _ in range(3):
            wall, lat = _lightserve_run_scalar(reqs)
            if sc_wall is None or wall < sc_wall:
                sc_wall, sc_lat = wall, lat
        co_wall = co_lat = stats = None
        for _ in range(3):
            wall, lat, st = _lightserve_run_coalesced(reqs)
            if co_wall is None or wall < co_wall:
                co_wall, co_lat, stats = wall, lat, st
        scalar_rate = len(reqs) / sc_wall
        co_rate = len(reqs) / co_wall
        _emit("lightserve_clients_headers_per_sec", co_rate, "headers/s",
              co_rate / scalar_rate, clients=len(reqs),
              spans=len(spans), scalar_headers_per_sec=round(scalar_rate, 1),
              flushes=stats["flushes"], largest_flush=stats["largest_flush"],
              verified_requests=stats["verified_requests"],
              coalesced_dupes=stats["coalesced_dupes"],
              verdict_cache_hits=stats["verdict_cache_hits"],
              batched_sigs=stats["batched_sigs"])
        _emit("lightserve_p99_s", _p99(co_lat), "s",
              _p99(co_lat) / _p99(sc_lat), clients=len(reqs),
              scalar_p99_s=round(_p99(sc_lat), 6),
              scalar_p50_s=round(sorted(sc_lat)[len(sc_lat) // 2], 6),
              coalesced_p50_s=round(sorted(co_lat)[len(co_lat) // 2], 6))

        # the BLS aggregated plane: same fleet discipline, pairing regime
        bls_blocks = _mk_light_serve_chain(8, 8, "lightserve-bench-bls",
                                           scheme="bls12381")
        bls_spans = [(1, 8), (2, 8), (1, 5), (3, 7)]
        bls_reqs = _lightserve_requests(bls_blocks, bls_spans, 8, now_ns)
        _lightserve_run_scalar(bls_reqs)
        _lightserve_run_coalesced(bls_reqs)
        bls_sc_wall, _ = _lightserve_run_scalar(bls_reqs)
        bls_co_wall, _, bls_stats = _lightserve_run_coalesced(bls_reqs)
        bls_sc_rate = len(bls_reqs) / bls_sc_wall
        bls_co_rate = len(bls_reqs) / bls_co_wall
        _emit("lightserve_bls_clients_headers_per_sec", bls_co_rate,
              "headers/s", bls_co_rate / bls_sc_rate, clients=len(bls_reqs),
              scalar_headers_per_sec=round(bls_sc_rate, 1),
              verified_requests=bls_stats["verified_requests"],
              batched_sigs=bls_stats["batched_sigs"])
    except Exception as e:
        for m in ("lightserve_clients_headers_per_sec", "lightserve_p99_s",
                  "lightserve_bls_clients_headers_per_sec"):
            _emit(m, 0.0, "error", 0.0, error=f"{type(e).__name__}: {e}")
    finally:
        schemes.reset()


CONFIGS = {
    "1": bench_stream,
    "2": bench_verify_commit_150,
    "3": bench_light_chain_1000,
    "4": bench_localnet,
    "5": bench_fast_sync_replay,
    "ingest": bench_ingest,
    "multichip": bench_multichip_scale,
    "churn": bench_churn,
    "crash": bench_crash,
    "exec": bench_exec,
    "aggsig": bench_aggsig,
    "lightserve": bench_lightserve,
    "soak": bench_soak,
    "wan": bench_wan,
    "10k": bench_verify_commit_10k,
}


def _emit_trace(path: str) -> None:
    """Write the run's span trace as Chrome trace-event JSON (loadable at
    https://ui.perfetto.dev) and emit a per-span stage-histogram summary
    line into the BENCH_*.json payload."""
    import sys

    from tendermint_tpu.libs.trace import tracer

    tracer.write(path)
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "tools"))
    try:
        from trace_summary import summarize

        spans = summarize(tracer.events())
    finally:
        sys.path.pop(0)
    _emit("trace_summary", float(len(tracer.events())), "events", 0.0,
          trace_path=path, spans=spans)


def main(argv=None) -> int:
    """Run the chosen configs; non-zero when any of them raised."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="all",
                    choices=list(CONFIGS) + ["all"],
                    help="BASELINE.json config; default runs every config, "
                         "flagship (10k) last")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable the span tracer (libs/trace.py) for the "
                         "whole run and write Chrome trace-event JSON here; "
                         "also emits a per-span summary line")
    args = ap.parse_args(argv)
    _enable_compile_cache()
    from tendermint_tpu.libs.trace import tracer as _tracer

    if args.trace_out:
        _tracer.enable()
    failed = []
    try:
        # flagship last: the driver records the final line. A config that
        # raises is reported and the rest still run; the exit code says so.
        keys = (("2", "3", "4", "ingest", "churn", "crash", "exec", "aggsig",
                 "lightserve", "soak", "wan", "5", "1", "multichip", "10k")
                if args.config == "all" else (args.config,))
        for key in keys:
            try:
                with _tracer.span(f"config_{key}"):
                    CONFIGS[key]()
            except Exception as e:
                import traceback

                traceback.print_exc()
                failed.append(key)
                _emit(f"config_{key}_failed", 0.0, "error", 0.0,
                      error=f"{type(e).__name__}: {e}")
    finally:
        # a failed run is exactly when the trace matters: flush the ring
        # to disk before any exception propagates
        if args.trace_out:
            _emit_trace(args.trace_out)
    if failed:
        print(f"bench: failed configs {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
