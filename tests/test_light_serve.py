"""Light-client serving plane: pure planning math, cache/limiter semantics,
and the coalescer differential — coalesced verdicts must be byte-identical
(exception type AND message) to the scalar light/verifier.verify spec across
valid, bad-signature, rotated-set, expired-trust, and BLS aggregated
batches, with and without an armed device.batch_verify fault."""

import asyncio
import json

import pytest

from tendermint_tpu import crypto
from tendermint_tpu.crypto import bls12381 as bls
from tendermint_tpu.crypto import schemes
from tendermint_tpu.libs.faults import faults
from tendermint_tpu.light import verifier
from tendermint_tpu.light.serve import (
    ClientLimiter,
    HeaderCache,
    ServeProvider,
    ShedError,
    TokenBucket,
    VerifyCoalescer,
    VerifyRequest,
    bisection_skeleton,
    fanout_queue_plan,
    plan_flushes,
)
from tendermint_tpu.types import MockPV, Validator, ValidatorSet
from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
from tendermint_tpu.types.block import Consensus, Header
from tendermint_tpu.types.light_block import LightBlock, SignedHeader
from tendermint_tpu.types.params import SignatureParams
from tendermint_tpu.types.vote import Vote
from tendermint_tpu.types.vote_set import VoteSet

from tests.test_light_client import (  # noqa: F401  (chain builders)
    CHAIN,
    T0,
    _keys,
    _mk_chain,
    _resign,
    _val_set,
)

NOW = T0 + 100 * 1_000_000_000


# -- pure planning math ------------------------------------------------------

def test_bisection_skeleton_orders_shallowest_first():
    sk = bisection_skeleton(1, 17)
    assert sk[0] == 9  # the root midpoint
    assert sk[1:3] == [5, 13]  # its children, breadth-first
    assert len(sk) == len(set(sk))
    assert all(1 < h < 17 for h in sk)
    # degenerate spans plan nothing
    assert bisection_skeleton(5, 5) == []
    assert bisection_skeleton(5, 6) == []
    # cap bounds the plan
    assert len(bisection_skeleton(1, 10_000, cap=8)) == 8


def test_plan_flushes_deadline_and_size_triggers():
    # 3 requests inside one deadline window: one flush at t0+deadline
    assert plan_flushes([0.0, 0.001, 0.002], 0.005, 64) == [(0.005, 3)]
    # size trigger fires early: batch closes at its max_batch'th arrival
    assert plan_flushes([0.0, 0.001, 0.002], 0.005, 2) == \
        [(0.001, 2), (0.007, 1)]
    # a gap larger than the deadline opens a new batch
    assert plan_flushes([0.0, 1.0], 0.005, 64) == [(0.005, 1), (1.005, 1)]
    assert plan_flushes([], 0.005, 8) == []
    # a dense burst closes on size, its tail on the deadline; every arrival
    # is in exactly one batch
    arrivals = [i * 0.00005 for i in range(100)] + [1.0]
    plan = plan_flushes(arrivals, 0.002, 32)
    assert sum(n for _, n in plan) == len(arrivals), plan
    assert max(n for _, n in plan) == 32
    with pytest.raises(ValueError):
        plan_flushes([], 0.005, 0)


def test_fanout_queue_plan_bounds_and_evicts():
    assert fanout_queue_plan(10, 10, 4) == (0, False)
    assert fanout_queue_plan(10, 7, 4) == (3, False)
    assert fanout_queue_plan(10, 0, 4) == (4, True)  # capped + evicted
    with pytest.raises(ValueError):
        fanout_queue_plan(1, 0, 0)


def test_token_bucket_refills_on_injected_clock():
    t = [0.0]
    tb = TokenBucket(rate=1.0, burst=2.0, clock=lambda: t[0])
    assert tb.allow() and tb.allow() and not tb.allow()
    t[0] = 1.0
    assert tb.allow() and not tb.allow()


def test_header_cache_lru_and_pinned_eviction():
    c = HeaderCache(capacity=3)
    c.put(1, "a")
    c.put(2, "b", pinned=True)
    c.put(3, "c")
    assert c.get(1) == "a"  # 1 now most-recent
    c.put(4, "d")  # evicts 3 (oldest UNPINNED; 2 is pinned)
    assert c.peek(3) is None and c.peek(2) == "b"
    assert c.stats["evictions"] == 1
    # all-pinned: capacity still a hard bound, oldest pin goes
    c2 = HeaderCache(capacity=2)
    c2.put(1, "a", pinned=True)
    c2.put(2, "b", pinned=True)
    c2.put(3, "c", pinned=True)
    assert len(c2) == 2 and c2.peek(1) is None
    assert c2.pinned_count() == 2
    # peek never touches accounting
    before = dict(c2.stats)
    c2.peek(2)
    assert c2.stats == before


class _StubScoreboard:
    def __init__(self, ban_after=3):
        self.strikes = {}
        self.ban_after = ban_after
        self.reasons = []

    def banned(self, pid):
        return self.strikes.get(pid, 0) >= self.ban_after

    def record_failure(self, pid, reason="error", severe=False):
        self.strikes[pid] = self.strikes.get(pid, 0) + 1
        self.reasons.append(reason)

    def record_success(self, pid):
        self.strikes[pid] = 0


def test_client_limiter_sheds_are_reason_labeled_and_ban():
    t = [0.0]
    sb = _StubScoreboard(ban_after=3)
    lim = ClientLimiter(rate=1.0, burst=2.0, scoreboard=sb,
                        clock=lambda: t[0])
    lim.admit("c1")
    lim.admit("c1")
    for _ in range(3):  # empty bucket: rate sheds accumulate strikes
        with pytest.raises(ShedError) as ei:
            lim.admit("c1")
        assert ei.value.reason == "client-rate"
    with pytest.raises(ShedError) as ei:  # banned now
        lim.admit("c1")
    assert ei.value.reason == "banned"
    assert lim.stats == {"admitted": 2, "rate_sheds": 3, "ban_sheds": 1}
    assert sb.reasons == ["rate"] * 3
    # other clients unaffected; rate<=0 disables limiting entirely
    lim.admit("c2")
    ClientLimiter(rate=0.0, burst=1.0).admit("anyone")


# -- the coalescer differential ---------------------------------------------

def _req(blocks, trusted_h, h, period=3600.0, now=NOW, drift=10.0,
         trust_level=(1, 3), key=None):
    return VerifyRequest(
        blocks[trusted_h].signed_header, blocks[trusted_h].validator_set,
        blocks[h].signed_header, blocks[h].validator_set,
        period, now, drift, trust_level, cache_key=key)


def _scalar_verdict(req):
    try:
        verifier.verify(req.trusted_sh, req.trusted_vals, req.untrusted_sh,
                        req.untrusted_vals, req.trusting_period_s, req.now_ns,
                        req.max_clock_drift_s, req.trust_level)
        return None
    except Exception as e:  # noqa: BLE001 — the verdict IS the exception
        return e


def _coalesce(reqs, backend=None, flush_max=None):
    """Run every request through ONE coalescer concurrently; return the
    per-request results (None or exception instance)."""

    async def run():
        co = VerifyCoalescer(flush_deadline_s=0.01,
                             flush_max=flush_max or max(len(reqs), 1),
                             backend=backend)
        try:
            return await asyncio.gather(
                *[co.submit(r) for r in reqs], return_exceptions=True), co
        finally:
            co.stop()

    return asyncio.run(run())


def _assert_verdict_parity(reqs, results):
    for req, got in zip(reqs, results):
        want = _scalar_verdict(req)
        if want is None:
            assert got is None, f"coalesced rejected what scalar accepts: {got!r}"
        else:
            assert type(got) is type(want), (got, want)
            assert str(got) == str(want), (got, want)


def _mixed_ed25519_batch():
    """One batch covering every verdict class the scalar spec produces."""
    a, b = _keys(0x30, 4), _keys(0x40, 4)
    rot = _mk_chain([a, a, a, a, b, b, b, b, b, b], 10)  # rotation at 5
    keys = _keys(0x80, 4)
    stable = _mk_chain([keys], 8)

    import copy
    bad_sig = copy.deepcopy(stable)
    bad_sig[6].signed_header.commit.signatures[0].signature = b"\x00" * 64
    bad_vals = copy.deepcopy(stable)
    bad_vals[6] = LightBlock(bad_vals[6].signed_header,
                             _val_set(_keys(0x90, 4)))  # wrong untrusted set

    return [
        _req(stable, 1, 8),                       # valid non-adjacent
        _req(stable, 4, 5),                       # valid adjacent
        _req(bad_sig, 1, 6),                      # ErrInvalidHeader(bad sig)
        _req(bad_vals, 1, 6),                     # valset hash mismatch
        _req(stable, 1, 8, period=1.0),           # ErrOldHeaderExpired
        _req(rot, 1, 10),                         # ErrNewValSetCantBeTrusted
        _req(stable, 2, 7),                       # another valid span
    ]


def test_coalesced_verdicts_match_scalar_ed25519(device_standin):
    reqs = _mixed_ed25519_batch()
    results, co = _coalesce(reqs)
    _assert_verdict_parity(reqs, results)
    assert co.stats["flushes"] >= 1
    assert co.stats["batched_sigs"] > 0
    # the batch took the device route (its seam stood in, verdicts the
    # host spec's: the planted bad signature is still refused above)
    assert sum(device_standin.calls) == co.stats["batched_sigs"]


def test_coalesced_verdicts_match_scalar_host_backend():
    reqs = _mixed_ed25519_batch()
    results, _ = _coalesce(reqs, backend="host")
    _assert_verdict_parity(reqs, results)


def _mk_bls_chain(chain_id, pvs, n_heights):
    """Aggregated-commit chain via the real VoteSet path (make_commit emits
    AggregatedCommit for a registered BLS chain)."""
    vals = ValidatorSet([
        Validator(pv.get_pub_key().address(), pv.get_pub_key(), 10)
        for pv in pvs])
    blocks = {}
    last_bid = BlockID(b"", PartSetHeader())
    for h in range(1, n_heights + 1):
        header = Header(
            version=Consensus(), chain_id=chain_id, height=h,
            time_ns=T0 + h * 1_000_000_000, last_block_id=last_bid,
            last_commit_hash=b"\x01" * 32, data_hash=b"\x02" * 32,
            validators_hash=vals.hash(), next_validators_hash=vals.hash(),
            consensus_hash=b"\x03" * 32, app_hash=b"\x04" * 32,
            last_results_hash=b"\x05" * 32, evidence_hash=b"\x06" * 32,
            proposer_address=pvs[0].get_pub_key().address())
        bid = BlockID(header.hash(), PartSetHeader(1, b"\x07" * 32))
        vs = VoteSet(chain_id, h, 0, SignedMsgType.PRECOMMIT, vals)
        for pv in pvs:
            addr = pv.get_pub_key().address()
            idx, _ = vals.get_by_address(addr)
            v = Vote(SignedMsgType.PRECOMMIT, h, 0, bid,
                     header.time_ns + 1000 + idx, addr, idx, b"")
            pv.sign_vote(chain_id, v)
            assert vs.add_vote(v)
        commit = vs.make_commit()
        assert hasattr(commit, "agg_sig"), "BLS chain must aggregate"
        blocks[h] = LightBlock(SignedHeader(header, commit), vals)
        last_bid = bid
    return blocks


def test_coalesced_verdicts_match_scalar_bls_aggregated():
    chain_id = "lightserve-bls"
    schemes.register_chain(chain_id, SignatureParams("bls12381", True))
    try:
        pvs = [MockPV(crypto.Bls12381PrivKey.generate(
            b"lsrv" + bytes([i]) * 4)) for i in range(4)]
        blocks = _mk_bls_chain(chain_id, pvs, 6)
        import copy
        bad = copy.deepcopy(blocks)
        sh = bad[5].signed_header
        c = sh.commit
        c.agg_sig = bytes([c.agg_sig[0] ^ 0x01]) + c.agg_sig[1:]
        reqs = [
            _req(blocks, 1, 6),          # valid skip over aggregated commits
            _req(blocks, 3, 4),          # valid adjacent
            _req(bad, 1, 5),             # tampered aggregate: rejected
            _req(blocks, 1, 6, period=1.0),  # expired
        ]
        results, co = _coalesce(reqs)
        _assert_verdict_parity(reqs, results)
        # aggregated commits pair inline: nothing enters the ed25519 batch
        assert co.stats["batched_sigs"] == 0
    finally:
        schemes.reset()
        bls.reset()


def test_coalesced_parity_survives_armed_device_fault():
    """With lightserve traffic mid-flight, an armed device.batch_verify
    fault degrades the batched call to host verify — verdicts must stay
    byte-identical to the scalar spec."""
    pytest.importorskip("jax")
    reqs = _mixed_ed25519_batch()
    faults.configure("device.batch_verify@1", seed=7)
    try:
        results, co = _coalesce(reqs, backend="jax")
    finally:
        faults.reset()
    _assert_verdict_parity(reqs, results)
    assert co.stats["batched_sigs"] > 0


# -- coalescer mechanics -----------------------------------------------------

def test_coalescer_dedup_and_verdict_cache():
    keys = _keys(0xA0, 4)
    blocks = _mk_chain([keys], 6)
    req = lambda: _req(blocks, 1, 5, key=("k", 1, 5))  # noqa: E731

    async def run():
        co = VerifyCoalescer(flush_deadline_s=0.005, flush_max=64)
        try:
            r = await asyncio.gather(*[co.submit(req()) for _ in range(8)])
            assert all(v is None for v in r)
            assert co.stats["requests"] == 8
            assert co.stats["verified_requests"] == 1  # one shared verify
            assert co.stats["coalesced_dupes"] == 7
            # across flushes: the verdict cache answers without a flush
            flushes = co.stats["flushes"]
            assert await co.submit(req()) is None
            assert co.stats["verdict_cache_hits"] == 1
            assert co.stats["flushes"] == flushes
        finally:
            co.stop()

    asyncio.run(run())


def test_coalescer_size_trigger_and_queue_full_shed():
    keys = _keys(0xB0, 4)
    blocks = _mk_chain([keys], 6)

    async def run():
        # size trigger: deadline is far out, yet flush_max completes us
        co = VerifyCoalescer(flush_deadline_s=30.0, flush_max=2)
        try:
            r = await asyncio.wait_for(
                asyncio.gather(co.submit(_req(blocks, 1, 5)),
                               co.submit(_req(blocks, 2, 6))), timeout=5.0)
            assert r == [None, None]
            assert co.stats["largest_flush"] == 2
        finally:
            co.stop()

        # queue-full: an explicit reason-labeled shed, never a stall
        co2 = VerifyCoalescer(flush_deadline_s=30.0, flush_max=64,
                              queue_limit=1)
        t1 = asyncio.ensure_future(co2.submit(_req(blocks, 1, 5)))
        await asyncio.sleep(0)  # let it enqueue
        with pytest.raises(ShedError) as ei:
            await co2.submit(_req(blocks, 2, 6))
        assert ei.value.reason == "queue-full"
        assert co2.stats["sheds"] == 1
        co2.stop()  # shutdown fails the queued request explicitly too
        with pytest.raises(ShedError) as ei:
            await t1
        assert ei.value.reason == "shutdown"

    asyncio.run(run())


def test_coalescer_survives_cancelled_clients():
    """A client that gives up must not poison the shared verification."""
    keys = _keys(0xC0, 4)
    blocks = _mk_chain([keys], 6)

    async def run():
        co = VerifyCoalescer(flush_deadline_s=0.005, flush_max=64)
        try:
            k = ("same", 1, 5)
            t1 = asyncio.ensure_future(co.submit(_req(blocks, 1, 5, key=k)))
            t2 = asyncio.ensure_future(co.submit(_req(blocks, 1, 5, key=k)))
            await asyncio.sleep(0)
            t1.cancel()
            assert await asyncio.wait_for(t2, timeout=5.0) is None
        finally:
            co.stop()

    asyncio.run(run())


# -- ServeProvider + tamper seam --------------------------------------------

def test_serve_provider_caches_and_tampers_only_when_armed():
    keys = _keys(0xD0, 4)
    blocks = _mk_chain([keys], 6)
    forged = _resign(
        {h: LightBlock(SignedHeader(lb.signed_header.header,
                                    lb.signed_header.commit),
                       lb.validator_set) for h, lb in
         _mk_chain([keys], 6).items()}, keys)

    async def run():
        p = ServeProvider(CHAIN, blocks, forged={4: forged[4]}, name="w1")
        lb = await p.light_block(4)
        assert lb is blocks[4]  # disarmed: honest block, never the forgery
        await p.light_block(4)
        assert p.cache.stats["hits"] == 1
        assert (await p.light_block(0)).signed_header.header.height == 6
        from tendermint_tpu.light.provider import ErrLightBlockNotFound
        with pytest.raises(ErrLightBlockNotFound):
            await p.light_block(99)
        assert p.id() == "w1"

        faults.configure("lightserve.lying_server@1", seed=3)
        try:
            assert (await p.light_block(4)) is forged[4]
            assert (await p.light_block(3)) is blocks[3]  # not forged
        finally:
            faults.reset()

    asyncio.run(run())


# -- the serving plane in-proc: a 64-client fleet ----------------------------

class _BlockStoreStub:
    def __init__(self, blocks):
        self.blocks = blocks

    def height(self):
        return max(self.blocks)

    def base(self):
        return min(self.blocks)

    def load_block_meta(self, h):
        from types import SimpleNamespace
        lb = self.blocks.get(h)
        return None if lb is None else SimpleNamespace(
            header=lb.signed_header.header)

    def load_block_commit(self, h):
        lb = self.blocks.get(h)
        return None if lb is None else lb.signed_header.commit

    load_seen_commit = load_block_commit


class _StateStoreStub:
    def __init__(self, blocks):
        self.blocks = blocks

    def load_validators(self, h):
        lb = self.blocks.get(h)
        return None if lb is None else lb.validator_set


def _mk_plane(blocks, **overrides):
    from tendermint_tpu.config import LightServeConfig
    from tendermint_tpu.light.serve import LightServePlane

    cfg = LightServeConfig()
    cfg.trusting_period_s = 10 * 365 * 24 * 3600.0  # chain fixture is 2023
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return LightServePlane(block_store=_BlockStoreStub(blocks),
                           state_store=_StateStoreStub(blocks),
                           chain_id=CHAIN, config=cfg)


def test_plane_serves_64_concurrent_clients():
    """The tier-1 fleet: >=64 concurrent clients against one serving plane
    — every verdict accepted, verification coalesced far below request
    count, header cache + skeleton prefetch shared across the fleet."""
    blocks = _mk_chain([_keys(0x10, 4)], 10)
    plane = _mk_plane(blocks)

    async def run():
        try:
            async def one(i):
                if i % 2:
                    return await plane.serve_verify(
                        8, 1 + (i % 3), client_id=f"c{i}")
                return plane.serve_header(8, trusted_height=1,
                                          client_id=f"c{i}")

            results = await asyncio.gather(*[one(i) for i in range(64)])
            for i, res in enumerate(results):
                if i % 2:
                    assert res is None, f"client {i} rejected: {res!r}"
                else:
                    assert res["signed_header"]["header"]["height"] == "8"
                    assert res["canonical"] is True
        finally:
            plane.stop()

    asyncio.run(run())
    st = plane.status()
    co = st["coalescer"]
    assert co["requests"] == 32 and co["flushes"] >= 1
    assert co["verified_requests"] <= 6  # 3 distinct spans, maybe 2 flushes
    assert co["coalesced_dupes"] + co["verdict_cache_hits"] >= 26
    assert st["cache"]["hits"] >= 30  # 31 of 32 header asks hit memory
    assert st["served"]["prefetched"] > 0 and st["cache"]["pinned"] > 0
    assert st["served"]["headers_served"] == 32
    assert st["served"]["verifies_served"] == 32


def test_plane_verify_rejections_and_admission():
    keys = _keys(0x70, 4)
    blocks = _mk_chain([keys], 6)

    async def run():
        plane = _mk_plane(blocks)
        try:
            # spec rejections surface as the scalar exception instance
            err = await plane.serve_verify(5, 1)
            assert err is None
            with pytest.raises(KeyError):  # malformed span
                await plane.serve_verify(1, 5)
        finally:
            plane.stop()

        # admission: a hammering client is shed with labeled reasons and
        # banned by abuse scoring; a polite client keeps being served
        plane2 = _mk_plane(blocks, per_client_rate=0.001,
                           per_client_burst=2, abuse_ban_threshold=3)
        try:
            reasons = []
            for _ in range(8):
                try:
                    plane2.serve_header(2, client_id="abuser")
                except ShedError as e:
                    reasons.append(e.reason)
            assert reasons.count("client-rate") == 3
            assert reasons.count("banned") == 3
            doc = plane2.serve_header(2, client_id="polite")
            assert doc["signed_header"]["header"]["height"] == "2"
            assert plane2.limiter.stats["rate_sheds"] == 3
            assert plane2.limiter.stats["ban_sheds"] == 3
        finally:
            plane2.stop()

    asyncio.run(run())


# -- ws fan-out: frame parity + slow-consumer eviction -----------------------

def test_ws_frame_byte_parity():
    aiohttp = pytest.importorskip("aiohttp")  # noqa: F841
    from tendermint_tpu.rpc.server import _render_ws_frame, _rpc_response

    for id_, query, data, events in [
        (1, "tm.event = 'NewBlock'", {"height": "5"}, {"tx.hash": ["ab"]}),
        ("sub-2", "tm.event = 'Tx'", {"k": [1, 2, {"n": None}]}, {}),
        (None, "q with \"quotes\" and \\u00e9", {"s": "v\n"}, {"e": []}),
    ]:
        frag = json.dumps({"data": data, "events": events})
        assert _render_ws_frame(id_, query, frag) == json.dumps(
            _rpc_response(id_, result={"query": query, "data": data,
                                       "events": events}))


def test_ws_fanout_evicts_never_reading_socket():
    pytest.importorskip("aiohttp")
    from tendermint_tpu.rpc.server import _WsFanout

    class NeverReadingWS:
        def __init__(self):
            self.closed_with = None
            self.sent = 0
            self._stall = asyncio.Event()

        async def send_str(self, text):
            await self._stall.wait()  # a consumer that never drains

        async def close(self, code=None, message=b""):
            self.closed_with = (code, message)

    async def run():
        ws = NeverReadingWS()
        evictions = [0]
        fan = _WsFanout(ws, maxsize=4,
                        on_evict=lambda: evictions.__setitem__(
                            0, evictions[0] + 1))
        ok = [fan.enqueue(f"frame-{i}") for i in range(6)]
        assert ok == [True] * 4 + [False, False]
        assert fan.evicted and evictions[0] == 1
        assert not fan.enqueue("late")  # dropped, no second eviction
        assert evictions[0] == 1
        for _ in range(10):
            if ws.closed_with is not None:
                break
            await asyncio.sleep(0.01)
        from aiohttp import WSCloseCode
        assert ws.closed_with == (WSCloseCode.TRY_AGAIN_LATER,
                                  b"slow consumer")
        fan.stop()

    asyncio.run(run())
