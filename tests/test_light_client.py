"""Light client: pure verifier rules, bisection over a validator-rotating
chain, trusting-period expiry, and witness divergence detection
(reference light/verifier.go, light/client.go, light/detector.go).
"""

import asyncio

import pytest

from tendermint_tpu import crypto
from tendermint_tpu.light import (
    LightClient,
    TrustOptions,
    verify_adjacent,
    verify_non_adjacent,
)
from tendermint_tpu.light.client import DivergenceError
from tendermint_tpu.light.provider import MockProvider
from tendermint_tpu.light.verifier import (
    ErrInvalidHeader,
    ErrNewValSetCantBeTrusted,
    ErrOldHeaderExpired,
)
from tendermint_tpu.types import MockPV, Validator, ValidatorSet
from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader, SignedMsgType
from tendermint_tpu.types.block import Commit, CommitSig, Consensus, Header
from tendermint_tpu.types.light_block import LightBlock, SignedHeader
from tendermint_tpu.types.vote import Vote

CHAIN = "light-chain"
T0 = 1_700_000_000_000_000_000


def _val_set(keys):
    return ValidatorSet([Validator(k.get_pub_key().address(), k.get_pub_key(), 10)
                         for k in keys])


def _mk_chain(key_sets, n_heights):
    """Build a signed header chain; key_sets[h-1] = pv list for height h."""
    blocks = {}
    last_bid = BlockID(b"", PartSetHeader())
    for h in range(1, n_heights + 1):
        keys = key_sets[min(h - 1, len(key_sets) - 1)]
        next_keys = key_sets[min(h, len(key_sets) - 1)]
        vals, next_vals = _val_set(keys), _val_set(next_keys)
        header = Header(
            version=Consensus(), chain_id=CHAIN, height=h,
            time_ns=T0 + h * 1_000_000_000,
            last_block_id=last_bid,
            last_commit_hash=b"\x01" * 32, data_hash=b"\x02" * 32,
            validators_hash=vals.hash(),
            next_validators_hash=next_vals.hash(),
            consensus_hash=b"\x03" * 32, app_hash=b"\x04" * 32,
            last_results_hash=b"\x05" * 32, evidence_hash=b"\x06" * 32,
            proposer_address=keys[0].get_pub_key().address(),
        )
        bid = BlockID(header.hash(), PartSetHeader(1, b"\x07" * 32))
        commit = _sign_commit(vals, keys, h, bid, header.time_ns)
        blocks[h] = LightBlock(SignedHeader(header, commit), vals)
        last_bid = bid
    return blocks


def _keys(seed, n):
    return [MockPV(crypto.Ed25519PrivKey.generate(bytes([seed + i]) * 32))
            for i in range(n)]


def test_verify_adjacent_and_rules():
    keys = _keys(0x10, 4)
    blocks = _mk_chain([keys], 3)
    now = T0 + 100 * 1_000_000_000
    period = 3600.0

    verify_adjacent(blocks[1].signed_header, blocks[2].signed_header,
                    blocks[2].validator_set, period, now, 10.0)

    # tampered header fails
    bad = blocks[2].signed_header
    import copy
    bad2 = copy.deepcopy(bad)
    bad2.header.app_hash = b"\xff" * 32
    with pytest.raises(Exception):
        verify_adjacent(blocks[1].signed_header, bad2,
                        blocks[2].validator_set, period, now, 10.0)

    # expired trusted header
    with pytest.raises(ErrOldHeaderExpired):
        verify_adjacent(blocks[1].signed_header, blocks[2].signed_header,
                        blocks[2].validator_set, 1.0, now, 10.0)


def test_verify_non_adjacent_trusting():
    keys = _keys(0x20, 4)
    blocks = _mk_chain([keys], 10)
    now = T0 + 100 * 1_000_000_000
    # same validator set throughout: skipping from 1 to 10 succeeds
    verify_non_adjacent(blocks[1].signed_header, blocks[1].validator_set,
                        blocks[10].signed_header, blocks[10].validator_set,
                        3600.0, now, 10.0)


def test_verify_non_adjacent_rotated_set_cant_be_trusted():
    a, b = _keys(0x30, 4), _keys(0x40, 4)
    # full rotation at height 5: heights 1-4 signed by A, 5+ by B
    blocks = _mk_chain([a, a, a, a, b, b, b, b, b, b], 10)
    now = T0 + 100 * 1_000_000_000
    with pytest.raises(ErrNewValSetCantBeTrusted):
        verify_non_adjacent(blocks[1].signed_header, blocks[1].validator_set,
                            blocks[10].signed_header, blocks[10].validator_set,
                            3600.0, now, 10.0)


def test_client_bisection_through_rotation():
    a, b = _keys(0x50, 4), _keys(0x60, 4)
    key_sets = [a, a, a, a, b, b, b, b, b, b]
    blocks = _mk_chain(key_sets, 10)
    primary = MockProvider(CHAIN, blocks)
    witness = MockProvider(CHAIN, blocks)
    now = T0 + 100 * 1_000_000_000

    async def run():
        client = LightClient(
            CHAIN,
            TrustOptions(3600.0, 1, blocks[1].signed_header.header.hash()),
            primary, [witness])
        lb = await client.verify_light_block_at_height(10, now_ns=now)
        assert lb.signed_header.header.height == 10
        # bisection stored intermediate trusted blocks
        assert client.store.latest_height() == 10
        assert len(client.store.heights()) >= 2

    asyncio.run(run())


def test_client_detects_divergent_witness():
    keys = _keys(0x70, 4)
    blocks = _mk_chain([keys], 6)
    # witness serves a forked chain (different app hash from height 4 on)
    forged_keys = _keys(0x70, 4)  # same keys — a real equivocation fork
    forked = _mk_chain([forged_keys], 6)
    for h in range(1, 7):
        forked[h].signed_header.header.app_hash = b"\xee" * 32
        # re-sign the forged chain
    forked = _resign(forked, forged_keys)

    primary = MockProvider(CHAIN, blocks)
    witness = MockProvider(CHAIN, forked)
    now = T0 + 100 * 1_000_000_000

    async def run():
        client = LightClient(
            CHAIN, TrustOptions(3600.0, 1, blocks[1].signed_header.header.hash()),
            primary, [witness])
        with pytest.raises(DivergenceError):
            await client.verify_light_block_at_height(5, now_ns=now)
        assert witness.evidence, "divergence must be reported to the witness"

    asyncio.run(run())


def _sign_commit(vals, keys, h, bid, time_ns):
    """Commit with signatures in VALIDATOR-SET order (sorted), as the real
    consensus produces them."""
    by_addr = {pv.get_pub_key().address(): pv for pv in keys}
    sigs = []
    for i, val in enumerate(vals.validators):
        pv = by_addr[val.address]
        vote = Vote(SignedMsgType.PRECOMMIT, h, 0, bid, time_ns + 1000 + i,
                    val.address, i, b"")
        pv.sign_vote(CHAIN, vote)
        sigs.append(CommitSig(BlockIDFlag.COMMIT, vote.validator_address,
                              vote.timestamp_ns, vote.signature))
    return Commit(h, 0, bid, sigs)


def _resign(blocks, keys):
    """Recompute hashes/commits after tampering (building a forked chain)."""
    out = {}
    last_bid = BlockID(b"", PartSetHeader())
    for h in sorted(blocks):
        lb = blocks[h]
        lb.signed_header.header.last_block_id = last_bid
        hdr = lb.signed_header.header
        bid = BlockID(hdr.hash(), PartSetHeader(1, b"\x07" * 32))
        commit = _sign_commit(lb.validator_set, keys, h, bid, hdr.time_ns)
        out[h] = LightBlock(SignedHeader(hdr, commit), lb.validator_set)
        last_bid = bid
    return out


def test_light_client_against_live_node(tmp_path):
    """HTTPProvider + LightClient against a real node over RPC: the decode
    path (ns-exact times, hashes) must reproduce header hashes bit-exactly."""
    pytest.importorskip("cryptography", reason="needs the optional 'cryptography' package (absent in slim containers)")
    from tests.test_node_rpc import _mk_node
    from tendermint_tpu.light.provider import HTTPProvider
    from tendermint_tpu.rpc.client import HTTPClient

    async def run():
        node = _mk_node(tmp_path)
        await node.start()
        try:
            client = HTTPClient(f"http://127.0.0.1:{node.rpc_server.bound_port}")
            for _ in range(300):
                st = await client.status()
                if int(st["sync_info"]["latest_block_height"]) >= 4:
                    break
                await asyncio.sleep(0.05)
            provider = HTTPProvider("rpc-chain", client)
            lb1 = await provider.light_block(1)
            lb1.validate_basic("rpc-chain")  # hash recomputation must match
            # genesis time in the test fixture is 2023; keep it unexpired
            lc = LightClient(
                "rpc-chain",
                TrustOptions(10 * 365 * 24 * 3600.0, 1,
                             lb1.signed_header.header.hash()),
                provider, [])
            lb4 = await lc.verify_light_block_at_height(4)
            assert lb4.signed_header.header.height == 4
            await client.close()
        finally:
            await node.stop()

    asyncio.run(run())


def test_verify_chain_batched_parity(device_standin):
    """verify_chain_batched must make the same accept/reject decisions as
    stepwise verify(), with all signatures in one batch (on the device
    route, its seam stood in)."""
    from tendermint_tpu.light.verifier import verify_chain_batched

    keys = _keys(0x80, 4)
    blocks = _mk_chain([keys], 8)
    now = T0 + 100 * 1_000_000_000
    chain = [blocks[h] for h in range(2, 9)]

    # happy path
    verify_chain_batched(blocks[1], chain, 3600.0, now, 10.0)
    assert len(device_standin.calls) == 1  # one dispatch for the chain

    # corrupt one signature mid-chain: same error as the stepwise path
    import copy
    bad_chain = copy.deepcopy(chain)
    sigs = bad_chain[3].signed_header.commit.signatures
    sigs[0].signature = b"\x00" * 64
    with pytest.raises(ErrInvalidHeader):
        verify_chain_batched(blocks[1], bad_chain, 3600.0, now, 10.0)

    # expired trust fails identically
    with pytest.raises(ErrOldHeaderExpired):
        verify_chain_batched(blocks[1], chain, 1.0, now, 10.0)


def test_light_proxy_verifies_primary(tmp_path):
    """Light proxy (reference light/proxy): commit/block/validators answers
    are verified against light-client state; a lying primary is rejected."""
    pytest.importorskip("cryptography", reason="needs the optional 'cryptography' package (absent in slim containers)")
    from tests.test_node_rpc import _mk_node
    from tendermint_tpu.light.provider import HTTPProvider
    from tendermint_tpu.light.proxy import LightProxy
    from tendermint_tpu.rpc.client import HTTPClient

    async def run():
        node = _mk_node(tmp_path)
        await node.start()
        proxy = None
        try:
            rpc = HTTPClient(f"http://127.0.0.1:{node.rpc_server.bound_port}")
            for _ in range(300):
                st = await rpc.status()
                if int(st["sync_info"]["latest_block_height"]) >= 4:
                    break
                await asyncio.sleep(0.05)
            provider = HTTPProvider("rpc-chain", rpc)
            lb1 = await provider.light_block(1)
            lc = LightClient(
                "rpc-chain",
                TrustOptions(10 * 365 * 24 * 3600.0, 1,
                             lb1.signed_header.header.hash()),
                provider, [])
            proxy = LightProxy(lc, rpc)
            port = await proxy.start()

            client = HTTPClient(f"http://127.0.0.1:{port}")
            cmt = await client.commit(3)
            assert cmt["signed_header"]["header"]["height"] == "3"
            blk = await client.block(3)
            assert blk["block"]["header"]["height"] == "3"
            vals = await client.validators(3)
            assert vals["total"] == "1"
            st = await client.status()  # forwarded route
            assert st["node_info"]["network"] == "rpc-chain"

            # a lying primary: tamper with the proxy's forwarded answer by
            # pointing it at a client that alters block data
            class LyingClient:
                def __init__(self, inner):
                    self.inner = inner

                async def block(self, height=None):
                    doc = await self.inner.block(height)
                    doc["block"]["data"]["txs"] = ["bGllcw=="]  # "lies"
                    return doc

                def __getattr__(self, name):
                    return getattr(self.inner, name)

            proxy.rpc = LyingClient(rpc)
            from tendermint_tpu.rpc.core import RPCError as _E

            with pytest.raises(_E):
                await client.block(3)
            await client.close()
            await rpc.close()
        finally:
            if proxy is not None:
                await proxy.stop()
            await node.stop()

    asyncio.run(run())


def test_light_proxy_verifies_abci_query(tmp_path):
    """abci_query through the proxy is proof-verified against the
    light-client app hash (reference light/rpc/client.go ABCIQuery →
    merkle ProofRuntime): honest answers pass, a forged value and a
    missing proof are rejected."""
    import base64 as b64mod

    pytest.importorskip("cryptography", reason="needs the optional 'cryptography' package (absent in slim containers)")
    from tests.test_node_rpc import _mk_node
    from tendermint_tpu.light.provider import HTTPProvider
    from tendermint_tpu.light.proxy import LightProxy
    from tendermint_tpu.rpc.client import HTTPClient

    async def run():
        node = _mk_node(tmp_path)
        # swap the app for the merkle-proof kvstore BEFORE start
        node_app = node.app
        from tendermint_tpu.abci.example.kvstore import (
            MerkleKVStoreApplication,
        )
        assert not isinstance(node_app, MerkleKVStoreApplication)
        proxy = None
        try:
            await node.start()
            rpc = HTTPClient(f"http://127.0.0.1:{node.rpc_server.bound_port}")
            # the default _mk_node app is plain kvstore (no proofs): the
            # proxy must REJECT its unproven answers
            await rpc.call("broadcast_tx_sync",
                           tx=b64mod.b64encode(b"k1=v1").decode())
            for _ in range(600):
                st = await rpc.status()
                if int(st["sync_info"]["latest_block_height"]) >= 3:
                    break
                await asyncio.sleep(0.05)
            provider = HTTPProvider("rpc-chain", rpc)
            lb1 = await provider.light_block(1)
            lc = LightClient(
                "rpc-chain",
                TrustOptions(10 * 365 * 24 * 3600.0, 1,
                             lb1.signed_header.header.hash()),
                provider, [])
            proxy = LightProxy(lc, rpc)
            port = await proxy.start()
            client = HTTPClient(f"http://127.0.0.1:{port}")

            from tendermint_tpu.rpc.core import RPCError as _E

            with pytest.raises(_E):  # plain kvstore serves no proofs
                await client.abci_query("", b"k1")
            await client.close()
            await rpc.close()
        finally:
            if proxy is not None:
                await proxy.stop()
            await node.stop()

    asyncio.run(run())


def test_light_proxy_merkle_query_end_to_end(tmp_path):
    """With the merkle kvstore app the proxy serves proof-verified queries;
    a lying primary forging the value is rejected."""
    import base64 as b64mod

    pytest.importorskip("cryptography", reason="needs the optional 'cryptography' package (absent in slim containers)")
    from tests.test_node_rpc import _mk_node
    from tendermint_tpu.light.provider import HTTPProvider
    from tendermint_tpu.light.proxy import LightProxy
    from tendermint_tpu.node import Node
    from tendermint_tpu.rpc.client import HTTPClient

    async def run():
        # build the node over the merkle app
        orig = _mk_node(tmp_path)
        cfg = orig.config
        cfg.base.proxy_app = "kvstore-merkle"
        node = Node(cfg, orig.priv_validator, orig.node_key, orig.genesis)
        proxy = None
        try:
            await node.start()
            rpc = HTTPClient(f"http://127.0.0.1:{node.rpc_server.bound_port}")
            await rpc.call("broadcast_tx_sync",
                           tx=b64mod.b64encode(b"k1=v1").decode())
            for _ in range(600):
                st = await rpc.status()
                if int(st["sync_info"]["latest_block_height"]) >= 4:
                    break
                await asyncio.sleep(0.05)
            provider = HTTPProvider("rpc-chain", rpc)
            lb1 = await provider.light_block(1)
            lc = LightClient(
                "rpc-chain",
                TrustOptions(10 * 365 * 24 * 3600.0, 1,
                             lb1.signed_header.header.hash()),
                provider, [])
            proxy = LightProxy(lc, rpc)
            port = await proxy.start()
            client = HTTPClient(f"http://127.0.0.1:{port}")

            doc = await client.abci_query("", b"k1")
            assert b64mod.b64decode(doc["response"]["value"]) == b"v1"

            # lying primary: forge the value; the proof must not verify
            class LyingClient:
                def __init__(self, inner):
                    self.inner = inner

                async def abci_query(self, path, data, height=0, prove=False):
                    doc = await self.inner.abci_query(
                        path, data, height=height, prove=prove)
                    doc["response"]["value"] = b64mod.b64encode(
                        b"forged").decode()
                    return doc

                def __getattr__(self, name):
                    return getattr(self.inner, name)

            from tendermint_tpu.rpc.core import RPCError as _E

            proxy.rpc = LyingClient(rpc)
            with pytest.raises(_E):
                await client.abci_query("", b"k1")
            await client.close()
            await rpc.close()
        finally:
            if proxy is not None:
                await proxy.stop()
            await node.stop()

    asyncio.run(run())
