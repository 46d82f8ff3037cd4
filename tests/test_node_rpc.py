"""Node assembly + RPC surface: a full node built from Config (node.py),
serving JSON-RPC/WS (rpc/), driven through the public HTTP client — and a
CLI-generated multi-process localnet (BASELINE config #4 shape).
(reference node/node.go:706, rpc/core/routes.go, cmd/tendermint/)
"""

import asyncio
import base64
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

pytest.importorskip("cryptography", reason="needs the optional 'cryptography' package (absent in slim containers)")

from tendermint_tpu.config import Config, test_config
from tendermint_tpu.p2p import NodeKey
from tendermint_tpu.privval.file_pv import FilePV
from tendermint_tpu.types import GenesisDoc, GenesisValidator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Block 1 carries the genesis time, and a light client refuses a trusted
# header older than its trusting period (config.py: 14 days): a constant
# here is a test that expires. The run's own start, taken once so that a
# node restarted on the same home sees the same genesis.
_GENESIS_TIME_NS = time.time_ns()


def _mk_node(tmp_path, rpc: bool = True, backend: str = "mem"):
    from tendermint_tpu import crypto
    from tendermint_tpu.node import Node

    home = str(tmp_path / "home")
    cfg = test_config(home)
    cfg.base.chain_id = "rpc-chain"
    cfg.base.db_backend = backend
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0" if rpc else ""
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    if os.path.exists(cfg.priv_validator_key_file()):
        pv = FilePV.load(cfg.priv_validator_key_file(),
                         cfg.priv_validator_state_file())
    else:
        pv = FilePV.generate(cfg.priv_validator_key_file(),
                             cfg.priv_validator_state_file())
        pv.save()
    nk = NodeKey(crypto.Ed25519PrivKey.generate(b"\x51" * 32))
    # sub-second test blocks with the default time_iota_ms=1000 make chain
    # time outrun the wall clock (+1s per block, BFT-time monotonicity —
    # the reference behaves identically); a 1ms iota keeps header times
    # real so light-client clock-drift checks hold in fast tests
    from tendermint_tpu.types.params import default_consensus_params

    params = default_consensus_params()
    params.block.time_iota_ms = 1
    genesis = GenesisDoc(chain_id="rpc-chain",
                         genesis_time_ns=_GENESIS_TIME_NS,
                         consensus_params=params,
                         validators=[GenesisValidator(pv.get_pub_key(), 10)])
    return Node(cfg, pv, nk, genesis)


def test_node_serves_rpc_end_to_end(tmp_path):
    async def run():
        node = _mk_node(tmp_path)
        await node.start()
        try:
            from tendermint_tpu.rpc.client import HTTPClient

            port = node.rpc_server.bound_port
            client = HTTPClient(f"http://127.0.0.1:{port}")

            # wait for a few blocks
            for _ in range(300):
                st = await client.status()
                if int(st["sync_info"]["latest_block_height"]) >= 2:
                    break
                await asyncio.sleep(0.05)
            assert int(st["sync_info"]["latest_block_height"]) >= 2
            assert st["node_info"]["network"] == "rpc-chain"

            # block / commit / validators / blockchain / genesis
            blk = await client.block(2)
            assert blk["block"]["header"]["height"] == "2"
            cmt = await client.commit(1)
            assert cmt["signed_header"]["header"]["height"] == "1"
            assert cmt["canonical"] is True
            vals = await client.validators()
            assert vals["total"] == "1"
            bc = await client.call("blockchain")
            assert int(bc["last_height"]) >= 2
            gen = await client.call("genesis")
            assert gen["genesis"]["chain_id"] == "rpc-chain"
            ni = await client.call("net_info")
            assert ni["listening"] is True

            # broadcast_tx_commit round-trips through consensus
            res = await client.broadcast_tx_commit(b"k1=v1")
            assert res["deliver_tx"]["code"] == 0
            assert int(res["height"]) > 0

            # the kvstore now answers abci_query (on the query connection)
            q = await client.abci_query("", b"k1")
            assert base64.b64decode(q["response"]["value"]) == b"v1"

            # indexer: tx lookup + search + block_search (kv backend)
            import hashlib
            txh = hashlib.sha256(b"k1=v1").hexdigest()
            txr = await client.call("tx", hash=txh)
            assert txr["tx_result"]["code"] == 0
            assert base64.b64decode(txr["tx"]) == b"k1=v1"
            sr = await client.call("tx_search",
                                   query=f"tx.height={txr['height']}")
            assert int(sr["total_count"]) >= 1
            bs = await client.call("block_search", query="height EXISTS")
            assert int(bs["total_count"]) >= 1

            # websocket subscription sees new blocks
            sub = await client.subscribe("tm.event='NewBlock'")
            got = await asyncio.wait_for(sub.__anext__(), 10)
            assert got["data"]["type"] == "tendermint/event/NewBlock"

            await client.close()
        finally:
            await node.stop()
    asyncio.run(run())


def test_node_restart_resumes_chain(tmp_path):
    """Stop at height >= 2, rebuild from the same home dir, chain continues
    (WAL + handshake replay through the node path, node.go restart shape)."""
    async def run():
        node = _mk_node(tmp_path, rpc=False, backend="sqlite")
        await node.start()
        try:
            for _ in range(300):
                if node.consensus_state.state.last_block_height >= 2:
                    break
                await asyncio.sleep(0.05)
            assert node.consensus_state.state.last_block_height >= 2
        finally:
            await node.stop()
        h1 = node.consensus_state.state.last_block_height

        node2 = _mk_node(tmp_path, rpc=False, backend="sqlite")
        # same data dir => same chain; must resume past h1, not restart at 0
        assert node2.initial_state.last_block_height >= h1 - 1
        await node2.start()
        try:
            for _ in range(300):
                if node2.consensus_state.state.last_block_height >= h1 + 1:
                    break
                await asyncio.sleep(0.05)
            assert node2.consensus_state.state.last_block_height >= h1 + 1
        finally:
            await node2.stop()
    asyncio.run(run())


@pytest.mark.slow
def test_cli_testnet_four_process_localnet(tmp_path):
    """BASELINE config #4 shape: `testnet --v 4` + four `start` processes
    produce a block-producing localnet; invariants checked over RPC
    (app-hash agreement at a common height)."""
    out = str(tmp_path / "tnet")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    base_port = 28700
    subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cmd", "testnet", "--v", "4",
         "--output-dir", out, "--chain-id", "cli-e2e",
         "--starting-port", str(base_port)],
        check=True, env=env, cwd=REPO, capture_output=True, timeout=120)

    procs = []
    try:
        for i in range(4):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu.cmd",
                 "--home", os.path.join(out, f"node{i}"),
                 "start", "--log-level", "warning"],
                env=env, cwd=REPO,
                stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT))

        def rpc(i, path):
            url = f"http://127.0.0.1:{base_port + 2 * i + 1}/{path}"
            with urllib.request.urlopen(url, timeout=5) as r:
                return json.load(r)["result"]

        deadline = time.time() + 90
        heights = [0] * 4
        while time.time() < deadline:
            try:
                heights = [int(rpc(i, "status")["sync_info"]
                               ["latest_block_height"]) for i in range(4)]
                if min(heights) >= 3:
                    break
            except Exception:
                pass
            time.sleep(1.0)
        assert min(heights) >= 3, f"localnet stuck: {heights}"

        hashes = {rpc(i, "commit?height=2")["signed_header"]["header"]["app_hash"]
                  for i in range(4)}
        assert len(hashes) == 1, hashes
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    # all four made progress and agreed; CLI + config + TCP + RPC end-to-end


def test_metrics_endpoint(tmp_path):
    """Prometheus /metrics (reference node.go:962 + per-module metrics.go)."""
    async def run():
        node = _mk_node(tmp_path)
        node.config.instrumentation.prometheus = True
        node.config.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
        await node.start()
        try:
            import aiohttp

            for _ in range(300):
                if node.consensus_state.state.last_block_height >= 2:
                    break
                await asyncio.sleep(0.05)
            async with aiohttp.ClientSession() as s:
                async with s.get(
                        f"http://127.0.0.1:{node.metrics_port}/metrics") as r:
                    text = await r.text()
            assert "tendermint_consensus_height " in text
            height_line = [l for l in text.splitlines()
                           if l.startswith("tendermint_consensus_height ")][0]
            assert int(float(height_line.split()[-1])) >= 2
            assert "tendermint_consensus_validators 1" in text
            assert "tendermint_state_block_processing_time_count" in text
            assert "tendermint_consensus_block_interval_seconds_bucket" in text
            # the verification/apply-plane sets are registered even when the
            # series are idle (this node self-proposes, it doesn't fast-sync)
            assert "# TYPE tendermint_crypto_batch_size histogram" in text
            assert ("# TYPE tendermint_blocksync_stage_seconds histogram"
                    in text)
        finally:
            await node.stop()
    asyncio.run(run())


def test_rollback_one_height(tmp_path):
    """(state/rollback.go) the node re-applies the last block after rollback."""
    async def run():
        node = _mk_node(tmp_path, rpc=False, backend="sqlite")
        await node.start()
        try:
            for _ in range(300):
                if node.consensus_state.state.last_block_height >= 3:
                    break
                await asyncio.sleep(0.05)
        finally:
            await node.stop()
        h = node.consensus_state.state.last_block_height

        from tendermint_tpu.node import _make_db
        from tendermint_tpu.state.rollback import rollback_state
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.store import BlockStore

        cfg = node.config
        bs = BlockStore(_make_db("sqlite", cfg.db_dir(), "blockstore"))
        ss = StateStore(_make_db("sqlite", cfg.db_dir(), "state"))
        # block store may be one ahead of the state store (stop mid-commit):
        # rollback's early-return path covers that; otherwise it goes back one
        prev = ss.load().last_block_height
        rolled_h, app_hash = rollback_state(bs, ss)
        assert rolled_h in (prev, prev - 1)
        assert ss.load().last_block_height == rolled_h

        # the node restarts and catches back up past h
        node2 = _mk_node(tmp_path, rpc=False, backend="sqlite")
        await node2.start()
        try:
            for _ in range(300):
                if node2.consensus_state.state.last_block_height >= h + 1:
                    break
                await asyncio.sleep(0.05)
            assert node2.consensus_state.state.last_block_height >= h + 1
        finally:
            await node2.stop()
    asyncio.run(run())
