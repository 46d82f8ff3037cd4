"""The open-loop load harness (tools/loadtime.py) against a REAL
single-validator consensus chain served over a real aiohttp RPCServer:
pre-planned sends land through broadcast_tx_sync, latency percentiles are
recovered from committed blocks, and the /tx_timeline scrape shows the
full rpc_received → committed stage chain with monotonic stamps — the
acceptance criterion's measurement path, minus only a multi-process
localnet."""

import asyncio
import threading
from types import SimpleNamespace

import pytest

pytest.importorskip("aiohttp", reason="RPC server needs aiohttp")

from tendermint_tpu.libs.metrics import NodeMetrics
from tendermint_tpu.libs.toolbox import load_tool
from tendermint_tpu.libs.txlife import TxLifecycle
from tendermint_tpu.rpc.server import RPCServer


def _rpc_node(cs, mempool, block_store, event_bus, genesis, pv):
    """The Environment surface loadtime's report walks (status, block,
    broadcast_tx_sync, num_unconfirmed_txs, tx_timeline)."""
    return SimpleNamespace(
        config=SimpleNamespace(
            rpc=SimpleNamespace(laddr="tcp://127.0.0.1:0",
                                max_body_bytes=1000000, unsafe=False,
                                timeout_broadcast_tx_commit=10.0),
            base=SimpleNamespace(moniker="ingest-test")),
        mempool=mempool,
        block_store=block_store,
        event_bus=event_bus,
        consensus_state=cs,
        genesis=genesis,
        node_key=SimpleNamespace(id="stub-node"),
        node_info=SimpleNamespace(listen_addr="", version="test",
                                  protocol_p2p=8, protocol_block=11,
                                  protocol_app=0),
        priv_validator=pv,
        _fast_sync=False,
    )


def test_open_loop_load_to_commit_with_timeline():
    from test_consensus_single import build_node

    lt = load_tool("loadtime")

    async def run():
        cs, mempool, app, event_bus, pv, extras = build_node()
        _state_store, block_store, genesis, conns = extras
        nm = NodeMetrics()
        tl = TxLifecycle(sample_rate=1.0)
        tl.metrics = nm.mempool
        mempool.metrics = nm.mempool
        mempool.txlife = tl
        node = _rpc_node(cs, mempool, block_store, event_bus, genesis, pv)
        server = RPCServer(node)
        server.metrics = nm.rpc
        await cs.start()
        await server.start("tcp://127.0.0.1:0")
        endpoint = f"http://127.0.0.1:{server.bound_port}"
        try:
            stats = await lt.open_loop_load(endpoint, rate=40.0,
                                            duration=2.0, size=64,
                                            clients=4)
            assert stats["planned"] == 80
            assert stats["accepted"] > 0, stats
            # settle: let the tail commit
            for _ in range(200):
                if mempool.size() == 0:
                    break
                await asyncio.sleep(0.05)
            # report_doc is blocking urllib — run it off-loop against the
            # live server
            doc = await asyncio.get_running_loop().run_in_executor(
                None, lt.report_doc, endpoint)
        finally:
            await server.stop()
            await cs.stop()
            conns.stop()
        assert doc["txs"] >= stats["accepted"] * 0.9, doc
        assert doc["txs_per_sec"] > 0
        lat = doc["latency_s"]
        assert {"p50", "p99", "p99.9"} <= set(lat)
        assert 0 < lat["p50"] <= lat["p99"] <= lat["p99.9"], lat
        # the acceptance probe: a sampled tx's timeline record carries
        # every stage from rpc_received through committed, monotonic
        tlr = doc["tx_timeline"]
        assert tlr["complete_rpc_to_commit_records"] >= 1, tlr
        assert tlr["node_commit_latency_s"]["p50"] > 0
        full = [r for r in tl.tail(500)
                if r["terminal"] == "committed"
                and {"rpc_received", "checktx_done", "mempool_admitted",
                     "proposal_included",
                     "committed"} <= {m[0] for m in r["marks"]}]
        assert full, tl.snapshot()
        times = [t for _, t in full[0]["marks"]]
        assert times == sorted(times)
        # the RPC front door counted the load
        ok_count = nm.rpc.request_seconds.count_value("broadcast_tx_sync",
                                                      "ok")
        assert ok_count == stats["sent"], (ok_count, stats)

    asyncio.run(run())
