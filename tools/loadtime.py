#!/usr/bin/env python3
"""loadtime: open-loop tx load harness + latency-percentile report
(reference test/loadtime, rebuilt open-loop: send times are PRE-PLANNED on
a fixed-rate schedule, so a stalled node cannot slow the offered load down
and hide its own latency — the coordinated-omission trap closed-loop
generators fall into. Latency is measured from each tx's PLANNED send
time, embedded in the tx itself and recovered from committed blocks.)

    # offered load: 4 clients, 50 tx/s for 10 s, 128-byte txs
    python tools/loadtime.py load --endpoint http://127.0.0.1:26657 \
        --rate 50 --duration 10 --size 128 --clients 4
    # recover per-tx latency from committed blocks (+ optional scrapes)
    python tools/loadtime.py report --endpoint http://127.0.0.1:26657 \
        --metrics-endpoint http://127.0.0.1:26660/metrics
    # both, one shot
    python tools/loadtime.py run --endpoint http://127.0.0.1:26657
    python tools/loadtime.py --self-test

The report walks committed blocks newest-known-first, parses every harness
tx (MAGIC || planned_send_ns || seq), and prints sustained committed txs/s
plus p50 / p99 / p99.9 end-to-end latency. When the node carries the
ingestion observability plane it also scrapes ``/tx_timeline`` (per-stage
lifecycle decomposition measured IN the node) and ``/metrics`` (mempool
admission/rejection counters, RPC endpoint latencies) so one run yields
the full trade-curve row.

Stdlib-only except the load path, which uses aiohttp when available and
falls back to thread-pooled urllib otherwise; --self-test is pure stdlib.
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import json
import os
import struct
import sys
import time
import urllib.request
from typing import Dict, List, Optional

MAGIC = b"ltm1"
#: latency percentiles the report prints (p50/p99/p99.9 are the gate rows)
PERCENTILES = (0.5, 0.9, 0.99, 0.999)

#: the ingest plane's signed-tx envelope framing (mempool/ingest.py —
#: kept in sync by its tests): magic | pubkey(32) | fee(8) | nonce(8) |
#: payload | sig(64). The report strips it so --signed runs recover the
#: same harness payload from committed blocks; building one needs the
#: repo's crypto (the only non-stdlib corner besides aiohttp).
STX_MAGIC = b"stx1"
_STX_HEADER = 4 + 32 + 8 + 8
_STX_MIN = _STX_HEADER + 64


# -- tx format ----------------------------------------------------------------

def make_tx(size: int, seq: int, send_ns: Optional[int] = None) -> bytes:
    """MAGIC || send_time_ns (8B) || seq (8B) || deterministic padding.
    ``send_ns`` is the PLANNED send time (open-loop contract); padding is
    seq-derived so every tx is unique without an os.urandom syscall per tx
    at high rates."""
    if send_ns is None:
        send_ns = time.time_ns()
    body = MAGIC + struct.pack(">QQ", send_ns, seq)
    pad = max(0, size - len(body))
    if pad:
        body += (struct.pack(">Q", seq * 0x9E3779B97F4A7C15 % 2**64)
                 * (pad // 8 + 1))[:pad]
    return body


def strip_envelope(tx: bytes) -> bytes:
    """The harness payload inside a signed ingest envelope (or the tx
    itself when unsigned)."""
    if tx.startswith(STX_MAGIC) and len(tx) >= _STX_MIN:
        return tx[_STX_HEADER:-64]
    return tx


def parse_tx(tx: bytes):
    tx = strip_envelope(tx)
    if not tx.startswith(MAGIC) or len(tx) < 20:
        return None
    send_ns, seq = struct.unpack(">QQ", tx[4:20])
    return send_ns, seq


def make_signed_txs(size: int, scheds_ns, fee: int = 1,
                    n_keys: int = 4) -> list:
    """Pre-signed envelope txs for every schedule slot, built BEFORE the
    open-loop clock starts (pure-python ed25519 signing is ~2 ms/tx — on
    the schedule it would read as node latency). Slots rotate across
    ``n_keys`` ephemeral senders so per-sender lanes and rate limits see
    real traffic shape. Needs the repo on PYTHONPATH (only this load
    path does; the report/parse side stays stdlib)."""
    # the canonical encoder, not a re-implementation: envelope drift
    # would otherwise silently turn every signed run into 100% rejects
    from tendermint_tpu import crypto  # lazy: load path only
    from tendermint_tpu.mempool.ingest import make_signed_tx

    keys = [crypto.Ed25519PrivKey.generate(
        struct.pack(">Q", 0x10ad + i) * 4) for i in range(n_keys)]
    return [make_signed_tx(keys[seq % n_keys], make_tx(size, seq, send_ns),
                           nonce=seq, fee=fee)
            for seq, send_ns in enumerate(scheds_ns)]


# -- schedule + percentile math ----------------------------------------------

def plan_schedule(rate: float, n: int, t0: float = 0.0) -> List[float]:
    """n send times on a fixed-rate grid starting at t0. Planned BEFORE any
    tx is sent: the i-th send happens at t0 + i/rate no matter how slow
    the node answered tx i-1."""
    if rate <= 0:
        raise ValueError("rate must be > 0")
    return [t0 + i / rate for i in range(n)]


def percentiles(lats: List[float], ps=PERCENTILES) -> Dict[str, float]:
    """Nearest-rank percentiles over a latency list (seconds)."""
    if not lats:
        return {}
    s = sorted(lats)
    out = {"min": s[0], "max": s[-1],
           "mean": sum(s) / len(s)}
    for p in ps:
        label = ("p" + repr(p * 100).rstrip("0").rstrip(".")).replace(
            "p100", "max")
        out[label] = s[min(len(s) - 1, int(p * len(s)))]
    return out


# -- load (open loop) ---------------------------------------------------------

def _payload(seq: int, tx: bytes) -> bytes:
    return json.dumps({
        "jsonrpc": "2.0", "id": seq, "method": "broadcast_tx_sync",
        "params": {"tx": base64.b64encode(tx).decode()}}).encode()


async def open_loop_load(endpoint: str, rate: float, duration: float,
                         size: int, clients: int = 4,
                         signed: bool = False) -> dict:
    """Drive ``rate`` tx/s for ``duration`` s through ``clients`` concurrent
    senders. Client c owns schedule slots c, c+clients, ... — a slow
    response delays only that client's later slots, and the report still
    measures every tx from its PLANNED time, so any harness lag shows up
    as latency (and in ``max_sched_lag_s``), never as hidden load.
    ``signed`` wraps every tx in the ingest plane's ed25519 envelope
    (pre-signed before the clock starts)."""
    n = max(1, int(rate * duration))
    clients = max(1, min(clients, n))
    # schedule starts in the future so slot 0 is real; signed runs lead
    # far enough to pre-sign every tx first (pure-python ed25519 ~2 ms/tx
    # — overruns surface honestly in max_sched_lag_s, never hidden)
    lead = 0.0035 * n + 0.5 if signed else 0.2
    t0 = time.monotonic() + lead
    wall0 = time.time_ns() + int(lead * 1e9)
    sched = plan_schedule(rate, n, t0)
    prebuilt = None
    if signed:
        prebuilt = make_signed_txs(
            size, [wall0 + int(i / rate * 1e9) for i in range(n)])
    stats = {"planned": n, "sent": 0, "accepted": 0, "rejected": 0,
             "errors": 0, "max_sched_lag_s": 0.0}

    try:
        import aiohttp
    except ImportError:
        aiohttp = None

    async def drive(post):
        async def client(ci: int) -> None:
            for seq in range(ci, n, clients):
                target = sched[seq]
                now = time.monotonic()
                if target > now:
                    await asyncio.sleep(target - now)
                else:
                    stats["max_sched_lag_s"] = max(
                        stats["max_sched_lag_s"], now - target)
                if prebuilt is not None:
                    tx = prebuilt[seq]
                else:
                    planned_ns = wall0 + int((sched[seq] - t0) * 1e9)
                    tx = make_tx(size, seq, planned_ns)
                stats["sent"] += 1
                try:
                    code = await post(seq, tx)
                except Exception:
                    stats["errors"] += 1
                    continue
                if code == 0:
                    stats["accepted"] += 1
                else:
                    stats["rejected"] += 1

        await asyncio.gather(*(client(c) for c in range(clients)))

    if aiohttp is not None:
        # bounded like the urllib fallback: a wedged node must show up as
        # errors + planned-time latency, not stall a client slot for
        # aiohttp's 5-minute default
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=10)) as session:
            async def post(seq, tx):
                async with session.post(
                        endpoint + "/", data=_payload(seq, tx),
                        headers={"Content-Type": "application/json"}) as r:
                    doc = await r.json(content_type=None)
                return int((doc.get("result") or {}).get("code", 1))

            await drive(post)
    else:
        loop = asyncio.get_running_loop()

        def post_sync(seq, tx):
            req = urllib.request.Request(
                endpoint + "/", data=_payload(seq, tx),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                doc = json.load(r)
            return int((doc.get("result") or {}).get("code", 1))

        async def post(seq, tx):
            return await loop.run_in_executor(None, post_sync, seq, tx)

        await drive(post)

    stats["offered_rate"] = rate
    stats["duration_s"] = duration
    stats["clients"] = clients
    stats["size_bytes"] = size
    stats["signed"] = bool(signed)
    return stats


def load(endpoint: str, rate: float, duration: float, size: int,
         clients: int = 4, signed: bool = False) -> int:
    stats = asyncio.run(open_loop_load(endpoint, rate, duration, size,
                                       clients, signed=signed))
    print(json.dumps(stats))
    return 0 if stats["errors"] < stats["planned"] else 1


# -- report -------------------------------------------------------------------

def _rpc_get(endpoint: str, path: str, timeout: float = 10.0):
    with urllib.request.urlopen(endpoint + "/" + path, timeout=timeout) as r:
        return json.load(r)["result"]


def parse_block_time_ns(header_time: str) -> int:
    """RFC3339 header time -> unix ns."""
    from datetime import datetime, timezone

    ts = header_time.rstrip("Z")
    frac_ns = 0
    if "." in ts:
        ts, frac = ts.split(".", 1)
        frac_ns = int(frac[:9].ljust(9, "0"))
    return int(datetime.fromisoformat(ts).replace(
        tzinfo=timezone.utc).timestamp()) * 10**9 + frac_ns


def latencies_from_blocks(blocks: List[dict]):
    """Per-tx latency from block docs ({"block": {"header", "data"}}):
    block time minus the embedded PLANNED send time. Returns
    (latencies_s, first_block_ns, last_block_ns, n_txs)."""
    lats: List[float] = []
    first_ns = last_ns = None
    for blk in blocks:
        block_ns = parse_block_time_ns(blk["block"]["header"]["time"])
        found = False
        for raw in blk["block"]["data"]["txs"]:
            parsed = parse_tx(base64.b64decode(raw))
            if parsed is None:
                continue
            send_ns, _seq = parsed
            lats.append((block_ns - send_ns) / 1e9)
            found = True
        if found:
            first_ns = block_ns if first_ns is None else min(first_ns,
                                                             block_ns)
            last_ns = block_ns if last_ns is None else max(last_ns, block_ns)
    return lats, first_ns, last_ns, len(lats)


def summarize_timeline(doc: dict) -> dict:
    """Roll the /tx_timeline records up: per-stage stamp counts, and
    percentile stats over the node-measured total_s of committed records
    (the in-node broadcast→commit truth, immune to clock skew between the
    harness and the node)."""
    records = doc.get("records", [])
    stage_counts: Dict[str, int] = {}
    commit_s = []
    admission_s = []
    complete = 0
    for rec in records:
        marks = {m[0]: m[1] for m in rec.get("marks", [])}
        for s in marks:
            stage_counts[s] = stage_counts.get(s, 0) + 1
        if "rpc_received" in marks and "mempool_admitted" in marks:
            # admission latency: RPC front door -> lane insertion, the
            # in-node CheckTx-path cost
            admission_s.append(
                max(0.0, marks["mempool_admitted"] - marks["rpc_received"]))
        if rec.get("terminal") == "committed":
            commit_s.append(rec.get("total_s", 0.0))
            if {"rpc_received", "checktx_done", "mempool_admitted",
                    "committed"} <= marks.keys():
                complete += 1
    return {
        "records": len(records),
        "sealed_total": doc.get("sealed_total", 0),
        "sample_rate": doc.get("sample_rate"),
        "stage_counts": stage_counts,
        "complete_rpc_to_commit_records": complete,
        "node_commit_latency_s": percentiles(commit_s),
        "admission_latency_s": percentiles(admission_s),
    }


def scrape_prom(text: str, wanted_prefixes=("tendermint_mempool_",
                                            "tendermint_rpc_")) -> dict:
    """{series: value} for the ingestion-plane series (histogram buckets
    skipped — sums/counts/counters/gauges carry the report)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name = line.split("{", 1)[0].split(" ", 1)[0]
        if not name.startswith(wanted_prefixes) or name.endswith("_bucket"):
            continue
        try:
            series, value = line.rsplit(" ", 1)
            out[series] = float(value)
        except ValueError:
            continue
    return out


#: the series whose reason labels summarize_rejections rolls up: every
#: way the ingestion plane refuses or drops load (admission-control
#: sheds, pre-admission failures, post-admission evictions)
_REJECTION_SERIES = ("tendermint_mempool_shed_txs_total",
                     "tendermint_mempool_failed_txs",
                     "tendermint_mempool_evicted_txs_total")


def summarize_rejections(metrics: Dict[str, float]) -> Dict[str, dict]:
    """{series-kind: {reason: count}} from a /metrics scrape — dropped
    load rendered next to the latency percentiles, so a report can never
    show a rosy p99 while the node quietly shed half the offered txs."""
    out: Dict[str, dict] = {}
    for series, value in metrics.items():
        name, _, labels = series.partition("{")
        if name not in _REJECTION_SERIES or not value:
            continue
        reason = "total"
        for part in labels.rstrip("}").split(","):
            k, _, v = part.partition("=")
            if k == "reason":
                reason = v.strip('"')
        kind = name.rsplit("tendermint_mempool_", 1)[-1]
        out.setdefault(kind, {})[reason] = value
    return out


def report_doc(endpoint: str, metrics_endpoint: Optional[str] = None,
               max_blocks: int = 2000) -> dict:
    """Walk committed blocks + scrape the observability surfaces; the dict
    ``report`` prints."""
    status = _rpc_get(endpoint, "status")
    latest = int(status["sync_info"]["latest_block_height"])
    base = max(1, int(status["sync_info"]["earliest_block_height"] or 1),
               latest - max_blocks + 1)
    blocks = []
    for h in range(base, latest + 1):
        blocks.append(_rpc_get(endpoint, f"block?height={h}"))
    lats, first_ns, last_ns, n_txs = latencies_from_blocks(blocks)
    doc: dict = {"blocks_scanned": len(blocks), "txs": n_txs}
    if lats:
        span_s = (last_ns - first_ns) / 1e9
        doc["commit_window_s"] = round(span_s, 3)
        if span_s > 0:
            # sustained rate over the commit window (first to last block
            # carrying harness txs)
            doc["txs_per_sec"] = round(n_txs / span_s, 3)
        # a single-block burst has NO window: emitting the raw count as a
        # rate would read as throughput — leave the key
        # absent so callers fail loud instead of recording a fiction
        doc["latency_s"] = {k: round(v, 4)
                            for k, v in percentiles(lats).items()}
    try:
        doc["tx_timeline"] = summarize_timeline(
            _rpc_get(endpoint, "tx_timeline?limit=200"))
    except Exception as e:
        doc["tx_timeline"] = {"error": f"{type(e).__name__}: {e}"}
    if metrics_endpoint:
        try:
            with urllib.request.urlopen(metrics_endpoint, timeout=10) as r:
                doc["metrics"] = scrape_prom(r.read().decode())
            doc["rejections"] = summarize_rejections(doc["metrics"])
        except Exception as e:
            doc["metrics"] = {"error": f"{type(e).__name__}: {e}"}
    return doc


def report(endpoint: str, metrics_endpoint: Optional[str] = None) -> int:
    doc = report_doc(endpoint, metrics_endpoint)
    print(json.dumps(doc, indent=1))
    return 0 if doc["txs"] else 1


# -- self-test ----------------------------------------------------------------

def _synthetic_node(n_blocks: int = 4, rate: float = 100.0):
    """A stdlib HTTP server imitating the RPC surface the report walks:
    /status, /block?height=N with harness txs, /tx_timeline, /metrics."""
    import http.server
    import threading

    t0_ns = 1_700_000_000 * 10**9
    blocks = {}
    seq = 0
    for h in range(1, n_blocks + 1):
        block_ns = t0_ns + h * 10**9
        txs = []
        for _ in range(int(rate) // n_blocks):
            # sent 0.35 s before its block committed
            txs.append(base64.b64encode(
                make_tx(64, seq, block_ns - 350_000_000)).decode())
            seq += 1
        blocks[h] = {"block": {
            "header": {"time": time.strftime(
                "%Y-%m-%dT%H:%M:%S", time.gmtime(block_ns // 10**9))
                + ".%09dZ" % (block_ns % 10**9)},
            "data": {"txs": txs}}}
    timeline = {"enabled": True, "sample_rate": 1.0, "active": 0,
                "sealed_total": seq, "records": [
                    {"key": "ab" * 32, "terminal": "committed", "height": 2,
                     "total_s": 0.31, "rechecks": 0,
                     "marks": [["rpc_received", 1.0], ["checktx_done", 1.1],
                               ["mempool_admitted", 1.1],
                               ["first_gossip", 1.15],
                               ["proposal_included", 1.2],
                               ["committed", 1.31]],
                     "durations": {"rpc_received": 0.0,
                                   "checktx_done": 0.1}}]}
    metrics_text = "\n".join([
        "# TYPE tendermint_mempool_admitted_txs_total counter",
        "tendermint_mempool_admitted_txs_total %d" % seq,
        'tendermint_mempool_failed_txs{reason="full"} 3',
        'tendermint_mempool_failed_txs{reason="invalid-sig"} 2',
        'tendermint_mempool_shed_txs_total{reason="queue-full"} 5',
        'tendermint_mempool_shed_txs_total{reason="sender-rate"} 0',
        'tendermint_mempool_evicted_txs_total{reason="priority-evicted"} 1',
        'tendermint_mempool_tx_stage_seconds_bucket{le="+Inf",stage="committed"} 9',
        'tendermint_rpc_request_seconds_count{endpoint="broadcast_tx_sync",outcome="ok"} %d' % seq,
    ]) + "\n"

    class H(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.startswith("/status"):
                body = {"result": {"sync_info": {
                    "latest_block_height": str(n_blocks),
                    "earliest_block_height": "1"}}}
            elif self.path.startswith("/block?height="):
                h = int(self.path.split("=", 1)[1])
                body = {"result": blocks[h]}
            elif self.path.startswith("/tx_timeline"):
                body = {"result": timeline}
            elif self.path.startswith("/metrics"):
                data = metrics_text.encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
                return
            else:
                self.send_response(404)
                self.end_headers()
                return
            data = json.dumps(body).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def self_test() -> int:
    # tx roundtrip: planned send time and seq survive; padding exact
    tx = make_tx(128, 7, 123456789)
    assert len(tx) == 128 and parse_tx(tx) == (123456789, 7)
    assert parse_tx(b"nope") is None
    assert len(make_tx(8, 1)) == 20  # never truncated below the header
    # two txs with the same seq differ only in send time; different seqs
    # differ in padding too (unique on the wire)
    assert make_tx(64, 1, 5) != make_tx(64, 2, 5)
    # a signed-envelope wrapping is transparent to the report (stdlib
    # fake: framing only, no real signature needed to parse)
    wrapped = STX_MAGIC + b"\xaa" * 32 + struct.pack(">QQ", 1, 7) \
        + tx + b"\xbb" * 64
    assert strip_envelope(wrapped) == tx
    assert parse_tx(wrapped) == (123456789, 7)
    assert strip_envelope(b"stx1short") == b"stx1short"  # malformed: as-is

    # open-loop schedule: exact fixed-rate grid, planned up front
    sched = plan_schedule(50.0, 100, t0=10.0)
    assert len(sched) == 100 and sched[0] == 10.0
    deltas = [b - a for a, b in zip(sched, sched[1:])]
    assert all(abs(d - 0.02) < 1e-9 for d in deltas), "grid not fixed-rate"

    # percentile math: nearest-rank on a known ladder
    p = percentiles([i / 100.0 for i in range(1, 101)])
    assert abs(p["p50"] - 0.51) < 1e-9 and abs(p["p99"] - 1.0) < 1e-9
    assert abs(p["p99.9"] - 1.0) < 1e-9 and p["min"] == 0.01
    assert percentiles([]) == {}

    # block-walk aggregation against synthetic docs
    srv = _synthetic_node()
    try:
        ep = f"http://127.0.0.1:{srv.server_address[1]}"
        doc = report_doc(ep, metrics_endpoint=ep + "/metrics")
        assert doc["txs"] == 100, doc
        assert abs(doc["latency_s"]["p50"] - 0.35) < 0.01, doc
        assert abs(doc["latency_s"]["p99.9"] - 0.35) < 0.01, doc
        # 100 txs across blocks 1..4 committed over a 3 s span
        assert abs(doc["txs_per_sec"] - 100 / 3.0) < 0.5, doc
        tlr = doc["tx_timeline"]
        assert tlr["complete_rpc_to_commit_records"] == 1, tlr
        assert tlr["stage_counts"]["committed"] == 1
        assert abs(tlr["node_commit_latency_s"]["p50"] - 0.31) < 1e-6
        # in-node admission latency (rpc_received -> mempool_admitted wall
        # delta over the timeline records) — the checktx-p99 gate's source
        adm = tlr["admission_latency_s"]
        assert abs(adm["p50"] - 0.1) < 1e-6 and abs(adm["p99"] - 0.1) < 1e-6
        mtx = doc["metrics"]
        assert mtx["tendermint_mempool_admitted_txs_total"] == 100.0
        assert mtx['tendermint_mempool_failed_txs{reason="full"}'] == 3.0
        assert not any("_bucket{" in s or s.endswith("_bucket")
                       for s in mtx), \
            "histogram bucket leaked into the scrape"
        # dropped load is first-class in the report: reason-labeled
        # sheds/failures/evictions rolled up next to the percentiles
        rej = doc["rejections"]
        assert rej["shed_txs_total"] == {"queue-full": 5.0}  # zeros dropped
        assert rej["failed_txs"] == {"full": 3.0, "invalid-sig": 2.0}
        assert rej["evicted_txs_total"] == {"priority-evicted": 1.0}
    finally:
        srv.shutdown()
    print("loadtime self-test OK (schedule, percentiles, report, scrapes)")
    return 0


# -- CLI ----------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="loadtime",
                                description=__doc__.split("\n")[0])
    p.add_argument("--self-test", action="store_true")
    sub = p.add_subparsers(dest="command")
    for name in ("load", "run"):
        sp = sub.add_parser(name)
        sp.add_argument("--endpoint", default="http://127.0.0.1:26657")
        sp.add_argument("--rate", type=float, default=50.0)
        sp.add_argument("--duration", type=float, default=10.0)
        sp.add_argument("--size", type=int, default=128)
        sp.add_argument("--clients", type=int, default=4)
        sp.add_argument("--signed", action="store_true",
                        help="wrap txs in the ingest plane's ed25519 "
                             "envelope (pre-signed; needs the repo on "
                             "PYTHONPATH)")
        if name == "run":
            sp.add_argument("--metrics-endpoint", default=None)
            sp.add_argument("--settle", type=float, default=4.0,
                            help="seconds to wait after load for tail "
                                 "txs to commit before the report")
    rp = sub.add_parser("report")
    rp.add_argument("--endpoint", default="http://127.0.0.1:26657")
    rp.add_argument("--metrics-endpoint", default=None)
    ns = p.parse_args(argv)
    if ns.self_test:
        return self_test()
    if ns.command is None:
        p.error("need a command (load/report/run) or --self-test")
    if ns.command == "load":
        return load(ns.endpoint, ns.rate, ns.duration, ns.size, ns.clients,
                    signed=ns.signed)
    if ns.command == "run":
        stats = asyncio.run(open_loop_load(ns.endpoint, ns.rate, ns.duration,
                                           ns.size, ns.clients,
                                           signed=ns.signed))
        time.sleep(ns.settle)
        doc = report_doc(ns.endpoint, ns.metrics_endpoint)
        doc["load"] = stats
        print(json.dumps(doc, indent=1))
        return 0 if doc["txs"] else 1
    return report(ns.endpoint, ns.metrics_endpoint)


if __name__ == "__main__":
    sys.exit(main())
