"""Fleet metrics aggregator: scrape every node's /metrics, roll up cluster
truth.

A 4-node (or 32-node) run reported through node0's /metrics answers "how is
node0", not "how is the cluster". This scraper polls all nodes' Prometheus
endpoints on an interval and emits cluster rollups:

* per-series min / median / max across nodes (last sample),
* cross-node blocks/min: committed-height delta of the cluster MAX between
  the first and last scrape — the chain's real rate, immune to one
  lagging node,
* gossip wakeups-per-peer-link (sum of wakeup deltas / directed links),

as JSON consumed by the e2e runner (which also exports the path via TMTPU_FLEET_JSON so node debugdump bundles can include the
snapshot).

    python tools/fleet_scrape.py --ports 28664,28665,28666,28667 \
        --duration 30 --interval 2 --out fleet.json
    python tools/fleet_scrape.py --self-test

Stdlib-only on purpose: it runs inside the e2e harness and on boxes
that can't import the package.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import sys
import threading
import time
import urllib.request
from typing import Dict, List, Optional, Tuple

DEFAULT_NAMESPACE = "tendermint"


def parse_metrics(text: str) -> Dict[str, float]:
    """Prometheus text exposition -> {series: value}; series is
    ``name`` or ``name{labels}`` verbatim. Histogram bucket lines are
    skipped (the rollup works on sums/counts/gauges/counters)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            series, value = line.rsplit(" ", 1)
        except ValueError:
            continue
        name = series.split("{", 1)[0]
        if name.endswith("_bucket"):
            continue
        try:
            out[series] = float(value)
        except ValueError:
            continue
    return out


def scrape_endpoint(url: str, timeout: float = 2.0) -> Dict[str, float]:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return parse_metrics(r.read().decode())


def _value_by_suffix(sample: Dict[str, float], suffix: str) -> Optional[float]:
    """First series whose bare name ends with ``suffix`` (label-free
    gauges; suffix-matched so per-node registry namespaces don't hide
    them)."""
    for s, v in sample.items():
        if s.split("{", 1)[0].endswith(suffix):
            return v
    return None


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


class FleetScraper:
    """Poll N /metrics endpoints on an interval; rollup() aggregates."""

    def __init__(self, endpoints: Dict[str, object], interval_s: float = 2.0,
                 namespace: str = DEFAULT_NAMESPACE,
                 out_path: Optional[str] = None):
        """``endpoints`` maps node name -> /metrics URL, or to a CALLABLE
        returning exposition text (in-proc fleets — tools/soak.py passes
        each node's ``registry.render`` so the whole pipeline runs with
        no HTTP servers). ``out_path``, if set, gets a fresh rollup JSON
        after every sweep (the debugdump seam: TMTPU_FLEET_JSON points
        nodes at this file)."""
        self.endpoints = dict(endpoints)
        self.interval_s = interval_s
        self.namespace = namespace
        self.out_path = out_path
        self.first: Dict[str, Tuple[float, Dict[str, float]]] = {}
        self.last: Dict[str, Tuple[float, Dict[str, float]]] = {}
        self.scrapes = 0
        self.errors = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    # -- sampling ----------------------------------------------------------

    def add_endpoint(self, name: str, url: str) -> None:
        """Safe while the loop runs (late-joining nodes)."""
        self.endpoints[name] = url

    def remove_endpoint(self, name: str) -> None:
        """Safe while the loop runs (churned-out nodes): a scheduled leave
        must stop counting as a scrape error against the fleet."""
        self.endpoints.pop(name, None)

    def sweep(self) -> int:
        """Scrape every endpoint once, concurrently; returns how many
        answered. Concurrency matters at fleet scale: serially, a few
        wedged-but-listening nodes (2s urlopen timeout each — exactly the
        stall scenario the debugdump snapshot targets) would stretch one
        sweep past interval_s and stale the rollup."""

        def one(name: str, url):
            try:
                if callable(url):
                    return name, parse_metrics(url()), time.time()
                return name, scrape_endpoint(url), time.time()
            except Exception:
                return name, None, 0.0

        ok = 0
        items = list(self.endpoints.items())
        if items:
            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=min(16, len(items))) as ex:
                for name, sample, now in ex.map(lambda kv: one(*kv), items):
                    if sample is None:
                        self.errors += 1
                        continue
                    with self._lock:
                        self.first.setdefault(name, (now, sample))
                        self.last[name] = (now, sample)
                    ok += 1
        self.scrapes += 1
        if self.out_path:
            try:
                self.write(self.out_path)
            except Exception:
                pass
        return ok

    def _run(self) -> None:
        while not self._stop.is_set():
            self.sweep()
            self._stop.wait(self.interval_s)

    def start(self) -> "FleetScraper":
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="fleet-scrape")
        self._thread.start()
        return self

    def stop(self) -> dict:
        """Stop the loop, take one final sweep, return the rollup."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(self.interval_s + 5.0)
            self._thread = None
        self.sweep()
        return self.rollup()

    # -- aggregation -------------------------------------------------------

    def _series_name(self, suffix: str) -> str:
        return f"{self.namespace}_{suffix}" if self.namespace else suffix

    def rollup(self) -> dict:
        with self._lock:
            first = dict(self.first)
            last = dict(self.last)
        nodes = sorted(last)
        series: Dict[str, dict] = {}
        all_names = sorted({s for _, sample in last.values()
                            for s in sample})
        for s in all_names:
            vals = [last[n][1][s] for n in nodes if s in last[n][1]]
            if not vals:
                continue
            series[s] = {"min": min(vals), "median": _median(vals),
                         "max": max(vals), "nodes": len(vals)}
        # cluster blocks/min from the committed-height series: the cluster
        # commits a height when ANY node does, so cluster truth is the MAX
        # across nodes at each sample point
        height_s = self._series_name("consensus_committed_height")
        out = {
            "nodes": nodes,
            "n_nodes": len(nodes),
            "scrapes": self.scrapes,
            "scrape_errors": self.errors,
            "series": series,
        }
        h_first = [first[n][1].get(height_s) for n in nodes
                   if height_s in first[n][1]]
        h_last = [last[n][1].get(height_s) for n in nodes
                  if height_s in last[n][1]]
        if h_first and h_last:
            t_first = min(first[n][0] for n in nodes)
            t_last = max(last[n][0] for n in nodes)
            elapsed = max(1e-9, t_last - t_first)
            blocks = max(h_last) - max(h_first)
            out["elapsed_s"] = round(elapsed, 3)
            out["cluster_height"] = max(h_last)
            out["cluster_blocks_per_min"] = round(blocks / elapsed * 60.0, 3)
        # gossip wakeups per directed peer link, from counter deltas summed
        # across nodes (each of the n nodes runs routines per peer)
        def counter_delta(prefix: str) -> float:
            """Summed last-minus-first deltas across all nodes and label
            sets of one counter family, clamped at 0 per series: a
            restarted node resets its counters (Prometheus rate()-style
            counter-reset handling)."""
            total = 0.0
            for n in nodes:
                for s, v in last[n][1].items():
                    if s.split("{", 1)[0] == prefix:
                        total += max(0.0, v - first[n][1].get(s, 0.0))
            return total

        delta = counter_delta(
            self._series_name("consensus_gossip_wakeups_total"))
        links = max(1, len(nodes) * (len(nodes) - 1))
        out["gossip_wakeups_delta"] = delta
        out["wakeups_per_peer_link"] = round(delta / links, 3)

        # ingestion-plane rollups (mempool + RPC series): counter deltas
        # summed across nodes over the scrape window — the cluster's tx
        # admission/rejection rate and RPC traffic, the fleet view the
        # mempool_full chaos cell reads
        admitted = counter_delta(
            self._series_name("mempool_admitted_txs_total"))
        rejected = counter_delta(self._series_name("mempool_failed_txs"))
        rpc_reqs = counter_delta(
            self._series_name("rpc_request_seconds_count"))
        out["txs_admitted_delta"] = admitted
        out["txs_rejected_delta"] = rejected
        out["rpc_requests_delta"] = rpc_reqs
        # divide by the UNROUNDED window (the rounded elapsed_s is 0.0
        # when only one sweep has landed — cluster_blocks_per_min floors
        # the same way); rates only exist once the window is real
        if nodes:
            window = (max(last[n][0] for n in nodes)
                      - min(first[n][0] for n in nodes))
            if window > 0:
                out["cluster_txs_admitted_per_sec"] = round(
                    admitted / window, 3)
                out["cluster_rpc_requests_per_sec"] = round(
                    rpc_reqs / window, 3)
        # per-node process watermarks (libs/watermark.py sampler): last
        # value + growth slope over the scrape window, clamped at zero
        # (a restarted node resets its gauges — same rate()-style
        # counter-reset handling as counter_delta). Matched by series
        # SUFFIX, not full name: in-proc fleets give every node its own
        # registry namespace, and the leak-slope SLO must still see them.
        process: Dict[str, dict] = {}
        for n in nodes:
            t0, s0 = first[n]
            t1, s1 = last[n]
            window = t1 - t0
            rec = {}
            for suffix in self.PROCESS_SUFFIXES:
                v1 = _value_by_suffix(s1, suffix)
                if v1 is None:
                    continue
                v0 = _value_by_suffix(s0, suffix)
                grown = max(0.0, v1 - (v1 if v0 is None else v0))
                rec[suffix[len("process_"):]] = {
                    "last": v1,
                    "slope_per_s": (round(grown / window, 3)
                                    if window > 0 else 0.0),
                }
            if rec:
                process[n] = rec
        if process:
            out["process"] = process
        return out

    #: the watermark gauge family (ProcessMetrics), namespace-agnostic
    PROCESS_SUFFIXES = ("process_rss_bytes", "process_open_fds",
                        "process_wal_bytes", "process_txlife_ring_depth",
                        "process_metric_series")

    def write(self, path: str) -> str:
        import os
        import tempfile

        doc = self.rollup()
        # unique tmp per call: stop()'s final sweep can race a wedged
        # worker sweep, and two writers on one shared tmp would tear it
        fd, tmp = tempfile.mkstemp(prefix=os.path.basename(path) + ".",
                                   dir=os.path.dirname(path) or ".")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1)
            os.replace(tmp, path)  # readers (debugdump) never see a tear
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path


# -- self-test ----------------------------------------------------------------

def _serve_synthetic(n_nodes: int):
    """Tiny per-node HTTP servers whose /metrics advance on every scrape:
    node i's committed height starts at 10+i and gains 2 per request."""
    import http.server

    servers = []

    def make_handler(state):
        class H(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                state["hits"] += 1
                h = state["h0"] + 2 * state["hits"]
                body = "\n".join([
                    "# HELP tendermint_consensus_committed_height x",
                    "# TYPE tendermint_consensus_committed_height gauge",
                    f"tendermint_consensus_committed_height {h}",
                    "tendermint_consensus_gossip_wakeups_total"
                    '{routine="data"} ' + str(20 * state["hits"]),
                    "tendermint_mempool_admitted_txs_total "
                    + str(5 * state["hits"]),
                    'tendermint_mempool_failed_txs{reason="full"} '
                    + str(2 * state["hits"]),
                    "tendermint_rpc_request_seconds_count"
                    '{endpoint="broadcast_tx_sync",outcome="ok"} '
                    + str(8 * state["hits"]),
                    "tendermint_consensus_stage_seconds_sum"
                    '{stage="commit_finalized"} 0.5',
                    "tendermint_consensus_stage_seconds_count"
                    '{stage="commit_finalized"} 10',
                    'tendermint_consensus_stage_seconds_bucket'
                    '{le="+Inf",stage="commit_finalized"} 10',
                    # process watermarks: rss ramps (leak-slope subject),
                    # wal SHRINKS (clamped to 0 slope — gauge reset
                    # handling), the rest hold steady
                    "tendermint_process_rss_bytes "
                    + str(1_000_000 + 4096 * state["hits"]),
                    "tendermint_process_open_fds 32",
                    "tendermint_process_wal_bytes "
                    + str(max(0, 16384 - 1000 * state["hits"])),
                    "tendermint_process_txlife_ring_depth 7",
                    "tendermint_process_metric_series 450",
                ]).encode() + b"\n"
                self.send_response(200)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        return H

    for i in range(n_nodes):
        state = {"h0": 10 + i, "hits": 0}
        srv = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), make_handler(state))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        servers.append(srv)
    return servers


def self_test() -> int:
    servers = _serve_synthetic(3)
    try:
        endpoints = {f"node{i}": f"http://127.0.0.1:{s.server_address[1]}"
                     "/metrics" for i, s in enumerate(servers)}
        sc = FleetScraper(endpoints, interval_s=0.05)
        assert sc.sweep() == 3
        time.sleep(0.25)
        assert sc.sweep() == 3
        roll = sc.rollup()
        assert roll["n_nodes"] == 3
        assert roll["scrape_errors"] == 0
        hs = roll["series"]["tendermint_consensus_committed_height"]
        # second scrape: node i reports 10+i+4 -> min 14, max 16, median 15
        assert (hs["min"], hs["median"], hs["max"]) == (14.0, 15.0, 16.0), hs
        # bucket lines never enter the rollup
        assert not any(s.startswith(
            "tendermint_consensus_stage_seconds_bucket")
            for s in roll["series"])
        assert "tendermint_consensus_stage_seconds_sum" \
            '{stage="commit_finalized"}' in roll["series"]
        # cluster height is the MAX across nodes: node2's 12+2*2 = 16
        assert roll["cluster_height"] == 16.0, roll
        assert roll["cluster_blocks_per_min"] > 0
        # wakeups: each node +20 per scrape -> delta 3*20 over 6 links
        assert abs(roll["wakeups_per_peer_link"] - 10.0) < 0.001, roll
        # ingestion rollups: one extra scrape per node between first and
        # last -> admitted +5, rejected +2, rpc +8, each summed over 3
        # nodes; the per-second rates divide by the window
        assert roll["txs_admitted_delta"] == 15.0, roll
        assert roll["txs_rejected_delta"] == 6.0, roll
        assert roll["rpc_requests_delta"] == 24.0, roll
        assert roll["cluster_txs_admitted_per_sec"] > 0, roll
        assert roll["cluster_rpc_requests_per_sec"] > 0, roll
        # process watermarks: rss grew 4096 over the window (positive
        # slope), wal SHRANK (slope clamps to 0.0, not negative), and
        # steady gauges report zero slope with a live last value
        proc = roll["process"]["node0"]
        assert proc["rss_bytes"]["last"] == 1_000_000 + 8192, proc
        assert proc["rss_bytes"]["slope_per_s"] > 0, proc
        assert proc["wal_bytes"]["slope_per_s"] == 0.0, proc
        assert proc["open_fds"] == {"last": 32.0, "slope_per_s": 0.0}, proc
        assert proc["txlife_ring_depth"]["last"] == 7.0, proc
        assert proc["metric_series"]["last"] == 450.0, proc
        # threaded mode + out_path freshness
        import os
        import tempfile

        fd, path = tempfile.mkstemp(suffix=".json")
        os.close(fd)
        try:
            sc2 = FleetScraper(endpoints, interval_s=0.05,
                               out_path=path).start()
            time.sleep(0.3)
            roll2 = sc2.stop()
            assert roll2["scrapes"] >= 2
            with open(path) as f:
                on_disk = json.load(f)
            assert on_disk["n_nodes"] == 3
        finally:
            os.unlink(path)
        # a dead endpoint degrades to errors, not a crash
        sc3 = FleetScraper({"gone": "http://127.0.0.1:9/metrics"},
                           interval_s=0.05)
        assert sc3.sweep() == 0 and sc3.errors == 1
        assert sc3.rollup()["n_nodes"] == 0
        # callable endpoints (in-proc fleets, no HTTP): scraped through
        # the same parse path, and the process rollup still finds the
        # watermarks under a per-node registry namespace
        calls = {"n": 0}

        def render():
            calls["n"] += 1
            return (f"churn_val0_12345_process_rss_bytes "
                    f"{100.0 + calls['n']}\n"
                    f"churn_val0_12345_consensus_committed_height 5\n")

        sc4 = FleetScraper({"inproc": render}, interval_s=0.05)
        assert sc4.sweep() == 1
        time.sleep(0.05)
        assert sc4.sweep() == 1
        r4 = sc4.rollup()
        assert r4["process"]["inproc"]["rss_bytes"]["last"] == 102.0, r4
        assert r4["process"]["inproc"]["rss_bytes"]["slope_per_s"] > 0, r4
        # a raising callable counts as a scrape error, not a crash
        def boom():
            raise RuntimeError("down")
        sc5 = FleetScraper({"bad": boom}, interval_s=0.05)
        assert sc5.sweep() == 0 and sc5.errors == 1
    finally:
        for s in servers:
            s.shutdown()
    print("fleet_scrape self-test OK (3 nodes, rollup + cluster rate)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--endpoints", default="",
                    help="comma-separated name=url pairs (or bare urls)")
    ap.add_argument("--ports", default="",
                    help="comma-separated /metrics ports on --host")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--namespace", default=DEFAULT_NAMESPACE)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the final rollup JSON here "
                         "(and keep it fresh during the run)")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    endpoints: Dict[str, str] = {}
    for i, part in enumerate(p for p in args.endpoints.split(",") if p):
        name, _, url = part.rpartition("=")
        endpoints[name or f"node{i}"] = url
    for i, port in enumerate(p for p in args.ports.split(",") if p):
        endpoints[f"node{i}"] = f"http://{args.host}:{int(port)}/metrics"
    if not endpoints:
        ap.error("no endpoints (use --endpoints or --ports, or --self-test)")
    sc = FleetScraper(endpoints, interval_s=args.interval,
                      namespace=args.namespace, out_path=args.out).start()
    try:
        time.sleep(args.duration)
    except KeyboardInterrupt:
        pass
    # stop()'s final sweep already refreshed args.out (the out_path seam)
    roll = sc.stop()
    print(json.dumps(roll, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
