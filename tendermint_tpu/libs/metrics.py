"""Prometheus-style metrics, dependency-free
(reference per-module metrics.go + prometheus/client_golang).

Counter / Gauge / Histogram with labels, collected in a Registry that
renders the text exposition format served on the node's
``instrumentation.prometheus_listen_addr`` /metrics endpoint
(reference node/node.go:962).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help_: str, label_names: Sequence[str] = ()):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        self._values: Dict[Tuple[str, ...], float] = {}
        self._lock = threading.Lock()

    def labels(self, *values: str) -> "_Bound":
        if len(values) != len(self.label_names):
            raise ValueError(f"{self.name}: expected {len(self.label_names)} "
                             f"labels, got {len(values)}")
        return _Bound(self, tuple(str(v) for v in values))

    def _fmt_labels(self, lv: Tuple[str, ...]) -> str:
        if not lv:
            return ""
        # sorted by label name — the SAME ordering Histogram bucket lines
        # use, so one metric's series never mix two orderings and raw-text
        # diffs/greps are deterministic (client_golang sorts identically)
        inner = ",".join(f'{k}="{_escape_label(v)}"'
                         for k, v in sorted(zip(self.label_names, lv)))
        return "{" + inner + "}"

    def render(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} {self.kind}"]
        with self._lock:
            items = sorted(self._values.items())
        for lv, val in items:
            out.append(f"{self.name}{self._fmt_labels(lv)} {_fmt(val)}")
        return out

    def _check_arity(self, labels: Tuple) -> Tuple[str, ...]:
        if len(labels) != len(self.label_names):
            raise ValueError(f"{self.name}: expected {len(self.label_names)} "
                             f"labels, got {len(labels)}")
        return tuple(str(v) for v in labels)

    def value(self, *labels: str) -> float:
        """Current value for a counter/gauge label set (0.0 if never
        touched) — the seam bench/debug tooling reads instead of parsing
        the exposition text."""
        lv = self._check_arity(labels)
        with self._lock:
            return self._values.get(lv, 0.0)


class _Bound:
    __slots__ = ("metric", "lv")

    def __init__(self, metric: "_Metric", lv: Tuple[str, ...]):
        self.metric = metric
        self.lv = lv

    def inc(self, amount: float = 1.0) -> None:
        self.metric._inc(self.lv, amount)

    def set(self, value: float) -> None:
        self.metric._set(self.lv, value)

    def observe(self, value: float) -> None:
        self.metric._observe(self.lv, value)


def _fmt(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(float(v))


def _escape_label(v: str) -> str:
    """Prometheus text-format label-value escaping: backslash, double
    quote, and newline must be escaped or the series line is unparseable
    (exposition format spec, "Line format")."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1.0) -> None:
        self._inc((), amount)

    def _inc(self, lv: Tuple[str, ...], amount: float) -> None:
        with self._lock:
            self._values[lv] = self._values.get(lv, 0.0) + amount

    def _set(self, lv, value):  # misuse guard
        raise TypeError("counters only go up")

    def _observe(self, lv, value):  # misuse guard
        raise TypeError(f"{self.name}: observe() is only valid on histograms")


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float) -> None:
        self._set((), value)

    def inc(self, amount: float = 1.0) -> None:
        self._inc((), amount)

    def _set(self, lv: Tuple[str, ...], value: float) -> None:
        with self._lock:
            self._values[lv] = float(value)

    def _inc(self, lv: Tuple[str, ...], amount: float) -> None:
        with self._lock:
            self._values[lv] = self._values.get(lv, 0.0) + amount

    def _observe(self, lv, value):  # misuse guard
        raise TypeError(f"{self.name}: observe() is only valid on histograms")


class Histogram(_Metric):
    kind = "histogram"
    DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                       2.5, 5.0, 10.0)

    def __init__(self, name: str, help_: str, label_names: Sequence[str] = (),
                 buckets: Optional[Sequence[float]] = None):
        super().__init__(name, help_, label_names)
        self.buckets = tuple(buckets or self.DEFAULT_BUCKETS)
        self._counts: Dict[Tuple[str, ...], List[int]] = {}
        self._sums: Dict[Tuple[str, ...], float] = {}
        self._totals: Dict[Tuple[str, ...], int] = {}

    def observe(self, value: float) -> None:
        self._observe((), value)

    def _observe(self, lv: Tuple[str, ...], value: float) -> None:
        with self._lock:
            counts = self._counts.setdefault(lv, [0] * len(self.buckets))
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
            self._sums[lv] = self._sums.get(lv, 0.0) + value
            self._totals[lv] = self._totals.get(lv, 0) + 1

    def _set(self, lv, value):  # misuse guard
        raise TypeError(f"{self.name}: set() is not valid on histograms")

    def _inc(self, lv, amount):  # misuse guard
        raise TypeError(f"{self.name}: inc() is not valid on histograms")

    def value(self, *labels):  # misuse guard: _values is never populated
        raise TypeError(f"{self.name}: histograms have no single value — "
                        "use sum_value()/count_value()")

    def sum_value(self, *labels: str) -> float:
        lv = self._check_arity(labels)
        with self._lock:
            return self._sums.get(lv, 0.0)

    def count_value(self, *labels: str) -> int:
        lv = self._check_arity(labels)
        with self._lock:
            return self._totals.get(lv, 0)

    def _bucket_labels(self, lv: Tuple[str, ...], le: str) -> str:
        # deterministic: label names sorted, `le` always last (Prometheus
        # only requires consistency, but scrapers and tests diff raw text)
        pairs = sorted(zip(self.label_names, lv))
        pairs.append(("le", le))
        return ",".join(f'{k}="{_escape_label(v)}"' for k, v in pairs)

    def render(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} histogram"]
        with self._lock:
            items = sorted(self._counts.items())
            for lv, counts in items:
                for b, c in zip(self.buckets, counts):
                    inner = self._bucket_labels(lv, _fmt(b))
                    out.append(f"{self.name}_bucket{{{inner}}} {c}")
                inner = self._bucket_labels(lv, "+Inf")
                out.append(f"{self.name}_bucket{{{inner}}} {self._totals[lv]}")
                out.append(f"{self.name}_sum{self._fmt_labels(lv)} "
                           f"{_fmt(self._sums[lv])}")
                out.append(f"{self.name}_count{self._fmt_labels(lv)} "
                           f"{self._totals[lv]}")
        return out


class Registry:
    def __init__(self, namespace: str = "tendermint"):
        self.namespace = namespace
        self._metrics: List[_Metric] = []
        self._names: set = set()
        self._lock = threading.Lock()

    def counter(self, subsystem: str, name: str, help_: str,
                labels: Sequence[str] = ()) -> Counter:
        return self._add(Counter(self._fq(subsystem, name), help_, labels))

    def gauge(self, subsystem: str, name: str, help_: str,
              labels: Sequence[str] = ()) -> Gauge:
        return self._add(Gauge(self._fq(subsystem, name), help_, labels))

    def histogram(self, subsystem: str, name: str, help_: str,
                  labels: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None) -> Histogram:
        return self._add(Histogram(self._fq(subsystem, name), help_, labels,
                                   buckets))

    def _fq(self, subsystem: str, name: str) -> str:
        parts = [p for p in (self.namespace, subsystem, name) if p]
        return "_".join(parts)

    def _add(self, m):
        with self._lock:
            if m.name in self._names:
                # a silent duplicate double-renders the series and Prometheus
                # rejects the whole scrape — fail at registration instead
                raise ValueError(f"metric {m.name!r} already registered")
            self._names.add(m.name)
            self._metrics.append(m)
        return m

    def render(self) -> str:
        lines: List[str] = []
        with self._lock:
            metrics = list(self._metrics)
        for m in metrics:
            lines.extend(m.render())
        return "\n".join(lines) + "\n"


# --- per-module metric sets (reference consensus/metrics.go etc.) -----------

class ConsensusMetrics:
    """(consensus/metrics.go — the load-bearing subset of its 23 series)"""

    def __init__(self, reg: Registry):
        g, c, h = reg.gauge, reg.counter, reg.histogram
        self.height = g("consensus", "height", "Height of the chain.")
        self.rounds = g("consensus", "rounds", "Round of the chain.")
        self.validators = g("consensus", "validators",
                            "Number of validators.")
        self.validators_power = g("consensus", "validators_power",
                                  "Total voting power of validators.")
        self.missing_validators = g("consensus", "missing_validators",
                                    "Validators missing from the last commit.")
        self.byzantine_validators = g("consensus", "byzantine_validators",
                                      "Validators that equivocated.")
        self.num_txs = g("consensus", "num_txs", "Txs in the latest block.")
        self.block_size_bytes = g("consensus", "block_size_bytes",
                                  "Size of the latest block.")
        self.total_txs = c("consensus", "total_txs", "Total committed txs.")
        self.block_interval_seconds = h(
            "consensus", "block_interval_seconds",
            "Time between this and the last block.")
        self.fast_syncing = g("consensus", "fast_syncing",
                              "Whether the node is fast syncing.")
        self.block_parts = c("consensus", "block_parts",
                             "Block parts transmitted per peer.", ["peer_id"])
        self.quorum_prevote_delay = h(
            "consensus", "quorum_prevote_delay",
            "Seconds from proposal timestamp to 2/3 prevotes.")
        self.missing_validators_power = g(
            "consensus", "missing_validators_power",
            "Voting power of validators missing from the last commit.")
        self.byzantine_validators_power = g(
            "consensus", "byzantine_validators_power",
            "Voting power of validators that equivocated.")
        self.validator_power = g(
            "consensus", "validator_power",
            "This node's voting power (0 when not a validator).")
        self.validator_last_signed_height = g(
            "consensus", "validator_last_signed_height",
            "Last height this node's validator signed.")
        self.validator_missed_blocks = c(
            "consensus", "validator_missed_blocks",
            "Blocks this node's validator missed signing.")
        self.committed_height = g(
            "consensus", "committed_height", "Latest committed height.")
        self.state_syncing = g(
            "consensus", "state_syncing",
            "Whether the node is state syncing.")
        self.proposal_receive_count = c(
            "consensus", "proposal_receive_count",
            "Proposals received.", ["status"])
        self.latest_block_height = g(
            "consensus", "latest_block_height",
            "Alias of committed height for dashboards.")
        # -- live consensus plane (event-driven gossip + WAL group commit) --
        self.gossip_wakeups_total = c(
            "consensus", "gossip_wakeups_total",
            "Gossip iterations triggered by an event wakeup.", ["routine"])
        self.gossip_polls_total = c(
            "consensus", "gossip_polls_total",
            "Gossip iterations triggered by the fallback sleep cap.",
            ["routine"])
        self.encode_cache_hits_total = c(
            "consensus", "encode_cache_hits_total",
            "Wire-encode cache hits (one encode served many sends).",
            ["kind"])
        self.encode_cache_misses_total = c(
            "consensus", "encode_cache_misses_total",
            "Wire-encode cache misses (message encoded fresh).", ["kind"])
        self.wal_fsyncs_total = c(
            "consensus", "wal_fsyncs_total", "WAL fsync calls.")
        self.wal_records_per_fsync = h(
            "consensus", "wal_records_per_fsync",
            "WAL records made durable by each fsync (group-commit batch).",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
        self.wal_fsync_seconds = h(
            "consensus", "wal_fsync_seconds", "WAL fsync latency.",
            buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                     0.01, 0.025, 0.05, 0.1))
        # -- robustness plane (fault injection / watchdog) ---------------
        self.wal_fsync_errors_total = c(
            "consensus", "wal_fsync_errors_total",
            "WAL fsync calls that failed (fatal per fsync_error_policy).")
        # attribute keeps the catalog name; the series is
        # tendermint_consensus_stalled_total (subsystem supplies the prefix)
        self.consensus_stalled_total = c(
            "consensus", "stalled_total",
            "Stall episodes: no committed-height advance for "
            "stall_watchdog_s.")
        self.gossip_peer_refreshes_total = c(
            "consensus", "gossip_peer_refreshes_total",
            "Silent-peer delivery bitmaps cleared for re-gossip "
            "(gossip_stall_refresh_s).")
        # -- observability plane (consensus/timeline.py stage timeline) --
        # series tendermint_consensus_stage_seconds{stage=...}: per-height
        # interval from the previous stage mark to this one, observed when
        # the height seals at commit — the per-phase latency decomposition
        # of the consensus round (arXiv 2302.00418 / 2410.03347 attribute
        # wins exactly this way)
        self.stage_seconds = h(
            "consensus", "stage_seconds",
            "Seconds from the previous consensus stage mark to this one "
            "(proposal_received, prevote_sent, prevote_quorum, "
            "precommit_sent, precommit_quorum, commit_finalized).",
            ["stage"],
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0))
        # -- degraded-network plane (round churn under WAN/gray/asym) ----
        # reasons: timeout_propose / timeout_prevote (timeout-driven step
        # escalations that put the round on the nil-vote path),
        # timeout_precommit (the round actually advances), polka_skip
        # (2/3-any votes seen at a higher round jump us forward)
        self.round_advances_total = c(
            "consensus", "round_advances_total",
            "Round-escalation events by cause (timeout_propose, "
            "timeout_prevote, timeout_precommit, polka_skip).", ["reason"])
        self.rounds_per_height = h(
            "consensus", "rounds_per_height",
            "Rounds a height took to commit (1 = no escalation).",
            buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32))


class MempoolMetrics:
    """(mempool/metrics.go — grown the ingestion-plane series a
    high-traffic mempool needs: depth in txs AND bytes on every mutation
    path, admission/rejection/eviction taxonomies, CheckTx/recheck
    latency distributions, and the per-tx lifecycle histograms fed by
    libs/txlife.py)."""

    #: CheckTx is an in-proc app call (~us) but socket/grpc apps reach ms
    CHECKTX_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                       0.01, 0.025, 0.05, 0.1, 0.25)
    #: broadcast→commit spans one to several block intervals
    COMMIT_LATENCY_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                              30.0, 60.0)

    def __init__(self, reg: Registry):
        g, c, h = reg.gauge, reg.counter, reg.histogram
        self.size = g("mempool", "size", "Number of uncommitted txs.")
        self.size_bytes = g("mempool", "size_bytes",
                            "Total bytes of uncommitted txs (depth-bytes).")
        self.tx_size_bytes = h(
            "mempool", "tx_size_bytes", "Tx sizes in bytes.",
            buckets=(32, 128, 512, 2048, 8192, 32768, 131072))
        self.failed_txs = c(
            "mempool", "failed_txs",
            "Txs rejected before admission, by reason "
            "(cache-dup, app-reject, full, too-large, invalid-sig, "
            "malformed-stx).", ["reason"])
        self.admitted_txs_total = c(
            "mempool", "admitted_txs_total",
            "Txs that passed CheckTx and entered the mempool.")
        self.evicted_txs_total = c(
            "mempool", "evicted_txs_total",
            "Admitted txs removed without committing, by reason "
            "(recheck-failed, flush, priority-evicted, ttl-expired).",
            ["reason"])
        # -- ingestion fast path (mempool/ingest.py) ---------------------
        self.shed_txs_total = c(
            "mempool", "shed_txs_total",
            "Txs refused by admission control before any verification "
            "or app work, by reason (queue-full, sender-rate, "
            "fee-floor).", ["reason"])
        self.intake_queue_depth = g(
            "mempool", "intake_queue_depth",
            "Ingest pipeline intake depth sampled at each micro-batch "
            "flush (bounded by mempool.ingest_queue_size).")
        self.preverified_txs_total = c(
            "mempool", "preverified_txs_total",
            "Signature pre-verification verdicts, by path/outcome "
            "(accepted/rejected via the batched pipeline, scalar for "
            "inline admissions).", ["outcome"])
        self.preverify_cache_hits_total = c(
            "mempool", "preverify_cache_hits_total",
            "Signature checks skipped because a cached pre-verification "
            "verdict stood, by consumer (batch, checktx, recheck — "
            "recheck hits are what keep commits from re-verification "
            "storms).", ["path"])
        self.preverify_latency_seconds = h(
            "mempool", "preverify_latency_seconds",
            "Wall seconds one micro-batch spent in signature "
            "pre-verification (host or device, routed by "
            "crypto.BatchVerifier).",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5))
        self.recheck_times = c("mempool", "recheck_times",
                               "Times txs were rechecked.")
        self.checktx_latency_seconds = h(
            "mempool", "checktx_latency_seconds",
            "App CheckTx latency for first-time admission checks.",
            buckets=self.CHECKTX_BUCKETS)
        self.recheck_latency_seconds = h(
            "mempool", "recheck_latency_seconds",
            "App CheckTx latency for post-block rechecks.",
            buckets=self.CHECKTX_BUCKETS)
        # -- per-tx lifecycle (libs/txlife.py) ---------------------------
        self.tx_stage_seconds = h(
            "mempool", "tx_stage_seconds",
            "Seconds from the previous lifecycle stage stamp to this one "
            "(rpc_received, preverified, checktx_done, mempool_admitted, "
            "first_gossip, proposal_included, committed, rechecked).",
            ["stage"],
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0))
        self.tx_commit_latency_seconds = h(
            "mempool", "tx_commit_latency_seconds",
            "End-to-end seconds from a sampled tx's first lifecycle stamp "
            "(rpc_received on the ingesting node) to its block commit.",
            buckets=self.COMMIT_LATENCY_BUCKETS)


class P2PMetrics:
    """(p2p/metrics.go)"""

    def __init__(self, reg: Registry):
        self.peers = reg.gauge("p2p", "peers", "Connected peers.")
        self.peer_receive_bytes_total = reg.counter(
            "p2p", "peer_receive_bytes_total",
            "Bytes received per channel.", ["chID"])
        self.peer_send_bytes_total = reg.counter(
            "p2p", "peer_send_bytes_total",
            "Bytes sent per channel.", ["chID"])


class RPCMetrics:
    """The RPC front door (no reference analog — rpc/jsonrpc has no
    metrics.go; an ingestion plane for millions of users starts with
    knowing what each endpoint costs). Per-endpoint latency/outcome,
    in-flight pressure, websocket-subscriber count, and request/response
    size distributions, all served back over the same /metrics endpoint
    the fleet scraper rolls up."""

    LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                       0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
    SIZE_BUCKETS = (64, 256, 1024, 4096, 16384, 65536, 262144, 1048576)

    def __init__(self, reg: Registry):
        g, c, h = reg.gauge, reg.counter, reg.histogram
        self.request_seconds = h(
            "rpc", "request_seconds",
            "RPC request latency per endpoint (outcome ok|error; unknown "
            "methods are bucketed under endpoint=\"unknown\" so scans "
            "cannot explode series cardinality).",
            ["endpoint", "outcome"], buckets=self.LATENCY_BUCKETS)
        self.requests_in_flight = g(
            "rpc", "requests_in_flight",
            "RPC requests currently being handled.")
        self.websocket_subscribers = g(
            "rpc", "websocket_subscribers",
            "Open /websocket connections.")
        self.request_size_bytes = h(
            "rpc", "request_size_bytes",
            "HTTP request body (POST) or path+query (GET) bytes.",
            buckets=self.SIZE_BUCKETS)
        self.response_size_bytes = h(
            "rpc", "response_size_bytes",
            "Serialized JSON response bytes.", buckets=self.SIZE_BUCKETS)
        self.ws_slow_consumer_evictions_total = c(
            "rpc", "ws_slow_consumer_evictions_total",
            "Websocket subscribers evicted because their bounded send "
            "queue overflowed (a stalled reader must never back up the "
            "event bus).")


class LightServeMetrics:
    """The light-client serving plane (light/serve.py): coalescer flush
    shape, header-cache effectiveness, and reason-labeled admission sheds
    for a population of thousands of concurrent light clients."""

    OCCUPANCY_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

    def __init__(self, reg: Registry):
        c, h = reg.counter, reg.histogram
        self.requests_total = c(
            "lightserve", "requests_total",
            "Serving requests per route.", ["route"])
        self.sheds_total = c(
            "lightserve", "sheds_total",
            "Admission sheds per reason (client-rate, banned, queue-full); "
            "every shed is an explicit RPC error, never a stall.",
            ["reason"])
        self.flushes_total = c(
            "lightserve", "flushes_total",
            "Coalescer flushes (one batched device call each).")
        self.flush_occupancy = h(
            "lightserve", "flush_occupancy",
            "Verify requests per coalescer flush.",
            buckets=self.OCCUPANCY_BUCKETS)
        self.verdict_cache_hits_total = c(
            "lightserve", "verdict_cache_hits_total",
            "Verify requests answered from the bounded verdict cache.")
        self.cache_hits_total = c(
            "lightserve", "cache_hits_total",
            "Header-cache hits on /light_header.")
        self.cache_misses_total = c(
            "lightserve", "cache_misses_total",
            "Header-cache misses on /light_header.")
        self.cache_prefetches_total = c(
            "lightserve", "cache_prefetches_total",
            "Bisection-skeleton heights prefetched and pinned.")
        self.client_bans_total = c(
            "lightserve", "client_bans_total",
            "Clients banned by the abuse scoreboard, per reason.",
            ["reason"])


class StateMetrics:
    """(state/metrics.go)"""

    def __init__(self, reg: Registry):
        self.block_processing_time = reg.histogram(
            "state", "block_processing_time",
            "Seconds in ApplyBlock.", buckets=(0.001, 0.005, 0.01, 0.025,
                                               0.05, 0.1, 0.25, 0.5, 1.0))
        # optimistic parallel execution plane (state/parallel.py)
        self.parallel_exec_blocks = reg.counter(
            "state", "parallel_exec_blocks_total",
            "Blocks executed via the optimistic parallel path.")
        self.parallel_exec_conflict_txs = reg.counter(
            "state", "parallel_exec_conflict_txs_total",
            "Txs serially re-executed after conflict validation.")
        self.parallel_exec_fallbacks = reg.counter(
            "state", "parallel_exec_fallbacks_total",
            "Blocks that fell back to the serial spec path.",
            labels=("reason",))


class CryptoMetrics:
    """The verification plane (no reference analog — the batched verifier
    is this build's defining feature, so its routing must be observable:
    batch-size and verify-latency distributions are the decisive tuning
    inputs for committee-based consensus [arXiv:2302.00418], and
    offload-vs-host routing counters the same for an offload engine
    [arXiv:2112.02229])."""

    #: batch sizes span 1 (evidence pairs) to 128k (10k-val windows)
    BATCH_BUCKETS = (1, 4, 16, 64, 256, 1024, 4096, 16384, 65536, 131072)
    LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                       0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

    def __init__(self, reg: Registry):
        g, c, h = reg.gauge, reg.counter, reg.histogram
        self.batch_size = h(
            "crypto", "batch_size",
            "Signatures per verification batch.", ["route", "plane"],
            buckets=self.BATCH_BUCKETS)
        self.verify_latency_seconds = h(
            "crypto", "verify_latency_seconds",
            "End-to-end batch verification latency.", ["route", "plane"],
            buckets=self.LATENCY_BUCKETS)
        self.routing_decisions_total = c(
            "crypto", "routing_decisions_total",
            "Batches routed per backend.", ["route", "plane"])
        self.device_fallbacks_total = c(
            "crypto", "device_fallbacks_total",
            "Device-path batches re-verified on host.", ["reason"])
        self.precomputed_hits_total = c(
            "crypto", "precomputed_hits_total",
            "Batches served entirely from precomputed verdicts.", ["plane"])
        self.pad_waste_ratio = g(
            "crypto", "pad_waste_ratio",
            "Padded-slot fraction of the last device batch.", ["plane"])
        self.vote_queue_depth = g(
            "crypto", "vote_queue_depth",
            "Votes pending in the micro-batcher at last flush.")
        self.vote_flush_latency_seconds = h(
            "crypto", "vote_flush_latency_seconds",
            "Vote micro-batch flush latency.", ["route"],
            buckets=self.LATENCY_BUCKETS)
        # -- device circuit breaker (crypto/breaker.py) ------------------
        self.breaker_state = g(
            "crypto", "breaker_state",
            "Circuit breaker state: 0 closed, 1 open, 2 half-open.",
            ["breaker"])
        self.breaker_transitions_total = c(
            "crypto", "breaker_transitions_total",
            "Circuit breaker state transitions.",
            ["breaker", "from", "to"])


class DeviceMetrics:
    """The device dispatch pipeline (crypto/phases.py recorder): per-segment
    pack / dispatch / fetch phase latencies, per-device dispatch traffic,
    and the pipeline-overlap ratio — the dispatch cost model, measured by
    the system itself. Offload engines are
    designed from exactly this stage-occupancy breakdown (arXiv 2112.02229)
    and committee-consensus throughput studies attribute wins through it
    (arXiv 2302.00418)."""

    #: phase times span ~100 us (CPU pack of a small chunk) to a
    #: multi-second fetch behind a cold compile
    PHASE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

    def __init__(self, reg: Registry):
        g, c, h = reg.gauge, reg.counter, reg.histogram
        self.segment_phase_seconds = h(
            "crypto", "segment_phase_seconds",
            "Seconds per dispatch phase of each device segment "
            "(pack: host wire packing; dispatch: async kernel call; "
            "fetch: dispatch return to verdicts host-resident).",
            ["phase", "plane"], buckets=self.PHASE_BUCKETS)
        self.segment_sigs = h(
            "crypto", "segment_sigs",
            "Signatures per dispatched device segment.", ["plane"],
            buckets=CryptoMetrics.BATCH_BUCKETS)
        self.pipeline_overlap_ratio = g(
            "crypto", "pipeline_overlap_ratio",
            "Last segmented call's in-flight wall over summed in-flight "
            "time (1.0 = serial dispatches, 0.5 = 2-deep fully overlapped).")
        self.device_dispatch_total = c(
            "crypto", "device_dispatch_total",
            "Segments dispatched per device ('host' = batches the scalar "
            "route kept off the device entirely).", ["device"])
        self.device_inflight = g(
            "crypto", "device_inflight",
            "Segments currently in flight per device.", ["device"])
        # -- aggregate-signature (BLS) plane telemetry --------------------
        # PR 17 made commits collapse to one pairing; these series make
        # that pairing visible: wall cost per call, calls per verify mode
        # (full / light / trusting — the three verify_commit* entries),
        # and the wire size the aggregation bought.
        self.pairing_seconds = h(
            "crypto", "pairing_seconds",
            "Wall seconds per aggregate-signature verify call (pack + "
            "subgroup checks + the one pairing), by crypto plane.",
            ["plane"], buckets=self.PHASE_BUCKETS)
        self.aggregate_verify_total = c(
            "crypto", "aggregate_verify_total",
            "Aggregate-signature verifications by scheme and verify mode "
            "(full/light/trusting).", ["scheme", "mode"])
        self.aggregated_commit_bytes = h(
            "crypto", "aggregated_commit_bytes",
            "Encoded wire size of verified aggregated commits (48-byte "
            "agg sig + signer bitmap + overhead; an ed25519 commit at the "
            "same validator count is ~100 B/signer).",
            buckets=(64, 96, 128, 192, 256, 384, 512, 1024, 4096, 16384))


class ProcessMetrics:
    """Process resource watermarks (libs/watermark.py sampler): the
    slow-leak surface. Sampled right before each /metrics render, so
    FleetScraper sees fresh values and the soak plane's leak-slope SLOs
    (bounded RSS/WAL/ring growth, bounded series cardinality) have a
    stream to judge."""

    def __init__(self, reg: Registry):
        g = reg.gauge
        self.rss_bytes = g(
            "process", "rss_bytes",
            "Resident set size of this process in bytes.")
        self.open_fds = g(
            "process", "open_fds",
            "Open file descriptors held by this process.")
        self.wal_bytes = g(
            "process", "wal_bytes",
            "On-disk bytes of this node's WALs including rotated "
            "segments.")
        self.txlife_ring_depth = g(
            "process", "txlife_ring_depth",
            "Sealed tx-lifecycle records currently held in the bounded "
            "ring.")
        self.metric_series = g(
            "process", "metric_series",
            "Rendered series cardinality of this node's own metric "
            "registry (label-set blowups show up here first).")


class FaultMetrics:
    """The fault-injection plane (libs/faults.py): how many injected
    faults actually fired, per site — the denominator every chaos
    assertion divides by."""

    def __init__(self, reg: Registry):
        self.faults_injected_total = reg.counter(
            "faults", "injected_total",
            "Injected faults fired, per site.", ["site"])


class RecoveryMetrics:
    """The crash-recovery plane (wired at node startup): what this boot
    had to repair and how long coming back took — recovery time as a
    measurable, gateable quantity instead of an anecdote. restarts_total
    is fed by the restart supervisor (the e2e runner exports the count/
    reason into the relaunched node's env so the series survives on the
    node's own /metrics)."""

    def __init__(self, reg: Registry):
        g, c = reg.gauge, reg.counter
        self.restarts_total = c(
            "recovery", "restarts_total",
            "Supervised restarts that led to boots of this node, by exit "
            "reason (crash, signal-<n>).", ["reason"])
        self.wal_repairs_total = c(
            "recovery", "wal_repairs_total",
            "Consensus-WAL torn tails truncated by repair-on-open.")
        self.wal_repaired_bytes_total = c(
            "recovery", "wal_repaired_bytes_total",
            "Undecodable bytes removed from the WAL tail at open.")
        self.wal_records_replayed = g(
            "recovery", "wal_records_replayed",
            "WAL records replayed into the state machine at the last boot "
            "(catchup replay for the in-flight height).")
        # attribute keeps the catalog name; the series is
        # tendermint_recovery_duration_seconds (subsystem supplies the
        # prefix — same convention as consensus_stalled_total)
        self.recovery_duration_seconds = g(
            "recovery", "duration_seconds",
            "Seconds from node assembly to consensus ready at the last "
            "boot (stores + handshake + WAL replay + reactor start).")


class BlocksyncMetrics:
    """The fast-sync apply plane (blockchain/reactor.py 2-deep pipeline)."""

    STAGE_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5)

    def __init__(self, reg: Registry):
        g, c, h = reg.gauge, reg.counter, reg.histogram
        self.stage_seconds = h(
            "blocksync", "stage_seconds",
            "Seconds per pipeline stage observation "
            "(hash/verify per window, exec/store per block).", ["stage"],
            buckets=self.STAGE_BUCKETS)
        self.window_blocks = h(
            "blocksync", "window_blocks",
            "Blocks applied per verify window.",
            buckets=(1, 2, 4, 8, 16, 32))
        self.pipelined_windows_total = c(
            "blocksync", "pipelined_windows_total",
            "Windows whose stage A overlapped the previous apply.")
        self.inline_windows_total = c(
            "blocksync", "inline_windows_total",
            "Windows verified inline (pipeline starved or first window).")
        self.set_spanning_windows_total = c(
            "blocksync", "set_spanning_windows_total",
            "Windows verified in one batch across a validator-set change "
            "(their headers claim more than one set).")
        self.spanning_window_blocks_total = c(
            "blocksync", "spanning_window_blocks_total",
            "Blocks applied from windows that span a validator-set change.")
        self.verdict_candidates_total = c(
            "blocksync", "verdict_candidates_total",
            "Signatures stage A verified ahead for the windows applied, "
            "each under its set's key or a guessed one.")
        self.verdict_misses_total = c(
            "blocksync", "verdict_misses_total",
            "Signatures the apply-time rules asked for that stage A had "
            "not verified (a guessed key that was not the set's, a row "
            "never guessed): verified then, on their own.")
        self.lookahead_stalls_total = c(
            "blocksync", "lookahead_stalls_total",
            "Iterations where the next window's blocks were not yet "
            "downloaded when the lookahead wanted to start.")
        self.stale_window_discards_total = c(
            "blocksync", "stale_window_discards_total",
            "Prepared windows discarded because the pool moved underneath "
            "them.")
        # -- adversarial resilience (libs/peerscore.py scoreboard) --------
        self.peer_bans_total = c(
            "blocksync", "peer_bans_total",
            "Block-sync peers banned after repeated bad blocks/commits.",
            ["reason"])
        self.sync_retries_total = c(
            "blocksync", "sync_retries_total",
            "Block windows redone after a bad block from a peer.")


class StateSyncMetrics:
    """The snapshot-restore plane (statesync/ — reference
    statesync/metrics.go, grown the adversarial counters a Byzantine
    bootstrap needs: who lied, how often we retried, and whether the
    victim banned anyone)."""

    RESTORE_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                       120.0, 300.0)

    def __init__(self, reg: Registry):
        g, c, h = reg.gauge, reg.counter, reg.histogram
        self.snapshots_offered_total = c(
            "statesync", "snapshots_offered_total",
            "Snapshots discovered from peers and added to the pool.")
        self.snapshots_rejected_total = c(
            "statesync", "snapshots_rejected_total",
            "Snapshots rejected during restore.", ["reason"])
        self.chunks_fetched_total = c(
            "statesync", "chunks_fetched_total",
            "Snapshot chunks received and queued.")
        self.chunks_discarded_total = c(
            "statesync", "chunks_discarded_total",
            "Chunks discarded (timeout, app retry, rejected sender).")
        self.chunks_refetched_total = c(
            "statesync", "chunks_refetched_total",
            "Chunks the app explicitly asked to refetch.")
        self.restore_duration_seconds = h(
            "statesync", "restore_duration_seconds",
            "Wall seconds per snapshot restore attempt.",
            ["result"], buckets=self.RESTORE_BUCKETS)
        self.discovery_rounds_total = c(
            "statesync", "discovery_rounds_total",
            "Snapshot re-discovery rounds (pool empty, peers re-asked).")
        self.peer_bans_total = c(
            "statesync", "peer_bans_total",
            "Sync peers banned for serving bad snapshot data.", ["reason"])
        self.sync_retries_total = c(
            "statesync", "sync_retries_total",
            "Chunk fetches retried against another peer.")
        self.fallbacks_total = c(
            "statesync", "fallbacks_total",
            "State-sync attempts abandoned for the fast-sync-from-genesis "
            "fallback (no viable snapshots / providers exhausted).")


class NodeMetrics:
    """All module metric sets over one registry (node/node.go:117
    MetricsProvider)."""

    def __init__(self, namespace: str = "tendermint"):
        self.registry = Registry(namespace)
        self.consensus = ConsensusMetrics(self.registry)
        self.mempool = MempoolMetrics(self.registry)
        self.rpc = RPCMetrics(self.registry)
        self.lightserve = LightServeMetrics(self.registry)
        self.p2p = P2PMetrics(self.registry)
        self.state = StateMetrics(self.registry)
        self.crypto = CryptoMetrics(self.registry)
        self.device = DeviceMetrics(self.registry)
        self.blocksync = BlocksyncMetrics(self.registry)
        self.statesync = StateSyncMetrics(self.registry)
        self.faults = FaultMetrics(self.registry)
        self.recovery = RecoveryMetrics(self.registry)
        self.process = ProcessMetrics(self.registry)
        # tracer ring saturation (libs/trace.py): a bounded ring that
        # silently ate its front reads as "nothing happened early on" —
        # this series (plus the export header's `dropped`) says otherwise
        self.trace_dropped_events_total = self.registry.counter(
            "trace", "dropped_events_total",
            "Trace events pushed off the bounded ring by newer events.")
