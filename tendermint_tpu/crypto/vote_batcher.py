"""Micro-batched verification for the streaming vote path (HOT LOOP #1).

The reference's hottest call site is one scalar ed25519 verify per gossiped
vote (types/vote_set.go:205 → vote.go:147). Votes arrive concurrently from
many peer tasks but are *consumed* by the single-writer consensus loop —
verifying inside that loop serializes everything, so batching must happen
in front of it:

* per-peer reactor tasks call :meth:`preverify` BEFORE enqueueing the vote
  to the state machine. Pre-verifications accumulate across peers; a flush
  fires when ``max_batch`` is reached or ``deadline_s`` after the first
  pending item (SURVEY.md §7: deadline micro-batching with host fallback);
* a flush below ``min_device_batch`` verifies on the host scalar path (a
  device call would cost more than it saves at low rate); above it, ONE
  batched device call covers every pending vote;
* verdicts land in a one-shot cache keyed by (pubkey, msg, sig). When the
  single-writer loop later reaches ``VoteSet.add_vote`` →
  :meth:`verify_vote`, the lookup hits and no signature work happens on the
  hot loop at all. A miss (catchup votes, adversarial replays, no reactor)
  falls back to the host scalar verify — correctness NEVER depends on
  pre-verification, and accept/reject stays byte-identical to the spec.

``stats`` counts device/host/cache traffic so tests can assert the device
path is provably taken.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import time
from typing import Dict, List, Optional, Tuple

from ..libs.faults import faults
from ..libs.trace import tracer
from . import batch as _batch  # module ref: reads the live metrics hook
from . import phases as _phases
from .breaker import classify_device_error, device_breaker

logger = logging.getLogger("tmtpu.votebatch")

# at/above this many pending sigs a flush goes to the device; below, host
DEFAULT_MIN_DEVICE_BATCH = 16
DEFAULT_MAX_BATCH = 1024
DEFAULT_DEADLINE_S = 0.003
# consensus liveness bound: if a device flush hasn't produced verdicts in
# this long (cold XLA compile on a fresh node, device stall), the batch is
# re-verified on the host scalar path and later flushes stay host-side
# until the device call finally completes. Found in the wild: a catchup
# vote burst on a fresh node dispatched a cold-compile flush and consensus
# sat at the same height forever awaiting the verdict futures.
DEFAULT_DEVICE_TIMEOUT_S = 3.0
_CACHE_CAP = 16384


class BatchVoteVerifier:
    """Shared by the consensus reactor (preverify) and VoteSet (verify)."""

    def __init__(self, min_device_batch: int = DEFAULT_MIN_DEVICE_BATCH,
                 max_batch: int = DEFAULT_MAX_BATCH,
                 deadline_s: float = DEFAULT_DEADLINE_S,
                 device_timeout_s: float = DEFAULT_DEVICE_TIMEOUT_S):
        self.min_device_batch = min_device_batch
        self.max_batch = max_batch
        self.deadline_s = deadline_s
        self.device_timeout_s = device_timeout_s
        self._device_warming = False  # a device flush is past its deadline
        self._pending: List[Tuple[bytes, bytes, bytes, bytes, asyncio.Future]] = []
        self._flush_handle: Optional[asyncio.TimerHandle] = None
        # strong refs to in-flight flush tasks (event loop keeps only weak
        # refs; a GC'd flush would strand every pending preverify future)
        self._flush_tasks: set = set()
        self._cache: "collections.OrderedDict[bytes, bool]" = collections.OrderedDict()
        self.stats = collections.Counter()

    # -- sync side (VoteSet.add_vote, single-writer loop) --------------------

    def verify(self, pub, msg: bytes, sig: bytes) -> bool:
        """Byte-identical to pub.verify_signature; consumes a cached verdict
        when the reactor already pre-verified this exact (pk, msg, sig)."""
        key = self._key(pub.bytes(), msg, sig)
        hit = self._cache.pop(key, None)
        if hit is not None:
            self.stats["cache_hits"] += 1
            return hit
        self.stats["sync_host_sigs"] += 1
        return pub.verify_signature(msg, sig)

    # -- async side (reactor per-peer tasks) ---------------------------------

    async def preverify(self, pub, msg: bytes, sig: bytes) -> bool:
        """Micro-batched verification; resolves when this item's batch does."""
        from . import Ed25519PubKey

        if not isinstance(pub, Ed25519PubKey):
            # rare key types never ride the ed25519 kernel (and must not
            # poison the cache with a wrong-scheme verdict); off the loop so
            # a flood of odd keys can't stall peer dispatch and timers
            self.stats["non_ed25519"] += 1
            loop = asyncio.get_running_loop()
            return await loop.run_in_executor(
                None, pub.verify_signature, msg, sig)
        pk = pub.bytes()
        key = self._key(pk, msg, sig)
        cached = self._cache.get(key)
        if cached is not None:
            self.stats["cache_hits_pre"] += 1
            return cached
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._pending.append((key, pk, msg, sig, fut))
        if len(self._pending) >= self.max_batch:
            self._do_flush()
        elif self._flush_handle is None:
            self._flush_handle = loop.call_later(self.deadline_s, self._do_flush)
        return await fut

    def _do_flush(self) -> None:
        if self._flush_handle is not None:
            self._flush_handle.cancel()
            self._flush_handle = None
        batch = self._pending
        self._pending = []
        if not batch:
            return
        t = asyncio.ensure_future(self._run_flush(batch))
        self._flush_tasks.add(t)
        t.add_done_callback(self._flush_tasks.discard)

    async def _run_flush(self, batch) -> None:
        from . import Ed25519PubKey

        n = len(batch)
        loop = asyncio.get_running_loop()
        cm = _batch.metrics
        if cm is not None:
            # depth AT flush time = the flush size plus whatever already
            # queued behind it while this coroutine was scheduled
            cm.vote_queue_depth.set(n + len(self._pending))
        t_flush0 = time.perf_counter()
        t_v0 = t_flush0  # start of the verify work actually charged
        route = "scalar"

        def _host_verify():
            # live-plane batch verified on host: zero device phases, still
            # counted (crypto/phases.py host ledger). On the device-timeout
            # path the background flush ALSO records device segments for
            # the same votes when it completes — that is real duplicated
            # work (both verifies ran), and the ledger counts work done,
            # not unique votes
            _phases.count_host("live", n)
            return [Ed25519PubKey(pk).verify_signature(m, s)
                    for _key, pk, m, s, _fut in batch]

        use_device = n >= self.min_device_batch and not self._device_warming
        if use_device and not device_breaker.allow():
            # breaker OPEN (shared with BatchVerifier): no device attempt,
            # the host scalar path keeps the vote plane verifying
            use_device = False
            self.stats["breaker_rejections"] += 1
            if cm is not None:
                cm.device_fallbacks_total.labels("breaker_open").inc()
        try:
            if use_device:
                route = "device"
                pks = [b[1] for b in batch]
                msgs = [b[2] for b in batch]
                sigs = [b[3] for b in batch]

                def _device_verify():
                    # chaos seam: an armed `device.vote_flush` site raises
                    # on the executor thread, exactly where a real kernel /
                    # runtime failure would surface. The ed25519_jax import
                    # lives here too so a broken jax install takes the same
                    # host-fallback + breaker path as a runtime failure
                    # instead of failing every pending preverify future
                    faults.inject("device.vote_flush")
                    from .ed25519_jax import batch_verify_stream

                    # plane=live set INSIDE the thunk: contextvars do not
                    # follow run_in_executor onto the worker thread, and the
                    # flush's pack/dispatch/fetch must land in the phase
                    # histograms next to the sync plane's segments
                    with _phases.telemetry(plane="live"):
                        return batch_verify_stream(pks, msgs, sigs)

                dev = loop.run_in_executor(None, _device_verify)
                try:
                    out = await asyncio.wait_for(
                        asyncio.shield(dev), self.device_timeout_s)
                except asyncio.TimeoutError:
                    route = "scalar"
                    # the timeout wait is flush latency, not verify latency
                    t_v0 = time.perf_counter()
                    # liveness over throughput: verify THIS batch on host
                    # now; let the (probably compiling) device call finish
                    # in the background and re-enable the device path then
                    self._device_warming = True
                    device_breaker.record_failure()

                    def _device_ready(f):
                        self._device_warming = False
                        if not f.cancelled() and f.exception() is not None:
                            # consume it: the batch was already host-verified,
                            # and an unretrieved exception would dump a
                            # traceback at GC on a consensus-critical node
                            logger.info("background device flush failed "
                                        "after timeout fallback: %s",
                                        f.exception())

                    dev.add_done_callback(_device_ready)
                    self.stats["device_timeouts"] += 1
                    self.stats["host_batches"] += 1
                    self.stats["host_sigs"] += n
                    if cm is not None:
                        cm.device_fallbacks_total.labels(
                            "device_timeout").inc()
                    results = await loop.run_in_executor(None, _host_verify)
                except Exception as e:
                    # device call FAILED (not merely slow): re-verify this
                    # batch on host — verdicts stay byte-identical, no
                    # pending preverify future is ever failed by a device
                    # error — and feed the breaker
                    route = "scalar"
                    t_v0 = time.perf_counter()
                    reason = classify_device_error(e)
                    logger.warning("device vote flush failed (%s, n=%d): %s "
                                   "— re-verifying on host", reason, n, e)
                    device_breaker.record_failure()
                    self.stats["device_errors"] += 1
                    self.stats["host_batches"] += 1
                    self.stats["host_sigs"] += n
                    if cm is not None:
                        cm.device_fallbacks_total.labels(reason).inc()
                    results = await loop.run_in_executor(None, _host_verify)
                else:
                    device_breaker.record_success()
                    self.stats["device_batches"] += 1
                    self.stats["device_sigs"] += n
                    results = [bool(v) for v in out]
            else:
                self.stats["host_batches"] += 1
                self.stats["host_sigs"] += n
                # off the event loop: even a sub-threshold flush shouldn't
                # stall peers/timers for ~ms of OpenSSL work
                results = await loop.run_in_executor(None, _host_verify)
        except Exception as e:  # pragma: no cover - defensive
            logger.exception("vote batch flush failed: %s", e)
            for _, _, _, _, fut in batch:
                if not fut.done():
                    fut.set_exception(e)
            return
        if cm is not None:
            now = time.perf_counter()
            cm.vote_flush_latency_seconds.labels(route).observe(now - t_flush0)
            cm.batch_size.labels(route, "votes").observe(n)
            cm.routing_decisions_total.labels(route, "votes").inc()
            # verify-only time (the same semantics batch.py gives this
            # series): on a device-timeout fallback t_v0 excludes the wait
            cm.verify_latency_seconds.labels(route, "votes").observe(
                now - t_v0)
        if tracer.enabled:
            tracer.instant("vote_flush", n=n, route=route)
        for (key, _pk, _m, _s, fut), ok in zip(batch, results):
            self._cache[key] = ok
            self._cache.move_to_end(key)
            if not fut.done():
                fut.set_result(ok)
        while len(self._cache) > _CACHE_CAP:
            self._cache.popitem(last=False)

    async def flush_now(self) -> None:
        """Force a flush (tests / shutdown)."""
        self._do_flush()
        await asyncio.sleep(0)

    @staticmethod
    def _key(pk: bytes, msg: bytes, sig: bytes) -> bytes:
        return b"%d|" % len(pk) + pk + b"|%d|" % len(msg) + msg + sig
