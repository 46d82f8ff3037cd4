"""Multi-device sharded streaming verifier — the dispatcher that finally
uses all N chips.

With several devices present every production dispatch used to go to
chip 0; the one multichip entry point
(:func:`sharded.batch_verify_sharded`) is a one-shot shard_map call nothing
routed through. This module shards :func:`verify.batch_verify_stream`
segments **round-robin across a device pool**, with:

* **one dedicated packing/transfer worker thread per device**: a lane's
  worker packs and transfers segment i+1 while its device executes
  segment i, and the lanes run side by side until host packing saturates
  (how far that scales on locally attached chips: not measured);
* **per-device circuit breakers** (crypto/breaker.lane_breaker): a sick
  chip degrades the pool to N-1 healthy lanes — its queued segments
  re-shard onto healthy peers with zero dropped signatures — instead of
  collapsing the whole verification plane to host fallback. Only when
  every lane is sick does the call raise, and then the caller's shared
  ``device_breaker`` fallback takes over exactly as before;
* **per-lane chaos sites** ``device.lane.<platform>:<id>`` (libs/faults):
  arm exactly one device label and watch the pool degrade.

Verdicts are byte-identical to the single-device path: segments are exact
slices of the same packed wire format, fetched and reassembled in order
(differential tests in tests/test_multidevice_stream.py). Every segment
records pack/dispatch/fetch phases with its lane's device label, so the
PR 8 ``crypto_device_dispatch_total{device}`` / ``crypto_device_inflight``
series and the Perfetto segment tracks show per-chip occupancy for free.

Knobs: ``TMTPU_VERIFY_DEVICES`` (device count; 0/1 disables the pool,
unset = all visible devices), ``TMTPU_DEVICE_BREAKER_THRESHOLD`` /
``TMTPU_DEVICE_BREAKER_COOLDOWN_S`` (per-lane breakers). The pool engages
from 2 x ``verify.SEG_MIN_SIGS`` signatures, in segments of at most
``verify.SEG_CHUNKS`` chunks. On machines with
one physical chip, ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
exercises the full dispatch topology against a forced host mesh.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax

from ...libs.faults import faults
from .. import phases
from ..breaker import lane_breaker
from . import verify as V

logger = logging.getLogger("tmtpu.multidevice")

ENV_DEVICES = "TMTPU_VERIFY_DEVICES"

#: fault-site family: one site per lane, e.g. ``device.lane.tpu:3``
LANE_SITE_PREFIX = "device.lane."


class AllLanesFailed(RuntimeError):
    """Every pool lane is sick or failed this batch; the caller's shared
    device_breaker / host-fallback path takes over."""


def plan_segments(k_total: int, n_lanes: int,
                  seg_chunks: int) -> List[Tuple[int, int]]:
    """Deterministic shard plan: ``[(size_chunks, lane_index), ...]``.

    Near-equal segments of at most ``seg_chunks`` scan-chunks, at least
    two per lane when the batch is big enough (each lane's worker then
    packs segment i+1 while its segment i executes — the same
    double-buffering the single-device path uses, now per lane), assigned
    round-robin so the plan is a pure function of (k_total, n_lanes,
    seg_chunks)."""
    if k_total <= 0:
        return []
    n_segs = min(k_total, max(-(-k_total // seg_chunks),
                              min(k_total, 2 * n_lanes)))
    base, extra = divmod(k_total, n_segs)
    sizes = [base + (1 if i < extra else 0) for i in range(n_segs)]
    return [(s, i % n_lanes) for i, s in enumerate(sizes)]


class DeviceLane:
    """One device plus its dedicated packing/transfer worker and breaker."""

    __slots__ = ("index", "device", "label", "breaker", "pool")

    def __init__(self, index: int, device):
        self.index = index
        self.device = device
        self.label = f"{device.platform}:{device.id}"
        self.breaker = lane_breaker(self.label)
        self.pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"ed25519-lane{index}")

    def shutdown(self) -> None:
        self.pool.shutdown(wait=False)


class MultiDeviceStream:
    """Shards one batch_verify_stream call across a pool of device lanes."""

    def __init__(self, devices: Optional[Sequence] = None,
                 min_sigs: Optional[int] = None,
                 seg_chunks: Optional[int] = None):
        if devices is None:
            devices = jax.devices()
        self.lanes = [DeviceLane(i, d) for i, d in enumerate(devices)]
        self.min_sigs = (min_sigs if min_sigs is not None
                         else 2 * V.SEG_MIN_SIGS)
        self.seg_chunks = (seg_chunks if seg_chunks is not None
                           else V.SEG_CHUNKS)
        self.stats = collections.Counter()

    # -- health -------------------------------------------------------------

    def eligible_lanes(self) -> List[DeviceLane]:
        """Lanes whose breakers would admit a dispatch (read-only check)."""
        return [l for l in self.lanes if l.breaker.peek()]

    def engaged(self, n: int) -> bool:
        """Should a batch of n shard across the pool? Needs enough
        signatures to amortize per-device dispatch overhead and at least
        two healthy lanes (with one, the single-device path is strictly
        better — no cross-lane coordination)."""
        return n >= self.min_sigs and len(self.eligible_lanes()) >= 2

    # -- the dispatcher -----------------------------------------------------

    def verify(self, pks, msgs, sigs, chunk: int, columns=None,
               t_entry: Optional[float] = None) -> np.ndarray:
        """(N,) bool — the batch as round-robin segments across healthy
        lanes, fetched and reassembled in order. A lane failure re-shards
        that segment onto the next healthy lane (zero dropped signatures)
        and feeds the lane's breaker; :class:`AllLanesFailed` surfaces only
        when no healthy lane remains."""
        n = len(pks)
        lanes = self.eligible_lanes()
        if not lanes:
            raise AllLanesFailed(
                f"0/{len(self.lanes)} device lanes healthy")
        plan = plan_segments(-(-n // chunk), len(lanes), self.seg_chunks)
        bounds, lo = [], 0
        for size, lane_i in plan:
            hi = min(lo + size * chunk, n)
            bounds.append((lo, hi, lane_i))
            lo = hi
        plane, height = phases.context()
        all_recs: List[phases.Segment] = []

        def submit(seg_i, a, b, lane):
            rec = phases.Segment(
                sigs=b - a, chunk=chunk, seg=seg_i, n_segs=len(bounds),
                device=lane.label, plane=plane, height=height)
            all_recs.append(rec)
            col = columns.slice(a, b) if columns is not None else None
            # a batch that came as columns alone has no rows to slice
            seg_msgs = msgs[a:b] if msgs is not None else None
            fut = lane.pool.submit(
                self._run_lane, lane, rec, pks[a:b], seg_msgs, sigs[a:b],
                chunk, col)
            return rec, fut

        # admit only lanes the plan actually dispatches to (allow() is the
        # MUTATING breaker check: it latches a half-open probe slot, and a
        # probe on a lane that never gets a segment would stay phantom-
        # in-flight for a whole cooldown, starving the lane's rejoin)
        admitted = []
        for lane in lanes[:min(len(bounds), len(lanes))]:
            if lane.breaker.allow():
                admitted.append(lane)
        if not admitted:
            raise AllLanesFailed(
                f"0/{len(self.lanes)} device lanes admitted a dispatch")
        lane_of = lambda i: admitted[i % len(admitted)]

        # windowed submission: at most ~2 queued segments per lane (the
        # same depth the single-device pipeline keeps). Submitting the
        # whole plan up front would hold every segment's packed host
        # arrays + dispatched device buffers live at once — unbounded by
        # batch size instead of by lane count.
        window = 2 * len(admitted)
        recs: List[Optional[phases.Segment]] = [None] * len(bounds)
        futs: List = [None] * len(bounds)
        for seg_i in range(min(window, len(bounds))):
            a, b, lane_i = bounds[seg_i]
            recs[seg_i], futs[seg_i] = submit(seg_i, a, b, lane_of(lane_i))
        if t_entry is not None:
            # stream-entry host work (bucket grouping) is critical-path
            # pack cost; charge it to segment 0 like the single-device path
            recs[0].t0 = t_entry

        out = np.zeros(n, dtype=bool)
        failed_lanes: set = set()
        try:
            for seg_i, (a, b, lane_i) in enumerate(bounds):
                lane = lane_of(lane_i)
                nxt = seg_i + window
                if nxt < len(bounds):
                    a2, b2, lane_i2 = bounds[nxt]
                    recs[nxt], futs[nxt] = submit(nxt, a2, b2,
                                                  lane_of(lane_i2))
                tried = set()
                while True:
                    t_wait0 = time.perf_counter()
                    try:
                        dev, ok = futs[seg_i].result()
                        arr = np.asarray(dev)
                    except Exception as e:
                        recs[seg_i].abandon()
                        tried.add(lane.label)
                        failed_lanes.add(lane.label)
                        lane.breaker.record_failure()
                        self.stats["lane_errors"] += 1
                        logger.warning(
                            "device lane %s failed segment %d/%d (n=%d): "
                            "%s — re-sharding to a healthy peer",
                            lane.label, seg_i, len(bounds), b - a, e)
                        lane = self._next_lane(tried)
                        if lane is None:
                            raise AllLanesFailed(
                                f"segment {seg_i} failed on every healthy "
                                f"lane ({sorted(tried)})") from e
                        self.stats["resharded_segments"] += 1
                        recs[seg_i], futs[seg_i] = submit(seg_i, a, b, lane)
                        continue
                    recs[seg_i].fetched(
                        wait_s=time.perf_counter() - t_wait0)
                    if lane.label not in failed_lanes:
                        lane.breaker.record_success()
                    out[a:b] = arr.reshape(-1)[:b - a] & ok
                    break
        finally:
            for r in all_recs:
                r.abandon()  # no-op for fetched records
        phases.observe_overlap(recs)
        self.stats["calls"] += 1
        self.stats["sigs"] += n
        return out

    def _next_lane(self, tried: set) -> Optional[DeviceLane]:
        """The next healthy lane not already tried for this segment."""
        for lane in self.lanes:
            if lane.label in tried:
                continue
            if lane.breaker.allow():
                return lane
        return None

    @staticmethod
    def _run_lane(lane: DeviceLane, rec, pks, msgs, sigs, chunk,
                  columns):
        """One segment on its lane's worker: per-lane chaos site, pack
        into the worker's scratch, commit to the lane's device, dispatch
        async. Runs on the lane thread; the coordinating thread fetches."""
        faults.inject(LANE_SITE_PREFIX + lane.label)
        return V._run_dispatch(rec, pks, msgs, sigs, chunk,
                               device=lane.device, columns=columns)

    def shutdown(self) -> None:
        for lane in self.lanes:
            lane.shutdown()


# -- the process pool ---------------------------------------------------------

_POOL: Optional[MultiDeviceStream] = None
_POOL_RESOLVED = False
_POOL_LOCK = threading.Lock()


def pool() -> Optional[MultiDeviceStream]:
    """The process-wide MultiDeviceStream, built lazily from jax.devices()
    and TMTPU_VERIFY_DEVICES. None when fewer than two devices are in
    play (or the env knob disables the pool)."""
    global _POOL, _POOL_RESOLVED
    if _POOL_RESOLVED:
        return _POOL
    with _POOL_LOCK:
        if _POOL_RESOLVED:
            return _POOL
        built = None
        try:
            env = os.environ.get(ENV_DEVICES)
            want = int(env) if env else None
            if want is None or want > 1:
                devs = jax.devices()
                count = len(devs) if want is None else min(want, len(devs))
                if count > 1:
                    built = MultiDeviceStream(devices=devs[:count])
                    logger.info(
                        "multi-device verify pool: %d lanes (%s)", count,
                        ", ".join(l.label for l in built.lanes))
        except Exception as e:  # no backend, bad env value, ...
            logger.warning("multi-device pool unavailable: %s", e)
        _POOL = built
        _POOL_RESOLVED = True
        return _POOL


def reset_pool() -> None:
    """Tear down the pool (tests / env-knob changes re-resolve lazily)."""
    global _POOL, _POOL_RESOLVED
    with _POOL_LOCK:
        if _POOL is not None:
            _POOL.shutdown()
        _POOL = None
        _POOL_RESOLVED = False


@contextlib.contextmanager
def disabled():
    """Force the single-device path inside the block (the chip smoke's A/B
    and parity tests measure 'what would this cost without the pool')."""
    global _POOL, _POOL_RESOLVED
    with _POOL_LOCK:
        prev = (_POOL, _POOL_RESOLVED)
        _POOL, _POOL_RESOLVED = None, True
    try:
        yield
    finally:
        with _POOL_LOCK:
            _POOL, _POOL_RESOLVED = prev
