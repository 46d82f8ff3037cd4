"""The counters the benchmark reads from jax and from the program, and the
check that the DEVICE did the device's work.

``CompileCounter`` and the route check are copied from ``chip_smoke.py``
(PR 22), where they ran on the chip. The program's production fallback
turns a refused kernel into "every verdict right, at host speed": a cell
that measures the device path has to fail then, not report.
"""

from __future__ import annotations

import threading
from typing import Dict, List


class CompileCounter:
    """Programs this process asked XLA for, from jax's monitoring events:
    every request ends in one backend_compile_duration event, and one the
    persistent cache served fires cache_hits first, on the same thread."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self._tl = threading.local()
        self._lock = threading.Lock()
        self.c = {"programs": 0, "cache_served": 0, "compiled": 0,
                  "compile_s": 0.0, "load_s": 0.0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event, **_kw):
        if event == self.HIT:
            self._tl.hit = True

    def _on_secs(self, event, secs, **_kw):
        if event != self.REQUEST:
            return
        hit = getattr(self._tl, "hit", False)
        self._tl.hit = False
        with self._lock:
            self.c["programs"] += 1
            if hit:
                self.c["cache_served"] += 1
                self.c["load_s"] += secs
            else:
                self.c["compiled"] += 1
                self.c["compile_s"] += secs

    def snap(self) -> dict:
        with self._lock:
            return dict(self.c)


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def program_snapshot() -> dict:
    """The program's own cumulative counters: the routing seam's ``stats``
    and the dispatch phases' totals."""
    from tendermint_tpu.crypto import batch, phases

    return {"stats": dict(batch.stats), "totals": phases.phase_totals()}


def all_segments(t0: float, t1: float) -> List[dict]:
    """The records the program's ring (256 entries) still holds that ran
    inside [t0, t1] (perf_counter seconds): verify dispatches, and under
    plane "exec" the executor's per-block phases."""
    from tendermint_tpu.crypto import phases

    return [r for r in phases.recent_segments()
            if r["t0"] >= t0 and r["t_end"] <= t1]


def verify_segments(t0: float, t1: float) -> List[dict]:
    return [r for r in all_segments(t0, t1) if r["plane"] != "exec"]


def route_faults(platform: str, before: dict, after: dict,
                 segments: List[dict]) -> Dict[str, int]:
    """What must be nought for the window to have been the device's:
    device errors, batches the breaker kept off the device, breakers not
    closed, segments on another device than ``platform``, and signatures
    the routing seam gave to the host."""
    from tendermint_tpu.crypto.breaker import (
        CLOSED,
        device_breaker,
        lane_breakers,
    )

    stats = delta(after["stats"], before["stats"])
    open_breakers = int(device_breaker.state != CLOSED) + sum(
        1 for b in lane_breakers().values() if b.state != CLOSED)
    off_device = sum(
        1 for r in segments
        if not r["device"].startswith((platform + ":", "mesh[")))
    return {"device_errors": stats["device_errors"],
            "breaker_rejections": stats["breaker_rejections"],
            "open_breakers": open_breakers,
            "segments_off_device": off_device,
            "host_routed_sigs": stats["host_sigs"]}
