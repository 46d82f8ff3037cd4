"""From a profiler trace to the device's numbers, as the TPU runtime saw them.

The one reduction every PR's traced run goes through:

    busy_s      union of the intervals in which a program ran on a device,
                inside the traced window, averaged over the devices used
    window_s    the traced window: the ``bench.traced_window`` annotation
    device_ops  device seconds by program
    idle_gaps   the device's idle seconds by what the host was doing: the
                program's own dispatch records (``crypto/phases.py``: pack,
                dispatch, in flight; the executor's validate / execute /
                commit) and the benchmark's spans, put on the trace's clock
    executions  every program execution (label, start, seconds)
    handover_s  the hand-over latency taken off each burst (below)

Where an execution's interval comes from. The TPU runtime writes two host
events for every program execution into the profiler's trace:
``tpu::System::Execute`` when the program is handed to the chip's queue, and
``tpu::System::Execute=>Done`` when the chip reports it finished. A chip runs
its queue in order, so execution i ran from max(handed_i, finished_{i-1}) to
finished_i. These are the runtime's observations, not the chip's own clock:
between handing a program to an IDLE chip and the report of its end lie the
program's run time and a hand-over latency (0.6-0.95 ms on a v5e host). A
program that queues behind another is reported late at both ends alike, so
the latency sits once on every burst of programs, on its first. It is
MEASURED in every traced run and taken off: right after the traced window
closes, before the profiler stops, the harness runs a program of a few
microseconds some times, each to its end (``harness.Probe``); the median of
handed -> finished over those is the latency, and the first execution of
every burst inside the window starts that much later. The yardstick stays
this one: a kernel PR that shortens the programs is read by the same events
with the same correction, and a run whose trace lacks the calibration, or
whose executions the program's dispatch records do not account for one to
one, gives no result.

Why not the device's own plane. It was tried first (my chip runs, PR 25).
The verify programs are ~260,000 operations of ~60 ns per 2,048-signature
chunk; the profiler records each (no trace mode gives programs without
their operations), needs ~16 us to hand each back (two requests: 32 s, a
fast-sync window: minutes), loses program events once the operations flood
its buffer (of four executions in two requests it kept three, once merged
two into one), and gives ``while`` / called-region events lengths that
outlast their program. The device plane's exact program times, read by
hand while it was complete, are in PERF.md: 27.51 ms (K=3), 18.35 ms (K=2),
73.4 ms (K=8): 4.48 us a signature lane. ``tests/test_device_trace.py``
holds the two against each other on a recorded trace that has both.

Host spans and the program's records are on ``time.perf_counter``; the
trace has a clock of its own. The two are tied at one point: the harness
reads perf_counter as it opens ``bench.traced_window``.
"""

from __future__ import annotations

import re
import statistics
from typing import List, Optional, Sequence, Tuple

import stats

WINDOW_EVENT = "bench.traced_window"
HANDED = "tpu::System::Execute"
FINISHED = "tpu::System::Execute=>Done"
#: which host activity an idle instant is charged to, first match first
PRIORITY = ("pack", "dispatch", "apply_validate", "apply_execute",
            "apply_commit", "in_flight")


def _profile_data(trace):
    """``trace``: a serialized XSpace (bytes) or the path of a .xplane.pb."""
    import jax

    if isinstance(trace, bytes):
        return jax.profiler.ProfileData.from_serialized_xspace(trace)
    return jax.profiler.ProfileData.from_file(trace)


def pair_executions(handed: Sequence[float], finished: Sequence[float]
                    ) -> List[Tuple[float, float, bool]]:
    """One chip's executions (start, end, first of a burst) from the times
    its programs were handed to its queue and the times it reported one
    finished (each in any order). The first of a burst met an idle chip."""
    handed, finished = sorted(handed), sorted(finished)
    if len(handed) != len(finished):
        raise ValueError(f"{len(handed)} programs handed to the chip, "
                         f"{len(finished)} reported finished: the trace "
                         "cuts an execution")
    out, free_at = [], float("-inf")
    for h, f in zip(handed, finished):
        if f < h:
            raise ValueError("a program finished before it was handed over")
        out.append((max(h, free_at), f, h >= free_at))
        free_at = f
    return out


def handover_seconds(executions: Sequence[Tuple[float, float, bool]],
                     after: float) -> Optional[float]:
    """The hand-over latency: the median of handed -> finished over the
    calibration program's executions, which the harness runs one at a time
    once the traced window has closed (at ``after``). The program itself
    takes microseconds, which stay in the figure. None without any."""
    sample = [e - s for s, e, first in executions if first and s >= after]
    return statistics.median(sample) if sample else None


def load(trace) -> dict:
    """A trace as plain values, in seconds on the trace's clock:
    {"devices": [{"name", "handed": [t], "finished": [t]}],
    "host": [(start, end, name)] (the benchmark's own annotations)}."""
    pd = _profile_data(trace)
    host = []
    handed: dict = {}
    finished: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                name = e.name
                if name.startswith("bench."):
                    host.append((e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9, name))
                elif name in (HANDED, FINISHED):
                    core = dict(e.stats).get("core_id", 0)
                    (handed if name == HANDED else finished).setdefault(
                        core, []).append(e.start_ns * 1e-9)
    return {"devices": [{"name": f"tpu core {core}", "handed": handed[core],
                         "finished": finished.get(core, [])}
                        for core in sorted(handed)],
            "host": host}


def host_activities(spans: Sequence[dict], segments: Sequence[dict],
                    shift: float) -> dict:
    """{activity: [intervals]} on the trace's clock (perf_counter + shift)."""
    acts: dict = {}

    def add(name, a, b):
        acts.setdefault(name, []).append((a + shift, b + shift))

    for s in segments:
        t_pack = s["t0"] + s["pack_s"]
        t_disp = t_pack + s["dispatch_s"]
        names = (("apply_validate", "apply_execute", "apply_commit")
                 if s["plane"] == "exec" else ("pack", "dispatch", "in_flight"))
        add(names[0], s["t0"], t_pack)
        add(names[1], t_pack, t_disp)
        add(names[2], t_disp, s["t_end"])
    for sp in spans:
        add("in_" + sp["name"] + "_other", sp["t0"], sp["t1"])
    return acts


def _labels(n_executions: int, segments: Sequence[dict]) -> List[str]:
    """A name for each execution of the window, in order. The only programs
    these cells run in a window are the verify programs, one execution a
    dispatch segment (the harness fails a run that compiles anything else),
    so execution i is segment i by order of dispatch. Another count means
    that a program ran which the dispatch records do not know, or that the
    program's ring lost records: then no kernel number can be trusted."""
    verify = sorted((s for s in segments if s["plane"] != "exec"),
                    key=lambda s: s["t0"] + s["pack_s"] + s["dispatch_s"])
    if len(verify) != n_executions:
        raise ValueError(
            f"{n_executions} program executions in the traced window, "
            f"{len(verify)} dispatch records of the program: they cannot "
            "be told apart")
    return [f"verify kernel K={-(-s['sigs'] // s['chunk'])} ({s['plane']})"
            for s in verify]


def reduce_planes(planes: dict, spans: Sequence[dict],
                  traced: Tuple[float, float], segments: Sequence[dict],
                  calibrated: bool = True) -> dict:
    """``calibrated`` False takes nothing off (hand-made planes in tests);
    the harness never passes it."""
    marks = [ev for ev in planes["host"] if ev[2] == WINDOW_EVENT]
    if not marks:
        raise ValueError(f"the trace holds no {WINDOW_EVENT} annotation")
    w0, w1, _ = marks[0]
    shift = w0 - traced[0]
    used = []
    handover = []
    for d in planes["devices"]:
        paired = pair_executions(d["handed"], d["finished"])
        late = handover_seconds(paired, w1) if calibrated else 0.0
        if late is None:
            raise ValueError(f"{d['name']}: no calibration execution after "
                             "the traced window: the hand-over latency is "
                             "not known")
        inside = stats.clip([(min(s + late, e) if first else s, e)
                             for s, e, first in paired], w0, w1)
        if inside:
            used.append((d["name"], inside))
            handover.append(late)
    if not used:
        raise ValueError("no program ran on a device in the traced window")
    busy_s = sum(sum(b - a for a, b in stats.union(iv))
                 for _, iv in used) / len(used)

    executions = []
    by_label: dict = {}
    multi = len(used) > 1
    for name, iv in used:
        # across several chips the records do not say which chip ran which
        labels = ([f"program on {name}"] * len(iv) if multi
                  else _labels(len(iv), segments))
        for label, (a, b) in zip(labels, iv):
            executions.append((label, a - w0, b - a))
            by_label[label] = by_label.get(label, 0.0) + b - a
    top = lambda d, n: sorted(d.items(), key=lambda kv: -kv[1])[:n]

    # idle seconds of the first device by what the host was doing
    remaining = stats.gaps(used[0][1], w0, w1)
    acts = host_activities(
        [dict(s, t0=max(s["t0"], traced[0]), t1=min(s["t1"], traced[1]))
         for s in spans if s["t1"] is not None and s["t1"] > traced[0]
         and s["t0"] < traced[1]], segments, shift)
    order = list(PRIORITY) + sorted(k for k in acts if k not in PRIORITY)
    idle: dict = {}
    for name in order:
        if name in acts:
            hit = stats.intersect(remaining, acts[name])
            if hit:
                idle[name] = sum(b - a for a, b in hit)
            remaining = stats.subtract(remaining, acts[name])
    left = sum(b - a for a, b in remaining)
    if left > 0:
        idle["between_requests"] = left
    return {"window_s": w1 - w0, "busy_s": busy_s,
            "devices": [name for name, _ in used],
            "device_ops": [[k, v] for k, v in top(by_label, 10)],
            "idle_gaps": [[k, v] for k, v in top(idle, 10)],
            "executions": executions,
            "handover_s": max(handover),
            "lanes": sum(-(-s["sigs"] // s["chunk"]) * s["chunk"]
                         for s in segments if s["plane"] != "exec"),
            "sigs": sum(s["sigs"] for s in segments if s["plane"] != "exec")}


def reduce(trace, spans: Sequence[dict], traced: Tuple[float, float],
           segments: Sequence[dict]) -> dict:
    return reduce_planes(load(trace), spans, traced, segments)


def kernel(trace: Optional[dict], pattern: str) -> Optional[dict]:
    """Device seconds of the executions whose label matches ``pattern`` in
    the traced window, with the signatures dispatched there; None where the
    trace holds no such execution."""
    if trace is None:
        return None
    hits = [x for x in trace["executions"] if re.search(pattern, x[0])]
    if not hits:
        return None
    return {"seconds": sum(x[2] for x in hits), "executions": len(hits),
            "sigs": trace["sigs"]}


def describe(trace, limit: int = 6) -> str:
    """The structure of a trace, for the by-hand look: planes, lines,
    event counts and the first events of each line."""
    out = []
    for plane in _profile_data(trace).planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for ln in lines:
            evs = list(ln.events)
            out.append(f"  LINE {ln.name!r}: {len(evs)} events")
            for e in evs[:limit]:
                out.append(f"    {e.name[:90]!r} start_ns={e.start_ns:.0f} "
                           f"dur_ns={e.duration_ns:.0f}")
    return "\n".join(out)
