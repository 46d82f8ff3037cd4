"""The benchmark's harness: one cell, one window, one result line.

Driven by data. ``BENCHMARK.json`` names the cell; the cell names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the traffic file names a driver
(``drivers/<driver>.py``) and the configuration a plain reference
(``reference/<reference>.py``); every metric has a file
``metrics/<name>.json`` that names a reader (``readers/<reader>.py``) and
its arguments. A later PR adds files and entries; it edits none.

A driver exposes four steps:

    build(config, traffic, seed) -> data        everything from the seed
    warm(data) -> None                          every shape the window uses
    window(data, seconds, probe) -> [request]   the timed path
    compare(data, requests, reference, control=False) -> {name: (value, limit)}

A request is ``{"t0", "t1", "units": {unit: amount}, ...}`` on the
``time.perf_counter`` clock; drivers may add keys for their own compare.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")


class BenchmarkError(Exception):
    """The run cannot give a result (no chip, unknown cell, bad file)."""


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchmarkError(f"cannot read {path}: {e}") from None


def load_benchmark() -> dict:
    with open(BENCHMARK_JSON) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchmarkError(
        f"no workload {name!r} in BENCHMARK.json (has: "
        f"{[c['name'] for c in bench['workloads']]})")


def metrics_of(bench: dict, cell: str, group: str) -> List[dict]:
    """The metrics of ``group`` ("end_to_end" | "per_layer") this cell
    reports: those that list it, and those that list no cells at all."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


# --- the probe the window carries -------------------------------------------

#: what the profiler records (libtpu's ``tpu_trace_mode``): the host's
#: events, the TPU runtime's among them, and nothing of the device's own
#: plane. Why, and what was tried: readers/device_trace.py.
TPU_TRACE_OPTIONS = {"tpu_trace_mode": "TRACE_ONLY_HOST"}


class Probe:
    """What a driver's window calls: ``span(name)`` around each request
    (and whatever else it wants attributed), ``tick()`` at each boundary
    where a trace may start or stop: between requests, or between the steps
    of a long one.

    Untraced, both cost a clock read. In a traced run a span is also a
    ``jax.profiler.TraceAnnotation``; the profiler starts at tick number
    ``trace_after_ticks`` and stops ``trace_ticks`` ticks later, so the
    traced window holds the same whole pieces of work in every run, and no
    program is in flight at either end. As it stops, with the traced window
    closed and the chip idle, it runs the calibration program
    ``CALIBRATION_RUNS`` times, each to its end and each after
    ``CALIBRATION_IDLE_S`` of rest (a chip that has just run a program
    takes the next one over faster than one that has idled, as the cells'
    chips do between bursts): the runtime's events of those give the
    hand-over latency that readers/device_trace.py takes off every burst of
    programs."""

    CALIBRATION_RUNS = 9
    CALIBRATION_IDLE_S = 0.005

    def __init__(self, after_ticks: int = 0, ticks: int = 0):
        self.after_ticks = after_ticks
        self.ticks = ticks                   # 0: an untraced run
        self.spans: List[dict] = []          # {"name","t0","t1"} perf_counter
        self.traced = None                   # (t0, t1) of the traced window
        self.segments: List[dict] = []       # the program's ring at stop
        self.xspace: Optional[bytes] = None  # the trace, serialized
        self.overhead_s = 0.0                # spent starting and stopping it
        self._n = 0
        self._session = None
        self._t_trace0 = None
        self._annotation = None
        self._calibrate = None

    def prepare(self) -> None:
        """In set-up, for a traced run: compile the calibration program (a
        few microseconds of the chip, nothing of the cell's) and run it
        once."""
        if not self.ticks:
            return
        import jax
        import numpy as np

        x = jax.device_put(np.arange(8 * 128, dtype=np.uint32).reshape(8, 128))
        fn = jax.jit(lambda v: (v * np.uint32(3) + np.uint32(1)).sum())
        self._calibrate = lambda: fn(x).block_until_ready()
        self._calibrate()

    @contextlib.contextmanager
    def span(self, name: str):
        rec = {"name": name, "t0": time.perf_counter(), "t1": None}
        self.spans.append(rec)
        note = contextlib.nullcontext()
        if self._t_trace0 is not None:
            import jax

            note = jax.profiler.TraceAnnotation("bench." + name)
        with note:
            try:
                yield rec
            finally:
                rec["t1"] = time.perf_counter()

    def clock(self) -> float:
        """``time.perf_counter`` less the seconds the profiler's own start
        and stop took. A window's deadline is read on this clock, so a
        traced window still holds ``--seconds`` of requests."""
        return time.perf_counter() - self.overhead_s

    def tick(self) -> None:
        if not self.ticks:
            return
        t = time.perf_counter()
        if self._n == self.after_ticks:
            self._start()
        elif self._n == self.after_ticks + self.ticks:
            self.stop()
        self._n += 1
        self.overhead_s += time.perf_counter() - t

    def _start(self) -> None:
        """A profiler session: the host's TraceMe events (the TPU
        runtime's among them), no Python call tracing (it slows the host
        path the trace is there to watch), no HLO text (10 MB a program
        here). The session is jax's own (``jax.profiler.start_trace`` wraps
        the same object); held directly because its ``stop()`` hands the
        trace back in memory, where ``stop_trace`` writes it to disk twice
        (.xplane.pb and .json.gz)."""
        import jax
        from jax._src.lib import _profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        opts.advanced_configuration = dict(TPU_TRACE_OPTIONS)
        self._session = _profiler.ProfilerSession(opts)
        self._t_trace0 = time.perf_counter()
        self._annotation = jax.profiler.TraceAnnotation("bench.traced_window")
        self._annotation.__enter__()

    def stop(self) -> None:
        if self._t_trace0 is None:
            return
        import counters

        t1 = time.perf_counter()
        self._annotation.__exit__(None, None, None)
        for _ in range(self.CALIBRATION_RUNS if self._calibrate else 0):
            time.sleep(self.CALIBRATION_IDLE_S)
            self._calibrate()
        self.xspace = self._session.stop()
        self._session = None
        self.traced = (self._t_trace0, t1)
        self._t_trace0 = None
        # now, not at the window's end: the program's ring holds 256 records
        self.segments = counters.all_segments(*self.traced)


# --- what the readers read ---------------------------------------------------

@dataclass
class Window:
    cell: dict
    config: dict
    traffic: dict
    device: dict
    setup_s: float
    t0: float                      # window start, perf_counter
    t1: float                      # end of the last request
    requests: List[dict]
    before: dict                   # counters.program_snapshot() at t0
    after: dict                    # ... at t1
    segments: List[dict]           # verify segments of the window in the ring
    extras: dict = field(default_factory=dict)   # the driver's own sums
    trace: Optional[dict] = None   # readers/device_trace.reduce() or None


def read_metric(name: str, win: Window):
    spec = load_json("metrics", name + ".json")
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(win, **spec.get("args", {}))


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


#: the longest requests of a window that the result line keeps
SLOWEST_KEPT = 8

#: the most bytes a result line may have. What a driver keeps of standard
#: output is a bounded tail, and a line cut at its head is not JSON: at 150
#: validators a line that listed every request was 162-173 KB (PERF.md 6).
RESULT_LINE_LIMIT = 16384


def request_rows(requests: List[dict], t0: float) -> List[list]:
    """Every request as ``[start_s, length_s, units]``: its start from the
    window's start and its length, in seconds, and its units summed."""
    return [[round(r["t0"] - t0, 6), round(r["t1"] - r["t0"], 6),
             sum(r["units"].values())] for r in requests]


def slowest(rows: List[list]) -> List[list]:
    """The ``SLOWEST_KEPT`` longest requests, longest first: a stall shows
    as one request of seconds among milliseconds (PERF.md, 2)."""
    return sorted(rows, key=lambda r: -r[1])[:SLOWEST_KEPT]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process_start: float, overrides: Optional[dict] = None,
             control: bool = False, requests_out: Optional[str] = None
             ) -> dict:
    """One run of one cell -> the result object (the caller prints it).
    Its size does not depend on how many requests the window held.

    ``overrides`` replaces keys of the configuration and traffic files
    (the CPU rehearsal's small sizes, a test's planted fault); the
    benchmark's own command never passes any. ``control`` True puts the
    reference's control in the program's place (tests); "also" compares
    the same window a second time with the control answering, under
    ``compared_control`` (seeds.py). ``requests_out`` names a file that
    gets every request of the window (``request_rows``, as JSON): what a
    shorter window of the same run would have read (PERF.md, 2); the
    benchmark's own command never passes it."""
    import counters

    bench = load_benchmark()
    cell = find_cell(bench, workload)
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    for k, v in (overrides or {}).items():
        target = config if k in config else traffic
        target[k] = v
    device = device_info()
    driver = importlib.import_module("drivers." + traffic["driver"])
    reference = importlib.import_module("reference." + config["reference"])
    compiles = counters.CompileCounter()

    data = driver.build(config, traffic, seed)
    driver.warm(data)
    probe = (Probe(traffic["trace_after_ticks"], traffic["trace_ticks"])
             if trace else Probe())
    probe.prepare()
    c_warm = compiles.snap()

    before = counters.program_snapshot()
    setup_s = time.perf_counter() - t_process_start
    t0 = time.perf_counter()
    try:
        requests = driver.window(data, seconds, probe)
    finally:
        probe.stop()
    t1 = max(r["t1"] for r in requests)
    after = counters.program_snapshot()
    c_window = compiles.snap()
    segments = counters.verify_segments(t0, t1)
    mem_peak = memory_peak_bytes()

    win = Window(cell, config, traffic, device, setup_s, t0, t1, requests,
                 before, after, segments, extras=data.get("extras", {}))
    if trace:
        from readers import device_trace

        if probe.xspace is None:
            raise BenchmarkError("the profiler gave no trace")
        try:
            win.trace = device_trace.reduce(probe.xspace, probe.spans,
                                            probe.traced, probe.segments)
        except ValueError as e:
            raise BenchmarkError(f"the trace cannot be reduced: {e}") from e
        probe.xspace = None

    # correctness: the window's own answers against the plain reference,
    # and the device's own work by the device (each with its limit)
    compared = dict(driver.compare(data, requests, reference,
                                   control=control is True))
    compared["compiles_in_window"] = (
        c_window["programs"] - c_warm["programs"], 0)
    for k, v in counters.route_faults(device["platform"], before, after,
                                      segments).items():
        compared[k] = (v, 0)
    failed = sum(1 for r in requests if r.get("failed"))
    correct = failed == 0 and all(v <= lim for v, lim in compared.values())

    metrics = {}
    for m in metrics_of(bench, workload,
                        "per_layer" if trace else "end_to_end"):
        value = read_metric(m["name"], win)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": correct, "attempted": len(requests), "failed": failed,
           "metrics": metrics,
           "device": dict(device, memory_peak_bytes=mem_peak)}
    if trace:
        out["device"]["busy_s"] = win.trace["busy_s"]
        out["device"]["window_s"] = win.trace["window_s"]
        out["breakdown"] = {"device_ops": win.trace["device_ops"][:10],
                            "idle_gaps": win.trace["idle_gaps"][:10]}
        out["device"]["handover_s"] = win.trace["handover_s"]
    out["workload"] = workload
    out["seed"] = seed
    out["window_s"] = t1 - t0
    rows = request_rows(requests, t0)
    if requests_out:
        with open(requests_out, "w") as f:
            json.dump(rows, f)
    out["slowest"] = slowest(rows)
    out["compile"] = {"warm": c_warm, "window": counters.delta(c_window,
                                                               c_warm)}
    if control == "also":
        out["compared_control"] = {
            k: {"value": v, "limit": lim} for k, (v, lim) in driver.compare(
                data, requests, reference, control=True).items()}
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    return out


def print_result(out: dict) -> None:
    """Each number compared beside its limit as the last lines of stderr,
    the result object as the last line of stdout: strict JSON (no NaN, no
    Infinity) of at most ``RESULT_LINE_LIMIT`` bytes. A line that cannot be
    read is no result: it is refused here, on the machine that made it,
    and nothing is printed."""
    try:
        line = json.dumps(out, allow_nan=False)
    except ValueError as e:
        raise BenchmarkError(f"the result is not strict JSON: {e}") from None
    if len(line.encode()) > RESULT_LINE_LIMIT:
        sizes = {k: len(json.dumps(v)) for k, v in out.items()}
        raise BenchmarkError(
            f"the result line has {len(line.encode())} bytes, over the "
            f"{RESULT_LINE_LIMIT} a line may have; its largest key is "
            f"{max(sizes, key=sizes.get)!r}")
    sys.stdout.flush()
    print(f"correct {out['correct']}: each number compared, beside its "
          "limit", file=sys.stderr)
    for k, v in out["compared"].items():
        print(f"compared {k}: value {v['value']} limit {v['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
