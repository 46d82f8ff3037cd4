"""Summarize a Chrome trace-event JSON (libs/trace.py; TMTPU_TRACE_OUT).

Prints per-span count / total / p50 / p99 so a trace answers "where
did the window go" without opening Perfetto:

    python tools/trace_summary.py /tmp/trace.json
    python tools/trace_summary.py --json /tmp/trace.json   # machine-readable
    python tools/trace_summary.py trace-*.json --node-prefix     # cluster view
    python tools/trace_summary.py --self-test                    # CI guard

Multiple inputs (per-node traces, or tools/trace_merge.py output alongside
originals) are summarized together; --node-prefix labels each file's spans
``<node>:<span>`` (node id from the trace header, else the file stem) so
per-node asymmetries stay visible in the combined table.

Dependency-free on purpose (stdlib only, no package import): it must run
against a dump bundle on a box that can't import jax.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List


def load_events(path: str) -> List[dict]:
    """Accept both the {"traceEvents": [...]} container and a bare event
    array (both are valid Chrome trace JSON)."""
    return load_labeled(path)[1]


def load_labeled(path: str):
    """(node label, events): label from the tracer's node_id export header
    (libs/trace.py set_identity) when present, else the file stem."""
    import os

    with open(path) as f:
        data = json.load(f)
    label = os.path.splitext(os.path.basename(path))[0]
    if isinstance(data, dict):
        events = data.get("traceEvents", [])
        if data.get("node_id"):
            label = str(data["node_id"])
    elif isinstance(data, list):
        events = data
    else:
        raise ValueError(f"{path}: not a trace-event JSON")
    if not isinstance(events, list):
        raise ValueError(f"{path}: traceEvents is not a list")
    return label, [e for e in events
                   if isinstance(e, dict) and e.get("name")
                   and e.get("ph") != "M"]


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile on a pre-sorted list (no numpy)."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def summarize(events: List[dict]) -> Dict[str, dict]:
    """name -> {count, total_us, p50_us, p99_us}; complete ("X") events
    contribute their dur, instants count with zero duration."""
    durs: Dict[str, List[float]] = {}
    for e in events:
        durs.setdefault(e["name"], []).append(float(e.get("dur", 0.0)))
    out: Dict[str, dict] = {}
    for name, vals in sorted(durs.items()):
        vals.sort()
        out[name] = {
            "count": len(vals),
            "total_us": round(sum(vals), 1),
            "p50_us": round(_percentile(vals, 0.50), 1),
            "p99_us": round(_percentile(vals, 0.99), 1),
        }
    return out


def by_height(events: List[dict]) -> Dict[int, Dict[str, float]]:
    """height -> {span name -> total_us} for events whose args carry a
    height (``height`` or ``h``). This is the live-plane attribution view:
    where each committed height's wall-clock went — gossip wait
    (``gossip_idle``), WAL sync (``wal_group``/``wal_fsync``), verify
    (``batch_verify``/``verify_window``), apply (``apply_block``)."""
    out: Dict[int, Dict[str, float]] = {}
    for e in events:
        args = e.get("args") or {}
        h = args.get("height", args.get("h"))
        if not isinstance(h, int):
            continue
        per = out.setdefault(h, {})
        per[e["name"]] = per.get(e["name"], 0.0) + float(e.get("dur", 0.0))
    return {h: {n: round(v, 1) for n, v in sorted(per.items())}
            for h, per in sorted(out.items())}


def render_by_height(table: Dict[int, Dict[str, float]]) -> str:
    if not table:
        return "(no height-tagged events)"
    names = sorted({n for per in table.values() for n in per})
    head = "height  " + "  ".join(f"{n:>{max(len(n), 10)}}" for n in names)
    lines = [head]
    for h, per in table.items():
        cells = "  ".join(f"{per.get(n, 0.0) / 1000.0:>{max(len(n), 10)}.2f}"
                          for n in names)
        lines.append(f"{h:>6}  {cells}")
    return "\n".join(lines) + "\n(cells: total ms per height)"


def render(summary: Dict[str, dict]) -> str:
    if not summary:
        return "(no events)"
    name_w = max(len("span"), max(len(n) for n in summary))
    lines = [f"{'span':<{name_w}}  {'count':>7}  {'total_ms':>10}  "
             f"{'p50_us':>9}  {'p99_us':>9}"]
    for name, s in summary.items():
        lines.append(f"{name:<{name_w}}  {s['count']:>7}  "
                     f"{s['total_us'] / 1000.0:>10.2f}  "
                     f"{s['p50_us']:>9.1f}  {s['p99_us']:>9.1f}")
    return "\n".join(lines)


def self_test() -> int:
    """Round-trip a synthetic trace through a temp file: the format this
    tool parses is exactly what libs/trace.py emits. Returns 0
    on success (CI runs this under pytest so the tool can't rot)."""
    import os
    import tempfile

    events = []
    t = 1000.0
    for i in range(8):
        for name, dur in (("verify_window", 500.0 + i), ("apply_window", 900.0),
                          ("apply_block", 55.0), ("window_flush", 20.0)):
            events.append({"name": name, "ph": "X", "ts": t, "dur": dur,
                           "pid": 1, "tid": 1, "args": {"i": i}})
            t += dur
    events.append({"name": "vote_flush", "ph": "i", "s": "t", "ts": t,
                   "pid": 1, "tid": 1})
    # height-tagged live-plane spans (consensus state.py / reactor.py emit
    # exactly this shape) for the --by-height view
    for h in (5, 5, 6):
        for name, dur in (("gossip_idle", 40.0), ("wal_group", 3.0),
                          ("apply_block", 55.0)):
            events.append({"name": name, "ph": "X", "ts": t, "dur": dur,
                           "pid": 1, "tid": 1, "args": {"height": h}})
            t += dur
    fd, path = tempfile.mkstemp(suffix=".json")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        loaded = load_events(path)
        summary = summarize(loaded)
        heights = by_height(loaded)
    finally:
        os.unlink(path)
    assert len(summary) == 7, summary
    assert summary["apply_window"]["count"] == 8
    assert summary["apply_window"]["p50_us"] == 900.0
    assert summary["vote_flush"]["total_us"] == 0.0
    assert summary["verify_window"]["p99_us"] >= summary["verify_window"]["p50_us"]
    assert set(heights) == {5, 6}, heights
    assert heights[5]["gossip_idle"] == 80.0
    assert heights[6]["wal_group"] == 3.0
    assert "gossip_idle" in render_by_height(heights)
    # multi-file + --node-prefix composition (merged cluster traces): the
    # node label comes from the export header, metadata events are skipped
    fd2, path2 = tempfile.mkstemp(suffix=".json")
    try:
        with os.fdopen(fd2, "w") as f:
            json.dump({"traceEvents": [
                {"name": "process_name", "ph": "M", "pid": 1,
                 "args": {"name": "nodeX"}},
                {"name": "verify_window", "ph": "X", "ts": 1.0, "dur": 7.0,
                 "pid": 1, "tid": 1}],
                "displayTimeUnit": "ms", "node_id": "nodeX"}, f)
        label, evs = load_labeled(path2)
        assert label == "nodeX" and len(evs) == 1, (label, evs)
        import contextlib
        import io

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            assert main(["--json", "--node-prefix", path2]) == 0
        assert "nodeX:verify_window" in buf.getvalue()
    finally:
        os.unlink(path2)
    print("trace_summary self-test OK "
          f"({len(summary)} spans, {sum(s['count'] for s in summary.values())}"
          f" events, {len(heights)} heights)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", nargs="*", help="Chrome trace-event JSON "
                    "path(s); several per-node traces combine into one "
                    "summary")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as JSON instead of a table")
    ap.add_argument("--by-height", action="store_true",
                    help="group height-tagged spans (gossip_idle, wal_group, "
                         "apply_block, verify/apply windows, stage_*) per "
                         "height — the live-plane latency attribution view")
    ap.add_argument("--node-prefix", action="store_true",
                    help="label every span '<node>:<span>' per input file "
                         "(node id from the trace header, else file stem)")
    ap.add_argument("--self-test", action="store_true",
                    help="run the built-in round-trip check and exit")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    if not args.trace:
        ap.error("trace path required (or --self-test)")
    events = []
    for path in args.trace:
        label, evs = load_labeled(path)
        if args.node_prefix:
            for e in evs:
                e = dict(e)
                e["name"] = f"{label}:{e['name']}"
                events.append(e)
        else:
            events.extend(evs)
    if args.by_height:
        table = by_height(events)
        if args.json:
            print(json.dumps({str(h): per for h, per in table.items()},
                             indent=2))
        else:
            print(render_by_height(table))
        return 0
    summary = summarize(events)
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        print(render(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
