"""Merge N nodes' Chrome trace JSONs into ONE Perfetto-loadable timeline.

Each node's tracer stamps its export with ``node_id`` and a wall↔perf epoch
pair (libs/trace.py set_identity). This tool re-bases every node's
perf_counter-domain timestamps onto the shared wall clock, gives each node
its own pid track (named via process_name metadata), and writes a single
trace where cross-node causality — proposal on node0, prevotes landing on
node1..3, commit spread — is visible on one screen:

    python tools/trace_merge.py node0.json node1.json ... --out merged.json
    python tools/trace_merge.py *.json                 # skew report only
    python tools/trace_merge.py --self-test            # CI guard

The skew report groups ``stage_commit_finalized`` spans (consensus
timeline, args.height) per height: first-to-last commit spread across
nodes, plus per-node slowest-stage attribution (which stage eats the most
mean wall-clock on each node).

Dependency-free on purpose (stdlib only): it must run against trace files
scp'd off a fleet onto a box that can't import jax.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple


def load_trace(path: str) -> dict:
    """Full trace document; bare event arrays are wrapped."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, list):
        data = {"traceEvents": data}
    if not isinstance(data, dict) or not isinstance(
            data.get("traceEvents", []), list):
        raise ValueError(f"{path}: not a trace-event JSON")
    return data


def node_label(doc: dict, path: str) -> str:
    label = doc.get("node_id")
    if label:
        return str(label)
    stem = os.path.splitext(os.path.basename(path))[0]
    return stem


def rebase_events(doc: dict) -> Tuple[List[dict], bool]:
    """Events with ``ts`` moved from the node's perf_counter domain onto
    the wall clock (unix microseconds). Returns (events, aligned): without
    an epoch header the events pass through untouched and aligned=False —
    the merge still renders, tracks just share no common zero."""
    events = [e for e in doc.get("traceEvents", [])
              if isinstance(e, dict) and e.get("ph") != "M"]
    epoch_unix = doc.get("epoch_unix_s")
    epoch_perf = doc.get("epoch_perf_us")
    if epoch_unix is None or epoch_perf is None:
        return [dict(e) for e in events], False
    base = float(epoch_unix) * 1e6 - float(epoch_perf)
    out = []
    for e in events:
        e2 = dict(e)
        e2["ts"] = float(e.get("ts", 0.0)) + base
        out.append(e2)
    return out, True


def merge(docs_with_labels: List[Tuple[str, dict]]) -> dict:
    """One merged Chrome trace: per-node pid tracks aligned on the wall
    clock, shifted so the earliest event sits at t=0."""
    tracks = []
    dropped_total = 0
    for label, doc in docs_with_labels:
        events, aligned = rebase_events(doc)
        dropped_total += int(doc.get("dropped", 0) or 0)
        tracks.append((label, events, aligned))
    any_aligned = any(aligned for _, _, aligned in tracks)

    def _min_ts(events: List[dict]) -> Optional[float]:
        return min((e["ts"] for e in events
                    if isinstance(e.get("ts"), (int, float))), default=None)

    # t=0 is the earliest ALIGNED event: an epoch-less track's private
    # perf-domain ts (tiny) must not drag the wall-clock tracks (~1.7e15us)
    # to a gigasecond offset that Perfetto fits into one sub-pixel view —
    # and neither must an aligned-but-EMPTY track (a node that died at
    # startup exports the header with no events; _min_ts -> None, skipped)
    aligned_mins = [m for m in (_min_ts(ev) for _, ev, aligned in tracks
                                if aligned) if m is not None]
    t0 = min(aligned_mins) if aligned_mins \
        else min((m for m in (_min_ts(ev) for _, ev, _ in tracks)
                  if m is not None), default=0.0)
    merged: List[dict] = []
    for pid, (label, events, aligned) in enumerate(tracks, start=1):
        name = label if aligned else f"{label} (unaligned)"
        # unaligned tracks rebase onto the merged origin by their OWN
        # first event — positions within the track stay truthful, only
        # the cross-track offset is arbitrary (hence the label)
        own_min = _min_ts(events)
        shift = t0 if aligned else (own_min if own_min is not None else 0.0)
        merged.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": name}})
        for e in events:
            e["pid"] = pid
            if isinstance(e.get("ts"), (int, float)):
                e["ts"] = e["ts"] - shift
            merged.append(e)
    return {"traceEvents": merged, "displayTimeUnit": "ms",
            "aligned": any_aligned, "dropped": dropped_total,
            "nodes": [label for label, _, _ in tracks]}


# -- skew report --------------------------------------------------------------

def commit_times(docs_with_labels: List[Tuple[str, dict]]
                 ) -> Dict[int, Dict[str, float]]:
    """height -> {node -> wall-clock commit time (us)} from the stage
    timeline's ``stage_commit_finalized`` spans (span END = the commit
    mark)."""
    out: Dict[int, Dict[str, float]] = {}
    for label, doc in docs_with_labels:
        events, aligned = rebase_events(doc)
        if not aligned:
            # an epoch-less trace's ts stay in its private perf domain —
            # mixing them into wall-clock spread math would report the
            # perf/unix offset (~decades) as cross-node skew
            continue
        for e in events:
            if e.get("name") != "stage_commit_finalized":
                continue
            h = (e.get("args") or {}).get("height")
            if not isinstance(h, int):
                continue
            t = float(e.get("ts", 0.0)) + float(e.get("dur", 0.0))
            # keep the FIRST commit of a height per node (restarts re-commit)
            out.setdefault(h, {}).setdefault(label, t)
    return out


def skew_report(docs_with_labels: List[Tuple[str, dict]]) -> dict:
    commits = commit_times(docs_with_labels)
    per_height = []
    for h in sorted(commits):
        times = commits[h]
        if len(times) < 2:
            continue
        first = min(times, key=times.get)
        last = max(times, key=times.get)
        per_height.append({
            "height": h,
            "nodes": len(times),
            "first": first,
            "last": last,
            "spread_ms": round((times[last] - times[first]) / 1000.0, 3),
        })
    spreads = [r["spread_ms"] for r in per_height]
    # slowest-stage attribution: per node, mean duration per stage span
    slowest: Dict[str, dict] = {}
    for label, doc in docs_with_labels:
        stages: Dict[str, List[float]] = {}
        for e in doc.get("traceEvents", []):
            name = e.get("name", "")
            if not name.startswith("stage_") or e.get("ph") != "X":
                continue
            stages.setdefault(name[len("stage_"):], []).append(
                float(e.get("dur", 0.0)))
        if not stages:
            continue
        means = {s: sum(v) / len(v) for s, v in stages.items()}
        worst = max(means, key=means.get)
        slowest[label] = {
            "slowest_stage": worst,
            "mean_ms": round(means[worst] / 1000.0, 3),
            "stage_mean_ms": {s: round(m / 1000.0, 3)
                              for s, m in sorted(means.items())},
        }
    return {
        "heights": len(per_height),
        "mean_spread_ms": round(sum(spreads) / len(spreads), 3) if spreads
        else 0.0,
        "max_spread_ms": max(spreads) if spreads else 0.0,
        "per_height": per_height,
        "slowest_stage_per_node": slowest,
    }


def render_skew(report: dict) -> str:
    lines = [f"cross-node skew over {report['heights']} heights: "
             f"mean {report['mean_spread_ms']} ms, "
             f"max {report['max_spread_ms']} ms"]
    rows = sorted(report["per_height"], key=lambda r: -r["spread_ms"])[:10]
    if rows:
        lines.append(f"{'height':>7}  {'nodes':>5}  {'spread_ms':>10}  "
                     f"first -> last")
        for r in rows:
            lines.append(f"{r['height']:>7}  {r['nodes']:>5}  "
                         f"{r['spread_ms']:>10.3f}  "
                         f"{r['first']} -> {r['last']}")
    for node, s in sorted(report["slowest_stage_per_node"].items()):
        lines.append(f"{node}: slowest stage {s['slowest_stage']} "
                     f"(mean {s['mean_ms']} ms)")
    return "\n".join(lines)


# -- self-test ----------------------------------------------------------------

def _synthetic_doc(node_id: str, epoch_unix_s: float, epoch_perf_us: float,
                   commit_wall_us: Dict[int, float]) -> dict:
    """A node trace whose stage_commit_finalized spans END at the given
    WALL-clock times, expressed in that node's private perf domain."""
    events = []
    for h, wall_us in commit_wall_us.items():
        perf_end = wall_us - epoch_unix_s * 1e6 + epoch_perf_us
        events.append({"name": "stage_commit_finalized", "ph": "X",
                       "ts": perf_end - 2000.0, "dur": 2000.0, "pid": 9,
                       "tid": 1, "args": {"height": h, "round": 0}})
        events.append({"name": "stage_prevote_quorum", "ph": "X",
                       "ts": perf_end - 9000.0, "dur": 5000.0, "pid": 9,
                       "tid": 1, "args": {"height": h, "round": 0}})
    return {"traceEvents": events, "displayTimeUnit": "ms", "dropped": 0,
            "node_id": node_id, "epoch_unix_s": epoch_unix_s,
            "epoch_perf_us": epoch_perf_us}


def self_test() -> int:
    """Two synthetic nodes with WILDLY different perf_counter origins but a
    known 50ms wall-clock commit skew: the merge must align them and the
    skew report must read exactly 50ms."""
    base = 1_700_000_000.0  # unix seconds
    a = _synthetic_doc("node-a", base, 111_000_000.0,
                       {5: base * 1e6 + 1_000_000.0,
                        6: base * 1e6 + 2_000_000.0})
    b = _synthetic_doc("node-b", base + 100.0, 999_000_000.0,
                       {5: base * 1e6 + 1_050_000.0,
                        6: base * 1e6 + 2_050_000.0})
    docs = [("node-a", a), ("node-b", b)]
    merged = merge(docs)
    assert merged["aligned"] is True
    pids = {e["pid"] for e in merged["traceEvents"] if e.get("ph") == "X"}
    assert pids == {1, 2}, pids
    names = [e["args"]["name"] for e in merged["traceEvents"]
             if e.get("ph") == "M"]
    assert names == ["node-a", "node-b"], names
    # after rebasing, node-b's height-5 commit ends exactly 50ms after
    # node-a's, even though their raw perf ts differ by ~888 seconds
    ends = {}
    for e in merged["traceEvents"]:
        if e.get("name") == "stage_commit_finalized":
            ends.setdefault(e["args"]["height"], {})[e["pid"]] = (
                e["ts"] + e["dur"])
    assert abs((ends[5][2] - ends[5][1]) - 50_000.0) < 1.0, ends
    assert min(e.get("ts", 0.0) for e in merged["traceEvents"]
               if e.get("ph") == "X") == 0.0
    report = skew_report(docs)
    assert report["heights"] == 2
    assert abs(report["max_spread_ms"] - 50.0) < 0.001, report
    assert report["per_height"][0]["first"] == "node-a"
    assert report["per_height"][0]["last"] == "node-b"
    for node in ("node-a", "node-b"):
        assert report["slowest_stage_per_node"][node]["slowest_stage"] == \
            "prevote_quorum"
    assert "node-a -> node-b" in render_skew(report)
    # an epoch-less trace still merges, on an unaligned track
    bare = {"traceEvents": [{"name": "x", "ph": "X", "ts": 5.0, "dur": 1.0,
                             "pid": 1, "tid": 1}]}
    m2 = merge([("node-a", a), ("old", bare)])
    names = [e["args"]["name"] for e in m2["traceEvents"]
             if e.get("ph") == "M"]
    assert names == ["node-a", "old (unaligned)"], names
    # the unaligned track must not drag the aligned tracks' zero: node-a's
    # first event still sits at t=0 and the bare track rebases by its own
    # origin (5.0), keeping every ts in one renderable window
    m2_ts = {e.get("name"): e["ts"] for e in m2["traceEvents"]
             if e.get("ph") == "X"}
    assert m2_ts["x"] == 0.0, m2_ts
    assert min(e["ts"] for e in m2["traceEvents"]
               if e.get("ph") == "X") == 0.0
    assert max(e["ts"] for e in m2["traceEvents"]
               if e.get("ph") == "X") < 2e9, "mixed merge left a track "\
        "at a wall-clock offset"
    # an epoch-less trace must not feed the skew math either: its commit
    # spans sit in a private perf domain, not on the shared wall clock
    bare_commit = {"traceEvents": [
        {"name": "stage_commit_finalized", "ph": "X", "ts": 7.0,
         "dur": 1.0, "pid": 1, "tid": 1, "args": {"height": 5}}]}
    r3 = skew_report([("node-a", a), ("old", bare_commit)])
    assert r3["heights"] == 0, r3
    assert r3["max_spread_ms"] == 0.0, r3
    # an aligned trace with NO events (node died at startup: header only)
    # must not drag t0 to 0 and push healthy tracks to wall-clock offsets
    empty = {"traceEvents": [], "node_id": "dead",
             "epoch_unix_s": base, "epoch_perf_us": 0.0}
    m3 = merge([("node-a", a), ("dead", empty)])
    assert min(e["ts"] for e in m3["traceEvents"]
               if e.get("ph") == "X") == 0.0
    assert max(e["ts"] for e in m3["traceEvents"]
               if e.get("ph") == "X") < 2e9, "empty aligned track dragged t0"
    print("trace_merge self-test OK (2 nodes, 2 heights, 50.0 ms skew)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("traces", nargs="*",
                    help="per-node Chrome trace-event JSONs "
                         "(TMTPU_TRACE_OUT output)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the merged Perfetto-loadable trace here")
    ap.add_argument("--json", action="store_true",
                    help="print the skew report as JSON")
    ap.add_argument("--self-test", action="store_true",
                    help="run the built-in alignment check and exit")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    if len(args.traces) < 2:
        ap.error("need at least two trace files (or --self-test)")
    docs = []
    for path in args.traces:
        doc = load_trace(path)
        docs.append((node_label(doc, path), doc))
    if args.out:
        merged = merge(docs)
        with open(args.out, "w") as f:
            json.dump(merged, f)
        print(f"wrote merged trace for {len(docs)} nodes to {args.out} "
              f"({len(merged['traceEvents'])} events, "
              f"aligned={merged['aligned']})")
    report = skew_report(docs)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render_skew(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
