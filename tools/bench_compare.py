"""Bench-regression gate: diff recorded bench.py runs, flag regressions,
exit nonzero.

A run-over-run trajectory checked by eyeball is folklore; this makes it a
machine-checked invariant:

    python tools/bench_compare.py old.json new.json
    python tools/bench_compare.py run1.json run2.json new.json  # trajectory too
    python tools/bench_compare.py --threshold \
        verify_commit_10k_sigs_per_sec=0.2 old.json new.json
    python tools/bench_compare.py --self-test

Accepted inputs: the driver's record format ({"tail": "<jsonl>", ...}), a
raw bench.py JSONL stream, or a JSON array of metric lines. The NEWEST file
(last argument) is gated against the one before it; earlier files only feed
the trajectory table.

Gating policy, by the bench's own unit conventions:
* throughput units (sigs/s, blocks/s, blocks/min): higher is better —
  regression when new < old * (1 - threshold);
* latency unit (s): lower is better — regression when
  new > old * (1 + threshold);
* informational units (ratio, events, ms/height, error) and *_failed
  markers: reported, never gated — EXCEPT the cost-structure ratios named
  in RATIO_GATED_LOWER_BETTER (currently the flagship's
  verify_commit_10k_breakdown_pack_share), which gate lower-is-better at
  the default threshold: a 7% -> 11.1% packing creep once ran ungated
  and this is the regression gate that would have caught it.

The default threshold is deliberately loose (30%): run-to-run spread on a
locally attached chip is not measured yet, and a gate that cries wolf
gets deleted. Tighten per-metric with --threshold NAME=FRAC.

Exit codes: 0 clean, 1 regression(s), 2 usage/parse error. Stdlib-only.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional

DEFAULT_THRESHOLD = 0.30

#: units gated as higher-is-better throughput; "headers/s" is the
#: light-client serving plane's fleet-throughput unit (bench.py config
#: lightserve, tools/lightserve_bench.py); "commits/min" is the
#: degraded-network plane's WAN-profile throughput (bench.py config wan,
#: tools/quorum_loss.py)
HIGHER_BETTER_UNITS = {"sigs/s", "blocks/s", "blocks/min", "txs/s",
                       "commits/s", "commits/min", "headers/s"}
#: units gated as lower-is-better latency; "breaches" is the soak
#: plane's SLO-miss count (tools/soak.py) — more breaches is strictly
#: worse, same gating shape as a latency
LOWER_BETTER_UNITS = {"s", "ms", "breaches"}
#: ratio-unit metrics gated lower-is-better DESPITE ratios defaulting to
#: informational: the 10k flagship's packing share crept 7% -> 11.1%
#: r04 -> r05 with nothing watching — cost-structure creep in these trips
#: the gate like a latency regression would
RATIO_GATED_LOWER_BETTER = {"verify_commit_10k_breakdown_pack_share"}


def load_bench(path: str) -> Dict[str, dict]:
    """{metric: line} from a driver record, raw JSONL, or a JSON array.
    Later lines win (bench emits each metric once; reruns append)."""
    with open(path) as f:
        text = f.read()
    lines: List[str] = []
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict) and "tail" in doc:
        lines = str(doc["tail"]).splitlines()
    elif isinstance(doc, dict) and "metric" in doc:
        lines = [text]
    elif isinstance(doc, list):
        lines = [json.dumps(e) for e in doc]
    else:
        lines = text.splitlines()
    out: Dict[str, dict] = {}
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "metric" in rec and "value" in rec:
            out[rec["metric"]] = rec
    if not out:
        raise ValueError(f"{path}: no bench metric lines found")
    return out


def load_history(path: str):
    """(labels, runs) from a cross-run history file: one JSON object per
    line, ``{"label": ..., "metrics": [bench rows]}`` (tools/soak.py
    --history appends these). A bare list of rows is accepted too, with
    the line number as its label. Blank/comment lines are skipped."""
    labels: List[str] = []
    runs: List[Dict[str, dict]] = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            doc = json.loads(line)
            if isinstance(doc, list):
                doc = {"label": f"run{i}", "metrics": doc}
            if not isinstance(doc, dict) or "metrics" not in doc:
                raise ValueError(
                    f"{path}:{i}: want {{'label', 'metrics'}} per line")
            run: Dict[str, dict] = {}
            for rec in doc["metrics"]:
                if isinstance(rec, dict) and "metric" in rec \
                        and "value" in rec:
                    run[rec["metric"]] = rec
            if not run:
                raise ValueError(f"{path}:{i}: no metric rows in entry")
            labels.append(str(doc.get("label", f"run{i}")))
            runs.append(run)
    if not runs:
        raise ValueError(f"{path}: empty history")
    return labels, runs


def gate_direction(metric: str, unit: str) -> Optional[str]:
    """'up' (higher better), 'down' (lower better), or None (not gated)."""
    if metric in RATIO_GATED_LOWER_BETTER and unit == "ratio":
        # checked before the generic _breakdown exclusion; the unit guard
        # keeps the crashed-config convention (unit "error") flagging the
        # row as errored instead of silently comparing garbage
        return "down"
    if metric.endswith("_failed") or "_breakdown" in metric \
            or metric == "trace_summary":
        return None
    if unit in HIGHER_BETTER_UNITS:
        return "up"
    if unit in LOWER_BETTER_UNITS:
        return "down"
    return None


def compare(old: Dict[str, dict], new: Dict[str, dict],
            thresholds: Dict[str, float],
            default_threshold: float = DEFAULT_THRESHOLD) -> List[dict]:
    """Per-metric verdicts for every metric in either run."""
    rows: List[dict] = []
    for metric in sorted(set(old) | set(new)):
        o, n = old.get(metric), new.get(metric)
        # direction comes from the OLD record's unit when it exists: a
        # crashed config re-emits its metric with unit "error" (bench.py's
        # except paths), and taking the new unit would silently un-gate it
        unit = (o or n).get("unit", "")
        direction = gate_direction(metric, unit)
        thr = thresholds.get(metric, default_threshold)
        row = {"metric": metric, "unit": unit,
               "old": o["value"] if o else None,
               "new": n["value"] if n else None,
               "direction": direction, "threshold": thr}
        if direction is None:
            if o is not None and n is not None and \
                    gate_direction(metric, n.get("unit", "")) is not None:
                # the REVERSE unit flip: the OLD record errored (direction
                # comes from its unit) while the new one gates — a crashed
                # baseline must not silently un-gate the metric; flag it so
                # the operator re-baselines instead of comparing garbage
                row["status"] = "errored"
            else:
                row["status"] = "info"
        elif o is None:
            row["status"] = "new"
        elif n is None:
            # the metric vanished — the config crashed or was deleted; a
            # silent disappearance must not read as "no regression"
            row["status"] = "missing"
        elif gate_direction(metric, n.get("unit", "")) != direction:
            # a gated metric flipped to a non-gated unit ("error"): the
            # config crashed — must not read as "no regression"
            row["status"] = "errored"
        else:
            ratio = (n["value"] / o["value"]) if o["value"] else float("inf")
            row["ratio"] = round(ratio, 3)
            if direction == "up":
                regressed = n["value"] < o["value"] * (1.0 - thr)
                improved = n["value"] > o["value"] * (1.0 + thr)
            else:
                regressed = n["value"] > o["value"] * (1.0 + thr)
                improved = n["value"] < o["value"] * (1.0 - thr)
            row["status"] = ("regressed" if regressed
                             else "improved" if improved else "ok")
        rows.append(row)
    return rows


def trajectory(runs: List[Dict[str, dict]], labels: List[str]) -> str:
    """metric × run table over every gated metric present anywhere."""
    metrics = sorted({m for run in runs for m in run
                      if gate_direction(m, run[m].get("unit", ""))
                      is not None})
    if not metrics:
        return "(no gated metrics)"
    w = max(len(m) for m in metrics)
    cols = [f"{lab[-14:]:>14}" for lab in labels]
    lines = [f"{'metric':<{w}}  " + "  ".join(cols)]
    for m in metrics:
        cells = []
        for run in runs:
            v = run.get(m, {}).get("value")
            cells.append(f"{v:>14.3f}" if isinstance(v, (int, float))
                         else f"{'-':>14}")
        lines.append(f"{m:<{w}}  " + "  ".join(cells))
    return "\n".join(lines)


def render(rows: List[dict]) -> str:
    w = max(len(r["metric"]) for r in rows)
    lines = [f"{'metric':<{w}}  {'old':>14}  {'new':>14}  {'ratio':>7}  "
             f"status"]
    for r in rows:
        old = f"{r['old']:.3f}" if isinstance(r["old"], (int, float)) else "-"
        new = f"{r['new']:.3f}" if isinstance(r["new"], (int, float)) else "-"
        ratio = f"{r['ratio']:.3f}" if "ratio" in r else "-"
        mark = {"regressed": " <-- REGRESSION",
                "missing": " <-- MISSING",
                "errored": " <-- ERRORED"}.get(r["status"], "")
        lines.append(f"{r['metric']:<{w}}  {old:>14}  {new:>14}  "
                     f"{ratio:>7}  {r['status']}{mark}")
    return "\n".join(lines)


def parse_thresholds(pairs: List[str]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for p in pairs:
        name, _, frac = p.partition("=")
        if not name or not frac:
            raise ValueError(f"--threshold wants NAME=FRACTION, got {p!r}")
        out[name] = float(frac)
    return out


# -- self-test ----------------------------------------------------------------

def _write(path: str, metrics: Dict[str, tuple]) -> None:
    with open(path, "w") as f:
        for m, (v, unit) in metrics.items():
            f.write(json.dumps({"metric": m, "value": v, "unit": unit,
                                "vs_baseline": 1.0}) + "\n")


def self_test() -> int:
    import os
    import tempfile

    d = tempfile.mkdtemp(prefix="bench-compare-")
    try:
        base = os.path.join(d, "old.json")
        _write(base, {"verify_commit_10k_sigs_per_sec": (157000.0, "sigs/s"),
                      "verify_commit_10k_multichip_sigs_per_sec":
                          (500000.0, "sigs/s"),
                      "localnet_4node_tx_commit_latency_p50": (1.1, "s"),
                      "localnet_4node_ingest_txs_per_sec": (24.0, "txs/s"),
                      "localnet_4node_ingest_commit_latency_p99_s":
                          (2.0, "s"),
                      "localnet_4node_ingest_checktx_p99_s": (0.02, "s"),
                      "verify_commit_10k_breakdown_pack_share":
                          (0.11, "ratio"),
                      "fast_sync_pipeline_breakdown_hash_store_share":
                          (0.2, "ratio")})
        # within the 30% window on throughput, latency, AND the gated
        # pack-share ratio: clean (other breakdown ratios stay info even
        # when they triple)
        ok = os.path.join(d, "ok.json")
        _write(ok, {"verify_commit_10k_sigs_per_sec": (140000.0, "sigs/s"),
                    "verify_commit_10k_multichip_sigs_per_sec":
                        (480000.0, "sigs/s"),
                    "localnet_4node_tx_commit_latency_p50": (1.3, "s"),
                    "localnet_4node_ingest_txs_per_sec": (22.0, "txs/s"),
                    "localnet_4node_ingest_commit_latency_p99_s":
                        (2.3, "s"),
                    "localnet_4node_ingest_checktx_p99_s": (0.024, "s"),
                    "verify_commit_10k_breakdown_pack_share":
                        (0.13, "ratio"),
                    "fast_sync_pipeline_breakdown_hash_store_share":
                        (0.6, "ratio")})
        assert main([base, ok]) == 0
        # the ingestion-plane rows gate like any throughput/latency pair:
        # a collapsed ingest rate (open-loop load no longer keeping up)
        # and a p99 blow-up each trip exit 1...
        ing_bad = os.path.join(d, "ingest_bad.json")
        _write(ing_bad, {"localnet_4node_ingest_txs_per_sec":
                         (10.0, "txs/s"),
                         "localnet_4node_ingest_commit_latency_p99_s":
                         (6.0, "s"),
                         "localnet_4node_ingest_checktx_p99_s":
                         (0.2, "s")})
        assert main(["--threshold", "verify_commit_10k_sigs_per_sec=9",
                     "--threshold",
                     "verify_commit_10k_multichip_sigs_per_sec=9",
                     "--threshold",
                     "localnet_4node_tx_commit_latency_p50=9",
                     "--threshold",
                     "verify_commit_10k_breakdown_pack_share=9",
                     base, ing_bad]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(base), load_bench(ing_bad), {})}
        assert rows["localnet_4node_ingest_txs_per_sec"][
            "status"] == "regressed"
        assert rows["localnet_4node_ingest_commit_latency_p99_s"][
            "status"] == "regressed"
        # the admission-latency row gates lower-better like any "s" metric:
        # a 10x checktx p99 blow-up trips on its own
        assert rows["localnet_4node_ingest_checktx_p99_s"][
            "status"] == "regressed"
        # (ing_bad also dropped the flagship rows — flagged as missing)
        assert rows["verify_commit_10k_sigs_per_sec"]["status"] == "missing"
        # ...a VANISHED ingest metric fails on its own...
        ing_gone = os.path.join(d, "ingest_gone.json")
        _write(ing_gone, {
            "verify_commit_10k_sigs_per_sec": (157000.0, "sigs/s"),
            "verify_commit_10k_multichip_sigs_per_sec":
                (500000.0, "sigs/s"),
            "localnet_4node_tx_commit_latency_p50": (1.1, "s"),
            "localnet_4node_ingest_txs_per_sec": (24.0, "txs/s"),
            "verify_commit_10k_breakdown_pack_share": (0.11, "ratio"),
        })
        assert main([base, ing_gone]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(base), load_bench(ing_gone), {})}
        assert rows["localnet_4node_ingest_commit_latency_p99_s"][
            "status"] == "missing"
        # ...and per-metric threshold overrides loosen both ingest gates
        assert main(["--threshold", "localnet_4node_ingest_txs_per_sec=0.9",
                     "--threshold",
                     "localnet_4node_ingest_commit_latency_p99_s=9",
                     "--threshold", "verify_commit_10k_sigs_per_sec=9",
                     "--threshold",
                     "verify_commit_10k_multichip_sigs_per_sec=9",
                     "--threshold",
                     "localnet_4node_tx_commit_latency_p50=9",
                     "--threshold",
                     "verify_commit_10k_breakdown_pack_share=9",
                     base, ing_bad]) == 1  # missing flagships still fail
        rows = {r["metric"]: r for r in compare(
            load_bench(base), load_bench(ing_bad),
            {"localnet_4node_ingest_txs_per_sec": 0.9,
             "localnet_4node_ingest_commit_latency_p99_s": 9.0})}
        assert rows["localnet_4node_ingest_txs_per_sec"]["status"] == "ok"
        assert rows["localnet_4node_ingest_commit_latency_p99_s"][
            "status"] == "ok"
        # flagship degraded 60%: gate trips — and the MULTICHIP flagship
        # is gated higher-better exactly like it (a silently-collapsed
        # device pool reads as a regression, not noise)
        bad = os.path.join(d, "bad.json")
        _write(bad, {"verify_commit_10k_sigs_per_sec": (60000.0, "sigs/s"),
                     "verify_commit_10k_multichip_sigs_per_sec":
                         (150000.0, "sigs/s"),
                     "localnet_4node_tx_commit_latency_p50": (1.0, "s"),
                     "localnet_4node_ingest_txs_per_sec": (24.0, "txs/s"),
                     "localnet_4node_ingest_commit_latency_p99_s":
                         (2.0, "s"),
                     "localnet_4node_ingest_checktx_p99_s": (0.02, "s"),
                     "verify_commit_10k_breakdown_pack_share":
                         (0.11, "ratio")})
        assert main([base, bad]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(base), load_bench(bad), {})}
        assert rows["verify_commit_10k_multichip_sigs_per_sec"][
            "status"] == "regressed"
        # the r04 -> r05 packing-share creep (0.07 -> 0.111, +59%), replayed
        # synthetically: lower-is-better ratio gating trips exit 1
        creep_old = os.path.join(d, "creep_old.json")
        creep_new = os.path.join(d, "creep_new.json")
        _write(creep_old, {"verify_commit_10k_breakdown_pack_share":
                           (0.07, "ratio")})
        _write(creep_new, {"verify_commit_10k_breakdown_pack_share":
                           (0.111, "ratio")})
        assert main([creep_old, creep_new]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(creep_old), load_bench(creep_new), {})}
        assert rows["verify_commit_10k_breakdown_pack_share"][
            "status"] == "regressed"
        # ...and a loosened per-metric threshold un-trips it
        assert main(["--threshold",
                     "verify_commit_10k_breakdown_pack_share=0.9",
                     creep_old, creep_new]) == 0
        # an ERRORED BASELINE must not silently un-gate the metric for the
        # next run (reverse unit flip: old=error, new=ratio)
        err_base = os.path.join(d, "err_base.json")
        _write(err_base, {"verify_commit_10k_breakdown_pack_share":
                          (0.0, "error")})
        assert main([err_base, creep_new]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(err_base), load_bench(creep_new), {})}
        assert rows["verify_commit_10k_breakdown_pack_share"][
            "status"] == "errored"
        rows = {r["metric"]: r for r in compare(
            load_bench(base), load_bench(bad), {})}
        assert rows["verify_commit_10k_sigs_per_sec"]["status"] == "regressed"
        # latency is gated lower-is-better
        slow = os.path.join(d, "slow.json")
        _write(slow, {"verify_commit_10k_sigs_per_sec": (157000.0, "sigs/s"),
                      "localnet_4node_tx_commit_latency_p50": (2.0, "s"),
                      "verify_commit_10k_breakdown_pack_share":
                          (0.11, "ratio")})
        assert main([base, slow]) == 1
        # a VANISHED gated metric is a failure, an informational one is not
        gone = os.path.join(d, "gone.json")
        _write(gone, {"localnet_4node_tx_commit_latency_p50": (1.1, "s")})
        assert main([base, gone]) == 1
        # a gated metric re-emitted with unit "error" (bench's crashed-
        # config convention) is a failure, not an un-gated info row
        err = os.path.join(d, "err.json")
        _write(err, {"verify_commit_10k_sigs_per_sec": (0.0, "error"),
                     "localnet_4node_tx_commit_latency_p50": (1.1, "s")})
        assert main([base, err]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(base), load_bench(err), {})}
        assert rows["verify_commit_10k_sigs_per_sec"]["status"] == "errored"
        # per-metric threshold override loosens the gate
        assert main(["--threshold", "verify_commit_10k_sigs_per_sec=0.9",
                     "--threshold",
                     "verify_commit_10k_multichip_sigs_per_sec=0.9",
                     "--threshold",
                     "localnet_4node_tx_commit_latency_p50=2.0",
                     base, bad]) == 0
        # the churn-plane rows gate like any throughput/latency pair: a
        # collapsed blocks/min under churn and a join-to-caught-up blow-up
        # each trip exit 1, a vanished row fails on its own, and per-metric
        # threshold overrides loosen both gates
        ch_base = os.path.join(d, "churn_base.json")
        _write(ch_base, {"inproc_churn8_blocks_per_min":
                         (14.0, "blocks/min"),
                         "inproc_churn8_join_caughtup_s": (8.0, "s")})
        ch_bad = os.path.join(d, "churn_bad.json")
        _write(ch_bad, {"inproc_churn8_blocks_per_min": (5.0, "blocks/min"),
                        "inproc_churn8_join_caughtup_s": (30.0, "s")})
        assert main([ch_base, ch_bad]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(ch_base), load_bench(ch_bad), {})}
        assert rows["inproc_churn8_blocks_per_min"]["status"] == "regressed"
        assert rows["inproc_churn8_join_caughtup_s"]["status"] == "regressed"
        ch_gone = os.path.join(d, "churn_gone.json")
        _write(ch_gone, {"inproc_churn8_blocks_per_min":
                         (14.0, "blocks/min")})
        assert main([ch_base, ch_gone]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(ch_base), load_bench(ch_gone), {})}
        assert rows["inproc_churn8_join_caughtup_s"]["status"] == "missing"
        assert main(["--threshold", "inproc_churn8_blocks_per_min=0.9",
                     "--threshold", "inproc_churn8_join_caughtup_s=9",
                     ch_base, ch_bad]) == 0
        # a crashed churn config re-emits its rows with unit "error":
        # flagged errored, never silently un-gated
        ch_err = os.path.join(d, "churn_err.json")
        _write(ch_err, {"inproc_churn8_blocks_per_min": (0.0, "error"),
                        "inproc_churn8_join_caughtup_s": (8.0, "s")})
        assert main([ch_base, ch_err]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(ch_base), load_bench(ch_err), {})}
        assert rows["inproc_churn8_blocks_per_min"]["status"] == "errored"
        # the scaling breakdown stays informational (never gated)
        assert gate_direction("inproc_churn_gossip_scaling_breakdown",
                              "ratio") is None
        # the crash-recovery row gates lower-better in BOTH directions: a
        # kill→caught-up blow-up regresses, a big speedup reads improved,
        # a vanished row fails, and a crashed config reads errored
        cr_base = os.path.join(d, "crash_base.json")
        _write(cr_base, {"inproc_crash4_kill_caughtup_s": (5.0, "s")})
        cr_bad = os.path.join(d, "crash_bad.json")
        _write(cr_bad, {"inproc_crash4_kill_caughtup_s": (20.0, "s")})
        assert main([cr_base, cr_bad]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(cr_base), load_bench(cr_bad), {})}
        assert rows["inproc_crash4_kill_caughtup_s"][
            "status"] == "regressed"
        cr_fast = os.path.join(d, "crash_fast.json")
        _write(cr_fast, {"inproc_crash4_kill_caughtup_s": (2.0, "s")})
        rows = {r["metric"]: r for r in compare(
            load_bench(cr_base), load_bench(cr_fast), {})}
        assert rows["inproc_crash4_kill_caughtup_s"]["status"] == "improved"
        assert main([cr_base, cr_fast]) == 0
        cr_gone = os.path.join(d, "crash_gone.json")
        _write(cr_gone, {"unrelated_row": (1.0, "s")})
        assert main([cr_base, cr_gone]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(cr_base), load_bench(cr_gone), {})}
        assert rows["inproc_crash4_kill_caughtup_s"]["status"] == "missing"
        cr_err = os.path.join(d, "crash_err.json")
        _write(cr_err, {"inproc_crash4_kill_caughtup_s": (0.0, "error")})
        assert main([cr_base, cr_err]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(cr_base), load_bench(cr_err), {})}
        assert rows["inproc_crash4_kill_caughtup_s"]["status"] == "errored"
        # ...and a loosened per-metric threshold un-trips the regression
        assert main(["--threshold", "inproc_crash4_kill_caughtup_s=9",
                     cr_base, cr_bad]) == 0
        # the exec A/B row gates higher-better in BOTH directions: a
        # committed-throughput collapse regresses, a jump reads improved
        ex_base = os.path.join(d, "exec_base.json")
        _write(ex_base, {"inproc_exec4_committed_txs_per_sec":
                         (100.0, "txs/s")})
        ex_bad = os.path.join(d, "exec_bad.json")
        _write(ex_bad, {"inproc_exec4_committed_txs_per_sec":
                        (40.0, "txs/s")})
        assert main([ex_base, ex_bad]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(ex_base), load_bench(ex_bad), {})}
        assert rows["inproc_exec4_committed_txs_per_sec"][
            "status"] == "regressed"
        ex_fast = os.path.join(d, "exec_fast.json")
        _write(ex_fast, {"inproc_exec4_committed_txs_per_sec":
                         (250.0, "txs/s")})
        assert main([ex_base, ex_fast]) == 0
        rows = {r["metric"]: r for r in compare(
            load_bench(ex_base), load_bench(ex_fast), {})}
        assert rows["inproc_exec4_committed_txs_per_sec"][
            "status"] == "improved"
        # ...while the exec phase breakdown stays informational
        assert gate_direction("inproc_exec4_phase_breakdown",
                              "ratio") is None
        # the aggregate-signature A/B rows gate higher-better in BOTH
        # directions on the commits/s unit: a collapsed BLS verify rate
        # regresses, a jump reads improved, and the informational
        # commit-size row (unit "bytes") never gates
        ag_base = os.path.join(d, "aggsig_base.json")
        _write(ag_base, {
            "verify_commit_1000val_ed25519_batched_commits_per_sec":
                (3.0, "commits/s"),
            "verify_commit_1000val_bls_aggregated_commits_per_sec":
                (16.0, "commits/s"),
            "aggregated_commit_1000val_bytes": (190.0, "bytes")})
        ag_bad = os.path.join(d, "aggsig_bad.json")
        _write(ag_bad, {
            "verify_commit_1000val_ed25519_batched_commits_per_sec":
                (3.0, "commits/s"),
            "verify_commit_1000val_bls_aggregated_commits_per_sec":
                (4.0, "commits/s"),
            "aggregated_commit_1000val_bytes": (700.0, "bytes")})
        assert main([ag_base, ag_bad]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(ag_base), load_bench(ag_bad), {})}
        assert rows["verify_commit_1000val_bls_aggregated_commits_per_sec"][
            "status"] == "regressed"
        assert rows["aggregated_commit_1000val_bytes"]["status"] == "info"
        ag_fast = os.path.join(d, "aggsig_fast.json")
        _write(ag_fast, {
            "verify_commit_1000val_ed25519_batched_commits_per_sec":
                (3.0, "commits/s"),
            "verify_commit_1000val_bls_aggregated_commits_per_sec":
                (40.0, "commits/s"),
            "aggregated_commit_1000val_bytes": (190.0, "bytes")})
        assert main([ag_base, ag_fast]) == 0
        rows = {r["metric"]: r for r in compare(
            load_bench(ag_base), load_bench(ag_fast), {})}
        assert rows["verify_commit_1000val_bls_aggregated_commits_per_sec"][
            "status"] == "improved"
        # ...and the loosened per-metric threshold un-trips the regression
        assert main([
            "--threshold",
            "verify_commit_1000val_bls_aggregated_commits_per_sec=0.9",
            ag_base, ag_bad]) == 0
        # the soak rows: the "breaches" unit gates lower-better in BOTH
        # directions — more SLO misses regress, fewer read improved —
        # and missing/errored rows trip like any gated metric
        assert gate_direction("inproc_soak_slo_breaches",
                              "breaches") == "down"
        so_base = os.path.join(d, "soak_base.json")
        _write(so_base, {"inproc_soak_slo_breaches": (2.0, "breaches"),
                         "inproc_soak_commit_p99_s": (6.0, "s")})
        so_bad = os.path.join(d, "soak_bad.json")
        _write(so_bad, {"inproc_soak_slo_breaches": (9.0, "breaches"),
                        "inproc_soak_commit_p99_s": (6.0, "s")})
        assert main([so_base, so_bad]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(so_base), load_bench(so_bad), {})}
        assert rows["inproc_soak_slo_breaches"]["status"] == "regressed"
        so_good = os.path.join(d, "soak_good.json")
        _write(so_good, {"inproc_soak_slo_breaches": (0.0, "breaches"),
                         "inproc_soak_commit_p99_s": (5.5, "s")})
        assert main([so_base, so_good]) == 0
        rows = {r["metric"]: r for r in compare(
            load_bench(so_base), load_bench(so_good), {})}
        assert rows["inproc_soak_slo_breaches"]["status"] == "improved"
        so_gone = os.path.join(d, "soak_gone.json")
        _write(so_gone, {"inproc_soak_commit_p99_s": (6.0, "s")})
        rows = {r["metric"]: r for r in compare(
            load_bench(so_base), load_bench(so_gone), {})}
        assert rows["inproc_soak_slo_breaches"]["status"] == "missing"
        assert main([so_base, so_gone]) == 1
        so_err = os.path.join(d, "soak_err.json")
        _write(so_err, {"inproc_soak_slo_breaches": (0.0, "error"),
                        "inproc_soak_commit_p99_s": (6.0, "s")})
        rows = {r["metric"]: r for r in compare(
            load_bench(so_base), load_bench(so_err), {})}
        assert rows["inproc_soak_slo_breaches"]["status"] == "errored"
        assert main([so_base, so_err]) == 1
        # ...and a loosened per-metric threshold un-trips the soak gate
        assert main(["--threshold", "inproc_soak_slo_breaches=4",
                     so_base, so_bad]) == 0
        # the light-client serving rows gate BOTH directions: the fleet
        # throughput ("headers/s") higher-better, the client p99 ("s")
        # lower-better — a collapsed coalescer regresses on either axis,
        # a faster one reads improved, and the crashed-config convention
        # (unit "error") trips rather than un-gates
        assert gate_direction("lightserve_clients_headers_per_sec",
                              "headers/s") == "up"
        assert gate_direction("lightserve_p99_s", "s") == "down"
        ls_base = os.path.join(d, "lightserve_base.json")
        _write(ls_base, {"lightserve_clients_headers_per_sec":
                         (2000.0, "headers/s"),
                         "lightserve_p99_s": (0.010, "s"),
                         "lightserve_bls_clients_headers_per_sec":
                         (400.0, "headers/s")})
        ls_bad = os.path.join(d, "lightserve_bad.json")
        _write(ls_bad, {"lightserve_clients_headers_per_sec":
                        (800.0, "headers/s"),
                        "lightserve_p99_s": (0.050, "s"),
                        "lightserve_bls_clients_headers_per_sec":
                        (400.0, "headers/s")})
        assert main([ls_base, ls_bad]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(ls_base), load_bench(ls_bad), {})}
        assert rows["lightserve_clients_headers_per_sec"][
            "status"] == "regressed"
        assert rows["lightserve_p99_s"]["status"] == "regressed"
        ls_fast = os.path.join(d, "lightserve_fast.json")
        _write(ls_fast, {"lightserve_clients_headers_per_sec":
                         (3500.0, "headers/s"),
                         "lightserve_p99_s": (0.004, "s"),
                         "lightserve_bls_clients_headers_per_sec":
                         (700.0, "headers/s")})
        assert main([ls_base, ls_fast]) == 0
        rows = {r["metric"]: r for r in compare(
            load_bench(ls_base), load_bench(ls_fast), {})}
        assert rows["lightserve_clients_headers_per_sec"][
            "status"] == "improved"
        assert rows["lightserve_p99_s"]["status"] == "improved"
        ls_gone = os.path.join(d, "lightserve_gone.json")
        _write(ls_gone, {"lightserve_p99_s": (0.010, "s")})
        assert main([ls_base, ls_gone]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(ls_base), load_bench(ls_gone), {})}
        assert rows["lightserve_clients_headers_per_sec"][
            "status"] == "missing"
        ls_err = os.path.join(d, "lightserve_err.json")
        _write(ls_err, {"lightserve_clients_headers_per_sec":
                        (0.0, "error"),
                        "lightserve_p99_s": (0.010, "s"),
                        "lightserve_bls_clients_headers_per_sec":
                        (400.0, "headers/s")})
        assert main([ls_base, ls_err]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(ls_base), load_bench(ls_err), {})}
        assert rows["lightserve_clients_headers_per_sec"][
            "status"] == "errored"
        # ...and loosened per-metric thresholds un-trip the pair
        assert main(["--threshold",
                     "lightserve_clients_headers_per_sec=0.9",
                     "--threshold", "lightserve_p99_s=9",
                     ls_base, ls_bad]) == 0
        # the degraded-network rows (bench.py config wan): WAN-profile
        # throughput ("commits/min") gates higher-better, quorum-loss
        # recovery ("s") lower-better — both directions trip, both read
        # improved when they move the right way, and the crashed-config
        # convention (unit "error") trips rather than un-gates
        assert gate_direction("inproc_wan4_commits_per_min",
                              "commits/min") == "up"
        assert gate_direction("inproc_quorumloss_recover_s", "s") == "down"
        wn_base = os.path.join(d, "wan_base.json")
        _write(wn_base, {"inproc_wan4_commits_per_min":
                         (28.0, "commits/min"),
                         "inproc_quorumloss_recover_s": (2.0, "s")})
        wn_bad = os.path.join(d, "wan_bad.json")
        _write(wn_bad, {"inproc_wan4_commits_per_min":
                        (12.0, "commits/min"),
                        "inproc_quorumloss_recover_s": (9.0, "s")})
        assert main([wn_base, wn_bad]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(wn_base), load_bench(wn_bad), {})}
        assert rows["inproc_wan4_commits_per_min"]["status"] == "regressed"
        assert rows["inproc_quorumloss_recover_s"]["status"] == "regressed"
        wn_good = os.path.join(d, "wan_good.json")
        _write(wn_good, {"inproc_wan4_commits_per_min":
                         (45.0, "commits/min"),
                         "inproc_quorumloss_recover_s": (1.0, "s")})
        assert main([wn_base, wn_good]) == 0
        rows = {r["metric"]: r for r in compare(
            load_bench(wn_base), load_bench(wn_good), {})}
        assert rows["inproc_wan4_commits_per_min"]["status"] == "improved"
        assert rows["inproc_quorumloss_recover_s"]["status"] == "improved"
        wn_err = os.path.join(d, "wan_err.json")
        _write(wn_err, {"inproc_wan4_commits_per_min": (0.0, "error"),
                        "inproc_quorumloss_recover_s": (2.0, "s")})
        assert main([wn_base, wn_err]) == 1
        rows = {r["metric"]: r for r in compare(
            load_bench(wn_base), load_bench(wn_err), {})}
        assert rows["inproc_wan4_commits_per_min"]["status"] == "errored"
        # ...and loosened per-metric thresholds un-trip the pair
        assert main(["--threshold", "inproc_wan4_commits_per_min=0.9",
                     "--threshold", "inproc_quorumloss_recover_s=9",
                     wn_base, wn_bad]) == 0
        # cross-run history (--history): the JSONL trend file soak.py
        # appends to — the newest entry gates against the one before it,
        # a drifting trend exits 1, an improving one exits 0, and a
        # single entry has nothing to gate yet
        hist_bad = os.path.join(d, "hist_bad.jsonl")
        with open(hist_bad, "w") as f:
            for label, breaches, p99 in (("r01", 0.0, 5.0),
                                         ("r02", 1.0, 5.5),
                                         ("r03", 6.0, 9.0)):
                f.write(json.dumps({"label": label, "metrics": [
                    {"metric": "inproc_soak_slo_breaches",
                     "value": breaches, "unit": "breaches"},
                    {"metric": "inproc_soak_commit_p99_s",
                     "value": p99, "unit": "s"}]}) + "\n")
        assert main(["--history", hist_bad]) == 1
        labels, runs = load_history(hist_bad)
        assert labels == ["r01", "r02", "r03"]
        rows = {r["metric"]: r for r in compare(runs[-2], runs[-1], {})}
        assert rows["inproc_soak_slo_breaches"]["status"] == "regressed"
        assert rows["inproc_soak_commit_p99_s"]["status"] == "regressed"
        table = trajectory(runs, labels)
        assert "inproc_soak_slo_breaches" in table and "r03" in table
        hist_ok = os.path.join(d, "hist_ok.jsonl")
        with open(hist_ok, "w") as f:
            for label, breaches in (("r01", 6.0), ("r02", 2.0),
                                    ("r03", 1.0)):
                f.write(json.dumps({"label": label, "metrics": [
                    {"metric": "inproc_soak_slo_breaches",
                     "value": breaches, "unit": "breaches"}]}) + "\n")
        assert main(["--history", hist_ok]) == 0
        hist_one = os.path.join(d, "hist_one.jsonl")
        with open(hist_one, "w") as f:
            f.write(json.dumps({"label": "r01", "metrics": [
                {"metric": "inproc_soak_slo_breaches",
                 "value": 0.0, "unit": "breaches"}]}) + "\n")
        assert main(["--history", hist_one]) == 0
        # a bare row list per line is accepted with generated labels
        hist_bare = os.path.join(d, "hist_bare.jsonl")
        with open(hist_bare, "w") as f:
            f.write(json.dumps([{"metric": "lightserve_p99_s",
                                 "value": 0.01, "unit": "s"}]) + "\n")
            f.write(json.dumps([{"metric": "lightserve_p99_s",
                                 "value": 0.09, "unit": "s"}]) + "\n")
        assert main(["--history", hist_bare]) == 1
        # the driver's record format ({"tail": jsonl}) parses identically
        drv = os.path.join(d, "driver.json")
        with open(drv, "w") as f:
            json.dump({"n": 5, "rc": 0, "tail": "noise\n" + json.dumps(
                {"metric": "verify_commit_10k_sigs_per_sec",
                 "value": 150000.0, "unit": "sigs/s",
                 "vs_baseline": 22.0}) + "\n"}, f)
        assert load_bench(drv)[
            "verify_commit_10k_sigs_per_sec"]["value"] == 150000.0
        assert main([drv, ok]) == 0
        # trajectory across 3 runs renders every gated metric — including
        # the now-gated pack share, but not the informational ratios
        table = trajectory([load_bench(p) for p in (base, ok, bad)],
                           ["r01", "r02", "r03"])
        assert "verify_commit_10k_sigs_per_sec" in table
        assert "verify_commit_10k_breakdown_pack_share" in table
        assert "fast_sync_pipeline_breakdown_hash_store_share" not in table
    finally:
        import shutil

        shutil.rmtree(d, ignore_errors=True)
    print("bench_compare self-test OK (gates, thresholds, formats, "
          "history trends)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("runs", nargs="*",
                    help="bench result files, oldest first; the last is "
                         "gated against the one before it")
    ap.add_argument("--threshold", action="append", default=[],
                    metavar="METRIC=FRAC",
                    help="per-metric regression threshold (repeatable)")
    ap.add_argument("--default-threshold", type=float,
                    default=DEFAULT_THRESHOLD)
    ap.add_argument("--json", action="store_true",
                    help="print the comparison rows as JSON")
    ap.add_argument("--history", metavar="PATH",
                    help="cross-run history file (JSONL, one run per "
                         "line; tools/soak.py --history appends these): "
                         "render the whole trajectory and gate the "
                         "newest entry against the one before it")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    try:
        thresholds = parse_thresholds(args.threshold)
        if args.history:
            if args.runs:
                ap.error("--history takes no positional run files")
            labels, runs = load_history(args.history)
        else:
            if len(args.runs) < 2:
                ap.error("need at least two run files "
                         "(or --history / --self-test)")
            labels, runs = list(args.runs), [load_bench(p)
                                             for p in args.runs]
    except (ValueError, OSError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 2
    if len(runs) < 2:
        # a one-entry history has nothing to gate yet: render it and
        # leave clean — the SECOND run is when the trend line starts
        print(trajectory(runs, labels))
        print("\nOK: single history entry, nothing to gate yet")
        return 0
    rows = compare(runs[-2], runs[-1], thresholds, args.default_threshold)
    bad = [r for r in rows
           if r["status"] in ("regressed", "missing", "errored")]
    if args.json:
        print(json.dumps({"rows": rows, "regressions": len(bad)}, indent=2))
        return 1 if bad else 0
    if len(runs) > 2:
        print(trajectory(runs, labels))
        print()
    print(render(rows))
    print()
    if bad:
        print(f"FAIL: {len(bad)} regression(s) beyond threshold: "
              + ", ".join(r["metric"] for r in bad))
        return 1
    print(f"OK: no regressions across {sum(1 for r in rows if r['direction'])}"
          " gated metrics")
    return 0


if __name__ == "__main__":
    sys.exit(main())
