"""tools/bench_compare.py: the bench-regression gate — exit codes,
per-metric thresholds, lower-is-better latency gating, driver-format
parsing (raw JSONL and the driver's {"tail": ...} record), and a multi-run
history staying machine-checkable. Every run is rows the test writes: the
numbers are made up, the gate's behaviour on them is what is asserted."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(__file__))
TOOL = os.path.join(REPO, "tools", "bench_compare.py")


def _mod():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import bench_compare

        return bench_compare
    finally:
        sys.path.pop(0)


def _run(*args):
    return subprocess.run([sys.executable, TOOL, *args],
                          capture_output=True, text=True, timeout=60)


def _write(path, metrics):
    with open(path, "w") as f:
        for m, (v, unit) in metrics.items():
            f.write(json.dumps({"metric": m, "value": v, "unit": unit,
                                "vs_baseline": 1.0}) + "\n")


def test_self_test_passes():
    res = _run("--self-test")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "self-test OK" in res.stdout


def _write_driver_record(path, metrics, n=1):
    """The driver's record of a bench run: the JSONL stream rides in
    "tail" behind whatever the process logged first."""
    tail = "WARNING: some start-up noise the parser must skip\n" + "".join(
        json.dumps({"metric": m, "value": v, "unit": unit,
                    "vs_baseline": 1.0}) + "\n"
        for m, (v, unit) in metrics.items())
    last = list(metrics.items())[-1]
    with open(path, "w") as f:
        json.dump({"n": n, "cmd": "python bench.py", "rc": 0, "tail": tail,
                   "parsed": {"metric": last[0], "value": last[1][0],
                              "unit": last[1][1], "vs_baseline": 1.0}}, f)


#: three runs of one history: every throughput metric improves run over
#: run, while the flagship's packing share creeps 0.070 -> 0.111 (+59%)
#: between the last two
_HISTORY = [
    {"verify_commit_150_vals_sigs_per_sec": (5000.0, "sigs/s"),
     "fast_sync_1000_vals_blocks_per_sec": (20.0, "blocks/s"),
     "verify_commit_10k_sigs_per_sec": (40000.25, "sigs/s")},
    {"verify_commit_150_vals_sigs_per_sec": (6000.0, "sigs/s"),
     "fast_sync_1000_vals_blocks_per_sec": (30.0, "blocks/s"),
     "localnet_4node_tx_commit_latency_p50": (1.3, "s"),
     "verify_commit_10k_breakdown_pack_share": (0.07, "ratio"),
     "verify_commit_10k_sigs_per_sec": (90000.5, "sigs/s")},
    {"verify_commit_150_vals_sigs_per_sec": (7000.0, "sigs/s"),
     "fast_sync_1000_vals_blocks_per_sec": (50.0, "blocks/s"),
     "localnet_4node_tx_commit_latency_p50": (1.1, "s"),
     "verify_commit_10k_breakdown_pack_share": (0.111, "ratio"),
     "verify_commit_10k_sigs_per_sec": (150000.75, "sigs/s")},
]


def _history(tmp_path):
    paths = []
    for i, metrics in enumerate(_HISTORY, start=1):
        path = str(tmp_path / f"run{i}.json")
        _write_driver_record(path, metrics, n=i)
        paths.append(path)
    return paths


def test_real_history_trips_on_pack_share_creep(tmp_path):
    """A run that improves every throughput metric BUT lets the flagship's
    packing share creep 7% -> 11.1% (+59%): with the pack-share ratio
    gated lower-is-better, the history itself must trip exit 1 on exactly
    that metric — and on nothing else."""
    _old, prev, last = _history(tmp_path)
    res = _run(prev, last)
    assert res.returncode == 1, res.stdout + res.stderr
    fail = next(l for l in res.stdout.splitlines() if l.startswith("FAIL"))
    assert "verify_commit_10k_breakdown_pack_share" in fail
    assert fail.startswith("FAIL: 1 regression(s)"), fail
    # loosening that one metric's threshold restores a clean pair
    res2 = _run("--threshold",
                "verify_commit_10k_breakdown_pack_share=0.6", prev, last)
    assert res2.returncode == 0, res2.stdout
    assert "OK: no regressions" in res2.stdout
    bc = _mod()
    run = bc.load_bench(last)  # the driver's record format parses
    assert run["verify_commit_10k_sigs_per_sec"]["value"] > 150000


def test_degraded_flagship_trips_gate(tmp_path):
    bc = _mod()
    last = _history(tmp_path)[-1]
    degraded = dict(bc.load_bench(last))
    rec = dict(degraded["verify_commit_10k_sigs_per_sec"])
    rec["value"] = rec["value"] * 0.5  # 50% < the 30% default threshold
    degraded["verify_commit_10k_sigs_per_sec"] = rec
    new = str(tmp_path / "new.json")
    with open(new, "w") as f:
        for line in degraded.values():
            f.write(json.dumps(line) + "\n")
    res = _run(last, new)
    assert res.returncode == 1, res.stdout
    assert "REGRESSION" in res.stdout
    assert "verify_commit_10k_sigs_per_sec" in res.stdout
    # loosening that one metric's threshold un-trips it
    res2 = _run("--threshold", "verify_commit_10k_sigs_per_sec=0.6",
                last, new)
    assert res2.returncode == 0, res2.stdout


def test_latency_gated_lower_is_better(tmp_path):
    old, new = str(tmp_path / "old.json"), str(tmp_path / "new.json")
    _write(old, {"localnet_4node_tx_commit_latency_p50": (1.0, "s")})
    _write(new, {"localnet_4node_tx_commit_latency_p50": (1.6, "s")})
    assert _run(old, new).returncode == 1
    _write(new, {"localnet_4node_tx_commit_latency_p50": (0.5, "s")})
    res = _run(old, new)
    assert res.returncode == 0
    assert "improved" in res.stdout


def test_missing_gated_metric_fails(tmp_path):
    old, new = str(tmp_path / "old.json"), str(tmp_path / "new.json")
    _write(old, {"verify_commit_10k_sigs_per_sec": (100.0, "sigs/s"),
                 "some_breakdown_share": (0.5, "ratio")})
    _write(new, {"some_breakdown_share": (0.9, "ratio")})
    res = _run(old, new)
    assert res.returncode == 1
    assert "MISSING" in res.stdout


def test_trajectory_table_over_history(tmp_path):
    files = _history(tmp_path)
    # the pack-share gate trips on the raw last pair (see above);
    # loosened here so this test isolates the trajectory rendering
    res = _run("--threshold",
               "verify_commit_10k_breakdown_pack_share=0.6", *files)
    assert res.returncode == 0, res.stdout + res.stderr
    # all three runs' flagship values appear in one row
    line = next(l for l in res.stdout.splitlines()
                if l.startswith("verify_commit_10k_sigs_per_sec "))
    assert "150000" in line and "90000" in line and "40000" in line
    # the gated pack share joined the trajectory table
    assert any(l.startswith("verify_commit_10k_breakdown_pack_share")
               for l in res.stdout.splitlines())


def test_parse_error_exits_2(tmp_path):
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("not a bench file\n")
    res = _run(bad, bad)
    assert res.returncode == 2
    assert "no bench metric lines" in res.stderr


def test_json_output(tmp_path):
    old, new = str(tmp_path / "old.json"), str(tmp_path / "new.json")
    _write(old, {"verify_commit_10k_sigs_per_sec": (100.0, "sigs/s")})
    _write(new, {"verify_commit_10k_sigs_per_sec": (10.0, "sigs/s")})
    res = _run("--json", old, new)
    assert res.returncode == 1
    doc = json.loads(res.stdout)
    assert doc["regressions"] == 1
    assert doc["rows"][0]["status"] == "regressed"
