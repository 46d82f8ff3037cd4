"""The CPU rehearsal of every cell end to end; the whole list of a window's
requests, written to a file on demand and never to the line; a traced run's
path up to the reduction, which on the CPU finds no TPU runtime event and
has to refuse; and the command's refusal off a TPU."""

import json
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


def test_rehearsal_of_every_cell():
    p = subprocess.run([sys.executable, f"{BENCH}/rehearse.py"], cwd=ROOT,
                       capture_output=True, text=True, timeout=3000)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.startswith("{")]
    with open(f"{ROOT}/BENCHMARK.json") as f:
        cells = [c["name"] for c in json.load(f)["workloads"]]
    assert "commit150.live" in cells and len(cells) >= 3
    assert [ln["workload"] for ln in lines] == cells
    for ln in lines:
        assert ln["rehearsal"] and ln["correct"], ln
        assert all(v["value"] <= v["limit"] for v in ln["compared"].values())
        # counts and verdicts only: nothing that reads as a time or a rate
        assert "metrics" not in ln


def test_requests_out_writes_every_request_and_the_line_stays_as_it_is(
        tmp_path, capsys):
    """``commit150.live`` at its own size: with ``requests_out`` the file
    holds one ``[start_s, length_s, units]`` for every request, and the
    printed line has the keys it has without it."""
    import time

    import harness
    import rehearse

    small = rehearse.small_for("commit150.live")
    assert small["validators"] == 150 and small["heavy_validators"] == 0
    plain = harness.run_cell("commit150.live", 79, 1.0, False,
                             time.perf_counter(), overrides=small)
    path = tmp_path / "requests.json"
    out = harness.run_cell("commit150.live", 79, 1.0, False,
                           time.perf_counter(), overrides=small,
                           requests_out=str(path))
    assert out["correct"] and plain["correct"]
    assert list(out) == list(plain) and "requests" not in out
    rows = json.loads(path.read_text())
    assert len(rows) == out["attempted"] >= 1
    assert all(len(r) == 3 and r[2] == 150 and r[1] > 0 for r in rows)
    assert [r[0] for r in rows] == sorted(r[0] for r in rows)
    assert out["slowest"] == sorted(rows, key=lambda r: -r[1])[:8]
    harness.print_result(out)
    line = capsys.readouterr().out.strip()
    assert "\n" not in line and len(line.encode()) < 4000
    assert list(json.loads(line))[-1] == "compared"
    assert [p.name for p in tmp_path.iterdir()] == ["requests.json"]


def test_traced_run_without_tpu_events_gives_no_result():
    """Probe, its calibration program, the profiler session and the loader
    all run; the reduction finds no execution of a chip and says so."""
    import time

    import harness
    import rehearse

    small = dict(rehearse.small_for("commit10k.live"), trace_after_ticks=0,
                 trace_ticks=1)
    with pytest.raises(harness.BenchmarkError, match="no program ran"):
        harness.run_cell("commit10k.live", 78, 1.0, True,
                         time.perf_counter(), overrides=small)


def test_run_refuses_without_a_tpu():
    p = subprocess.run([sys.executable, f"{BENCH}/run.py", "--workload",
                        "commit10k.live", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, capture_output=True,
                       text=True, timeout=600,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
