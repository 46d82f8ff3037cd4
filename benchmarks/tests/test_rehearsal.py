"""The CPU rehearsal of both drivers end to end; a traced run's path up to
the reduction, which on the CPU finds no TPU runtime event and has to
refuse; and the command's refusal off a TPU."""

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
    assert {ln["workload"] for ln in lines} == {"commit10k.live",
                                                "sync1000.catchup"}
    for ln in lines:
        assert ln["rehearsal"] and ln["correct"], ln
        assert all(v["value"] <= v["limit"] for v in ln["compared"].values())
        # counts and verdicts only: nothing that reads as a time or a rate
        assert "metrics" not in ln


def test_traced_run_without_tpu_events_gives_no_result():
    """Probe, its calibration program, the profiler session and the loader
    all run; the reduction finds no execution of a chip and says so."""
    import time

    import harness
    import rehearse

    small = dict(rehearse.SMALL, trace_after_ticks=0, trace_ticks=1)
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
