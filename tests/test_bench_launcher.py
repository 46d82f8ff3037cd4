"""bench.py and tools/device_profile.py as launchers: a failed config or
scaling cell fails the run, every row names the device it ran on, and the
workload is decided without touching jax (a parent that has touched jax
holds the chip its children would need)."""

import json
import os
import subprocess
import sys

import pytest

import bench
from tendermint_tpu.libs.toolbox import load_tool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rows(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


def test_failed_config_fails_the_run_without_retry(monkeypatch, capsys):
    calls = []

    def boom():
        calls.append(1)
        raise RuntimeError("kernel refused")

    monkeypatch.setitem(bench.CONFIGS, "1", boom)
    assert bench.main(["--config", "1"]) == 1
    assert len(calls) == 1, "a failed config is not retried"
    row = _rows(capsys)[-1]
    assert row["metric"] == "config_1_failed" and row["unit"] == "error"
    assert "kernel refused" in row["error"]


def test_every_row_names_platform_kind_and_count(monkeypatch, capsys):
    monkeypatch.setitem(bench.CONFIGS, "1",
                        lambda: bench._emit("m", 1.0, "sigs/s", 1.0))
    assert bench.main(["--config", "1"]) == 0
    row = _rows(capsys)[-1]
    assert (row["platform"], row["device_kind"], row["device_count"]) == \
        ("cpu", "cpu", 8)


def test_scaling_cell_error_fails_the_config(monkeypatch):
    dp = load_tool("device_profile")
    monkeypatch.setattr(dp, "run_scale", lambda *a, **kw: {
        "workload": "synthetic", "table": [],
        "cell_errors": [{"devices": 2, "error": "timeout"}]})
    monkeypatch.setattr(bench, "_tools_mod", lambda name: dp)
    with pytest.raises(RuntimeError, match="scaling cells failed"):
        bench.bench_multichip_scale()


def test_stub_kernel_scaling_prints_counts_never_a_rate(monkeypatch, capsys):
    dp = load_tool("device_profile")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(dp, "run_scale", lambda *a, **kw: {
        "workload": "synthetic", "table": [
            {"devices": 2, "mode": "multidev", "chunk": 2048, "threads": 2,
             "sigs": 40960, "sigs_per_sec": 123456.0}]})
    monkeypatch.setattr(bench, "_tools_mod", lambda name: dp)
    bench.bench_multichip_scale()
    out = capsys.readouterr().out
    assert "sigs_per_sec" not in out and "123456" not in out
    assert json.loads(out.splitlines()[-1])["cells"][0]["sigs"] == 40960


@pytest.mark.parametrize("pin,want", [("cpu", "synthetic"), ("", "ed25519")])
def test_workload_is_decided_without_importing_jax(pin, want):
    env = dict(os.environ, JAX_PLATFORMS=pin)
    if not pin:
        del env["JAX_PLATFORMS"]
    res = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, 'tools'); import device_profile as d;"
         " print(d.resolve_workload('auto'), 'jax' in sys.modules)"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=60)
    assert res.stdout.split() == [want, "False"], res.stdout + res.stderr
