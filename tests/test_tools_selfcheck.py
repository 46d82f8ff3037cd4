"""tools/selfcheck.py as the tier-1 seam against tool rot: discovery sees
every --self-test-capable tool and the full toolbox passes in subprocesses
(argument parsing, imports, exit codes — the operator-facing surface)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(__file__))
TOOL = os.path.join(REPO, "tools", "selfcheck.py")


def _run(*args, timeout=420):
    return subprocess.run([sys.executable, TOOL, *args],
                          capture_output=True, text=True, timeout=timeout)


def test_selfcheck_self_test():
    res = _run("--self-test", timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "self-test OK" in res.stdout


def test_discovery_sees_the_toolbox():
    res = _run("--list", timeout=60)
    assert res.returncode == 0, res.stderr
    tools = set(res.stdout.split())
    assert {"trace_summary.py", "trace_merge.py", "fleet_scrape.py",
            "chaos_matrix.py", "loadtime.py", "churn.py", "crashmatrix.py",
            "soak.py", "quorum_loss.py", "execbench.py"} == tools
    assert "selfcheck.py" not in tools


def test_unknown_only_errors():
    res = _run("--only", "no_such_tool", timeout=60)
    assert res.returncode == 2
    assert "unknown tools" in res.stderr


def test_full_toolbox_passes():
    """Every tools/*.py --self-test, each in a fresh subprocess. This IS
    the CI guard the satellite asks for: any tool rot fails tier-1."""
    res = _run()
    assert res.returncode == 0, res.stdout + res.stderr
    lines = [l for l in res.stdout.splitlines() if l.startswith("PASS ")]
    assert len(lines) == 10, res.stdout
    assert "FAIL" not in res.stdout
