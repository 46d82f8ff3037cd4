"""Run every tools/*.py --self-test in a fresh subprocess; fail loud.

The tools directory is the operator's toolbox (trace_summary, trace_merge,
fleet_scrape, chaos_matrix, loadtime, churn, crashmatrix, soak,
quorum_loss, execbench) and each carries a built-in --self-test. This
runner discovers them (any tools/*.py whose source mentions --self-test)
and executes each in a subprocess — argument
parsing, imports, and exit codes included — so a refactor that rots a tool
is caught by pytest (tests/test_tools_selfcheck.py), not by the first
operator who needs it during an incident:

    python tools/selfcheck.py            # run them all
    python tools/selfcheck.py --list     # show what would run
    python tools/selfcheck.py --only trace_merge,loadtime
    python tools/selfcheck.py --self-test

Stdlib-only; subprocesses inherit a CPU-pinned JAX env so a tool that
imports the package never takes the chip.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
from typing import List

TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TOOLS_DIR)
PER_TOOL_TIMEOUT_S = 180


def discover(tools_dir: str = TOOLS_DIR) -> List[str]:
    """Tool filenames (sorted) that advertise a --self-test flag."""
    out = []
    for name in sorted(os.listdir(tools_dir)):
        if not name.endswith(".py") or name == os.path.basename(__file__):
            continue
        try:
            with open(os.path.join(tools_dir, name)) as f:
                src = f.read()
        except OSError:
            continue
        if "--self-test" in src:
            out.append(name)
    return out


def _env() -> dict:
    env = dict(os.environ)
    # mirror conftest's CPU pin: a self-test must not take the chip (it
    # has one owner) nor time anything on it
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_tool(name: str, timeout_s: float = PER_TOOL_TIMEOUT_S) -> dict:
    return run_tool_at(TOOLS_DIR, name, timeout_s)


def run_tool_at(tools_dir: str, name: str,
                timeout_s: float = PER_TOOL_TIMEOUT_S) -> dict:
    """run_tool against an arbitrary directory (self-test seam)."""
    path = os.path.join(tools_dir, name)
    t0 = time.time()
    try:
        res = subprocess.run([sys.executable, path, "--self-test"],
                             capture_output=True, text=True,
                             timeout=timeout_s, env=_env(), cwd=REPO)
        rc, out = res.returncode, (res.stdout + res.stderr)
    except subprocess.TimeoutExpired:
        rc, out = -1, f"timed out after {timeout_s}s"
    return {"tool": name, "rc": rc, "seconds": round(time.time() - t0, 2),
            "output_tail": out[-2000:]}


def self_test() -> int:
    tools = discover()
    # the whole point is catching rot in the known toolbox — if discovery
    # stops seeing these, THIS tool rotted
    for expected in ("trace_summary.py", "trace_merge.py",
                     "fleet_scrape.py", "chaos_matrix.py",
                     "loadtime.py", "churn.py", "crashmatrix.py",
                     "soak.py", "quorum_loss.py", "execbench.py"):
        assert expected in tools, (expected, tools)
    assert os.path.basename(__file__) not in tools  # no recursion
    # prove the runner distinguishes pass from fail without running the
    # real (slow) toolbox: a known-good and a known-bad synthetic tool
    import tempfile

    d = tempfile.mkdtemp(prefix="selfcheck-")
    try:
        good = os.path.join(d, "good.py")
        with open(good, "w") as f:
            f.write("import sys\nprint('ok')  # --self-test\nsys.exit(0)\n")
        bad = os.path.join(d, "bad.py")
        with open(bad, "w") as f:
            f.write("import sys\nsys.exit(3)  # --self-test\n")
        assert discover(d) == ["bad.py", "good.py"]
        results = [run_tool_at(d, "good.py"), run_tool_at(d, "bad.py")]
        assert results[0]["rc"] == 0 and results[1]["rc"] == 3, results
    finally:
        import shutil

        shutil.rmtree(d, ignore_errors=True)
    print(f"selfcheck self-test OK ({len(tools)} tools discovered)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", default="",
                    help="comma-separated tool names (with or without .py)")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--timeout", type=float, default=PER_TOOL_TIMEOUT_S)
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    tools = discover()
    if args.only:
        want = {t if t.endswith(".py") else t + ".py"
                for t in args.only.split(",") if t}
        missing = want - set(tools)
        if missing:
            print(f"selfcheck: unknown tools {sorted(missing)} "
                  f"(have {tools})", file=sys.stderr)
            return 2
        tools = [t for t in tools if t in want]
    if args.list:
        print("\n".join(tools))
        return 0
    failed = []
    for name in tools:
        r = run_tool(name, args.timeout)
        status = "PASS" if r["rc"] == 0 else "FAIL"
        print(f"{status} {name} ({r['seconds']}s)")
        if r["rc"] != 0:
            failed.append(name)
            print(r["output_tail"])
    if failed:
        print(f"selfcheck: {len(failed)}/{len(tools)} failed: {failed}")
        return 1
    print(f"selfcheck: {len(tools)}/{len(tools)} tools OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
