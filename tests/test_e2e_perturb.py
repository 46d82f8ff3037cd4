"""Multi-process e2e perturbations (reference test/e2e/runner/perturb.go:28-66
kill/pause/restart + post-run invariant checks over RPC): a CLI-generated
localnet survives a SIGKILL'd validator, keeps making progress on 3/4 power,
and the restarted node catches back up; app hashes agree across all nodes.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

pytest.importorskip(
    "cryptography",
    reason="the multi-process net's TCP transport needs the optional "
           "'cryptography' package (absent in slim containers)")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE_PORT = 28800


def _rpc(i, path, base_port=BASE_PORT):
    url = f"http://127.0.0.1:{base_port + 2 * i + 1}/{path}"
    with urllib.request.urlopen(url, timeout=5) as r:
        return json.load(r)["result"]


def _heights(n, base_port=BASE_PORT):
    out = []
    for i in range(n):
        try:
            out.append(int(_rpc(i, "status", base_port)["sync_info"]
                           ["latest_block_height"]))
        except Exception:
            out.append(-1)
    return out


def _spawn(env, out, i):
    return subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cmd",
         "--home", os.path.join(out, f"node{i}"),
         "start", "--log-level", "warning"],
        env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)


def _testnet_env(out, base_port):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cmd", "testnet", "--v", "4",
         "--output-dir", out, "--chain-id", "perturb-e2e",
         "--starting-port", str(base_port)],
        check=True, env=env, cwd=REPO, capture_output=True, timeout=120)
    return env


@pytest.mark.slow
def test_kill_and_restart_validator(tmp_path):
    out = str(tmp_path / "tnet")
    env = _testnet_env(out, BASE_PORT)

    procs = {i: _spawn(env, out, i) for i in range(4)}
    try:
        # phase 1: all four make progress
        deadline = time.time() + 90
        while time.time() < deadline:
            hs = _heights(4)
            if min(hs) >= 2:
                break
            time.sleep(1)
        assert min(_heights(4)) >= 2, f"no initial progress: {_heights(4)}"

        # perturbation: SIGKILL node 3 (perturb.go "kill")
        procs[3].send_signal(signal.SIGKILL)
        procs[3].wait(timeout=10)
        h_at_kill = max(_heights(3))

        # liveness on 3/4 voting power
        deadline = time.time() + 90
        while time.time() < deadline:
            hs = _heights(3)
            if min(hs) >= h_at_kill + 3:
                break
            time.sleep(1)
        assert min(_heights(3)) >= h_at_kill + 3, \
            f"net stalled after kill: {_heights(3)}"

        # restart: the node recovers via WAL/handshake replay and catches up
        procs[3] = _spawn(env, out, 3)
        deadline = time.time() + 120
        while time.time() < deadline:
            hs = _heights(4)
            if hs[3] >= h_at_kill + 3:
                break
            time.sleep(1)
        assert _heights(4)[3] >= h_at_kill + 3, \
            f"restarted node did not catch up: {_heights(4)}"

        # invariant: app-hash agreement at a common height (test/e2e/tests)
        common = min(_heights(4)) - 1
        hashes = {_rpc(i, f"commit?height={common}")["signed_header"]
                  ["header"]["app_hash"] for i in range(4)}
        assert len(hashes) == 1, hashes
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow
def test_pause_and_resume_validator(tmp_path):
    """perturb.go "pause": SIGSTOP one validator — the net keeps committing
    on 3/4 power, and after SIGCONT the frozen node (whose peers never saw
    it exit) rejoins and catches up; app hashes agree everywhere."""
    base_port = BASE_PORT + 100  # keep clear of the kill test's TIME_WAIT
    out = str(tmp_path / "tnet")
    env = _testnet_env(out, base_port)

    procs = {i: _spawn(env, out, i) for i in range(4)}
    try:
        # phase 1: all four make progress
        deadline = time.time() + 90
        while time.time() < deadline:
            if min(_heights(4, base_port)) >= 2:
                break
            time.sleep(1)
        assert min(_heights(4, base_port)) >= 2, \
            f"no initial progress: {_heights(4, base_port)}"

        # perturbation: freeze node 3 mid-flight (no exit, no FIN — its
        # sockets stay open, the hard case for peer bookkeeping)
        procs[3].send_signal(signal.SIGSTOP)
        h_at_pause = max(_heights(3, base_port))

        # liveness on 3/4 voting power while one validator is frozen
        deadline = time.time() + 90
        while time.time() < deadline:
            if min(_heights(3, base_port)) >= h_at_pause + 3:
                break
            time.sleep(1)
        assert min(_heights(3, base_port)) >= h_at_pause + 3, \
            f"net stalled while paused: {_heights(3, base_port)}"

        # resume: the thawed node rejoins without a restart and catches up
        procs[3].send_signal(signal.SIGCONT)
        target = max(_heights(3, base_port)) + 2
        deadline = time.time() + 120
        while time.time() < deadline:
            if _heights(4, base_port)[3] >= target:
                break
            time.sleep(1)
        assert _heights(4, base_port)[3] >= target, \
            f"resumed node did not catch up: {_heights(4, base_port)}"
        assert procs[3].poll() is None, "paused node died instead of rejoining"

        # invariant: app-hash agreement at a common height
        common = min(_heights(4, base_port)) - 1
        hashes = {_rpc(i, f"commit?height={common}", base_port)
                  ["signed_header"]["header"]["app_hash"] for i in range(4)}
        assert len(hashes) == 1, hashes
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGCONT)  # can't terminate a stopped proc
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
