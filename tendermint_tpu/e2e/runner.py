"""E2E testnet runner (reference test/e2e/runner/main.go stages:
setup → start → load → perturb → wait → test → stop).

Drives subprocess nodes (python -m tendermint_tpu.cmd start) generated from
a Manifest. Perturbations follow test/e2e/runner/perturb.go:28-66: kill
(SIGKILL + relaunch), restart (SIGTERM + relaunch), pause (SIGSTOP/SIGCONT),
disconnect (approximated with a long SIGSTOP so peers drop and re-dial —
subprocess nets have no network namespace to unplug).

Invariants after the run (reference test/e2e/tests/): all nodes reach a
common height, app hashes agree at sampled heights, txs injected during the
load stage are queryable everywhere, and byzantine double-votes surface as
committed DuplicateVoteEvidence.
"""

from __future__ import annotations

import base64
import json
import os
import signal
import subprocess
import sys
import time
import urllib.parse
import urllib.request
from typing import Dict, List, Optional

from ..config import CONFIG_DIR, DATA_DIR, Config
from ..libs.supervisor import (RestartSupervisor, policy_from_manifest,
                               write_crashloop_bundle)
from .manifest import Manifest, NodeManifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _have_aiohttp() -> bool:
    """The node's /metrics server needs aiohttp; slim containers without it
    must still run e2e nets (just without the fleet scrape plane)."""
    import importlib.util

    return importlib.util.find_spec("aiohttp") is not None


def _fleet_scrape_mod():
    """Import tools/fleet_scrape.py (stdlib-only, lives outside the
    package)."""
    from ..libs.toolbox import load_tool

    return load_tool("fleet_scrape")


class E2EError(Exception):
    pass


class Runner:
    def __init__(self, manifest: Manifest, root: str, base_port: int = 29000):
        self.m = manifest
        self.root = root
        self.base_port = base_port
        self.procs: Dict[str, subprocess.Popen] = {}
        self.signers: Dict[str, subprocess.Popen] = {}
        self.configs: Dict[str, Config] = {}
        self.node_ids: Dict[str, str] = {}
        self.loaded_txs: List[bytes] = []
        self.departed: set = set()    # clean stop_at leaves (not failures)
        #: crash-recovery plane: one supervisor per restart_policy !=
        #: "never" node; poll_restarts() consults them whenever a wait
        #: loop notices a dead process
        self.supervisors: Dict[str, RestartSupervisor] = {
            nm.name: RestartSupervisor(policy_from_manifest(nm), nm.name)
            for nm in manifest.nodes if nm.restart_policy != "never"}
        self.crashloop_bundles: Dict[str, str] = {}
        #: nodes launched at least once — a fail_point arms ONLY the first
        #: launch, whoever relaunches (supervisor, perturbation, joiner)
        self._launched: set = set()
        #: name -> join-to-caught-up seconds for late joiners (the churn
        #: metric: launch → height >= the net's height at launch time)
        self.join_stats: Dict[str, float] = {}
        self._join_marks: Dict[str, tuple] = {}
        self._fleet = None            # FleetScraper while the net runs
        self.fleet_rollup: Optional[dict] = None
        self._log = open(os.path.join(root, "runner.log"), "w") \
            if os.path.isdir(root) else None

    # -- ports ---------------------------------------------------------------

    def _ports(self, i: int):
        base = self.base_port + 4 * i
        return base, base + 1, base + 2  # p2p, rpc, privval (+3 = metrics)

    def _rpc_port(self, name: str) -> int:
        idx = [n.name for n in self.m.nodes].index(name)
        return self._ports(idx)[1]

    def _metrics_port(self, name: str) -> int:
        idx = [n.name for n in self.m.nodes].index(name)
        return self.base_port + 4 * idx + 3

    # -- stages --------------------------------------------------------------

    def setup(self) -> None:
        """Generate per-node homes, one shared genesis, manifest knobs
        applied to each config."""
        from ..p2p import NodeKey
        from ..privval.file_pv import FilePV
        from ..types import GenesisDoc, GenesisValidator

        os.makedirs(self.root, exist_ok=True)
        pvs: Dict[str, FilePV] = {}
        for i, nm in enumerate(self.m.nodes):
            home = os.path.join(self.root, nm.name)
            p2p, rpc, pvp = self._ports(i)
            cfg = Config(root_dir=home)
            cfg.base.chain_id = self.m.chain_id
            cfg.base.moniker = nm.name
            cfg.base.proxy_app = "kvstore-snapshot"
            cfg.base.fast_sync = nm.fast_sync
            cfg.p2p.laddr = f"tcp://127.0.0.1:{p2p}"
            cfg.rpc.laddr = f"tcp://127.0.0.1:{rpc}"
            cfg.mempool.version = nm.mempool_version
            if _have_aiohttp():
                # fleet observability: every node serves /metrics on the
                # 4th port of its block so the runner's fleet scraper can
                # roll up cluster-truth series during the run
                cfg.instrumentation.prometheus = True
                cfg.instrumentation.prometheus_listen_addr = (
                    f"tcp://127.0.0.1:{self._metrics_port(nm.name)}")
            if nm.privval == "tcp":
                cfg.base.priv_validator_laddr = f"tcp://127.0.0.1:{pvp}"
            if nm.state_sync:
                cfg.statesync.enable = True
                cfg.statesync.discovery_time = 3.0
                # adversarial nets: chunk peers may be lying — time out and
                # strike fast so a bounded run reaches ban/fallback verdicts
                cfg.statesync.chunk_request_timeout = 5.0
                cfg.statesync.peer_ban_threshold = 2
            os.makedirs(os.path.join(home, CONFIG_DIR), exist_ok=True)
            os.makedirs(os.path.join(home, DATA_DIR), exist_ok=True)
            pv = FilePV.generate(cfg.priv_validator_key_file(),
                                 cfg.priv_validator_state_file())
            pv.save()
            pvs[nm.name] = pv
            nk = NodeKey.load_or_gen(cfg.node_key_file())
            self.node_ids[nm.name] = nk.id
            self.configs[nm.name] = cfg

        powers = self.m.validators or {
            nm.name: 10 for nm in self.m.nodes if nm.mode == "validator"}
        genesis = GenesisDoc(
            chain_id=self.m.chain_id,
            genesis_time_ns=time.time_ns(),
            initial_height=self.m.initial_height,
            validators=[GenesisValidator(pvs[name].get_pub_key(), power)
                        for name, power in powers.items()
                        if name in pvs],
        )
        for i, nm in enumerate(self.m.nodes):
            cfg = self.configs[nm.name]
            cfg.p2p.persistent_peers = ",".join(
                self._peer_addr(other) for other in self._peers_of(nm))
            if self.m.topology == "seed" and not nm.seed_node:
                cfg.p2p.seeds = ",".join(
                    self._peer_addr(o) for o in self.m.nodes if o.seed_node)
            if nm.seed_node:
                cfg.p2p.seed_mode = True
            genesis.save_as(cfg.genesis_file())
            cfg.save()

    def _peer_addr(self, nm: NodeManifest) -> str:
        idx = [n.name for n in self.m.nodes].index(nm.name)
        return f"{self.node_ids[nm.name]}@127.0.0.1:{self._ports(idx)[0]}"

    def _peers_of(self, nm: NodeManifest) -> List[NodeManifest]:
        """Persistent peers per the manifest topology: every other node
        (full_mesh), graph neighbors (sparse — the SAME seeded ring+chords
        graph p2p.inproc.sparse_edges builds for in-proc nets), or nobody
        (seed — discovery fills the peer set via PEX)."""
        if self.m.topology == "seed":
            return []
        others = [o for o in self.m.nodes if o.name != nm.name]
        if self.m.topology == "full_mesh":
            return others
        from ..p2p.inproc import sparse_edges

        edges = sparse_edges([n.name for n in self.m.nodes],
                             degree=self.m.sparse_degree,
                             seed=self.m.topology_seed)
        mine = {b if a == nm.name else a
                for a, b in edges if nm.name in (a, b)}
        return [o for o in others if o.name in mine]

    def _env(self, nm: NodeManifest, first_launch: bool = True,
             restart_reason: str = "") -> dict:
        env = dict(os.environ)
        # nodes of a multi-process net stay CPU-pinned: a chip has one
        # owner. They share one compile cache: JAX_COMPILATION_CACHE_DIR
        # where the caller set it (inherited), else <checkout>/.jax_cache
        # (libs/compilecache.py) — never a per-node home
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        if nm.misbehaviors:
            env["TMTPU_MISBEHAVIORS"] = ",".join(
                f"{h}:{b}" for h, b in sorted(nm.misbehaviors.items()))
            env["TMTPU_UNSAFE_PV"] = "1"
        if nm.faults:
            # arm the node's fault plane (libs/faults.py reads these at
            # import, so the subprocess starts with the sites live)
            env["TMTPU_FAULTS"] = nm.faults
            env["TMTPU_FAULTS_SEED"] = str(nm.faults_seed)
        if nm.fail_point and first_launch:
            # one-shot: the FIRST process dies at the boundary; supervised
            # relaunches drop the arming so recovery can be observed
            env["TMTPU_FAIL_POINT"] = nm.fail_point
        if restart_reason:
            # the restarted node exports restarts_total{reason} on its own
            # /metrics (libs/metrics.py RecoveryMetrics, wired in node.py)
            env["TMTPU_RESTART_REASON"] = restart_reason
        # stall watchdog: an e2e node that silently stops committing should
        # leave a debugdump bundle behind, not just a hung run
        env.setdefault("TMTPU_STALL_WATCHDOG_S", "60")
        # cluster observability: node traces carry the manifest name, and a
        # watchdog debugdump snapshots the runner's fleet rollup (the
        # scraper keeps this file fresh while the net runs)
        env["TMTPU_NODE_ID"] = nm.name
        env["TMTPU_FLEET_JSON"] = os.path.join(self.root, "fleet.json")
        return env

    def _launch(self, nm: NodeManifest, restart_reason: str = "") -> None:
        cfg = self.configs[nm.name]
        # the one-shot fail_point arming is derived HERE, not passed by
        # callers: perturbation relaunches and supervised restarts alike
        # must drop it or the node dies at the boundary forever
        env = self._env(nm, first_launch=nm.name not in self._launched,
                        restart_reason=restart_reason)
        self._launched.add(nm.name)
        sup = self.supervisors.get(nm.name)
        if sup is not None:
            sup.on_launch()
        if nm.privval == "tcp" and nm.name not in self.signers:
            pvp = cfg.base.priv_validator_laddr.rpartition(":")[-1]
            self.signers[nm.name] = subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu.cmd", "signer",
                 "--key-file", cfg.priv_validator_key_file(),
                 "--state-file", cfg.priv_validator_state_file(),
                 "--chain-id", self.m.chain_id,
                 "--addr", f"127.0.0.1:{pvp}"],
                env=env, cwd=REPO,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        log = open(os.path.join(self.root, f"{nm.name}.log"), "a")
        self.procs[nm.name] = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu.cmd",
             "--home", cfg.root_dir, "start", "--log-level",
             os.environ.get("TMTPU_E2E_LOG_LEVEL", "warning")],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT)

    def start(self) -> None:
        """Launch genesis nodes; late joiners wait for their start_at."""
        for nm in self.m.nodes:
            if nm.start_at == 0:
                self._launch(nm)
        self.wait_for_height(max(2, self.m.initial_height + 1),
                             nodes=[n.name for n in self.m.nodes
                                    if n.start_at == 0])

    def start_late_joiners(self) -> None:
        for nm in self.m.nodes:
            if nm.start_at == 0 or nm.name in self.procs:
                continue
            self.wait_for_height(nm.start_at)
            if nm.state_sync:
                self._point_state_sync(nm)
            # join-to-caught-up: the clock starts at launch, the target is
            # the net's height NOW (what "caught up" meant when it joined)
            self._join_marks[nm.name] = (time.time(), max(1, self.max_height()))
            self._launch(nm)
            if self._fleet is not None:
                self._fleet.add_endpoint(
                    nm.name,
                    f"http://127.0.0.1:{self._metrics_port(nm.name)}/metrics")

    def measure_join_catchup(self, timeout: float = 180.0) -> Dict[str, float]:
        """Block until each launched late joiner reaches the height the net
        held when it was launched; records seconds into join_stats."""
        for name, (t0, target) in list(self._join_marks.items()):
            deadline = time.time() + timeout
            while time.time() < deadline:
                self.poll_restarts()
                if self.height(name) >= target:
                    self.join_stats[name] = round(time.time() - t0, 3)
                    break
                time.sleep(0.5)
            else:
                raise E2EError(
                    f"joiner {name} never caught up to h={target}")
            del self._join_marks[name]
        return self.join_stats

    def apply_churn_stops(self) -> None:
        """The leave half of the churn schedule: nodes with stop_at get a
        clean SIGTERM once the net reaches that height and are excluded
        from post-run invariants — a scheduled departure is not a dead
        node. Processed in stop_at order so multi-leave schedules play out
        deterministically."""
        for nm in sorted((n for n in self.m.nodes if n.stop_at),
                         key=lambda n: (n.stop_at, n.name)):
            proc = self.procs.get(nm.name)
            if proc is None:
                continue
            self.wait_for_height(nm.stop_at)
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            self.procs.pop(nm.name, None)
            self.departed.add(nm.name)
            if self._fleet is not None:
                self._fleet.remove_endpoint(nm.name)

    def poll_restarts(self) -> None:
        """Crash-recovery supervision: relaunch any supervised node whose
        process died (non-clean exit, not a scheduled departure) after its
        policy's backoff; on crash-loop give-up, write the debugdump
        bundle and leave the node down (invariant checks will then fail
        loudly — a crash loop IS a failed run). Called from every wait
        loop so supervision needs no extra thread."""
        by_name = {nm.name: nm for nm in self.m.nodes}
        for name, sup in self.supervisors.items():
            proc = self.procs.get(name)
            if proc is None or name in self.departed:
                continue
            rc = proc.poll()
            if rc is None:
                continue  # still running
            delay = sup.on_exit(rc)
            if delay is None:
                if sup.gave_up and name not in self.crashloop_bundles:
                    self.crashloop_bundles[name] = write_crashloop_bundle(
                        self.root, sup,
                        extras={"manifest_node": name,
                                "home": self.configs[name].root_dir},
                        log_path=os.path.join(self.root, f"{name}.log"))
                    self._note(f"supervisor gave up on {name} "
                               f"(crash loop); bundle at "
                               f"{self.crashloop_bundles[name]}")
                # staying down (clean exit or give-up): drop the carcass so
                # the next poll doesn't re-record the same exit forever
                self.procs.pop(name, None)
                continue
            self._note(f"supervisor restarting {name} (rc={rc}, "
                       f"restart #{sup.restarts}) after {delay:.2f}s")
            time.sleep(delay)
            self._launch(by_name[name],
                         restart_reason=sup.history[-1].reason)

    def _note(self, msg: str) -> None:
        if self._log:
            self._log.write(msg + "\n")
            self._log.flush()

    def _point_state_sync(self, nm: NodeManifest) -> None:
        """Fill rpc_servers + trust root from the live net just before the
        joiner starts (reference test/e2e/runner/setup.go does the same with
        a light-client trust height)."""
        donors = [o for o in self.m.nodes
                  if o.name in self.procs and not o.state_sync][:2]
        if len(donors) < 2:
            donors = donors * 2
        h = self.rpc(donors[0].name, "status")["sync_info"]["latest_block_height"]
        trust_h = max(1, int(h) - 2)
        commit = self.rpc(donors[0].name, f"commit?height={trust_h}")
        trust_hash = commit["signed_header"]["commit"]["block_id"]["hash"]
        cfg = self.configs[nm.name]
        cfg.statesync.rpc_servers = [
            f"http://127.0.0.1:{self._rpc_port(d.name)}" for d in donors]
        cfg.statesync.trust_height = trust_h
        cfg.statesync.trust_hash = trust_hash
        cfg.save()

    def load(self, n_txs: Optional[int] = None) -> None:
        """Inject txs via broadcast_tx_sync round-robin over live nodes."""
        names = [n.name for n in self.m.nodes if n.name in self.procs]
        n_txs = n_txs if n_txs is not None else max(4, self.m.load_tx_rate * 2)
        for i in range(n_txs):
            tx = f"e2e{len(self.loaded_txs)}=v{i}".encode()
            name = names[i % len(names)]
            try:
                self.rpc_post(name, "broadcast_tx_sync",
                              {"tx": base64.b64encode(tx).decode()})
                self.loaded_txs.append(tx)
            except Exception:
                pass  # a node may be mid-perturbation; coverage, not load
            time.sleep(1.0 / max(1, self.m.load_tx_rate))

    def perturb(self) -> None:
        """Apply each node's perturbations sequentially
        (test/e2e/runner/perturb.go)."""
        for nm in self.m.nodes:
            for p in nm.perturb:
                proc = self.procs.get(nm.name)
                if proc is None:
                    continue
                if p == "kill":
                    proc.send_signal(signal.SIGKILL)
                    proc.wait()
                    time.sleep(2.0)
                    self._launch(nm)
                elif p == "restart":
                    proc.send_signal(signal.SIGTERM)
                    try:
                        proc.wait(timeout=15)
                    except subprocess.TimeoutExpired:
                        proc.kill()
                        proc.wait()
                    self._launch(nm)
                elif p == "pause":
                    proc.send_signal(signal.SIGSTOP)
                    time.sleep(5.0)
                    proc.send_signal(signal.SIGCONT)
                elif p == "disconnect":
                    # no netns for subprocesses: a long stop makes every peer
                    # drop the conn (ping timeout) and re-dial on CONT
                    proc.send_signal(signal.SIGSTOP)
                    time.sleep(12.0)
                    proc.send_signal(signal.SIGCONT)
                time.sleep(2.0)

    def wait(self, blocks: Optional[int] = None) -> None:
        """Let the net advance `blocks` past the current max height."""
        target = self.max_height() + (blocks or self.m.wait_blocks)
        self.wait_for_height(target)

    # -- fleet metrics (tools/fleet_scrape.py) -------------------------------

    def start_fleet_scrape(self, interval_s: float = 2.0) -> None:
        """Scrape every launched node's /metrics on an interval; the rollup
        JSON (root/fleet.json) stays fresh for debugdump bundles and is
        summarized into self.fleet_rollup at stop."""
        if self._fleet is not None or not _have_aiohttp():
            return
        endpoints = {
            name: f"http://127.0.0.1:{self._metrics_port(name)}/metrics"
            for name in self.procs}
        if not endpoints:
            return
        mod = _fleet_scrape_mod()
        self._fleet = mod.FleetScraper(
            endpoints, interval_s=interval_s,
            out_path=os.path.join(self.root, "fleet.json")).start()

    def stop_fleet_scrape(self) -> Optional[dict]:
        if self._fleet is None:
            return None
        # stop()'s final sweep already refreshed out_path (root/fleet.json)
        self.fleet_rollup = self._fleet.stop()
        self._fleet = None
        return self.fleet_rollup

    def stop(self) -> None:
        self.stop_fleet_scrape()
        for proc in list(self.procs.values()) + list(self.signers.values()):
            try:
                proc.send_signal(signal.SIGTERM)
            except Exception:
                pass
        deadline = time.time() + 15
        for proc in list(self.procs.values()) + list(self.signers.values()):
            try:
                proc.wait(timeout=max(0.1, deadline - time.time()))
            except Exception:
                proc.kill()
        if self._log:
            self._log.close()

    # -- RPC helpers ---------------------------------------------------------

    def rpc(self, name: str, path: str, timeout: float = 5.0):
        url = f"http://127.0.0.1:{self._rpc_port(name)}/{path}"
        with urllib.request.urlopen(url, timeout=timeout) as r:
            doc = json.load(r)
        if "error" in doc and doc["error"]:
            raise E2EError(f"{name} /{path}: {doc['error']}")
        return doc["result"]

    def rpc_post(self, name: str, method: str, params: dict,
                 timeout: float = 10.0):
        body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                           "params": params}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self._rpc_port(name)}/", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            doc = json.load(r)
        if "error" in doc and doc["error"]:
            raise E2EError(f"{name} {method}: {doc['error']}")
        return doc["result"]

    def metric_value(self, name: str, series_prefix: str,
                     timeout: float = 5.0) -> float:
        """Sum a node's /metrics series whose line starts with
        `series_prefix` (label sets summed) — how e2e assertions read ban /
        fault / retry counters off a live node. 0.0 when the series is
        absent or the endpoint is down."""
        url = f"http://127.0.0.1:{self._metrics_port(name)}/metrics"
        try:
            with urllib.request.urlopen(url, timeout=timeout) as r:
                text = r.read().decode()
        except Exception:
            return 0.0
        total = 0.0
        for line in text.splitlines():
            if not line.startswith(series_prefix) or line.startswith("#"):
                continue
            rest = line[len(series_prefix):]
            if rest and rest[0] not in "{ ":
                continue  # longer metric name sharing the prefix
            try:
                total += float(line.rsplit(None, 1)[-1])
            except ValueError:
                continue
        return total

    def height(self, name: str) -> int:
        try:
            return int(self.rpc(name, "status")
                       ["sync_info"]["latest_block_height"])
        except Exception:
            return -1

    def max_height(self) -> int:
        return max([self.height(n) for n in self.procs] or [0])

    def wait_all_alive(self, timeout: float = 180.0) -> None:
        """Block until every launched node answers /status — node startup
        (python + jax import + WAL replay) can take a minute under CI load,
        and invariants checked against a still-booting node read as a dead
        net."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            self.poll_restarts()
            down = [n for n in self.procs if self.height(n) < 0]
            if not down:
                return
            for n in down:  # an unsupervised crashed process never answers
                if (self.procs[n].poll() is not None
                        and n not in self.supervisors):
                    raise E2EError(
                        f"node {n} exited rc={self.procs[n].returncode}")
            time.sleep(1.0)
        raise E2EError(f"nodes never became reachable: {down}")

    def wait_for_height(self, h: int, nodes: Optional[List[str]] = None,
                        timeout: float = 180.0) -> None:
        names = nodes or list(self.procs)
        deadline = time.time() + timeout
        while time.time() < deadline:
            self.poll_restarts()
            if any(self.height(n) >= h for n in names):
                return
            time.sleep(1.0)
        raise E2EError(
            f"height {h} not reached in {timeout}s: "
            f"{ {n: self.height(n) for n in names} }")

    # -- invariants (reference test/e2e/tests/) ------------------------------

    def check_invariants(self) -> None:
        self.check_heights_agree()
        self.check_app_hashes()
        self.check_txs_everywhere()

    def check_heights_agree(self, spread: int = 3) -> None:
        hs = {n: self.height(n) for n in self.procs}
        if min(hs.values()) < 1:
            raise E2EError(f"dead node: {hs}")
        if max(hs.values()) - min(hs.values()) > spread:
            # stragglers get a grace period to catch up
            target = max(hs.values())
            deadline = time.time() + 60
            while time.time() < deadline:
                hs = {n: self.height(n) for n in self.procs}
                if min(hs.values()) >= target - spread:
                    return
                time.sleep(1.0)
            raise E2EError(f"heights diverged: {hs}")

    def check_app_hashes(self) -> None:
        """All nodes report the same app hash at a sampled common height."""
        h = min(self.height(n) for n in self.procs) - 1
        if h < 2:
            raise E2EError("chain too short for app-hash check")
        hashes = {}
        for n in self.procs:
            doc = self.rpc(n, f"commit?height={h}")
            hashes[n] = doc["signed_header"]["header"]["app_hash"]
        if len(set(hashes.values())) != 1:
            raise E2EError(f"app hash mismatch at {h}: {hashes}")

    def check_txs_everywhere(self) -> None:
        """Every loaded tx's key is queryable on every node."""
        if not self.loaded_txs:
            return
        sample = self.loaded_txs[:: max(1, len(self.loaded_txs) // 4)]
        for n in self.procs:
            for tx in sample:
                key = tx.split(b"=", 1)[0]
                q = self.rpc(
                    n, f'abci_query?path=%22%22&data={key.hex()}', timeout=10)
                value = q["response"].get("value")
                if not value:
                    raise E2EError(f"tx key {key!r} missing on {n}")

    def check_evidence_committed(self, timeout: float = 90.0) -> None:
        """A byzantine manifest must produce committed DuplicateVoteEvidence
        (reference evidence pool -> block evidence path)."""
        deadline = time.time() + timeout
        names = list(self.procs)
        while time.time() < deadline:
            top = self.max_height()
            for h in range(2, top):
                for n in names:
                    try:
                        blk = self.rpc(n, f"block?height={h}")
                    except Exception:
                        continue
                    ev = blk["block"].get("evidence") or []
                    if ev:
                        return
            time.sleep(2.0)
        raise E2EError("no evidence committed within deadline")

    # -- one-call orchestration ----------------------------------------------

    def run(self) -> None:
        """setup → start → load → late joiners (join-to-caught-up timed) →
        perturb → load → churn leaves (stop_at) → wait → invariants →
        stop. Raises E2EError on any failed invariant."""
        self.setup()
        try:
            self.start()
            self.start_fleet_scrape()
            self.load()
            self.start_late_joiners()
            self.wait_all_alive()
            self.measure_join_catchup()
            self.perturb()
            self.load()
            self.apply_churn_stops()
            self.wait_all_alive()
            self.wait()
            self.check_invariants()
            if any(nm.misbehaviors for nm in self.m.nodes):
                self.check_evidence_committed()
        finally:
            self.stop()
