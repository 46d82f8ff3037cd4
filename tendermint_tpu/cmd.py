"""Operator CLI (reference cmd/tendermint/main.go:16-49 command set).

Usage:  python -m tendermint_tpu.cmd [--home DIR] <command> [...]

Commands: init, start, testnet, gen-node-key, show-node-id, gen-validator,
show-validator, reset-unsafe, version. (replay/rollback/light arrive with
their subsystems.)
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import shutil
import sys
import time

from . import config as cfgmod
from .config import Config

VERSION = "tendermint-tpu/0.1.0"


def cmd_init(args) -> int:
    """(cmd/tendermint/commands/init.go) scaffold config + genesis + keys."""
    from .p2p import NodeKey
    from .privval.file_pv import FilePV
    from .types import GenesisDoc, GenesisValidator

    cfg = Config(root_dir=args.home)
    if args.chain_id:
        cfg.base.chain_id = args.chain_id
    os.makedirs(os.path.join(args.home, cfgmod.CONFIG_DIR), exist_ok=True)
    os.makedirs(os.path.join(args.home, cfgmod.DATA_DIR), exist_ok=True)

    pv_key, pv_state = cfg.priv_validator_key_file(), cfg.priv_validator_state_file()
    if os.path.exists(pv_key):
        pv = FilePV.load(pv_key, pv_state)
        print(f"found existing validator key {pv_key}")
    else:
        pv = FilePV.generate(pv_key, pv_state)
        pv.save()
        print(f"generated validator key {pv_key}")

    nk = NodeKey.load_or_gen(cfg.node_key_file())
    print(f"node id: {nk.id}")

    gen_file = cfg.genesis_file()
    if not os.path.exists(gen_file):
        chain_id = args.chain_id or f"test-chain-{os.urandom(3).hex()}"
        genesis = GenesisDoc(
            chain_id=chain_id,
            genesis_time_ns=time.time_ns(),
            validators=[GenesisValidator(pv.get_pub_key(), 10)],
        )
        genesis.save_as(gen_file)
        print(f"generated genesis {gen_file} (chain {chain_id})")
    cfg.save()
    print(f"wrote config {os.path.join(args.home, 'config', 'config.toml')}")
    return 0


def build_node(args):
    """The node `start` runs, assembled from ``args.home`` (config,
    genesis and keys as `init`/`testnet` wrote them) plus the start
    command's overrides. Also switches on the persistent XLA compile
    cache: the batched-verify kernels take minutes to compile cold, and
    without it every fresh node process pays that on its first
    device-routed batch. The cache is placed by
    ``JAX_COMPILATION_CACHE_DIR`` or, unset, at ``<checkout>/.jax_cache``
    — never under the node home, which is a temp directory in every
    harness (libs/compilecache.py holds the rule and the host-fingerprint
    warning)."""
    from .libs.compilecache import enable_compile_cache
    from .node import Node

    warn = enable_compile_cache()
    if warn:
        logging.getLogger("tmtpu.node").warning("%s", warn)
    cfg = Config.load(args.home)
    if args.p2p_laddr:
        cfg.p2p.laddr = args.p2p_laddr
    if args.rpc_laddr:
        cfg.rpc.laddr = args.rpc_laddr
    if args.persistent_peers:
        cfg.p2p.persistent_peers = args.persistent_peers
    if args.proxy_app:
        cfg.base.proxy_app = args.proxy_app
    cfg.validate_basic()
    return Node.default(cfg)


def cmd_start(args) -> int:
    """(cmd/tendermint/commands/run_node.go) run a node until SIGINT."""
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname).1s %(message)s")
    node = build_node(args)
    cfg = node.config

    # TMTPU_TRACE_OUT=<prefix>: run the whole node under the span tracer and
    # write <prefix>-<pid>.json (Chrome trace-event JSON) on shutdown, so a
    # localnet's per-height live-plane breakdown (gossip wait / WAL sync /
    # apply) is recoverable with tools/trace_summary.py --by-height
    trace_prefix = os.environ.get("TMTPU_TRACE_OUT")
    from .libs.trace import tracer as _tracer

    # stamp the trace with this node's identity + wall↔perf epoch so
    # tools/trace_merge.py can align N nodes' traces onto one timeline
    # (TMTPU_NODE_ID overrides for runners that name nodes themselves)
    _tracer.set_identity(os.environ.get("TMTPU_NODE_ID")
                         or cfg.base.moniker or f"pid-{os.getpid()}")
    if trace_prefix:
        _tracer.enable()

    async def run():
        # SIGUSR1 -> synchronous in-process dump of thread stacks, asyncio
        # task stacks, round state and peer table — works even when the
        # event loop is wedged (reference keeps a pprof listener for this,
        # node/node.go:896; see libs/debugdump.py)
        from .libs import debugdump

        debugdump.install(args.home, node=node,
                          loop=asyncio.get_running_loop())
        await node.start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        try:
            import signal

            for sig in (signal.SIGINT, signal.SIGTERM):
                loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):
            pass
        fatal = asyncio.create_task(node.fatal_event.wait())
        stopped = asyncio.create_task(stop.wait())
        await asyncio.wait({fatal, stopped},
                           return_when=asyncio.FIRST_COMPLETED)
        if node.fatal_event.is_set():
            print(f"FATAL: {node.fatal_error}")
            await node.stop()
            raise SystemExit(1)
        print("shutting down...")
        fatal.cancel()
        await node.stop()
        if trace_prefix:
            from .libs.trace import tracer as _tracer

            path = f"{trace_prefix}-{os.getpid()}.json"
            _tracer.write(path)
            print(f"wrote span trace {path}")

    asyncio.run(run())
    return 0


def cmd_testnet(args) -> int:
    """(cmd/tendermint/commands/testnet.go) N-node config bundles with a
    shared genesis and fully-meshed persistent peers."""
    from .p2p import NodeKey
    from .privval.file_pv import FilePV
    from .types import GenesisDoc, GenesisValidator

    n = args.v
    out = args.output_dir
    chain_id = args.chain_id or f"chain-{os.urandom(3).hex()}"
    pvs, node_keys, configs = [], [], []
    for i in range(n):
        home = os.path.join(out, f"node{i}")
        cfg = Config(root_dir=home)
        cfg.base.chain_id = chain_id
        cfg.base.moniker = f"node{i}"
        cfg.p2p.laddr = f"tcp://127.0.0.1:{args.starting_port + 2 * i}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{args.starting_port + 2 * i + 1}"
        if getattr(args, "prometheus", False):
            # metrics ports start right after the nodes' p2p/rpc block
            # ([starting_port, starting_port + 2v)), collision-free for any v
            cfg.instrumentation.prometheus = True
            cfg.instrumentation.prometheus_listen_addr = (
                f"tcp://127.0.0.1:{args.starting_port + 2 * args.v + i}")
        os.makedirs(os.path.join(home, cfgmod.CONFIG_DIR), exist_ok=True)
        os.makedirs(os.path.join(home, cfgmod.DATA_DIR), exist_ok=True)
        pv = FilePV.generate(cfg.priv_validator_key_file(),
                             cfg.priv_validator_state_file())
        pv.save()
        nk = NodeKey.load_or_gen(cfg.node_key_file())
        pvs.append(pv)
        node_keys.append(nk)
        configs.append(cfg)

    genesis = GenesisDoc(
        chain_id=chain_id,
        genesis_time_ns=time.time_ns(),
        validators=[GenesisValidator(pv.get_pub_key(), 10) for pv in pvs],
    )
    for i, cfg in enumerate(configs):
        peers = ",".join(
            f"{node_keys[j].id}@127.0.0.1:{args.starting_port + 2 * j}"
            for j in range(n) if j != i)
        cfg.p2p.persistent_peers = peers
        cfg.base.fast_sync = False
        genesis.save_as(cfg.genesis_file())
        cfg.save()
    print(f"wrote {n}-node testnet under {out} (chain {chain_id})")
    for i, nk in enumerate(node_keys):
        print(f"  node{i}: id={nk.id} p2p={configs[i].p2p.laddr} "
              f"rpc={configs[i].rpc.laddr}")
    return 0


def cmd_gen_node_key(args) -> int:
    from .p2p import NodeKey

    cfg = Config(root_dir=args.home)
    nk = NodeKey.load_or_gen(cfg.node_key_file())
    print(nk.id)
    return 0


def cmd_show_node_id(args) -> int:
    from .p2p import NodeKey

    cfg = Config(root_dir=args.home)
    nk = NodeKey.load(cfg.node_key_file())
    print(nk.id)
    return 0


def cmd_gen_validator(args) -> int:
    from .privval.file_pv import FilePV

    pv = FilePV.generate("", "")
    pub = pv.get_pub_key()
    print(json.dumps({
        "address": pub.address().hex().upper(),
        "pub_key": {"type": "tendermint/PubKeyEd25519",
                    "value": pub.bytes().hex()},
        "priv_key": {"type": "tendermint/PrivKeyEd25519",
                     "value": pv.priv_key.bytes().hex()},
    }, indent=2))
    return 0


def cmd_show_validator(args) -> int:
    from .privval.file_pv import FilePV

    cfg = Config(root_dir=args.home)
    pv = FilePV.load(cfg.priv_validator_key_file(),
                     cfg.priv_validator_state_file())
    pub = pv.get_pub_key()
    print(json.dumps({"type": "tendermint/PubKeyEd25519",
                      "value": pub.bytes().hex()}))
    return 0


def cmd_reset_unsafe(args) -> int:
    """(cmd unsafe-reset-all) wipe data, keep config + validator key."""
    cfg = Config(root_dir=args.home)
    data = os.path.join(args.home, cfgmod.DATA_DIR)
    if os.path.isdir(data):
        shutil.rmtree(data)
    os.makedirs(data, exist_ok=True)
    # reset priv validator state (sign state) but keep the key
    state_file = cfg.priv_validator_state_file()
    with open(state_file, "w") as f:
        json.dump({"height": 0, "round": 0, "step": 0}, f)
    print(f"reset {data}")
    return 0


def cmd_rollback(args) -> int:
    """(cmd rollback; state/rollback.go) roll state back one height."""
    from .node import _make_db
    from .state.rollback import rollback_state
    from .state.store import StateStore
    from .store import BlockStore

    cfg = Config.load(args.home)
    block_store = BlockStore(_make_db(cfg.base.db_backend, cfg.db_dir(),
                                      "blockstore"))
    state_store = StateStore(_make_db(cfg.base.db_backend, cfg.db_dir(),
                                      "state"))
    height, app_hash = rollback_state(block_store, state_store)
    print(f"rolled back state to height {height} and hash {app_hash.hex()}")
    return 0


def cmd_light(args) -> int:
    """(cmd/tendermint/commands/light.go) verifying light proxy."""
    from .light.client import LightClient, TrustOptions
    from .light.provider import HTTPProvider
    from .light.proxy import LightProxy
    from .rpc.client import HTTPClient

    async def run():
        primary = HTTPClient(args.primary)
        provider = HTTPProvider(args.chain_id, primary)
        witnesses = [HTTPProvider(args.chain_id, HTTPClient(w))
                     for w in (args.witnesses.split(",") if args.witnesses
                               else [])]
        lc = LightClient(
            args.chain_id,
            TrustOptions(args.trust_period, args.trust_height,
                         bytes.fromhex(args.trust_hash)),
            provider, witnesses)
        from .node import _parse_laddr

        proxy = LightProxy(lc, primary)
        host, port = _parse_laddr(args.laddr)
        bound = await proxy.start(host, port)
        print(f"light proxy for {args.chain_id} on port {bound} "
              f"(primary {args.primary})")
        stop = asyncio.Event()
        try:
            import signal

            loop = asyncio.get_running_loop()
            for sig in (signal.SIGINT, signal.SIGTERM):
                loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):
            pass
        await stop.wait()
        await proxy.stop()

    asyncio.run(run())
    return 0


def cmd_version(args) -> int:
    print(VERSION)
    return 0


def _fetch_rpc(base_url: str, path: str):
    import json as _json
    import urllib.request

    with urllib.request.urlopen(f"{base_url}/{path}", timeout=10) as r:
        return _json.load(r)


def cmd_debug(args) -> int:
    """(cmd/tendermint/commands/debug/{dump,kill}.go) capture a diagnostic
    bundle from a RUNNING node over RPC + its home dir: status, net_info,
    dump_consensus_state, consensus_state, config, WAL tail. ``debug kill``
    captures the bundle and then SIGKILLs the node."""
    import shutil
    import signal as _signal
    import time as _time

    cfg = Config.load(args.home)
    rpc = args.rpc_laddr or cfg.rpc.laddr
    base = "http://" + rpc.split("://", 1)[-1]
    out = args.output_dir or os.path.join(
        args.home, f"debug-{int(_time.time())}")
    os.makedirs(out, exist_ok=True)

    for route in ("status", "net_info", "consensus_state",
                  "dump_consensus_state"):
        try:
            doc = _fetch_rpc(base, route)
            with open(os.path.join(out, f"{route}.json"), "w") as f:
                json.dump(doc, f, indent=2)
        except Exception as e:
            with open(os.path.join(out, f"{route}.err"), "w") as f:
                f.write(str(e))

    # config + WAL tail from the home dir
    cfg_file = os.path.join(args.home, cfgmod.CONFIG_DIR, "config.toml")
    if os.path.exists(cfg_file):
        shutil.copy(cfg_file, os.path.join(out, "config.toml"))
    try:
        from .consensus.wal import WAL

        # repair=False: the node may be live and holding the file open for
        # append — a read-only observer must never truncate its tail
        wal = WAL(cfg.wal_file(), repair=False)
        msgs = list(wal.iter_messages())[-200:]
        with open(os.path.join(out, "wal_tail.jsonl"), "w") as f:
            for m in msgs:
                f.write(json.dumps({"type": m.type, "time_ns": m.time_ns,
                                    "data": m.data}, default=str) + "\n")
    except Exception as e:
        with open(os.path.join(out, "wal_tail.err"), "w") as f:
            f.write(str(e))

    print(f"wrote debug bundle to {out}")
    if args.action == "kill":
        pid = args.pid
        if not pid:
            print("debug kill: --pid required", file=sys.stderr)
            return 1
        # in-process dump first (debug/kill.go captures goroutine profiles
        # before the kill): the node's SIGUSR1 handler writes stacks to its
        # home even when its loop — and therefore RPC — is wedged
        try:
            os.kill(pid, _signal.SIGUSR1)
            _time.sleep(1.0)
            os.kill(pid, _signal.SIGKILL)
            print(f"killed pid {pid}")
        except ProcessLookupError:
            print(f"pid {pid} already gone")
    return 0


def cmd_replay(args) -> int:
    """(cmd/tendermint/commands/replay.go, consensus/replay_file.go) rebuild
    the node from its home dir — the ABCI handshake replays stored blocks
    into the app (consensus/replay.go ReplayBlocks) — then feed the WAL tail
    for the in-flight height through the real consensus state machine,
    printing each message; ``--console`` pauses between messages."""
    from .consensus.replay import _replay_message
    from .node import Node

    logging.basicConfig(level=logging.WARNING)
    cfg = Config.load(args.home)
    cfg.p2p.laddr = ""      # replay is offline: no listeners
    cfg.rpc.laddr = ""
    node = Node.default(cfg)  # handshake replay of stored blocks happens here
    cs = node.consensus_state
    height = cs.rs.height
    print(f"handshake replayed chain to height {height - 1}; "
          f"replaying WAL for in-flight height {height}")
    count = 0
    cs._replay_mode = True
    try:
        for m in cs.wal.messages_after_end_height(height - 1):
            count += 1
            summary = {k: v for k, v in (m.data or {}).items()
                       if k in ("height", "round", "step", "type",
                                "duration_ns")}
            print(f"#{count:<5} {m.type:<12} {summary}")
            if args.console:
                try:
                    if input("replay> ").strip() in ("q", "quit"):
                        break
                except EOFError:
                    break
            try:
                _replay_message(cs, m)
            except Exception as e:
                print(f"  !! replay error: {e}")
    finally:
        cs._replay_mode = False
    rs = cs.rs
    print(f"replayed {count} WAL messages; round state now "
          f"{rs.height}/{rs.round}/{int(rs.step)}")
    return 0


def cmd_compact_db(args) -> int:
    """(cmd compact-db; reference compacts goleveldb) VACUUM every sqlite
    store under the data dir."""
    import sqlite3

    cfg = Config.load(args.home)
    n = 0
    for name in sorted(os.listdir(cfg.db_dir())):
        if not name.endswith(".db"):
            continue
        path = os.path.join(cfg.db_dir(), name)
        before = os.path.getsize(path)
        con = sqlite3.connect(path)
        con.execute("VACUUM")
        con.close()
        after = os.path.getsize(path)
        print(f"{name}: {before} -> {after} bytes")
        n += 1
    if n == 0:
        print("no .db files found (mem backend?)")
    return 0


def cmd_reindex_event(args) -> int:
    """(cmd reindex-event) rebuild the tx index from stored blocks + their
    persisted ABCI responses (state/txindex kv sink)."""
    from .libs.db import SQLiteDB
    from .state.store import StateStore
    from .state.txindex import KVTxIndexer, TxResult
    from .store import BlockStore

    cfg = Config.load(args.home)
    dbdir = cfg.db_dir()
    block_store = BlockStore(SQLiteDB(os.path.join(dbdir, "blockstore.db")))
    state_store = StateStore(SQLiteDB(os.path.join(dbdir, "state.db")))
    indexer = SQLiteDB(os.path.join(dbdir, "txindex.db"))
    txi = KVTxIndexer(indexer)
    count = 0
    for h in range(block_store.base(), block_store.height() + 1):
        block = block_store.load_block(h)
        resps = state_store.load_abci_responses(h)
        if block is None or resps is None:
            continue
        for i, tx in enumerate(block.data.txs):
            r = resps.deliver_txs[i] if i < len(resps.deliver_txs) else None
            txi.index(TxResult(
                height=h, index=i, tx=tx,
                code=getattr(r, "code", 0), data=getattr(r, "data", b""),
                log=getattr(r, "log", ""),
                gas_wanted=getattr(r, "gas_wanted", 0),
                gas_used=getattr(r, "gas_used", 0),
                events={}))
            count += 1
    print(f"reindexed {count} txs over heights "
          f"{block_store.base()}..{block_store.height()}")
    return 0


def cmd_signer(args) -> int:
    """Remote signer process: serves a FilePV to a node over the privval
    SecretConnection link (the tmkms role; reference privval/signer_server.go).
    Runs until SIGINT."""
    import signal as _signal
    import threading

    from .privval.file_pv import FilePV
    from .privval.signer import SignerServer

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname).1s %(message)s")
    pv = FilePV.load(args.key_file, args.state_file)
    host, _, port = args.addr.rpartition("://")[-1].rpartition(":")
    server = SignerServer(pv, args.chain_id, (host or "127.0.0.1", int(port)))
    server.start()
    stop = threading.Event()
    for sig in (_signal.SIGINT, _signal.SIGTERM):
        _signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    server.stop()
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tmtpu",
                                description="tendermint-tpu node CLI")
    p.add_argument("--home", default=os.path.expanduser("~/.tmtpu"))
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("init", help="scaffold config/genesis/keys")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(fn=cmd_init)

    sp = sub.add_parser("start", help="run a node")
    sp.add_argument("--p2p-laddr", dest="p2p_laddr", default="")
    sp.add_argument("--rpc-laddr", dest="rpc_laddr", default="")
    sp.add_argument("--persistent-peers", dest="persistent_peers", default="")
    sp.add_argument("--proxy-app", dest="proxy_app", default="")
    sp.add_argument("--log-level", dest="log_level", default="info")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("testnet", help="generate N-node localnet configs")
    sp.add_argument("--v", type=int, default=4)
    sp.add_argument("--output-dir", dest="output_dir", default="./mytestnet")
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--starting-port", dest="starting_port", type=int,
                    default=26656)
    sp.add_argument("--prometheus", action="store_true",
                    help="serve /metrics on starting_port+2v+i per node")
    sp.set_defaults(fn=cmd_testnet)

    sp = sub.add_parser("light", help="verifying light-client proxy")
    sp.add_argument("chain_id")
    sp.add_argument("--primary", required=True)
    sp.add_argument("--witnesses", default="")
    sp.add_argument("--trust-height", dest="trust_height", type=int,
                    required=True)
    sp.add_argument("--trust-hash", dest="trust_hash", required=True)
    sp.add_argument("--trust-period", dest="trust_period", type=float,
                    default=168 * 3600.0)
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    sp.set_defaults(fn=cmd_light)

    sp = sub.add_parser("debug", help="capture a diagnostic bundle "
                                      "(dump) or capture-then-kill")
    sp.add_argument("action", choices=("dump", "kill"))
    sp.add_argument("--output-dir", dest="output_dir", default="")
    sp.add_argument("--rpc-laddr", dest="rpc_laddr", default="")
    sp.add_argument("--pid", type=int, default=0,
                    help="node pid (required for kill)")
    sp.set_defaults(fn=cmd_debug)

    sp = sub.add_parser("replay", help="replay blocks + WAL through the "
                                       "state machine (offline)")
    sp.set_defaults(fn=cmd_replay, console=False)

    sp = sub.add_parser("replay-console",
                        help="interactive step-by-step WAL replay")
    sp.set_defaults(fn=cmd_replay, console=True)

    sp = sub.add_parser("signer", help="remote privval signer process")
    sp.add_argument("--key-file", dest="key_file", required=True)
    sp.add_argument("--state-file", dest="state_file", required=True)
    sp.add_argument("--chain-id", dest="chain_id", required=True)
    sp.add_argument("--addr", required=True,
                    help="node's priv_validator_laddr to dial, host:port")
    sp.set_defaults(fn=cmd_signer)

    for name, fn in [("compact-db", cmd_compact_db),
                     ("reindex-event", cmd_reindex_event),
                     ("rollback", cmd_rollback),
                     ("gen-node-key", cmd_gen_node_key),
                     ("show-node-id", cmd_show_node_id),
                     ("gen-validator", cmd_gen_validator),
                     ("show-validator", cmd_show_validator),
                     ("unsafe-reset-all", cmd_reset_unsafe),
                     ("version", cmd_version)]:
        sp = sub.add_parser(name)
        sp.set_defaults(fn=fn)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
