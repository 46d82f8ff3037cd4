"""Signal-triggered in-process diagnostic dump (VERDICT r4 #7).

``cmd debug`` collects its bundle over RPC — useless against a node whose
event loop is wedged, which is precisely when a dump matters. The reference
always carries an out-of-band pprof listener (node/node.go:56,896) and
``debug kill`` snapshots goroutine profiles before the SIGKILL
(cmd/tendermint/commands/debug/kill.go). The analog here: a SIGUSR1 handler
registered with ``signal.signal`` — NOT ``loop.add_signal_handler``, whose
callbacks are loop callbacks and never run while the loop is stuck inside a
callback — that synchronously writes:

* every thread's current stack (``sys._current_frames``);
* every asyncio task of the node's loop with its await stack;
* the consensus round state repr and the open-peer table.

The handler runs between Python bytecodes of whatever the main thread is
executing, so a loop wedged in pure-Python spin still dumps; only a thread
blocked inside a C call with the GIL held can suppress it (same limitation
as Go's SIGQUIT dump for a wedged cgo call).
"""

from __future__ import annotations

import faulthandler
import os
import signal
import sys
import time
import traceback
from typing import Optional

_INSTALLED: dict = {}

# how many trailing trace events land in the dump bundle (full rings are
# 64k events — the tail is what describes the moments before the wedge)
_TRACE_TAIL_EVENTS = 512
# how many trailing device-segment phase records ride in device.json
_DEVICE_SEGMENT_TAIL = 64
# how many sealed heights of the consensus stage timeline ride along
_TIMELINE_TAIL_HEIGHTS = 32
# give the off-thread metrics render this long before the dump moves on
_METRICS_RENDER_TIMEOUT_S = 2.0


def write_dump(out_dir: str, node=None, loop=None, extras=None) -> str:
    """Write stacks + node state under out_dir; returns the dump path.
    ``extras`` is an optional JSON-safe dict the caller wants in the
    bundle (``extras.json``) — e.g. the watchdog's halt classification
    and per-validator vote bitmap."""
    os.makedirs(out_dir, exist_ok=True)

    if extras:
        try:
            import json

            with open(os.path.join(out_dir, "extras.json"), "w") as f:
                json.dump(extras, f, indent=1, default=str)
        except Exception:
            traceback.print_exc(file=sys.stderr)

    with open(os.path.join(out_dir, "threads.txt"), "w") as f:
        for tid, frame in sys._current_frames().items():
            f.write(f"--- thread {tid} ---\n")
            f.write("".join(traceback.format_stack(frame)))
            f.write("\n")

    if loop is not None:
        import asyncio

        with open(os.path.join(out_dir, "tasks.txt"), "w") as f:
            try:
                tasks = asyncio.all_tasks(loop)
            except Exception as e:
                f.write(f"could not enumerate tasks: {e}\n")
                tasks = []
            for task in tasks:
                f.write(f"--- {task!r} ---\n")
                try:
                    for frame in task.get_stack(limit=40):
                        f.write("".join(traceback.format_stack(frame, limit=8)))
                except Exception as e:
                    f.write(f"  <stack unavailable: {e}>\n")
                f.write("\n")

    # metrics-registry snapshot: the same exposition text /metrics serves,
    # but collected without the event loop — works when the RPC/metrics
    # listener's loop is the thing that's wedged. render() takes the metric
    # locks, and this handler may have interrupted the very frame holding
    # one (signal handlers run on the main thread between bytecodes), so it
    # runs on a helper thread with a join timeout instead of deadlocking
    # the node harder than the wedge being diagnosed.
    if node is not None and getattr(node, "metrics", None) is not None:
        try:
            import threading

            path = os.path.join(out_dir, "metrics.prom")

            def _render_and_write():
                try:
                    text = node.metrics.registry.render()
                    with open(path, "w") as f:
                        f.write(text)
                except Exception:
                    traceback.print_exc(file=sys.stderr)

            t = threading.Thread(target=_render_and_write, daemon=True,
                                 name="debugdump-metrics")
            t.start()
            t.join(_METRICS_RENDER_TIMEOUT_S)
            # on timeout the daemon thread finishes the write (or not)
            # once the interrupted frame releases its lock; nothing blocks
        except Exception:
            traceback.print_exc(file=sys.stderr)

    # span-trace ring tail (libs/trace.py): the last hot-path spans before
    # the wedge, loadable in Perfetto like a bench trace
    try:
        import json

        from .trace import tracer

        events = tracer.tail(_TRACE_TAIL_EVENTS)
        if events:
            with open(os.path.join(out_dir, "trace_tail.json"), "w") as f:
                json.dump(tracer.chrome_trace(events), f)
    except Exception:
        traceback.print_exc(file=sys.stderr)

    # per-height stage timeline tail (consensus/timeline.py): a watchdog
    # dump should say WHICH consensus stage the stalled height wedged in —
    # the in-flight record's marks end exactly where progress stopped
    try:
        import json

        tl = getattr(getattr(node, "consensus_state", None), "timeline",
                     None)
        if tl is not None:
            with open(os.path.join(out_dir, "stage_timeline.json"), "w") as f:
                json.dump(tl.snapshot(_TIMELINE_TAIL_HEIGHTS), f, indent=1)
    except Exception:
        traceback.print_exc(file=sys.stderr)

    # device-plane snapshot (crypto/phases.py + libs/compilecache.py): the
    # jax backend + device inventory, cumulative phase stats, the last-N
    # segment records, and whether the persistent compile cache was built
    # for THIS host's CPU features (the cpu_aot_loader SIGILL footgun) —
    # a wedged or SIGILL-adjacent dispatch must be attributable post-mortem
    try:
        import json

        from ..crypto import phases

        doc = {
            "phase_totals": phases.phase_totals(),
            "recent_segments": phases.recent_segments(_DEVICE_SEGMENT_TAIL),
        }
        try:
            # per-device lane health (multi-device pool): which chips are
            # degraded, and the pool's reshard/error counters
            from ..crypto.breaker import lane_breakers

            doc["lane_breakers"] = {
                label: {"state": b.state, "stats": dict(b.stats)}
                for label, b in lane_breakers().items()}
            md = sys.modules.get(
                "tendermint_tpu.crypto.ed25519_jax.multidevice")
            if md is not None and md._POOL is not None:
                doc["multidevice_pool"] = {
                    "lanes": [l.label for l in md._POOL.lanes],
                    "stats": dict(md._POOL.stats)}
        except Exception as e:
            doc["lane_breakers"] = f"unavailable: {e}"
        try:
            from . import compilecache

            doc["compile_cache"] = compilecache.status()
        except Exception as e:
            doc["compile_cache"] = f"unavailable: {e}"
        # report jax only if this process already imported it: a dump
        # handler must never pay (or wedge on) a cold jax/backend init
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                doc["jax_backend"] = jax.default_backend()
                doc["devices"] = [f"{d.platform}:{d.id}"
                                  for d in jax.devices()]
            except Exception as e:
                doc["jax_error"] = f"{type(e).__name__}: {e}"
        else:
            doc["jax_backend"] = None
        with open(os.path.join(out_dir, "device.json"), "w") as f:
            json.dump(doc, f, indent=1, default=str)
    except Exception:
        traceback.print_exc(file=sys.stderr)

    # per-tx lifecycle tail (libs/txlife.py): the ingestion plane's view of
    # the moments before the wedge — which stage sampled txs stalled in,
    # how deep the active map ran, and the last sealed broadcast→commit
    # records with their stage decompositions
    try:
        import json

        tl = getattr(getattr(node, "mempool", None), "txlife", None)
        if tl is not None:
            with open(os.path.join(out_dir, "txlife.json"), "w") as f:
                json.dump(tl.snapshot(_TIMELINE_TAIL_HEIGHTS), f, indent=1)
    except Exception:
        traceback.print_exc(file=sys.stderr)

    # statesync progress (statesync/syncer.py progress()): a bootstrap that
    # wedged mid-restore must be diagnosable post-mortem — which snapshot,
    # how many chunks landed, and which peers were struck/banned
    try:
        import json

        ss = getattr(node, "statesync_reactor", None)
        if ss is not None:
            syncer = getattr(ss, "syncer", None)
            progress = (syncer.progress() if syncer is not None
                        else getattr(ss, "last_progress", None))
            if progress is not None:
                with open(os.path.join(out_dir, "statesync.json"), "w") as f:
                    json.dump(progress, f, indent=1)
    except Exception:
        traceback.print_exc(file=sys.stderr)

    # fleet-rollup snapshot, when a fleet scraper is running alongside this
    # node (the e2e runner exports TMTPU_FLEET_JSON and keeps the file
    # fresh): the cluster's view of the moment this node stalled
    try:
        fleet = os.environ.get("TMTPU_FLEET_JSON")
        if fleet and os.path.exists(fleet):
            import shutil

            shutil.copy(fleet, os.path.join(out_dir, "fleet_rollup.json"))
    except Exception:
        traceback.print_exc(file=sys.stderr)

    # soak report, when this dump fires during a game-day run (tools/
    # soak.py exports TMTPU_SOAK_REPORT and rewrites the file per SLO
    # evaluation): the chaos schedule + breach attributions in flight
    try:
        soak = os.environ.get("TMTPU_SOAK_REPORT")
        if soak and os.path.exists(soak):
            import shutil

            shutil.copy(soak, os.path.join(out_dir, "soak_report.json"))
    except Exception:
        traceback.print_exc(file=sys.stderr)

    if node is not None:
        with open(os.path.join(out_dir, "node_state.txt"), "w") as f:
            try:
                rs = node.consensus_state.rs
                f.write(f"round_state: height={rs.height} round={rs.round} "
                        f"step={rs.step}\n")
            except Exception as e:
                f.write(f"round_state unavailable: {e}\n")
            try:
                peers = node.switch.peers
                f.write(f"peers ({len(peers)}):\n")
                for pid, peer in list(peers.items()):
                    f.write(f"  {pid} {getattr(peer, 'node_info', None)!r}\n")
            except Exception as e:
                f.write(f"peer table unavailable: {e}\n")
            try:
                f.write(f"blocks_synced: "
                        f"{node.blockchain_reactor.blocks_synced}\n")
            except Exception:
                pass
    return out_dir


def install(home_dir: str, node=None, loop=None,
            signum: int = signal.SIGUSR1) -> None:
    """Register the dump handler; main thread only (CPython rule). Also arms
    faulthandler on SIGABRT so hard crashes leave stacks too."""

    def _handler(_sig, _frame):
        out = os.path.join(home_dir, f"debug-{int(time.time())}")
        try:
            write_dump(out, node=node, loop=loop)
        except Exception:
            traceback.print_exc(file=sys.stderr)

    signal.signal(signum, _handler)
    _INSTALLED[signum] = home_dir
    try:
        faulthandler.enable()
    except Exception:
        pass


def installed_home(signum: int = signal.SIGUSR1) -> Optional[str]:
    return _INSTALLED.get(signum)
