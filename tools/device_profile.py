"""Device-plane profiler: the ONE supported tool for dispatch-cost and
scaling profiles.

It replaced eight throwaway probe scripts. Three subcommands, each emitting
a schema-versioned PROFILE JSON (``tmtpu-device-profile/v1``) plus a
markdown table. No profile of a locally attached chip has been taken yet:
every figure these probes would give there is "not measured".

* ``cost-model`` — the dispatch cost model, measured: fixed dispatch cost
  (resident input, scalar output), per-thread transfer bandwidth from a
  payload-size ladder, the no-cross-run-dedup check (a near-copy payload
  must pay full price), the same-thread-pipelining check (do two dispatches
  from one thread cost ~2x one?), and the worker-overlap probe (does a
  second thread's dispatch overlap an in-flight one — the assumption the
  flagship's segmented pipeline is built on). Trivial kernels: measures the
  host-to-device path, not ed25519 compute.
* ``sweep`` — chunk-size x SEG_CHUNKS grid through the real
  ``batch_verify_stream`` path -> sigs/s table with pack-share and
  pipeline-overlap from the crypto/phases.py recorder.
* ``scale`` — devices x chunk scaling, one fresh subprocess per device
  count (the forced host-platform CPU mesh makes this dry-runnable on a
  machine with no TPU: ``--host-mesh``). Three modes per cell: the
  ``sharded`` psum path (ed25519_jax/sharded.py), raw ``threads`` x
  devices dense-stream dispatch, and ``multidev`` — the PRODUCTION
  multi-device dispatcher (ed25519_jax/multidevice.py MultiDeviceStream)
  the multichip flagship metric rides. On a machine with a chip every
  cell runs in the calling process (the chip has one owner).

Workloads: ``--workload ed25519`` runs the real verify kernels;
``--workload synthetic`` swaps in byte-identical-shape stub kernels (same
wire format, same host packing, trivial device compute) so transfer/
dispatch costs are measurable on CPU-only machines without multi-minute
XLA compiles of the verify kernel. ``auto`` (default) picks synthetic on
the CPU backend, ed25519 elsewhere. Signature bytes are random — the
kernels do identical work for invalid signatures, so throughput numbers
are unaffected and no signing keys are needed.

    python tools/device_profile.py cost-model --out profile.json
    python tools/device_profile.py sweep --chunks 1024,2048,4096 --seg-chunks 5,10,20
    python tools/device_profile.py scale --devices 1,2,4,8 --chunks 1024,2048
    python tools/device_profile.py --self-test
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SCHEMA = "tmtpu-device-profile/v1"
#: synthetic SCALE cells burn this much per-element device work so the
#: dispatch topology — not GIL-bound host packing — is what scales
DEFAULT_SCALE_DEVICE_WORK = 20000
KINDS = ("cost-model", "sweep", "scale")
MB = 1 << 20

#: per-kind required result keys (the schema's load-bearing part)
REQUIRED_RESULTS = {
    "cost-model": ("fixed_dispatch_ms", "transfer", "no_cross_run_dedup",
                   "same_thread_pipelining", "worker_overlap"),
    "sweep": ("workload", "table"),
    "scale": ("workload", "table"),
}
_ROW_KEYS = {
    "sweep": ("chunk", "seg_chunks", "sigs_per_sec"),
    "scale": ("devices", "mode", "sigs_per_sec"),
}


# -- schema -------------------------------------------------------------------

def platform_info() -> Dict:
    info: Dict = {"python": sys.version.split()[0]}
    try:
        import platform as _pf

        info["machine"] = _pf.machine()
    except Exception:
        info["machine"] = "unknown"
    try:
        import jax

        info["backend"] = jax.default_backend()
        devs = jax.devices()
        info["n_devices"] = len(devs)
        info["devices"] = [f"{d.platform}:{d.id}" for d in devs]
    except Exception as e:
        info["backend"] = f"unavailable: {type(e).__name__}"
        info["n_devices"] = 0
        info["devices"] = []
    return info


def make_doc(kind: str, config: Dict, results: Dict) -> Dict:
    return {
        "schema": SCHEMA,
        "kind": kind,
        "generated_by": "tools/device_profile.py",
        "generated_unix": time.time(),
        "platform": platform_info(),
        "config": config,
        "results": results,
    }


def validate_profile(doc) -> List[str]:
    """Schema check for a PROFILE JSON; returns a list of problems (empty
    = valid). Hand-rolled: the toolbox is stdlib-only by contract."""
    errs: List[str] = []
    if not isinstance(doc, dict):
        return [f"document is {type(doc).__name__}, want object"]
    if doc.get("schema") != SCHEMA:
        errs.append(f"schema: want {SCHEMA!r}, got {doc.get('schema')!r}")
    kind = doc.get("kind")
    if kind not in KINDS:
        errs.append(f"kind: want one of {KINDS}, got {kind!r}")
    if not isinstance(doc.get("generated_unix"), (int, float)):
        errs.append("generated_unix: missing or not a number")
    plat = doc.get("platform")
    if not isinstance(plat, dict):
        errs.append("platform: missing or not an object")
    else:
        if not isinstance(plat.get("backend"), str):
            errs.append("platform.backend: missing or not a string")
        if not isinstance(plat.get("n_devices"), int):
            errs.append("platform.n_devices: missing or not an int")
        if not isinstance(plat.get("devices"), list):
            errs.append("platform.devices: missing or not a list")
    if not isinstance(doc.get("config"), dict):
        errs.append("config: missing or not an object")
    res = doc.get("results")
    if not isinstance(res, dict):
        errs.append("results: missing or not an object")
        return errs
    for key in REQUIRED_RESULTS.get(kind, ()):
        if key not in res:
            errs.append(f"results.{key}: missing")
    if kind == "cost-model" and isinstance(res.get("transfer"), dict):
        bw = res["transfer"].get("bandwidth_mbps")
        # None = ladder delta below the noise floor; a non-finite number
        # would serialize as invalid JSON (Infinity/NaN tokens)
        if bw is not None and not (isinstance(bw, (int, float))
                                   and -1e18 < bw < 1e18):
            errs.append(f"results.transfer.bandwidth_mbps: bad value {bw!r}")
    for tkind, row_keys in _ROW_KEYS.items():
        if kind != tkind:
            continue
        table = res.get("table")
        if not isinstance(table, list) or not table:
            errs.append("results.table: missing or empty")
            continue
        for i, row in enumerate(table):
            if not isinstance(row, dict):
                errs.append(f"results.table[{i}]: not an object")
                continue
            for k in row_keys:
                if k not in row:
                    errs.append(f"results.table[{i}].{k}: missing")
            sps = row.get("sigs_per_sec")
            if not (isinstance(sps, (int, float)) and sps >= 0):
                errs.append(f"results.table[{i}].sigs_per_sec: bad value "
                            f"{sps!r}")
    return errs


def to_markdown(doc: Dict) -> str:
    """A compact markdown rendering of the profile (for the PR/README)."""
    kind = doc.get("kind")
    plat = doc.get("platform", {})
    head = (f"### device_profile {kind} — backend {plat.get('backend')}"
            f" ({plat.get('n_devices')} devices)")
    res = doc.get("results", {})
    lines = [head, ""]
    if kind == "cost-model":
        fd = res["fixed_dispatch_ms"]
        tr = res["transfer"]
        bw = tr.get("bandwidth_mbps")
        lines += ["| probe | result |", "|---|---|",
                  f"| fixed dispatch (resident input) | "
                  f"{fd['min']:.2f}/{fd['med']:.2f} ms min/med |",
                  f"| transfer bandwidth (per thread) | "
                  + (f"{bw:.1f} MB/s |" if bw is not None
                     else "n/a (ladder delta below noise floor) |"),
                  f"| cross-run dedup | "
                  f"{'none (full price)' if res['no_cross_run_dedup']['holds'] else 'DETECTED'} |",
                  f"| same-thread pipelining | "
                  f"{'none (2x cost)' if not res['same_thread_pipelining']['pipelined'] else 'DETECTED'} "
                  f"(ratio {res['same_thread_pipelining']['ratio']:.2f}) |",
                  f"| worker-thread overlap | "
                  f"{'works' if res['worker_overlap']['overlaps'] else 'NO OVERLAP'} "
                  f"(ratio {res['worker_overlap']['ratio']:.2f}) |"]
    elif kind == "sweep":
        lines += ["| chunk | SEG_CHUNKS | sigs/s | pack share | overlap |",
                  "|---|---|---|---|---|"]
        for r in res["table"]:
            ov = r.get("overlap_ratio")
            lines.append(
                f"| {r['chunk']} | {r['seg_chunks']} | "
                f"{r['sigs_per_sec']:.0f} | {r.get('pack_share', 0):.3f} | "
                f"{'-' if ov is None else f'{ov:.2f}'} |")
    elif kind == "scale":
        lines += ["| devices | mode | chunk | threads | sigs/s |",
                  "|---|---|---|---|---|"]
        for r in res["table"]:
            lines.append(
                f"| {r['devices']} | {r['mode']} | "
                f"{r.get('chunk') or '-'} | {r.get('threads') or '-'} | "
                f"{r['sigs_per_sec']:.0f} |")
    return "\n".join(lines)


# -- workload -----------------------------------------------------------------

def build_workload(n: int, msg_len: int = 110, seed: int = 7):
    """Commit-shaped synthetic batch: shared message template with 8
    varying 'timestamp' bytes per item (engages the sparse wire format the
    real path uses), random 32-byte pks, random 64-byte sigs with the s
    half's top byte zeroed (s < L, so the host ok-mask passes every row).
    Verdicts will be garbage — the kernels do identical work either way,
    which is all a throughput/cost probe needs."""
    import numpy as np

    rng = np.random.default_rng(seed)
    tpl = rng.integers(0, 256, msg_len, dtype=np.uint8)
    arr = np.broadcast_to(tpl, (n, msg_len)).copy()
    ts = (1_700_000_000_000_000_000 + np.arange(n, dtype=np.uint64))
    for k in range(8):  # 8 varying bytes, big-endian, vote-timestamp-like
        arr[:, 40 + k] = ((ts >> (8 * (7 - k))) & 0xFF).astype(np.uint8)
    msgs = [row.tobytes() for row in arr]
    pks = [b.tobytes() for b in rng.integers(0, 256, (n, 32), dtype=np.uint8)]
    sig_arr = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    sig_arr[:, 63] = 0  # s < L
    sigs = [b.tobytes() for b in sig_arr]
    return pks, msgs, sigs


def resolve_workload(choice: str) -> str:
    """``auto``: stub kernels where the process is pinned to the CPU
    backend, the real kernels elsewhere. Decided from the platform pin
    (``JAX_PLATFORMS``) alone, WITHOUT importing jax: a launcher that
    touched jax would hold the chip its cells need."""
    if choice != "auto":
        return choice
    from tendermint_tpu.libs.compilecache import env_pins_cpu

    return "synthetic" if env_pins_cpu() else "ed25519"


def install_stub_kernels(V, sharded=None, device_work: int = 0):
    """Swap the verify kernels for byte-identical-SHAPE stubs (same wire
    format in, same verdict shape out, trivial compute) and return a
    restore() callable. The host pack/transfer/dispatch path — the thing
    the dispatch cost model is about — stays 100% real.

    ``device_work`` > 0 burns that many deterministic per-element LCG
    rounds on device before deciding — a stand-in for the real kernel's
    compute so SCALE measurements see a device-bound workload (with
    trivial stubs a multi-device cell measures host packing contention,
    not the dispatch topology it exists to measure). The verdict stays a
    per-item function of the wire bytes, invariant to segmentation."""
    import jax
    import jax.numpy as jnp

    orig = (V._verify_kernel, V._verify_stream_kernel,
            V._verify_sparse_stream_kernel,
            sharded._verify_kernel if sharded is not None else None)

    def _burn(x):
        if not device_work:
            return x
        return jax.lax.fori_loop(
            0, device_work,
            lambda i, acc: acc * jnp.uint32(1664525)
            + jnp.uint32(1013904223), x)

    def _decide(per_item):
        # LCG rounds are a bijection on uint32, so parity of the burned
        # value is as deterministic as parity of the sum itself
        return _burn(per_item) % 2 == 0

    def _kern(blocks, nblk, s_words):
        return _decide(jnp.sum(blocks, axis=(0, 1), dtype=jnp.uint32)
                       + jnp.sum(s_words, axis=0, dtype=jnp.uint32)
                       + nblk.astype(jnp.uint32))

    stub_kernel = jax.jit(_kern)
    stub_kernel.__wrapped__ = _kern  # sharded full_step calls __wrapped__

    @jax.jit
    def stub_stream(blocks, nblk, s_words):
        return _decide(jnp.sum(blocks, axis=(1, 2), dtype=jnp.uint32)
                       + jnp.sum(s_words, axis=1, dtype=jnp.uint32)
                       + nblk.astype(jnp.uint32))

    @jax.jit
    def stub_sparse(templates, diff_cols, diff_vals, mlen, r_b, a_b, s_b):
        # PER-ITEM only (no whole-template/column-set term): the stub
        # verdict must be invariant to how a batch is segmented across
        # dispatches, so multi-device sharding tests can assert verdict
        # parity against the single-device layout
        per = (jnp.sum(diff_vals, axis=1, dtype=jnp.uint32)
               + jnp.sum(r_b, axis=1, dtype=jnp.uint32)
               + jnp.sum(a_b, axis=1, dtype=jnp.uint32)
               + jnp.sum(s_b, axis=1, dtype=jnp.uint32)
               + mlen.astype(jnp.uint32))
        return _decide(per)

    V._verify_kernel = stub_kernel
    V._verify_stream_kernel = stub_stream
    V._verify_sparse_stream_kernel = stub_sparse
    if sharded is not None:
        sharded._verify_kernel = stub_kernel

    def restore():
        (V._verify_kernel, V._verify_stream_kernel,
         V._verify_sparse_stream_kernel) = orig[:3]
        if sharded is not None:
            sharded._verify_kernel = orig[3]

    return restore


# -- cost-model ---------------------------------------------------------------

def _timed_ms(fn, runs: int) -> Dict[str, float]:
    ts = []
    for i in range(runs):
        t0 = time.perf_counter()
        fn(i)
        ts.append((time.perf_counter() - t0) * 1e3)
    return {"min": min(ts), "med": statistics.median(ts),
            "runs_ms": [round(t, 3) for t in ts]}


def run_cost_model(payload_mb: float = 4.0, runs: int = 4) -> Dict:
    """The dispatch cost model, measured with trivial kernels (perturbed
    inputs + fetched outputs everywhere, so no cache of identical repeat
    computations can flatter a reading)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    nbytes = max(int(payload_mb * MB), 1 << 12)

    touch = jax.jit(lambda a: jnp.sum(a, dtype=jnp.int32))
    base = rng.integers(0, 255, nbytes, dtype=np.uint8)
    np.asarray(touch(base))  # compile

    # 1. fixed dispatch cost: input resident on device, scalar output
    resident = jax.device_put(base)
    fixed = _timed_ms(lambda i: np.asarray(touch(resident)), runs)

    # 2. per-thread transfer bandwidth from a payload ladder (perturbed
    #    fresh bytes each run so no cache can serve them)
    per_size = []
    for frac in (0.125, 0.5, 1.0):
        sz = max(int(nbytes * frac), 1 << 12)

        def one(i, sz=sz):
            a = rng.integers(0, 255, sz, dtype=np.uint8)
            np.asarray(touch(a))

        one(0)  # compile this shape
        t = _timed_ms(one, runs)
        per_size.append({"mb": sz / MB, "min_ms": round(t["min"], 3),
                         "med_ms": round(t["med"], 3)})
    d_ms = per_size[-1]["min_ms"] - per_size[0]["min_ms"]
    d_mb = per_size[-1]["mb"] - per_size[0]["mb"]
    # below the noise floor the ladder measures dispatch jitter, not
    # transfer: report null rather than a garbage (or Infinity — invalid
    # JSON) number
    bandwidth = round(d_mb / (d_ms / 1e3), 2) if d_ms > 0.05 else None

    # 3. cross-run dedup: a near-copy of the previous payload must pay the
    #    same as fresh bytes (no delta compression in the path)
    def fresh(i):
        np.asarray(touch(rng.integers(0, 255, nbytes, dtype=np.uint8)))

    near = base.copy()

    def near_copy(i):
        near[i] ^= 1
        near[nbytes // 2 + i] ^= 1
        np.asarray(touch(near))

    t_fresh = _timed_ms(fresh, runs)
    t_near = _timed_ms(near_copy, runs)
    dedup_ratio = t_near["min"] / max(t_fresh["min"], 1e-6)

    # 4. same-thread pipelining: two independent dispatches from ONE thread,
    #    both fetched at the end — a serial path costs ~2x one
    def two(i):
        a = rng.integers(0, 255, nbytes, dtype=np.uint8)
        b = rng.integers(0, 255, nbytes, dtype=np.uint8)
        ra, rb = touch(a), touch(b)
        np.asarray(ra), np.asarray(rb)

    t_one = t_fresh
    t_two = _timed_ms(two, runs)
    pipe_ratio = t_two["min"] / max(t_one["min"], 1e-6)

    # 5. worker overlap: the same two dispatches from two THREADS — the
    #    overlap the segmented pipeline exploits (its size on a locally
    #    attached chip: not measured)
    def one_thread_job():
        a = rng.integers(0, 255, nbytes, dtype=np.uint8)
        np.asarray(touch(a))

    def overlapped(i):
        ths = [threading.Thread(target=one_thread_job) for _ in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()

    t_serial2 = t_two
    t_overlap = _timed_ms(overlapped, runs)
    overlap_ratio = t_overlap["min"] / max(t_serial2["min"], 1e-6)

    return {
        "fixed_dispatch_ms": {"min": round(fixed["min"], 3),
                              "med": round(fixed["med"], 3)},
        "transfer": {"bandwidth_mbps": bandwidth, "per_size": per_size},
        "no_cross_run_dedup": {
            "fresh_min_ms": round(t_fresh["min"], 3),
            "near_copy_min_ms": round(t_near["min"], 3),
            "ratio": round(dedup_ratio, 3),
            # a near-copy at >=70% of fresh cost means no dedup is helping
            "holds": bool(dedup_ratio >= 0.7)},
        "same_thread_pipelining": {
            "one_min_ms": round(t_one["min"], 3),
            "two_min_ms": round(t_two["min"], 3),
            "ratio": round(pipe_ratio, 3),
            # two-for-much-less-than-2x would mean the runtime pipelines a
            # single thread's dispatches; 1.5x is the decision boundary
            "pipelined": bool(pipe_ratio < 1.5)},
        "worker_overlap": {
            "serial_two_min_ms": round(t_serial2["min"], 3),
            "overlapped_two_min_ms": round(t_overlap["min"], 3),
            "ratio": round(overlap_ratio, 3),
            "overlaps": bool(overlap_ratio < 0.8)},
    }


# -- sweep --------------------------------------------------------------------

def run_sweep(sigs: int, chunks: List[int], seg_chunks: List[int],
              workload: str, runs: int = 3,
              seg_min_sigs: Optional[int] = None) -> Dict:
    """chunk x SEG_CHUNKS grid through the real batch_verify_stream path;
    sigs/s + pack share + pipeline overlap per cell from crypto/phases.py."""
    from tendermint_tpu.crypto import phases
    from tendermint_tpu.crypto.ed25519_jax import verify as V

    restore = (install_stub_kernels(V) if workload == "synthetic"
               else lambda: None)
    pks, msgs, sigs_b = build_workload(sigs)
    rows = []
    old_sc, old_min = V.SEG_CHUNKS, V.SEG_MIN_SIGS
    try:
        if seg_min_sigs is not None:
            V.SEG_MIN_SIGS = seg_min_sigs
        for chunk in chunks:
            for sc in seg_chunks:
                V.SEG_CHUNKS = sc
                V.batch_verify_stream(pks, msgs, sigs_b, chunk=chunk)  # warm
                phases.reset()
                times = []
                for _ in range(runs):
                    t0 = time.perf_counter()
                    V.batch_verify_stream(pks, msgs, sigs_b, chunk=chunk)
                    times.append(time.perf_counter() - t0)
                tot = phases.phase_totals()
                wall = sum(times)
                fly_sum = tot["inflight_sum_s"]
                rows.append({
                    "chunk": chunk, "seg_chunks": sc, "sigs": sigs,
                    "best_s": round(min(times), 4),
                    "sigs_per_sec": round(sigs / min(times), 1),
                    "pack_share": round(tot["pack_s"] / max(wall, 1e-9), 4),
                    "segments": int(tot["segments"]),
                    "overlap_ratio": (
                        round(tot["inflight_union_s"] / fly_sum, 3)
                        if fly_sum > 0 else None),
                })
    finally:
        V.SEG_CHUNKS, V.SEG_MIN_SIGS = old_sc, old_min
        restore()
    return {"workload": workload, "table": rows}


# -- scale --------------------------------------------------------------------

def run_scale_cell(devices: int, chunks: List[int], sigs: int,
                   workload: str, host_mesh: bool, runs: int = 3,
                   threads: Optional[int] = None,
                   device_work: int = DEFAULT_SCALE_DEVICE_WORK) -> Dict:
    """One device-count cell, meant to run in a FRESH process (the forced
    host-platform device count is fixed at backend init). Measures (a) the
    sharded psum-tally path over the whole mesh and (b) per-chunk rows
    where N threads each dispatch a dense stream shard to their own
    device — the scaling assumption the multichip dispatcher rests on
    (worker-thread overlap; on locally attached chips: not measured)."""
    if host_mesh:
        # strip any previous force-count token, then pin ours (XLA_FLAGS
        # is read when the backend initializes, on first use)
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
        import jax

        jax.config.update("jax_platforms", "cpu")
    else:
        import jax

    import numpy as np

    from tendermint_tpu.crypto import phases  # noqa: F401 (recorder active)
    from tendermint_tpu.crypto.ed25519_jax import sharded as S
    from tendermint_tpu.crypto.ed25519_jax import verify as V

    if len(jax.devices()) < devices:
        raise RuntimeError(f"need {devices} devices, have "
                           f"{len(jax.devices())} (use --host-mesh)")
    restore = (install_stub_kernels(V, sharded=S, device_work=device_work)
               if workload == "synthetic" else lambda: None)
    n_threads = threads or devices
    pks, msgs, sigs_b = build_workload(sigs)
    rows = []
    try:
        # (a) sharded mesh: one shard_map dispatch + exact psum tally
        mesh = S.make_mesh(devices)
        S.batch_verify_sharded(pks, msgs, sigs_b, mesh=mesh)  # warm
        times = []
        for _ in range(runs):
            t0 = time.perf_counter()
            S.batch_verify_sharded(pks, msgs, sigs_b, mesh=mesh)
            times.append(time.perf_counter() - t0)
        rows.append({"devices": devices, "mode": "sharded", "chunk": None,
                     "threads": None, "sigs": sigs,
                     "sigs_per_sec": round(sigs / min(times), 1)})

        # (b) threads x devices: thread j packs + dispatches its shard onto
        # device j — the multichip dispatcher's shape (one packing/transfer
        # worker per device, overlapping in-flight execution)
        devs = jax.devices()[:devices]
        per = max(-(-sigs // n_threads) // V.LANE, 1) * V.LANE
        shards = [(pks[a:a + per], msgs[a:a + per], sigs_b[a:a + per])
                  for a in range(0, sigs, per)]
        for chunk in chunks:
            shard_chunk = min(chunk, per)

            def job(j):
                p, m, s = shards[j % len(shards)]
                args, _ok = V._pack_stream_dense(p, m, s, shard_chunk)
                dev_args = [jax.device_put(a, devs[j % devices])
                            for a in args]
                np.asarray(V._verify_stream_kernel(*dev_args))

            used = min(n_threads, len(shards))
            for j in range(used):
                job(j)  # warm every device + shape
            times = []
            for _ in range(runs):
                ths = [threading.Thread(target=job, args=(j,))
                       for j in range(used)]
                t0 = time.perf_counter()
                for t in ths:
                    t.start()
                for t in ths:
                    t.join()
                times.append(time.perf_counter() - t0)
            # actual signatures verified (the tail shard can be short —
            # counting `per * used` would inflate the scaling table)
            done_sigs = sum(len(shards[j % len(shards)][0])
                            for j in range(used))
            rows.append({"devices": devices, "mode": "threads",
                         "chunk": chunk, "threads": used,
                         "sigs": done_sigs,
                         "sigs_per_sec": round(done_sigs / min(times), 1)})

        # (c) the PRODUCTION dispatcher: MultiDeviceStream shards one
        # batch_verify_stream call round-robin across per-device lanes
        # (one packing/transfer worker each, per-device breakers) — the
        # rows the multichip flagship metric is judged against
        from tendermint_tpu.crypto.ed25519_jax import multidevice as MD

        pool = MD.MultiDeviceStream(devices=devs, min_sigs=0)
        try:
            for chunk in chunks:
                c = min(chunk, max(sigs // 2 // V.LANE, 1) * V.LANE)
                pool.verify(pks, msgs, sigs_b, chunk=c)  # warm every lane
                times = []
                for _ in range(runs):
                    t0 = time.perf_counter()
                    pool.verify(pks, msgs, sigs_b, chunk=c)
                    times.append(time.perf_counter() - t0)
                rows.append({"devices": devices, "mode": "multidev",
                             "chunk": c, "threads": devices, "sigs": sigs,
                             "sigs_per_sec": round(sigs / min(times), 1)})
        finally:
            pool.shutdown()
    finally:
        restore()
    return {"devices": devices, "rows": rows}


def run_scale(devices_list: List[int], chunks: List[int], sigs: int,
              workload: str, host_mesh: bool, runs: int,
              threads: Optional[int], timeout_s: float = 600.0,
              device_work: int = DEFAULT_SCALE_DEVICE_WORK) -> Dict:
    """The scaling table, one cell per device count.

    ``host_mesh``: one CPU-pinned ``_scale-cell`` subprocess per count (a
    process can only force one host-platform device count), strictly one
    after another. Otherwise — a machine with a chip — every cell runs in
    THIS process over the first ``d`` attached devices: a chip belongs to
    one process at a time, so a parent that has touched jax would starve
    its children. Cell failures land in ``cell_errors``; callers treat
    them as a failed run."""
    rows, errors = [], []
    for d in devices_list:
        if not host_mesh:
            try:
                cell = run_scale_cell(d, chunks, sigs, workload, False,
                                      runs=runs, threads=threads,
                                      device_work=device_work)
            except Exception as e:  # reported per cell, fails the run
                errors.append({"devices": d,
                               "error": f"{type(e).__name__}: {e}"})
                continue
            rows.extend(cell["rows"])
            continue
        cmd = [sys.executable, os.path.abspath(__file__), "_scale-cell",
               "--devices", str(d), "--sigs", str(sigs),
               "--chunks", ",".join(map(str, chunks)),
               "--workload", workload, "--runs", str(runs),
               "--device-work", str(device_work), "--host-mesh"]
        if threads:
            cmd += ["--threads", str(threads)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=timeout_s, env=env, cwd=REPO)
        except subprocess.TimeoutExpired:
            errors.append({"devices": d, "error": "timeout"})
            continue
        cell = None
        for line in res.stdout.splitlines():
            if line.startswith("{"):
                try:
                    cell = json.loads(line)
                except json.JSONDecodeError:
                    pass
        if res.returncode != 0 or cell is None:
            errors.append({"devices": d, "rc": res.returncode,
                           "stderr_tail": res.stderr[-800:]})
            continue
        rows.extend(cell["rows"])
    out: Dict = {"workload": workload, "table": rows}
    if errors:
        out["cell_errors"] = errors
    return out


# -- CLI ----------------------------------------------------------------------

def _ints(s: str) -> List[int]:
    return [int(x) for x in s.split(",") if x]


def emit(doc: Dict, out: Optional[str], md: Optional[str]) -> None:
    errs = validate_profile(doc)
    if errs:  # the tool must never write an artifact its own schema rejects
        raise SystemExit("device_profile: generated profile fails its "
                         "schema: " + "; ".join(errs))
    print(to_markdown(doc))
    if out:
        with open(out, "w") as f:
            # allow_nan=False: an Infinity/NaN that slipped past the schema
            # would serialize as tokens strict JSON parsers reject
            json.dump(doc, f, indent=1, allow_nan=False)
        print(f"\nwrote {out}")
    else:
        print()
        print(json.dumps(doc, allow_nan=False))
    if md:
        with open(md, "w") as f:
            f.write(to_markdown(doc) + "\n")


def self_test() -> int:
    import numpy as np  # noqa: F401 — fail fast if the env lacks numpy

    # 1. schema: hand-built minimal docs of each kind validate; mutations
    #    are rejected with pointed messages
    samples = {
        "cost-model": {
            "fixed_dispatch_ms": {"min": 1.0, "med": 2.0},
            "transfer": {"bandwidth_mbps": 10.0, "per_size": []},
            "no_cross_run_dedup": {"holds": True},
            "same_thread_pipelining": {"ratio": 2.0, "pipelined": False},
            "worker_overlap": {"ratio": 0.6, "overlaps": True},
        },
        "sweep": {"workload": "synthetic", "table": [
            {"chunk": 2048, "seg_chunks": 10, "sigs_per_sec": 1000.0,
             "pack_share": 0.1, "overlap_ratio": 0.8}]},
        "scale": {"workload": "synthetic", "table": [
            {"devices": 2, "mode": "sharded", "chunk": None,
             "threads": None, "sigs_per_sec": 500.0}]},
    }
    for kind, res in samples.items():
        doc = make_doc(kind, {"synthetic_sample": True}, res)
        assert validate_profile(doc) == [], (kind, validate_profile(doc))
        assert to_markdown(doc).startswith("### device_profile")
        broken = json.loads(json.dumps(doc))
        del broken["results"][REQUIRED_RESULTS[kind][0]]
        errs = validate_profile(broken)
        assert errs and REQUIRED_RESULTS[kind][0] in errs[0], errs
    assert validate_profile({"schema": "nope"})  # wrong everything
    assert validate_profile([1, 2])  # not even an object
    # bandwidth: null (below noise floor) is valid; Infinity is not JSON
    nf = make_doc("cost-model", {}, json.loads(
        json.dumps(samples["cost-model"])))
    nf["results"]["transfer"]["bandwidth_mbps"] = None
    assert validate_profile(nf) == []
    nf["results"]["transfer"]["bandwidth_mbps"] = float("inf")
    assert any("bandwidth" in e for e in validate_profile(nf))

    # 2. workload builder: template-similar messages (sparse-format
    #    eligible), s < L on every row
    pks, msgs, sigs = build_workload(256)
    assert len({len(m) for m in msgs}) == 1 and len(pks) == 256
    assert all(s[63] == 0 for s in sigs)
    diff_cols = {i for a in msgs[1:4] for i, (x, y)
                 in enumerate(zip(msgs[0], a)) if x != y}
    assert 0 < len(diff_cols) <= 8, diff_cols

    # 3. a real (micro) cost-model run end-to-end through emit's schema
    #    check — trivial kernels, so this is cheap even on cold CPU
    doc = make_doc("cost-model", {"payload_mb": 0.0625, "runs": 2},
                   run_cost_model(payload_mb=0.0625, runs=2))
    assert validate_profile(doc) == [], validate_profile(doc)

    # 4. a micro sweep with stub kernels through the REAL segmented
    #    batch_verify_stream path (chunk=128 -> 4 scan chunks, forced
    #    segmentation) — phases recorder feeds pack share + overlap
    doc = make_doc("sweep", {"sigs": 512}, run_sweep(
        sigs=512, chunks=[128], seg_chunks=[2], workload="synthetic",
        runs=1, seg_min_sigs=0))
    assert validate_profile(doc) == [], validate_profile(doc)
    row = doc["results"]["table"][0]
    assert row["sigs_per_sec"] > 0 and row["segments"] >= 2, row
    assert row["overlap_ratio"] is not None

    # 5. one scale cell in a fresh subprocess on a forced 2-device CPU
    #    mesh: the sharded row, a threads x devices row, AND the
    #    production MultiDeviceStream dispatcher row all land
    doc = make_doc("scale", {"devices": [2]}, run_scale(
        [2], chunks=[128], sigs=256, workload="synthetic", host_mesh=True,
        runs=1, threads=None, timeout_s=300.0))
    errs = validate_profile(doc)
    assert errs == [], (errs, doc["results"].get("cell_errors"))
    modes = {r["mode"] for r in doc["results"]["table"]}
    assert modes == {"sharded", "threads", "multidev"}, \
        doc["results"]["table"]
    md_row = next(r for r in doc["results"]["table"]
                  if r["mode"] == "multidev")
    assert md_row["sigs_per_sec"] > 0 and md_row["devices"] == 2

    print("device_profile self-test OK (schema, workload, cost-model, "
          "sweep, scale cell incl. multidev stream)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("command", nargs="?",
                    choices=list(KINDS) + ["_scale-cell"])
    ap.add_argument("--out", help="write the PROFILE JSON here "
                                  "(default: print to stdout)")
    ap.add_argument("--md", help="also write the markdown table here")
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--payload-mb", type=float, default=4.0,
                    help="cost-model probe payload size")
    ap.add_argument("--sigs", type=int, default=20480,
                    help="sweep/scale workload size")
    ap.add_argument("--chunks", type=_ints, default=[1024, 2048, 4096],
                    help="comma-separated chunk sizes")
    ap.add_argument("--seg-chunks", type=_ints, default=[5, 10, 20],
                    help="comma-separated SEG_CHUNKS values (sweep)")
    ap.add_argument("--seg-min-sigs", type=int, default=None,
                    help="override SEG_MIN_SIGS for the sweep (0 forces "
                         "the segmented pipeline on)")
    ap.add_argument("--devices", type=_ints, default=[1, 2, 4, 8],
                    help="comma-separated device counts (scale); "
                         "_scale-cell takes a single count")
    ap.add_argument("--threads", type=int, default=None,
                    help="scale: dispatch threads per cell "
                         "(default: one per device)")
    ap.add_argument("--device-work", type=int,
                    default=DEFAULT_SCALE_DEVICE_WORK,
                    help="scale w/ synthetic stubs: per-element LCG rounds "
                         "burned on device so the cell is device-bound "
                         "like the real workload (0 = trivial stubs)")
    ap.add_argument("--workload", choices=("auto", "ed25519", "synthetic"),
                    default="auto",
                    help="real verify kernels, or shape-identical stubs "
                         "(auto: synthetic on the CPU backend)")
    ap.add_argument("--host-mesh", action="store_true",
                    help="scale: force an N-device host-platform CPU mesh "
                         "per cell (auto-enabled on the CPU backend)")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    if not args.command:
        ap.error("need a subcommand (cost-model | sweep | scale) "
                 "or --self-test")

    if args.command == "_scale-cell":
        cell = run_scale_cell(args.devices[0], args.chunks, args.sigs,
                              resolve_workload(args.workload),
                              args.host_mesh, runs=args.runs,
                              threads=args.threads,
                              device_work=args.device_work)
        print(json.dumps(cell))
        return 0

    workload = resolve_workload(args.workload)
    if args.command == "cost-model":
        doc = make_doc("cost-model",
                       {"payload_mb": args.payload_mb, "runs": args.runs},
                       run_cost_model(args.payload_mb, args.runs))
    elif args.command == "sweep":
        doc = make_doc("sweep",
                       {"sigs": args.sigs, "chunks": args.chunks,
                        "seg_chunks": args.seg_chunks, "runs": args.runs,
                        "workload": workload},
                       run_sweep(args.sigs, args.chunks, args.seg_chunks,
                                 workload, runs=args.runs,
                                 seg_min_sigs=args.seg_min_sigs))
    else:  # scale
        host_mesh = args.host_mesh or workload == "synthetic"
        doc = make_doc("scale",
                       {"devices": args.devices, "chunks": args.chunks,
                        "sigs": args.sigs, "runs": args.runs,
                        "threads": args.threads, "host_mesh": host_mesh,
                        "device_work": args.device_work,
                        "workload": workload},
                       run_scale(args.devices, args.chunks, args.sigs,
                                 workload, host_mesh, args.runs,
                                 args.threads,
                                 device_work=args.device_work))
    emit(doc, args.out, args.md)
    if doc["results"].get("cell_errors"):
        print(f"device_profile: cells failed: "
              f"{doc['results']['cell_errors']}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
