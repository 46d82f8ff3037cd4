#!/usr/bin/env python3
"""chip_smoke.py — the verification plane on the attached TPU, end to end.

The quickest proof that the system still starts on the chip and gives the
host spec's answers there. ONE process (a chip belongs to one process at a
time), the entry points a user would call, sizes a deployment would call
real, all data made from ``--seed``:

1. device            jax.devices() must be a TPU, else exit at once
2. differential      adversarial corpus through batch_verify at the edges
                     of two one-call buckets and through the segmented
                     stream at 10,240: verdicts byte-identical to the host
                     spec (crypto/ed25519.py)
3. verify_commit_10k ValidatorSet.verify_commit / _light / _light_trusting
                     at 10,240 validators (auto routing): accept, and on a
                     tampered and an under-2/3 commit the host backend's
                     exact error; plus a 150-validator commit on the device
4. fast_sync_1000    a fresh node fast-syncs 3 verify windows of a
                     1,000-validator chain (BlockchainReactor window loop +
                     BlockExecutor.apply_block): source chain's app hash
5. node              the node `cmd start` builds, chip visible: /status, a
                     burst of signed stx1 txs over RPC through ingest
                     pre-verification, every acknowledged tx committed and
                     read back, every bad signature rejected

After every phase the counters the program already keeps must show that
the DEVICE did the device's work: no device error, no breaker rejection,
every breaker closed, segments labelled ``tpu:``, the routing threshold
calibrated. The production host fallback would otherwise turn a refused
kernel into "all verdicts correct" at host speed.

Each phase makes a first call (compiles) and a steady call of the same
shape, and fails if the steady call compiles. Programs compiled and
programs the persistent cache served are counted from jax's own monitoring
events. Sizes are chosen so that a cold one-chip run compiles six verify
programs and no others (everything else is a sub-second helper: the
calibration probe, jnp conversions); a second run against the same cache
compiles none of them:

    _verify_kernel 128 lanes    differential 1/127/128, ingest batches
    _verify_kernel 256 lanes    differential 255/256, 150 validators, ingest
    _verify_kernel 2048 lanes   differential 2047/2048
    sparse stream K=3 C=4       10,240 sigs: five chunks split 3 + 2
    sparse stream K=2 C=4       (stream differential and VerifyCommit* share)
    sparse stream K=8 C=96      fast-sync window: sixteen chunks split 8 + 8

``--chips 4`` runs ONLY the multi-chip phase (the multi-device pool on a
40,960-signature catch-up window vs the single-device path, then the
sharded mesh step) — the driver never passes it.

``--rehearse`` runs everything at tiny sizes on whatever backend there is,
reports instead of aborting on the platform check, and ALWAYS ends
``"ok": false`` (exit 3 when every phase passed, 1 otherwise): a CPU run
can never be taken for a chip run.

Every phase prints one JSON line; the LAST line of stdout is
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import json
import logging
import os
import shutil
import struct
import sys
import threading
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")


def _benchmark_module(name: str):
    """A module of benchmarks/ (scripts that import each other by bare
    name, so the directory goes on sys.path): the smoke shares the
    benchmark's compile counter and its fast-sync chain and replay."""
    import importlib

    bench_dir = os.path.join(HERE, "benchmarks")
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    return importlib.import_module(name)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --- corpora (shared with tests/test_tpu_device.py) -------------------------

def _keypair(rng):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    sk = Ed25519PrivateKey.from_private_bytes(rng.bytes(32))
    return sk, sk.public_key().public_bytes_raw()


def adversarial_corpus(n: int, seed: int):
    """n (pk, msg, sig) tuples: ~60% valid, the rest corrupted R, corrupted
    s, non-canonical s (s + L) and wrong message. Message lengths stay
    inside ONE SHA-512 block-count bucket (two blocks), so a batch is one
    compiled program per lane bucket."""
    from tendermint_tpu.crypto import ed25519 as host

    rng = np.random.default_rng(seed)
    pks, msgs, sigs = [], [], []
    for i in range(n):
        sk, pk = _keypair(rng)
        msg = rng.bytes(48 + int(rng.integers(0, 63)))
        sig = sk.sign(msg)
        kind = i % 10
        if kind == 6:  # corrupted R
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        elif kind == 7:  # corrupted s
            sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        elif kind == 8:  # non-canonical s (s + L)
            s = int.from_bytes(sig[32:], "little") + host.L
            sig = sig[:32] + s.to_bytes(32, "little")
        elif kind == 9:  # wrong message
            msg = msg + b"!"
        pks.append(pk)
        msgs.append(msg)
        sigs.append(sig)
    return pks, msgs, sigs


def edge_encodings():
    """Public keys every strict verifier must reject: x = 0 with the sign
    bit set (y = 1, y = p - 1) and non-canonical y >= p — under a signature
    that would verify if the key were accepted leniently."""
    from tendermint_tpu.crypto import ed25519 as host

    pks = [(y | 1 << 255).to_bytes(32, "little") for y in (1, host.P - 1)]
    pks += [y.to_bytes(32, "little") for y in (host.P, host.P + 1)]
    s = 7
    s_b = host._pt_mul(s, (host.B[0], host.B[1], 1,
                           host.B[0] * host.B[1] % host.P))
    sig = host._pt_encode(s_b) + s.to_bytes(32, "little")
    msg = b"forged".ljust(64, b".")
    return pks, [msg] * len(pks), [sig] * len(pks)


def votelike_stream_corpus(n: int, seed: int, chunk: int = 2048):
    """n vote-like rows (one template, sparse per-row diffs — the shape a
    commit has) with rejects at both ends, the middle and every segment
    boundary of the stream pipeline. -> (pks, msgs, sigs, bad index set)."""
    from tendermint_tpu.crypto.ed25519_jax import verify as V

    rng = np.random.default_rng(seed)
    base = bytes(rng.bytes(100))
    sk, pk = _keypair(rng)
    msgs = []
    for i in range(n):
        m = bytearray(base)
        m[40:48] = int(i).to_bytes(8, "little")
        msgs.append(bytes(m))
    sigs = [sk.sign(m) for m in msgs]
    bad = {0, 1, n // 2, n - 1}
    row = 0
    for size in V._segment_sizes(-(-n // chunk))[:-1]:
        row += size * chunk
        bad |= {row - 1, row, row + 1}
    bad = {i for i in bad if 0 <= i < n}
    for i in bad:
        sigs[i] = sigs[i][:32] + bytes(32)
    return [pk] * n, msgs, sigs, bad


def host_verdicts(pks, msgs, sigs) -> np.ndarray:
    """The host spec (pure-Python crypto/ed25519.py), row by row."""
    from tendermint_tpu.crypto import ed25519 as host

    return np.array([host.verify(p, m, s)
                     for p, m, s in zip(pks, msgs, sigs)], dtype=bool)


# --- counters the program already keeps -------------------------------------

def _delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            d = v - before.get(k, 0)
            out[k] = round(d, 3) if isinstance(d, float) else d
    return out


class _WarningTap(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records = []

    def emit(self, record):
        self.records.append(f"{record.name}: {record.getMessage()}")


class Smoke:
    def __init__(self, args, platform: str):
        self.args = args
        self.platform = platform          # what segments must be labelled
        self.compiles = _benchmark_module("counters").CompileCounter()
        self.failed = []
        self.warnings = _WarningTap()
        logging.getLogger("tmtpu").addHandler(self.warnings)

    # -- counters ------------------------------------------------------------

    def _snap(self) -> dict:
        from tendermint_tpu.crypto import batch, phases

        return {"compile": self.compiles.snap(), "stats": dict(batch.stats),
                "totals": phases.phase_totals()}

    def first_then_steady(self, fn):
        """First call (compiles), then a steady call of the same shape,
        which must not compile. -> (first result, steady result, timing)."""
        c0 = self.compiles.snap()
        t0 = time.perf_counter()
        first = fn()
        t1 = time.perf_counter()
        c1 = self.compiles.snap()
        steady = fn()
        t2 = time.perf_counter()
        c2 = self.compiles.snap()
        check(c2["programs"] == c1["programs"],
              f"steady call compiled {c2['programs'] - c1['programs']} "
              "program(s)")
        return first, steady, {
            "first_s": round(t1 - t0, 3), "steady_s": round(t2 - t1, 4),
            "first_programs": c1["programs"] - c0["programs"],
            "first_cache_served": c1["cache_served"] - c0["cache_served"]}

    def check_routes(self, before: dict, device_sigs: int,
                     segment_sigs: int) -> dict:
        """The device did the device's work: asserted from crypto.batch
        stats, the breakers and the crypto/phases.py segment records."""
        from tendermint_tpu.crypto import batch, phases
        from tendermint_tpu.crypto.breaker import (
            CLOSED,
            device_breaker,
            lane_breakers,
        )

        after = self._snap()
        stats = _delta(after["stats"], before["stats"])
        totals = _delta(after["totals"], before["totals"])
        check(batch.stats["device_errors"] == 0,
              f"device_errors = {batch.stats['device_errors']}")
        check(batch.stats["breaker_rejections"] == 0,
              f"breaker_rejections = {batch.stats['breaker_rejections']}")
        check(device_breaker.state == CLOSED,
              f"shared device breaker is {device_breaker.state}")
        for label, b in lane_breakers().items():
            check(b.state == CLOSED, f"lane breaker {label}: {b.state}")
        check(stats["device_sigs"] >= device_sigs,
              f"device_sigs grew by {stats['device_sigs']}, phase routed "
              f"{device_sigs} to the device")
        # verify segments only: the executor files its per-block phases
        # in the same ring (plane "exec", device "app")
        new = int(totals["segments"])
        recs = [r for r in phases.recent_segments()[-min(new, 256):]
                if r["plane"] != "exec"]
        check(recs, "no device segment recorded")
        seg_sigs = sum(r["sigs"] for r in recs)
        check(seg_sigs >= segment_sigs or new > phases.RING_CAPACITY,
              f"device segments carried {seg_sigs} sigs, phase sent "
              f"{segment_sigs}")
        labels = sorted({r["device"] for r in recs})
        for lab in labels:
            check(lab.startswith((self.platform + ":", "mesh[")),
                  f"segment on device label {lab!r}, not {self.platform}:")
        bad = [w for w in self.warnings.records if "calibration failed" in w]
        check(not bad, f"device threshold defaulted, not calibrated: {bad}")
        return {"device_sigs": stats["device_sigs"],
                "device_batches": stats["device_batches"],
                "host_sigs": stats["host_sigs"],
                "precomputed_sigs": stats["precomputed_sigs"],
                "segments": len(recs), "segment_sigs": seg_sigs,
                "device_labels": labels,
                "device_errors": batch.stats["device_errors"],
                "breaker_rejections": batch.stats["breaker_rejections"],
                "breaker": device_breaker.state}

    def phase(self, name: str, fn) -> None:
        before = self._snap()
        info = {"phase": name}
        t0 = time.perf_counter()
        try:
            info.update(fn(before) or {})
            info["ok"] = True
        except Exception as e:  # recorded: the run then ends "ok": false
            traceback.print_exc()
            info["ok"] = False
            info["error"] = f"{type(e).__name__}: {e}"[:2000]
            self.failed.append(name)
        info["seconds"] = round(time.perf_counter() - t0, 3)
        info["compile"] = _delta(self.compiles.snap(), before["compile"])
        print(json.dumps(info), flush=True)

    # -- phase 2 -------------------------------------------------------------

    def differential(self, before):
        from concurrent.futures import ThreadPoolExecutor

        from tendermint_tpu.crypto.ed25519_jax import (
            batch_verify,
            batch_verify_stream,
        )
        from tendermint_tpu.crypto.ed25519_jax import verify as V

        seed = self.args.seed
        sizes = ((1, 127, 128, 255, 256) if self.args.rehearse
                 else (1, 127, 128, 255, 256, 2047, 2048))
        n_stream = 2 * 2048 if self.args.rehearse else 10240
        check(self.args.rehearse or n_stream >= V.SEG_MIN_SIGS,
              "stream size does not reach the segmented pipeline")
        out = {"sizes": list(sizes), "stream": n_stream}
        corpora = {str(n): adversarial_corpus(n, seed + n) for n in sizes}
        stream_key = f"stream_{n_stream}"
        stream = votelike_stream_corpus(n_stream, seed + 1)
        calls = {k: (lambda c=c: np.asarray(batch_verify(*c)))
                 for k, c in corpora.items()}
        calls[stream_key] = lambda: np.asarray(
            batch_verify_stream(*stream[:3], chunk=2048))
        # ONE first call per distinct program — the first size of each lane
        # bucket, and the stream — side by side: XLA compiles off the GIL,
        # so a cold run pays its longest compile, not their sum. The
        # stream's host-spec verdicts (~40 s of pure Python) ride along.
        by_bucket = {}
        for n in sizes:
            by_bucket.setdefault(V._pad_to(n), str(n))
        first_keys = list(by_bucket.values()) + [stream_key]

        def timed(key):
            t0 = time.perf_counter()
            got = calls[key]()
            return got, time.perf_counter() - t0

        c0 = self.compiles.snap()
        with ThreadPoolExecutor(len(first_keys) + 1) as pool:
            want_stream = pool.submit(host_verdicts, *stream[:3])
            futs = {k: pool.submit(timed, k) for k in first_keys}
            first = {k: f.result() for k, f in futs.items()}
            want = {stream_key: want_stream.result()}
        c1 = self.compiles.snap()
        out["first_calls"] = {
            "programs": c1["programs"] - c0["programs"],
            "cache_served": c1["cache_served"] - c0["cache_served"],
            "seconds": {k: round(t, 3) for k, (_g, t) in first.items()}}
        expect = np.ones(n_stream, dtype=bool)
        expect[list(stream[3])] = False
        check((want[stream_key] == expect).all(),
              "host spec disagrees with the stream corpus")
        # then a steady call of every size: none may compile, and every
        # verdict, first call or steady, is the host spec's
        timings, sent = {}, 0
        for key, call in calls.items():
            n = n_stream if key == stream_key else int(key)
            if key not in want:
                want[key] = host_verdicts(*corpora[key])
                check(0 < int(want[key].sum()) < n or n == 1,
                      f"n={n}: corpus is not mixed")
            got, steady_s = timed(key)
            check(self.compiles.snap()["programs"] == c1["programs"],
                  f"steady call at {key} compiled")
            timings[key] = round(steady_s, 4)
            for g in [got] + ([first[key][0]] if key in first else []):
                sent += n
                bad = np.nonzero(g != want[key])[0]
                check(bad.size == 0, f"{key}: device disagrees with the "
                      f"host spec at rows {bad[:8].tolist()}")
        edge = edge_encodings()
        got = np.asarray(batch_verify(*edge))
        sent += len(edge[0])
        check(not got.any(), f"edge encodings accepted: {got.tolist()}")
        check((got == host_verdicts(*edge)).all(),
              "edge encodings disagree with the host spec")
        out["steady_s"] = timings
        # batch_verify* are below the BatchVerifier seam: its stats do not
        # move, the segment records do
        out["route"] = self.check_routes(before, 0, sent)
        return out

    # -- phase 3 -------------------------------------------------------------

    def verify_commit(self, before):
        from tendermint_tpu.crypto.batch import device_threshold

        n_vals = 256 if self.args.rehearse else 10240
        n_heavy = n_vals // 5 - 8     # top-stake validators, > 1/3 of power
        chain_id = "smoke-commit"
        vs, keys = make_val_set(n_vals, self.args.seed, n_heavy=n_heavy)
        commit = sign_commit(vs, keys, 100, chain_id)
        bid = commit.block_id
        trust = (1, 3)
        calls = {
            "verify_commit": lambda c: vs.verify_commit(
                chain_id, bid, 100, c),
            "verify_commit_light": lambda c: vs.verify_commit_light(
                chain_id, bid, 100, c),
            "verify_commit_light_trusting":
                lambda c: vs.verify_commit_light_trusting(chain_id, c, trust),
        }
        thr = device_threshold()
        check(n_vals >= thr, f"calibrated threshold {thr} keeps "
              f"{n_vals} signatures on the host")
        out = {"validators": n_vals, "device_threshold": thr}
        routed = 0

        # good commit: accepted, under auto routing
        _, _, t = self.first_then_steady(lambda: calls["verify_commit"](commit))
        out["verify_commit"] = t
        routed += 2 * n_vals
        for name in ("verify_commit_light", "verify_commit_light_trusting"):
            c0 = self.compiles.snap()
            t0 = time.perf_counter()
            calls[name](commit)
            out[name] = {"steady_s": round(time.perf_counter() - t0, 4)}
            check(self.compiles.snap()["programs"] == c0["programs"],
                  f"{name} compiled: its shapes should be verify_commit's")
            routed += n_vals

        # bad commits: the host backend's exact exception. The tampered
        # row sits inside every early-exit prefix (the heavy tier alone
        # passes 1/3); the under-2/3 commit lacks the heavy tier, which
        # still leaves the 1/3 a trusting check asks for.
        tampered = tamper_commit(commit, n_vals // 10)
        short = drop_signers(commit, range(n_heavy))
        n_short = n_vals - n_heavy
        for label, bad_commit, n_sigs in (("tampered", tampered, n_vals),
                                          ("short", short, n_short)):
            errors = {}
            for name, call in calls.items():
                got = _raised(lambda: call(bad_commit))
                want = _raised(lambda: _on_host(lambda: call(bad_commit)))
                accepts = label == "short" and name.endswith("trusting")
                check((got is None) == accepts,
                      f"{name} on the {label} commit: {got}")
                check(got == want, f"{name} on the {label} commit raised "
                      f"{got}, the host backend {want}")
                errors[name] = got and got[0]
                routed += n_sigs
            out[label] = {"errors": errors, "signatures": n_sigs}

        # the size real chains run, forced onto the device
        vs150, keys150 = make_val_set(150, self.args.seed + 150)
        c150 = sign_commit(vs150, keys150, 7, chain_id)
        _, _, t = self.first_then_steady(lambda: _on_backend(
            "jax", lambda: vs150.verify_commit(chain_id, c150.block_id, 7,
                                               c150)))
        out["verify_commit_150_forced_device"] = t
        routed += 2 * 150
        bad150 = tamper_commit(c150, 75)
        got = _raised(lambda: _on_backend(
            "jax", lambda: vs150.verify_commit(chain_id, bad150.block_id, 7,
                                               bad150)))
        want = _raised(lambda: _on_host(
            lambda: vs150.verify_commit(chain_id, bad150.block_id, 7,
                                        bad150)))
        check(got is not None and got == want,
              f"150 validators tampered: device {got}, host {want}")
        routed += 150
        out["route"] = self.check_routes(before, routed, routed)
        return out

    # -- phase 4 -------------------------------------------------------------

    def fast_sync(self, before):
        replay = _benchmark_module("drivers.fast_sync_replay")

        n_vals = 256 if self.args.rehearse else 1000
        windows = 2 if self.args.rehearse else 3
        n = 16 * windows
        t0 = time.perf_counter()
        data = replay.build(
            {"validators": n_vals, "power": 10, "blocks": n,
             "verify_window_pairs": 16},
            {"chain_id": "smoke-sync", "tampered_chains": []},
            self.args.seed)
        build_s = time.perf_counter() - t0
        chain = data["sound"]
        # block n + 1 carries the source state after height n
        source = chain["blocks"][n].header

        def sync():
            reactor = replay._sync(data, chain)
            st = reactor.state
            check(st.last_block_height == n,
                  f"synced to {st.last_block_height}, source is at {n}")
            check(st.app_hash == source.app_hash,
                  f"app hash {st.app_hash.hex()} != source "
                  f"{source.app_hash.hex()}")
            check(st.last_block_id == source.last_block_id,
                  "last block ID differs from the source chain's")
            return reactor.stage_breakdown()

        first, steady, t = self.first_then_steady(sync)
        # each window carries both signature planes of its 16 pairs
        # (LastCommit + light); block 1 has no LastCommit
        per_sync = n * 2 * n_vals - n_vals
        out = {"validators": n_vals, "blocks": n, "windows": windows,
               "build_chain_s": round(build_s, 2), "sync": t,
               "app_hash": source.app_hash.hex(),
               "pipelined_windows": steady["pipelined_windows"],
               "inline_windows": steady["inline_windows"]}
        out["route"] = self.check_routes(before, 2 * per_sync, 2 * per_sync)
        return out

    # -- phase 5 -------------------------------------------------------------

    def node(self, before):
        from tendermint_tpu import cmd
        from tendermint_tpu.crypto import phases
        from tendermint_tpu.crypto.batch import device_threshold

        burst = 16 if self.args.rehearse else 256
        home = os.path.join(OUT_DIR, f"node-{os.getpid()}")
        check(cmd.main(["--home", home, "init", "--chain-id",
                        "smoke-node"]) == 0, "cmd init failed")
        node = cmd.build_node(argparse.Namespace(
            home=home, p2p_laddr="tcp://127.0.0.1:0",
            rpc_laddr="tcp://127.0.0.1:0", persistent_peers="",
            proxy_app=""))
        check(node.ingest is not None, "node carries no ingest pipeline")

        def host_ingest():
            t = phases.phase_totals()
            return (t.get("host_sigs_ingest", 0),
                    t.get("host_batches_ingest", 0))

        sigs0, batches0 = host_ingest()
        try:
            out = asyncio.run(_drive_node(self, node, burst))
        finally:
            shutil.rmtree(home, ignore_errors=True)  # keep chiprun_out small
        n_signed = out["signed_txs"]
        ing = node.ingest.stats
        check(ing["batched_sigs"] == n_signed,
              f"ingest batched {ing['batched_sigs']} sigs of {n_signed}")
        host_sigs, host_batches = (a - b for a, b in
                                   zip(host_ingest(), (sigs0, batches0)))
        thr = device_threshold()
        # a batch stays on the host only below the calibrated threshold
        check(host_sigs <= host_batches * (thr - 1),
              f"{host_sigs} ingest sigs in {host_batches} host batches: a "
              f"batch of >= {thr} stayed on the host")
        on_device = n_signed - host_sigs
        check(on_device >= n_signed // 2,
              f"only {on_device} of {n_signed} ingest sigs reached the "
              "device: no device-sized batches formed")
        vb = node.consensus_state.vote_verifier.stats
        check(vb["device_timeouts"] == 0 and vb["device_errors"] == 0,
              f"vote batcher: {dict(vb)}")
        out.update(ingest_batches=ing["batches"],
                   ingest_device_sigs=on_device, ingest_host_sigs=host_sigs,
                   vote_batcher=dict(vb))
        out["route"] = self.check_routes(before, on_device, on_device)
        return out

    # -- --chips 4 -----------------------------------------------------------

    def multichip(self, before):
        import jax

        from tendermint_tpu.crypto import phases
        from tendermint_tpu.crypto.ed25519_jax import multidevice
        from tendermint_tpu.crypto.ed25519_jax.sharded import (
            batch_verify_sharded,
            make_mesh,
        )
        from tendermint_tpu.types.validator_set import (
            verify_commit_light_batched,
        )

        n_dev = len(jax.devices())
        check(n_dev >= 4, f"--chips 4 needs four devices, found {n_dev}")
        n_vals = 4096 if self.args.rehearse else 10240
        chain_id = "smoke-multichip"
        vs, keys = make_val_set(n_vals, self.args.seed)
        commits = [sign_commit(vs, keys, h, chain_id) for h in range(5, 9)]
        # tampered rows sit inside the 2/3 early-exit prefix of the replay
        commits[1] = tamper_commit(commits[1], 17)
        commits[3] = tamper_commit(commits[3], n_vals // 2)
        entries = [(vs, chain_id, c.block_id, c.height, c) for c in commits]
        n_sigs = 4 * n_vals
        pool = multidevice.pool()
        check(pool is not None and pool.engaged(n_sigs),
              f"multi-device pool not engaged for {n_sigs} signatures")
        check(len(pool.lanes) == n_dev, f"pool has {len(pool.lanes)} lanes")

        def _errs(results):
            return [None if e is None else (type(e).__name__, str(e))
                    for e in results]

        def _fresh():
            for c in commits:
                c.__dict__.pop("_sb_cache", None)

        seg0 = phases.phase_totals()["segments"]
        sigs0 = pool.stats["sigs"]
        pooled, _, t_pool = self.first_then_steady(
            lambda: (_fresh(), _errs(verify_commit_light_batched(entries)))[1])
        check(pool.stats["sigs"] - sigs0 == 2 * n_sigs,
              f"pool verified {pool.stats['sigs'] - sigs0} sigs of "
              f"{2 * n_sigs}: the single-device path ran instead")
        n_new = int(phases.phase_totals()["segments"] - seg0)
        per_lane = {}
        for r in phases.recent_segments()[-min(n_new, 256):]:
            per_lane[r["device"]] = per_lane.get(r["device"], 0) + 1
        want_labels = sorted(l.label for l in pool.lanes)
        check(sorted(per_lane) == want_labels and min(per_lane.values()) > 0,
              f"dispatch per lane {per_lane}, lanes {want_labels}")
        with multidevice.disabled():
            single, _, t_single = self.first_then_steady(
                lambda: (_fresh(),
                         _errs(verify_commit_light_batched(entries)))[1])
        check(pool.stats["sigs"] - sigs0 == 2 * n_sigs,
              "the pool ran inside multidevice.disabled()")
        check(pooled == single, f"pool {pooled} != single device {single}")
        check([e is None for e in pooled] == [True, False, True, False],
              f"window verdicts {pooled}")
        on_host = _errs(_on_host(
            lambda: (_fresh(), verify_commit_light_batched(entries))[1]))
        check(pooled == on_host, f"pool {pooled} != host backend {on_host}")

        # the sharded mesh step: verdicts + the exact voting-power tally
        sb = commits[0].vote_sign_bytes_all(chain_id)
        pks = [v.pub_key.bytes() for v in vs.validators]
        sigs = [cs.signature for cs in commits[0].signatures]
        bad = {3, n_vals // 2, n_vals - 1}
        for i in bad:
            sigs[i] = sigs[i][:32] + bytes(32)
        rng = np.random.default_rng(self.args.seed)
        powers = [int(p) for p in rng.integers(1 << 31, 1 << 40, n_vals)]
        mesh = make_mesh(4)
        (verdict, tally), _, t_mesh = self.first_then_steady(
            lambda: batch_verify_sharded(pks, sb, sigs, powers=powers,
                                         mesh=mesh))
        want = np.ones(n_vals, dtype=bool)
        want[list(bad)] = False
        check((np.asarray(verdict) == want).all(), "sharded verdicts differ")
        want_tally = sum(p for i, p in enumerate(powers) if i not in bad)
        check(tally == want_tally and want_tally > 1 << 31,
              f"sharded tally {tally} != {want_tally}")
        routed = 4 * n_sigs
        return {"signatures": n_sigs, "lanes": want_labels,
                "dispatch_per_lane": per_lane, "pool": t_pool,
                "single_device": t_single, "sharded_mesh": t_mesh,
                "tally": str(tally), "errors": pooled,
                "route": self.check_routes(before, routed,
                                           routed + 2 * n_vals)}


# --- data -------------------------------------------------------------------

def make_val_set(n_vals: int, seed: int, n_heavy: int = 0):
    """A seeded validator set in two stake tiers (real sets are skewed):
    ``n_heavy`` validators at power 30 sort first, the rest hold 10."""
    from tendermint_tpu import crypto
    from tendermint_tpu.types import Validator, ValidatorSet

    rng = np.random.default_rng(seed)
    keys, vals = {}, []
    for i in range(n_vals):
        sk, pk = _keypair(rng)
        pub = crypto.Ed25519PubKey(pk)
        keys[pub.address()] = sk
        vals.append(Validator(pub.address(), pub, 30 if i < n_heavy else 10))
    return ValidatorSet(vals), keys


def sign_commit(vs, keys, height: int, chain_id: str):
    """A canonical commit for ``height`` signed by every validator. Block
    ID and timestamps derive from the height (row i signs base + i ns, so
    rows differ in the low timestamp bytes only, as a real commit's do)."""
    import hashlib

    from tendermint_tpu.types.basic import (
        BlockID,
        BlockIDFlag,
        PartSetHeader,
        SignedMsgType,
    )
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.canonical import vote_sign_bytes_batch

    bid = BlockID(hashlib.sha256(b"smoke-block-%d" % height).digest(),
                  PartSetHeader(1, hashlib.sha256(b"smoke-parts").digest()))
    n = len(vs.validators)
    # mid-second, so every row's nanos varint has one length
    base = 1_700_000_000_500_000_000 + height * 1_000_000_000
    ts = [base + i for i in range(n)]
    sbs = vote_sign_bytes_batch(chain_id, SignedMsgType.PRECOMMIT, height, 0,
                                [bid] * n, ts)
    sigs = [CommitSig(BlockIDFlag.COMMIT, v.address, t,
                      keys[v.address].sign(sb))
            for v, t, sb in zip(vs.validators, ts, sbs)]
    return Commit(height, 0, bid, sigs)


def tamper_commit(commit, idx: int):
    """The same commit with signature ``idx`` corrupted (s flipped)."""
    import dataclasses

    from tendermint_tpu.types.block import Commit

    sigs = list(commit.signatures)
    sig = sigs[idx].signature
    sigs[idx] = dataclasses.replace(
        sigs[idx], signature=sig[:40] + bytes([sig[40] ^ 1]) + sig[41:])
    return Commit(commit.height, commit.round, commit.block_id, sigs)


def drop_signers(commit, idxs):
    """The same commit with the validators ``idxs`` absent."""
    from tendermint_tpu.types.block import Commit, CommitSig

    sigs = list(commit.signatures)
    for i in idxs:
        sigs[i] = CommitSig.new_absent()
    return Commit(commit.height, commit.round, commit.block_id, sigs)


def _raised(fn):
    """(exception type name, message) of what fn raises, or None."""
    try:
        fn()
    except Exception as e:  # compared, never swallowed
        return type(e).__name__, str(e)
    return None


def _on_backend(backend: str, fn):
    """fn under a pinned BatchVerifier backend (the program's own knob)."""
    prev = os.environ.get("TMTPU_BATCH_BACKEND")
    os.environ["TMTPU_BATCH_BACKEND"] = backend
    try:
        return fn()
    finally:
        if prev is None:
            del os.environ["TMTPU_BATCH_BACKEND"]
        else:
            os.environ["TMTPU_BATCH_BACKEND"] = prev


def _on_host(fn):
    return _on_backend("host", fn)


# --- the node's client side -------------------------------------------------

def _kv_of(tx: bytes):
    """The key and value the kvstore app derives from a tx
    (abci/example/kvstore.py deliver_tx)."""
    raw = tx.decode("utf-8", errors="replace")
    k, v = raw.split("=", 1) if "=" in raw else (raw, raw)
    return k, v


async def _drive_node(smoke: Smoke, node, burst: int) -> dict:
    from tendermint_tpu import crypto
    from tendermint_tpu.mempool.ingest import (
        make_signed_tx,
        verify_signed_tx_scalar,
    )
    from tendermint_tpu.rpc.client import HTTPClient

    seed = smoke.args.seed
    keys = [crypto.Ed25519PrivKey.generate(struct.pack(">Q", seed + i) * 4)
            for i in range(4)]

    def signed(seq: int) -> bytes:
        return make_signed_tx(keys[seq % 4],
                              b"smoke-key-%06d=value-%06d" % (seq, seq),
                              nonce=seq, fee=1)

    good = [[signed(b * burst + i) for i in range(burst)] for b in range(2)]
    bad = []
    for i in range(4):   # a flipped signature byte, a flipped payload byte
        tx = bytearray(signed(10 ** 6 + i))
        tx[-1 - i] ^= 1
        bad.append(bytes(tx))
    for tx in bad:
        check(verify_signed_tx_scalar(tx) == (False, "sig"),
              "bad tx is not a signature failure on the scalar path")
    await node.start()
    client = HTTPClient(f"http://127.0.0.1:{node.rpc_server.bound_port}")
    try:
        t0 = time.perf_counter()
        height = 0
        while height < 1:
            check(time.perf_counter() - t0 < 120, "node made no block in 120s")
            st = await client.status()
            height = int(st["sync_info"]["latest_block_height"])
            await asyncio.sleep(0.1)
        check(st["node_info"]["network"] == "smoke-node", f"/status: {st}")
        out = {"first_block_s": round(time.perf_counter() - t0, 2)}

        async def send(tx):
            return await client.call("broadcast_tx_sync",
                                     tx=base64.b64encode(tx).decode())

        async def send_burst(txs):
            return await asyncio.gather(*(send(tx) for tx in txs))

        c0 = smoke.compiles.snap()
        t0 = time.perf_counter()
        acks = [await send_burst(good[0] + bad[:2])]
        t1 = time.perf_counter()
        c1 = smoke.compiles.snap()
        acks.append(await send_burst(good[1] + bad[2:]))
        t2 = time.perf_counter()
        check(smoke.compiles.snap()["programs"] == c1["programs"],
              "steady ingest burst compiled")
        out["ingest"] = {"first_s": round(t1 - t0, 3),
                         "steady_s": round(t2 - t1, 4),
                         "first_programs": c1["programs"] - c0["programs"]}
        sent = good[0] + bad[:2] + good[1] + bad[2:]
        acked = []
        for tx, res in zip(sent, acks[0] + acks[1]):
            if tx in bad:
                # the code and log the scalar path gives (ingest.py check_tx)
                check(res["code"] == 1 and res["codespace"] == "ingest"
                      and "invalid-sig" in res["log"],
                      f"bad-signature tx answered {res}")
            else:
                check(res["code"] == 0, f"good tx refused: {res}")
                acked.append((tx, res["hash"]))
        check(len(acked) == 2 * burst, f"{len(acked)} acknowledged")
        # every acknowledged tx is committed and its key reads back
        deadline = time.perf_counter() + 120
        pending = list(acked)
        while pending:
            check(time.perf_counter() < deadline,
                  f"{len(pending)} acknowledged txs not committed in 120s")
            still = []
            for tx, h in pending:
                try:
                    res = await client.call("tx", hash=h)
                    check(int(res["height"]) > 0
                          and res["tx_result"].get("code", 0) == 0,
                          f"tx {h} committed badly: {res}")
                except Exception as e:
                    if "not found" not in str(e):
                        raise
                    still.append((tx, h))
            pending = still
            if pending:
                await asyncio.sleep(0.5)
        for tx, _h in acked:
            k, v = _kv_of(tx)
            res = await client.abci_query("", k.encode("utf-8"))
            got = base64.b64decode(res["response"].get("value") or "")
            check(got == v.encode("utf-8"), f"key {k!r} read back {got!r}")
        for tx in bad:
            k, _v = _kv_of(tx)
            res = await client.abci_query("", k.encode("utf-8"))
            check(not res["response"].get("value"),
                  "a rejected tx reached the application")
        st = await client.status()
        out.update(signed_txs=len(sent), acknowledged=len(acked),
                   rejected=len(bad), committed_and_read_back=len(acked),
                   height=int(st["sync_info"]["latest_block_height"]))
        return out
    finally:
        await client.close()
        await node.stop()


# --- main -------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from tendermint_tpu.libs.compilecache import enable_compile_cache

    warn = enable_compile_cache()
    if warn:
        print(warn, file=sys.stderr)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    info = {"phase": "device", **device, "jax": jax.__version__,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "cache_placed_by": ("JAX_COMPILATION_CACHE_DIR"
                                if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                                else "default"),
            "seed": args.seed, "chips": args.chips,
            "rehearse": args.rehearse}
    info["ok"] = device["platform"] == "tpu" and device["count"] == args.chips
    if not info["ok"] and not args.rehearse:
        # nothing ran: no line on stdout that could be read as a result
        print(f"chip_smoke: need {args.chips} TPU device(s), jax found "
              f"{device}\n{json.dumps(info)}", file=sys.stderr)
        return 1
    print(json.dumps(info), flush=True)

    os.makedirs(OUT_DIR, exist_ok=True)
    smoke = Smoke(args, device["platform"])
    if args.chips == 4:
        smoke.phase("multichip_40960", smoke.multichip)
    else:
        smoke.phase("differential", smoke.differential)
        smoke.phase("verify_commit_10k", smoke.verify_commit)
        smoke.phase("fast_sync_1000", smoke.fast_sync)
        smoke.phase("node", smoke.node)
    total = smoke.compiles.snap()
    print(json.dumps({"phase": "summary", "failed": smoke.failed,
                      "compile": {k: round(v, 3) if isinstance(v, float)
                                  else v for k, v in total.items()},
                      "warnings": smoke.warnings.records[:20]}), flush=True)
    passed = not smoke.failed
    # a rehearsal never reads as a chip run, whatever happened
    print(json.dumps({"ok": passed and not args.rehearse, "device": device}),
          flush=True)
    if args.rehearse:
        return 3 if passed else 1
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
