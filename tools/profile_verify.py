"""Device-plane profiler for the batched verifier (VERDICT r3 weak #6:
"you can't push further without knowing where the µs/sig go").

Runs a jax.profiler trace around one sparse-stream verification and prints
the device-op time breakdown plus the host-side stage split (pack /
dispatch+transfer+compute / fetch). Device op durations come from the
profiler trace; the stage split is host wall-clock around
``block_until_ready``. Only the process that holds the chip can trace it.

Usage: python tools/profile_verify.py [--n 8192] [--chunk 2048]
"""

import argparse
import collections
import glob
import gzip
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_batch(n: int):
    from bench import build_batch as bb

    return bb(n)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--trace-dir", default="")
    args = ap.parse_args()

    import jax

    from tendermint_tpu.crypto.ed25519_jax import verify as V

    pks, msgs, sigs, _pubs = build_batch(args.n)

    # stage split (host wall clock)
    t0 = time.perf_counter()
    sp = V.prepare_sparse_stream(pks, msgs, sigs, chunk=args.chunk)
    t_pack = time.perf_counter() - t0
    path = "sparse" if sp is not None else "dense"

    out = V.batch_verify_stream(pks, msgs, sigs, chunk=args.chunk)  # compile
    assert np.asarray(out).all()
    t0 = time.perf_counter()
    out = V.batch_verify_stream(pks, msgs, sigs, chunk=args.chunk)
    t_total = time.perf_counter() - t0

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="verify-trace-")
    with jax.profiler.trace(trace_dir):
        np.asarray(V.batch_verify_stream(pks, msgs, sigs, chunk=args.chunk))

    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz")))
    if not files:
        print("no trace captured (profiler unsupported on this backend)")
        return 1
    with gzip.open(files[-1]) as f:
        doc = json.load(f)
    pids = {e["pid"]: e["args"].get("name", "")
            for e in doc["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    dev_pids = {p for p, nm in pids.items() if "TPU" in nm or "GPU" in nm
                or "/device" in nm}
    tot = collections.Counter()
    for e in doc["traceEvents"]:
        if e.get("ph") == "X" and e.get("pid") in dev_pids:
            tot[e["name"]] += e.get("dur", 0)
    dev_total_us = max(
        (d for nm, d in tot.items() if nm.startswith("jit_")), default=0)

    print(f"path: {path}   n={args.n} chunk={args.chunk}")
    print(f"host pack:          {t_pack * 1e3:8.1f} ms "
          f"({t_pack / args.n * 1e6:6.2f} us/sig)")
    print(f"end-to-end:         {t_total * 1e3:8.1f} ms "
          f"({t_total / args.n * 1e6:6.2f} us/sig)")
    print(f"device compute:     {dev_total_us / 1e3:8.1f} ms "
          f"({dev_total_us / args.n:6.2f} us/sig)")
    transfer = t_total - t_pack - dev_total_us / 1e6
    print(f"transfer+dispatch:  {transfer * 1e3:8.1f} ms (residual)")
    print("\ntop device ops:")
    for name, dur in tot.most_common(12):
        print(f"  {dur / 1e3:9.2f} ms  {name[:90]}")
    from tendermint_tpu.crypto.batch import device_threshold

    print(f"\nBatchVerifier break-even threshold: {device_threshold()} sigs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
