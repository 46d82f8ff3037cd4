#!/usr/bin/env python3
"""The CPU rehearsal: every cell's control flow at a tiny size, on whatever
backend jax finds. It proves paths, arguments, counters and the comparison;
it prints counts and verdicts and NO time, rate or share: a number from a
CPU run is never written under the name of a device metric.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py [--workload NAME]

Untraced only: the trace reduction reads the TPU runtime's events, which no
other backend writes (tests/test_device_trace.py holds it to a trace
recorded on the chip).

Exit 0 when every rehearsed cell came out correct.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

#: tiny sizes; keys of a cell's configuration or traffic file
SMALL = {"validators": 256, "heavy_validators": 48, "blocks": 32,
         "pool_commits": 4, "check_sample": 2}


def small_for(workload: str) -> dict:
    """``SMALL`` as a cap: a size the cell's own files already hold below it
    stays (``vals150`` rehearses at its own 150 equal-power validators,
    not as a second copy of ``vals10000`` at 256)."""
    import harness

    cell = harness.find_cell(harness.load_benchmark(), workload)
    sizes = {**harness.load_json("configs", cell["config"] + ".json"),
             **harness.load_json("traffic", cell["traffic"] + ".json")}
    return {k: min(v, sizes[k]) for k, v in SMALL.items() if k in sizes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=25)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)

    import harness
    from tendermint_tpu.libs.compilecache import enable_compile_cache

    enable_compile_cache()
    bench = harness.load_benchmark()
    names = args.workload or [c["name"] for c in bench["workloads"]]
    ok = True
    for name in names:
        out = harness.run_cell(name, args.seed, args.seconds, False,
                               T_PROCESS_START,
                               overrides=small_for(name))
        ok = ok and out["correct"]
        print(json.dumps({
            "rehearsal": True, "workload": name, "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "platform": out["device"]["platform"],
            "metrics_that_would_print": sorted(out["metrics"]),
            "compared": out["compared"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
