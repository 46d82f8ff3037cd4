#!/usr/bin/env python3
"""Hand tool: many seeds of one cell in ONE process (set-up is minutes, so a
dozen seeds share one warm-up), and the control in the program's place.

    python3 benchmarks/seeds.py --workload NAME --seeds 11 12 13 ... \
        --seconds 6 --control-seeds 11 12 13

Each seed builds its own data, runs a short window on the timed path and
compares it; for a control seed (one of the seeds) the same window is
compared a second time with the reference's control answering in the
program's place, which has to come out NOT correct. One
JSON line per run on standard output, a summary as the last line. The
benchmark's own runs never call this. A TPU is required.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)

    import harness
    from tendermint_tpu.libs.compilecache import enable_compile_cache

    enable_compile_cache()
    device = harness.device_info()
    if device["platform"] != "tpu":
        print(f"seeds.py: needs a TPU, jax found {device}", file=sys.stderr)
        return 2
    bad = []
    for seed in args.seeds:
        with_control = seed in args.control_seeds
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               time.perf_counter(),
                               control="also" if with_control else False)
        line = {"seed": seed, "correct": out["correct"],
                "attempted": out["attempted"], "failed": out["failed"],
                "compared": out["compared"],
                "platform": device["platform"], "metrics": out["metrics"]}
        if not out["correct"]:
            bad.append(("sound run not correct", seed))
        if with_control:
            ctl = out["compared_control"]
            line["compared_control"] = ctl
            line["control_correct"] = all(v["value"] <= v["limit"]
                                          for v in ctl.values())
            if line["control_correct"]:
                bad.append(("control correct", seed))
        print(json.dumps(line), flush=True)
    print(json.dumps({"summary": True, "workload": args.workload,
                      "seeds": args.seeds,
                      "control_seeds": args.control_seeds,
                      "unexpected": bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
