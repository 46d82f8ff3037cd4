#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. The last line of standard output is the result object; without a
TPU, or with fewer chips than the cell asks for, there is no result and the
exit code is not 0. ``benchmarks/README.md`` says how cells, configurations,
traffic, drivers and metric readers are added as files.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--requests-out", metavar="PATH",
                    help="by hand: write every request of the window, "
                    "[[start_s, length_s, units], ...], as JSON to PATH")
    args = ap.parse_args(argv)

    import harness

    try:
        bench = harness.load_benchmark()
        cell = harness.find_cell(bench, args.workload)
        # the program's one rule for the persistent compile cache:
        # JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache
        from tendermint_tpu.libs.compilecache import enable_compile_cache

        enable_compile_cache()
        device = harness.device_info()
        if device["platform"] != "tpu" or device["count"] < cell["chips"]:
            raise harness.BenchmarkError(
                f"{args.workload} needs {cell['chips']} TPU chip(s); jax "
                f"found {device}. The CPU rehearsal is "
                "benchmarks/rehearse.py")
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS_START,
                               requests_out=args.requests_out)
        harness.print_result(out)
    except (harness.BenchmarkError, ImportError, OSError) as e:
        print(f"benchmarks/run.py: no result: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
