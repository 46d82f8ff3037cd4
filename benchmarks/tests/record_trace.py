#!/usr/bin/env python3
"""Hand tool, on the chip: record the small trace that
``test_device_trace.py`` checks the reduction against.

    python3 benchmarks/tests/record_trace.py [--device-plane]

This records the runtime's events from a toy program with the cells'
structure: a jitted function named like the verify kernels, a loop inside
it, two executions a "request", host sleeps between requests. It goes
through the harness's own ``Probe``, calibration runs included.
``--device-plane`` records the device's own plane beside them
(``tpu_trace_mode`` TRACE_ONLY_XLA), as the checked-in one has, so the test
can hold the runtime's events against the chip's own record. Output: ``chiprun_out/recorded_trace/v5e_small.xplane.pb`` and
``v5e_small.json`` (spans, the traced window, made-up segment records and
the reduced numbers); move both to ``benchmarks/tests/data/``.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    import harness

    if "--device-plane" in sys.argv[1:]:
        harness.TPU_TRACE_OPTIONS["tpu_trace_mode"] = "TRACE_ONLY_XLA"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from readers import device_trace

    @jax.jit
    def _verify_toy_kernel(x):
        def body(i, v):
            return (v * jnp.uint32(3) + i.astype(jnp.uint32)) % 65521
        return jax.lax.fori_loop(0, 200, body, x).sum()

    x = np.arange(16 * 128, dtype=np.uint32).reshape(16, 128)
    _verify_toy_kernel(x).block_until_ready()
    probe = harness.Probe(after_ticks=1, ticks=3)
    probe.prepare()
    segments = []
    probe.tick()                     # tick 0: nothing yet
    probe.tick()                     # tick 1: the profiler starts
    for _ in range(3):
        with probe.span("request"):
            for _ in range(2):
                t0 = time.perf_counter()
                out = _verify_toy_kernel(x)
                t_disp = time.perf_counter()
                out.block_until_ready()
                t_end = time.perf_counter()
                segments.append({"plane": "votes", "sigs": 2048,
                                 "chunk": 2048, "t0": t0,
                                 "pack_s": 0.0, "dispatch_s": t_disp - t0,
                                 "fetch_s": t_end - t_disp, "t_end": t_end})
            time.sleep(0.002)
        time.sleep(0.003)
        if probe.traced is None:
            probe.tick()             # the third of these stops the profiler
    spans = [s for s in probe.spans if s["t1"] is not None]
    tr = device_trace.reduce(probe.xspace, spans, probe.traced, segments)
    k = (device_trace.kernel(tr, "verify.*kernel")
         or {"executions": 0, "seconds": 0.0})
    dest = os.path.join(ROOT, "chiprun_out", "recorded_trace")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, "v5e_small.xplane.pb"), "wb") as f:
        f.write(probe.xspace)
    side = {"device": harness.device_info(), "spans": spans,
            "traced": list(probe.traced), "segments": segments,
            "expect": {"devices": tr["devices"],
                       "window_s": tr["window_s"], "busy_s": tr["busy_s"],
                       "sigs": tr["sigs"], "kernel_executions": k["executions"],
                       "kernel_seconds": k["seconds"],
                       "handover_s": tr["handover_s"],
                       "device_ops": tr["device_ops"],
                       "idle_gaps": tr["idle_gaps"]}}
    with open(os.path.join(dest, "v5e_small.json"), "w") as f:
        json.dump(side, f, indent=1)
    with open(os.path.join(dest, "structure.txt"), "w") as f:
        f.write(device_trace.describe(probe.xspace, limit=12))
    print(json.dumps({"trace_bytes": len(probe.xspace),
                      "expect": side["expect"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
