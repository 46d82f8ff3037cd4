"""Device time of the verify programs per signature, in microseconds: the
summed durations of the programs' executions in the traced window as the
TPU runtime observed them, the measured hand-over latency taken off
(readers/device_trace.py; the executions whose label matches ``pattern``),
over the signatures of the segments dispatched in that window. Padded lanes
of a last chunk are the signatures' cost: 10,000 signatures run in 10,240
lanes."""

from readers import device_trace


def read(win, pattern):
    k = device_trace.kernel(win.trace, pattern)
    if k is None or not k["sigs"]:
        return None
    return 1e6 * k["seconds"] / k["sigs"]
