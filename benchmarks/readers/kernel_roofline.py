"""The verify programs' share of their roofline, in percent: the least
time the chip could take for the signatures dispatched in the traced
window (``peaks.verify_roofline_seconds``: int8-equivalent operations
counted from the algorithm over the published int8 peak, or bytes over
HBM bandwidth, whichever is larger) over the programs' device time there.
Returns nothing where the trace holds no such program."""

import peaks
from readers import device_trace


def read(win, pattern):
    k = device_trace.kernel(win.trace, pattern)
    if k is None or not k["sigs"] or k["seconds"] <= 0:
        return None
    least = peaks.verify_roofline_seconds(k["sigs"], win.device["kind"])
    return 100.0 * least["seconds"] / k["seconds"]
