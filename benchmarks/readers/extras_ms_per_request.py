"""Seconds the driver kept over the window (``data["extras"]``, the
growth of one of the program's cumulative counters), per request, in
milliseconds. Nothing where the program keeps no such counter (the
driver's extra is None)."""


def read(win, key):
    grown = win.extras.get(key)
    if grown is None or not win.requests:
        return None
    return 1e3 * grown / len(win.requests)
