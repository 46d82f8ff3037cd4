"""Seconds of the program's dispatch phases (``crypto/phases.py`` totals:
``pack_s``, ``dispatch_s``, ``fetch_s``, ``wait_s``) that the window added,
per request, in milliseconds. Phase seconds are summed per thread: two
segments in flight at once both count, so the sum can exceed the wall."""


def read(win, phases):
    if not win.requests:
        return None
    grown = sum(win.after["totals"][p] - win.before["totals"][p]
                for p in phases)
    return 1e3 * grown / len(win.requests)
