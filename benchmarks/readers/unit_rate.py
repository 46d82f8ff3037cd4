"""All units of work (``unit``: a key of each request's ``units``) that
came back through the entry point, over the whole window's wall time: from
the window's start to the end of its last request, stalls included."""

import stats


def read(win, unit):
    done = sum(r["units"].get(unit, 0) for r in win.requests
               if not r.get("failed"))
    return stats.rate(done, win.t1 - win.t0)
