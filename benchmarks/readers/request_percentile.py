"""A percentile, over every request of the window, of the time from
handing the request to the entry point until its answer is on the host."""

import stats


def read(win, q):
    return 1e3 * stats.percentile([r["t1"] - r["t0"] for r in win.requests],
                                  q)
