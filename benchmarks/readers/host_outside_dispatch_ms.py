"""Per request, the wall time the entry point spent outside the dispatch
plane: the request's wall minus the span from its first segment's start
to its last segment's end (``crypto/phases.py`` records). What is left is
the entry's own host work: sign-bytes, the columnar hint, the tally and the
scalar replay. Mean, in milliseconds, over the requests whose segments the
program's ring of 256 records still holds at the window's end."""


def read(win):
    outside = []
    for r in win.requests:
        segs = [s for s in win.segments
                if s["t0"] >= r["t0"] and s["t_end"] <= r["t1"]]
        if segs:
            span = max(s["t_end"] for s in segs) - min(s["t0"] for s in segs)
            outside.append(r["t1"] - r["t0"] - span)
    return 1e3 * sum(outside) / len(outside) if outside else None
