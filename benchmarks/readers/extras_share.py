"""A share, in percent, of sums the driver kept over the window
(``data["extras"]``): the ``part`` keys over the ``whole`` keys."""


def read(win, part, whole):
    total = sum(win.extras[k] for k in whole)
    return 100.0 * sum(win.extras[k] for k in part) / total if total else None
