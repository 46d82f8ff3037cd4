"""A share, in percent, of the routing seam's counters
(``crypto/batch.py`` ``stats``) over the window: the sum of the ``part``
keys' growth over the sum of the ``whole`` keys' growth."""


def read(win, part, whole):
    def grown(keys):
        return sum(win.after["stats"][k] - win.before["stats"][k]
                   for k in keys)

    total = grown(whole)
    return 100.0 * grown(part) / total if total else None
