"""The program's objects as they arrive: new, with nothing memoized.

The cells hand the same seeded commits and blocks to the program again and
again (signing fresh ones costs seconds), while a validator or a syncing
node meets each once. The program memoizes sign-bytes, hashes and part sets
on the objects it is given, so a reused object would serve the second
request from the first one's work. ``as_received`` builds a new object of
the same class through its public constructor, from the public fields of
the old one, and knows no name of any memo: whatever the program hangs on
an object, under whatever name, stays behind on the old one.
"""

from __future__ import annotations

import dataclasses


def as_received(obj, **replaced):
    """A new ``type(obj)`` from ``obj``'s constructor fields whose names do
    not start with an underscore; ``replaced`` overrides fields (nested
    objects that need renewing themselves)."""
    kwargs = {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)
              if f.init and not f.name.startswith("_")}
    kwargs.update(replaced)
    return type(obj)(**kwargs)
