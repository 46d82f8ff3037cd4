"""Persistent XLA compile-cache setup with host-feature fingerprinting.

The persistent compile cache is load-bearing (the ed25519 verify kernels
take minutes to compile cold on CPU), but it carries a footgun: XLA:CPU
caches AOT-compiled machine code, and a cache directory populated on a
machine with different CPU features loads anyway — ``cpu_aot_loader``
prints a wall of "Machine type used for XLA:CPU compilation doesn't match
the machine type for execution ... could lead to execution errors such as
SIGILL" to stderr and the process may die mid-dispatch.

This module is the one place cache dirs get enabled
(:func:`enable_compile_cache`: ``JAX_COMPILATION_CACHE_DIR`` where set, else
``<checkout>/.jax_cache``). It stamps each cache
directory with a host fingerprint (machine arch + a hash of the CPU
feature flags) on first use and, when a later process finds a stamp from a
DIFFERENT host, returns a loud human-readable warning for the caller to
log at startup — instead of the risk living only in buried stderr. The
last check's outcome is kept in module state so debugdump's ``device.json``
can carry it post-mortem (:func:`status`).

Fingerprinting is advisory: any I/O failure degrades to "no warning", never
to a broken cache.
"""

from __future__ import annotations

import hashlib
import json
import os
import platform
import tempfile
import time
from typing import Dict, Optional

#: stamp file written inside the cache dir (ignored by XLA's key lookups)
MARKER_NAME = "tmtpu_host_fingerprint.json"

_status: Dict = {"cache_dir": None, "fingerprint": None, "marker": None,
                 "mismatch": None}


def _cpu_flags() -> str:
    """Sorted CPU feature flags from /proc/cpuinfo ('' when unavailable —
    e.g. macOS — which degrades to arch-only fingerprinting)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return " ".join(sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    return ""


def host_fingerprint() -> Dict:
    flags = _cpu_flags()
    return {
        "machine": platform.machine(),
        "flags_sha256": hashlib.sha256(flags.encode()).hexdigest(),
        "n_flags": len(flags.split()),
    }


def check_cache_dir(cache_dir: str) -> Optional[str]:
    """Stamp ``cache_dir`` with this host's fingerprint, or compare against
    an existing stamp. Returns a warning string when the cache was built on
    a host with different CPU features (the cpu_aot_loader SIGILL risk),
    else None."""
    fp = host_fingerprint()
    _status.update(cache_dir=cache_dir, fingerprint=fp, marker=None,
                   mismatch=None)
    marker = os.path.join(cache_dir, MARKER_NAME)
    try:
        prev = None
        if os.path.exists(marker):
            try:
                with open(marker) as f:
                    prev = json.load(f)
            except (OSError, ValueError):
                prev = None  # torn/unreadable marker: re-stamp below —
                # a broken marker must not silently disable the warning
        if prev is not None:
            _status["marker"] = prev
            if (prev.get("machine"), prev.get("flags_sha256")) != \
                    (fp["machine"], fp["flags_sha256"]):
                warn = (
                    f"persistent XLA compile cache {cache_dir!r} was built "
                    f"on a host with different CPU features (cache: "
                    f"{prev.get('machine')}/"
                    f"{str(prev.get('flags_sha256'))[:12]}, this host: "
                    f"{fp['machine']}/{fp['flags_sha256'][:12]}) — cached "
                    "XLA:CPU AOT kernels can SIGILL at dispatch "
                    "(cpu_aot_loader); delete the cache directory to "
                    "recompile for this host")
                _status["mismatch"] = warn
                return warn
        else:
            os.makedirs(cache_dir, exist_ok=True)
            # a marker-less dir that ALREADY holds cache entries predates
            # the fingerprint (or was copied here): its origin is
            # unverifiable. Warn once,
            # then stamp with origin recorded, so a cache genuinely built
            # on this host doesn't cry wolf forever while a copied one
            # still got its one loud startup warning.
            has_entries = any(not name.startswith(MARKER_NAME)
                              for name in os.listdir(cache_dir))
            doc = dict(fp, written_unix=time.time(),
                       origin=("preexisting-unverified" if has_entries
                               else "fresh"))
            # unique tmp per process: N nodes sharing one cache
            # directory all stamp at first start, and a fixed tmp
            # path could interleave writers into a torn marker
            fd, tmp = tempfile.mkstemp(prefix=MARKER_NAME + ".",
                                       dir=cache_dir)
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, marker)
            _status["marker"] = doc
            if has_entries:
                warn = (
                    f"persistent XLA compile cache {cache_dir!r} already "
                    "holds entries but carries no host fingerprint — if it "
                    "was copied from another machine its XLA:CPU AOT "
                    "kernels can SIGILL at dispatch (cpu_aot_loader). "
                    "Stamped with THIS host's fingerprint; delete the "
                    "cache directory if it came from elsewhere")
                _status["mismatch"] = warn
                return warn
    except Exception:
        pass  # advisory only
    return None


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: one fixed path for every process of this
    checkout — benches, tests, tools and nodes alike. The directory is
    part of a cache entry's key, so a cache that moves (a node home, a
    temp name) never hits."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def _names_cpu_first(platforms: Optional[str]) -> bool:
    return (platforms or "").split(",")[0].strip() == "cpu"


def env_pins_cpu() -> bool:
    """Does ``JAX_PLATFORMS`` pin this process (and its children) to the
    CPU backend? Decidable WITHOUT importing jax — what a launcher needs:
    one that touched jax would hold the chip its children want."""
    return _names_cpu_first(os.environ.get("JAX_PLATFORMS"))


def _pinned_to_cpu() -> bool:
    """Does this process run XLA:CPU as its backend? Read from the
    platform pin (``jax_platforms``: the variable as jax read it, or a
    later config update) without initializing a backend — a launcher may
    enable the cache and must still stay off the chip."""
    import jax

    return _names_cpu_first(jax.config.jax_platforms)


def enable_compile_cache(min_compile_secs: int = 2) -> Optional[str]:
    """Switch on jax's persistent compile cache — the ONE rule every
    caller uses. Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own
    handling of it stands and no directory is set in code; where it is
    not, the cache is :func:`default_cache_dir`. Launchers hand children
    that variable, nothing else.

    Runs the host-fingerprint check when this process is pinned to the
    CPU backend: only XLA:CPU entries are host-specific machine code. A
    process on an accelerator may find a cache stamped by another host
    (the checkout was copied with its ``.jax_cache``); the entries it
    reads and writes are accelerator programs, so there is nothing to
    warn about and the stamp is left as it is. Returns the mismatch
    warning for the caller to log, or None."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = placed or default_cache_dir()
    warn = None
    if _pinned_to_cpu():
        warn = check_cache_dir(cache_dir)
    else:
        _status.update(cache_dir=cache_dir, fingerprint=None, marker=None,
                       mismatch=None)
    import jax

    if not placed:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return warn


def status() -> Dict:
    """Last check's outcome (for debugdump device.json)."""
    return dict(_status)
