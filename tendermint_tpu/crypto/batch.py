"""BatchVerifier — the batched verification seam the reference lacks.

The reference verifies one signature at a time (crypto/crypto.go:22-28 has
only PubKey.VerifySignature; SURVEY.md north star). Here, callers collect
(pubkey, msg, sig) tuples and verify them in one device call:

    bv = BatchVerifier()
    bv.add(pub, msg, sig)          # any number of times
    ok_all, per_item = bv.verify() # one TPU kernel launch

A batch whose messages the caller already holds as arrays (a uniform
commit's sign-bytes, crypto/signcols.SignColumns) comes in whole through
``add_columns(pks, sigs, columns)`` in place of the ``add`` calls, and goes
to the device packer as it is: no row is built unless something reads rows.

Backends:
* "jax"  — the batched TPU/CPU-XLA kernel (ed25519_jax.batch_verify);
* "host" — scalar loop over PubKey.verify_signature (OpenSSL or pure-Python).

Decisions are byte-identical across backends (enforced by differential
tests). Default backend: "jax" when a device batch is worthwhile, "host" for
tiny batches where kernel-launch latency would dominate — the threshold is
overridable for benchmarking. Set env TMTPU_BATCH_BACKEND to pin one.
"""

from __future__ import annotations

import contextvars
import logging
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import Ed25519PubKey, PubKey
from . import phases as _phases
from ..libs.faults import faults
from ..libs.trace import tracer
from .breaker import classify_device_error, device_breaker

logger = logging.getLogger("tmtpu.batch")

# below this many signatures the host scalar loop beats a device round-trip.
# The break-even point depends on per-dispatch overhead, which differs by
# orders of magnitude between attachments — so "auto" calibrates once.
DEFAULT_DEVICE_THRESHOLD = 16
_HOST_SIGS_PER_SEC_ESTIMATE = 7000.0  # OpenSSL verify ~140 us/op
_calibrated_threshold: Optional[int] = None
# batch_verify_stream's chunk: up to this many signatures ride the one-call
# program, which packs rows; above it the stream packs from columns
STREAM_CHUNK = 2048


# routed batches must never lose to the scalar loop: bias the calibrated
# break-even up so near-threshold commits stay on host (the device win at
# the margin is ~0, the loss on a slow attachment is large)
_CALIBRATION_SAFETY = 1.25


def device_threshold() -> int:
    """Break-even batch size for the device path, measured once: dispatch
    overhead (seconds) x host verify rate. Override: TMTPU_DEVICE_THRESHOLD.

    The probe carries a fresh ~32KB payload (a ~150-sig commit's wire
    weight): a payload-free jit call measures only the fixed dispatch cost
    and underestimates a device whose transfers are slow, which once routed
    sub-threshold commits to a path slower than the scalar loop. Fresh
    random bytes per call keep any result cache out of the reading."""
    global _calibrated_threshold
    env = os.environ.get("TMTPU_DEVICE_THRESHOLD")
    if env:
        return int(env)
    if _calibrated_threshold is None:
        try:
            import time

            import jax
            import jax.numpy as jnp
            import numpy as np

            f = jax.jit(lambda x: x.astype(jnp.int32).sum())

            def _probe() -> float:
                x = np.frombuffer(os.urandom(256 * 128),
                                  dtype=np.uint8).reshape(256, 128)
                t0 = time.perf_counter()
                np.asarray(f(x))
                return time.perf_counter() - t0

            _probe()  # compile
            overhead = min(_probe(), _probe())
            _calibrated_threshold = max(
                DEFAULT_DEVICE_THRESHOLD,
                int(overhead * _HOST_SIGS_PER_SEC_ESTIMATE
                    * _CALIBRATION_SAFETY))
        except Exception as e:
            # calibration failure is routing advice, not correctness: fall
            # back to the static default — but say so, a silent except here
            # once hid a broken device path for a whole bench run
            logger.warning("device-threshold calibration failed (%s); "
                           "using default %d", e, DEFAULT_DEVICE_THRESHOLD)
            _calibrated_threshold = DEFAULT_DEVICE_THRESHOLD
    return _calibrated_threshold


# verdicts precomputed by a wider batching scope (e.g. the light client's
# chain-batched verifier): (pk_bytes, msg, sig) -> bool. Consulted before any
# dispatch so an enclosing batch costs ONE device call total. A batch the
# map answers in part verifies only the rest (routed by the whole batch's
# size) and writes those verdicts into the map.
precomputed_verdicts: "contextvars.ContextVar[Optional[Dict]]" = \
    contextvars.ContextVar("tmtpu_precomputed_verdicts", default=None)


def precompute(items: Sequence[Tuple[PubKey, bytes, bytes]],
               plane: str = "light", backend: Optional[str] = None,
               device_threshold: Optional[int] = None
               ) -> Dict[Tuple[bytes, bytes, bytes], bool]:
    """Verify ``(pub, msg, sig)`` tuples in ONE batched call and return the
    verdict map shaped for :data:`precomputed_verdicts` — the entry point a
    wider batching scope (the light-serving coalescer, the chain-batched
    verifier) uses to fold many independent verifications into a single
    device dispatch, then replay exact scalar semantics against the map."""
    bv = BatchVerifier(backend=backend, device_threshold=device_threshold,
                       plane=plane)
    for pub, msg, sig in items:
        bv.add(pub, msg, sig)
    _, verdicts = bv.verify()
    return {(items[i][0].bytes(), items[i][1], items[i][2]):
            bool(verdicts[i]) for i in range(len(items))}

# routing observability (VERDICT r3: batch sizes / routing decisions were
# invisible): cumulative counters, cheap ints only
stats = {
    "host_batches": 0, "host_sigs": 0,
    "device_batches": 0, "device_sigs": 0,
    "precomputed_batches": 0, "precomputed_sigs": 0,
    # triples a precomputed map in scope did not hold: verified here
    "precomputed_misses": 0,
    "largest_batch": 0,
    # batches that came in through add_columns: verified on the device from
    # their columns, no row built / rows built after all (host route,
    # device error, precomputed lookup, one-call program)
    "columnar_batches": 0, "columnar_sigs": 0, "columnar_fallbacks": 0,
    # robustness plane: device attempts that raised (fell back to host) and
    # batches the open circuit breaker kept off the device entirely
    "device_errors": 0, "breaker_rejections": 0,
}

# CryptoMetrics hook, wired by the node (same idiom as p2p's
# set_p2p_metrics): None outside a node process, so library callers
# (tests, bench, light client as a library) pay one None-check per batch
metrics = None


def set_crypto_metrics(m) -> None:
    global metrics
    metrics = m


def _padded_slots(n: int, chunk: int = STREAM_CHUNK) -> int:
    """Device slots a batch of n occupies after padding: the stream path
    rounds up to whole chunks, the one-call path to the next power-of-two
    lane bucket (ed25519_jax.verify._pad_to). Used for the pad-waste gauge
    only — approximate is fine, wrong can't corrupt anything."""
    if n <= 0:
        return 0
    if n > chunk:
        return -(-n // chunk) * chunk
    size = 128  # LANE
    while size < n:
        size *= 2
    return size


class BatchVerifier:
    def __init__(self, backend: Optional[str] = None,
                 device_threshold: Optional[int] = None,
                 plane: str = "votes"):
        self._backend = backend or os.environ.get("TMTPU_BATCH_BACKEND") or "auto"
        if self._backend not in ("auto", "jax", "host"):
            raise ValueError(f"unknown batch backend {self._backend!r}")
        self._threshold = device_threshold
        # metric label only: which verification plane this batch serves
        # ("votes" live commits, "light" light/fast-sync, "evidence")
        self.plane = plane
        self._pks: List[bytes] = []
        # None while the batch is columnar: its messages are _columns alone
        self._msgs: Optional[List[bytes]] = []
        self._sigs: List[bytes] = []
        self._non_ed25519: List[Tuple[int, PubKey]] = []
        self._columns = None

    def __len__(self) -> int:
        return len(self._pks)

    def add_columns(self, pks: List[bytes], sigs: List[bytes],
                    columns) -> None:
        """The columnar way in: a whole batch of ed25519 signatures at once
        — raw 32-byte keys, signatures, and the messages as
        crypto/signcols.SignColumns, all aligned — in place of one ``add``
        a row. The lists are taken as they are (not copied, never written
        to). Into a batch that already holds rows, or followed by ``add``,
        it degrades to rows."""
        if len(pks) != len(sigs) or len(pks) != len(columns):
            raise ValueError("add_columns: keys, signatures and columns "
                             "must align")
        if self._pks:
            self._rows_form()
            self._pks.extend(pks)
            self._msgs.extend(columns.rows())
            self._sigs.extend(sigs)
            self._columns = None
        else:
            self._pks, self._msgs, self._sigs = pks, None, sigs
            self._columns = columns

    def _rows_form(self) -> None:
        """A columnar batch becomes lists of its own with rows built."""
        if self._msgs is None:
            self._pks, self._sigs = list(self._pks), list(self._sigs)
            self._msgs = self._columns.rows()
            self._columns = None

    def set_columns(self, columns) -> None:
        """Columnar sign-bytes (crypto/signcols.SignColumns) aligned 1:1
        with the rows added so far — a packing HINT for the device path
        (skips per-segment structure re-discovery). Rows must reconstruct
        byte-identically to the added msgs; verdicts cannot change either
        way. Cleared by verify() with the rest of the batch."""
        self._columns = columns

    def add(self, pub: PubKey, msg: bytes, sig: bytes) -> None:
        if self._msgs is None:
            self._rows_form()
        if not isinstance(pub, Ed25519PubKey):
            # rare key types verify on host; remember position for the verdict
            self._non_ed25519.append((len(self._pks), pub))
        self._pks.append(pub.bytes())
        self._msgs.append(msg)
        self._sigs.append(sig)

    def verify(self) -> Tuple[bool, np.ndarray]:
        """-> (all_valid, per-item bool array). Resets the collected batch."""
        pks, msgs, sigs = self._pks, self._msgs, self._sigs
        non_ed = self._non_ed25519
        columns = self._columns
        self._pks, self._msgs, self._sigs, self._non_ed25519 = [], [], [], []
        self._columns = None
        n = len(pks)
        if n == 0:
            return True, np.zeros(0, dtype=bool)
        came_columnar = msgs is None

        def rows() -> List[bytes]:
            # a columnar batch's messages as bytes, for whatever reads rows
            nonlocal msgs
            if msgs is None:
                msgs = columns.rows()
            return msgs

        stats["largest_batch"] = max(stats["largest_batch"], n)
        pre = precomputed_verdicts.get()
        n_route = n     # the size the route is chosen by
        held = None     # (the map's verdicts, positions it lacked)
        if pre is not None:
            m = rows()
            hits = [pre.get((pks[i], m[i], sigs[i])) for i in range(n)]
            miss = [i for i, h in enumerate(hits) if h is None]
            if not miss:
                out = np.array(hits, dtype=bool)
                stats["precomputed_batches"] += 1
                stats["precomputed_sigs"] += n
                stats["columnar_fallbacks"] += came_columnar
                if metrics is not None:
                    metrics.precomputed_hits_total.labels(self.plane).inc()
                return bool(out.all()), out
            stats["precomputed_misses"] += len(miss)
            asked = [(pks[i], m[i], sigs[i]) for i in miss]
            if len(miss) < n:
                # a verdict is a function of its triple alone: the map
                # answers what it holds, and only the rest is verified,
                # on the route the whole batch would have taken
                stats["precomputed_sigs"] += n - len(miss)
                held = (np.array([bool(h) for h in hits]), miss)
                at = {i: j for j, i in enumerate(miss)}
                non_ed = [(at[i], pk) for i, pk in non_ed if i in at]
                pks = [pks[i] for i in miss]
                msgs = [m[i] for i in miss]
                sigs = [sigs[i] for i in miss]
                columns = None
                n = len(miss)

        backend = self._backend
        if backend == "auto":
            thr = (self._threshold if self._threshold is not None
                   else device_threshold())
            backend = "jax" if n_route >= thr else "host"
        if backend == "jax" and not device_breaker.allow():
            # breaker OPEN: zero device attempts until the cooldown admits a
            # half-open probe; the host path keeps verifying meanwhile
            backend = "host"
            stats["breaker_rejections"] += 1
            if metrics is not None:
                metrics.device_fallbacks_total.labels("breaker_open").inc()

        non_ed_idx = {i: pk for i, pk in non_ed}

        def _host_verify() -> np.ndarray:
            m = rows()
            res = np.zeros(n, dtype=bool)
            for i in range(n):
                pub = non_ed_idx.get(i) or Ed25519PubKey(pks[i])
                res[i] = pub.verify_signature(m[i], sigs[i])
            return res

        route = "device" if backend == "jax" else "scalar"
        t0 = time.perf_counter()
        # tracer.span is a shared no-op when disabled (one attribute check
        # inside span() plus the kwargs dict — noise next to any verify)
        with tracer.span("batch_verify", n=n, route=route,
                         plane=self.plane) as sp:
            if backend == "jax":
                try:
                    # chaos seam: an armed `device.batch_verify` site raises
                    # here, exercising the same fallback a real device error
                    # takes
                    faults.inject("device.batch_verify")
                    from .ed25519_jax import batch_verify_stream

                    # batch_verify_stream == batch_verify below one chunk;
                    # above, it scans fixed-size chunks inside one device
                    # execution
                    if not non_ed_idx:
                        # the lists go down as they are (the stream drops
                        # a hint that does not align 1:1 with the rows)
                        if n <= STREAM_CHUNK:
                            rows()  # the one-call program packs rows
                        on_device = True
                        out = batch_verify_stream(pks, msgs, sigs,
                                                  chunk=STREAM_CHUNK,
                                                  columns=columns)
                    else:
                        # rare non-ed25519 keys verify on host, verdicts
                        # merged by index; the holes break the hint
                        ed_pos = [i for i in range(n) if i not in non_ed_idx]
                        on_device = bool(ed_pos)
                        out = np.zeros(n, dtype=bool)
                        if ed_pos:
                            out[ed_pos] = batch_verify_stream(
                                [pks[i] for i in ed_pos],
                                [msgs[i] for i in ed_pos],
                                [sigs[i] for i in ed_pos],
                                chunk=STREAM_CHUNK)
                        for i, pub in non_ed_idx.items():
                            out[i] = pub.verify_signature(msgs[i], sigs[i])
                except Exception as e:
                    # a device failure never surfaces to the caller: the
                    # batch re-verifies on host (byte-identical verdicts)
                    # and the breaker remembers, so persistent failure stops
                    # paying the device attempt at all
                    reason = classify_device_error(e)
                    logger.warning(
                        "device batch verify failed (%s, n=%d, plane=%s): "
                        "%s — re-verifying on host", reason, n, self.plane, e)
                    device_breaker.record_failure()
                    stats["device_errors"] += 1
                    if metrics is not None:
                        metrics.device_fallbacks_total.labels(reason).inc()
                    route = "scalar"
                    # keep the trace honest: the span was opened with
                    # route="device" but the work below is the host path
                    sp.set(route="scalar", device_error=reason)
                    t0 = time.perf_counter()  # charge only the host verify
                    out = _host_verify()
                else:
                    if on_device:
                        # only real device evidence closes/holds the
                        # breaker: an all-non-ed25519 batch never touched
                        # the device, and letting it report success would
                        # falsely close a half-open probe
                        device_breaker.record_success()
            else:
                out = _host_verify()
            # columnar: the device verified it from its columns, no row built
            columnar = came_columnar and msgs is None
            sp.set(columnar=columnar)
        stats["device_batches" if route == "device" else "host_batches"] += 1
        stats["device_sigs" if route == "device" else "host_sigs"] += n
        if columnar:
            stats["columnar_batches"] += 1
            stats["columnar_sigs"] += n
        else:
            stats["columnar_fallbacks"] += came_columnar
        if route != "device":
            # scalar-routed (or device-fallback) batches record zero device
            # phases but still count on the device plane's ledger
            _phases.count_host(self.plane, n)
        if metrics is not None:
            elapsed = time.perf_counter() - t0
            metrics.routing_decisions_total.labels(route, self.plane).inc()
            metrics.batch_size.labels(route, self.plane).observe(n)
            metrics.verify_latency_seconds.labels(route,
                                                  self.plane).observe(elapsed)
            if route == "device":
                n_ed = n - len(non_ed_idx)  # only ed25519 rows ride the kernel
                slots = _padded_slots(n_ed)
                if slots:
                    metrics.pad_waste_ratio.labels(self.plane).set(
                        (slots - n_ed) / slots)
        if pre is not None:
            # the map learns what was verified under it: a later batch in
            # the same scope that asks for the same triple finds it there
            pre.update(zip(asked, map(bool, out)))
        if held is not None:
            verdicts, miss = held
            verdicts[miss] = out
            out = verdicts
        return bool(out.all()), out
