"""Vectorized BLS12-381 batch engine: lane-parallel Fq limb arithmetic.

The one genuinely data-parallel op in the aggregated-commit plane is the
aggregate-pubkey sum (many G2 points, one result), so that is what this
engine vectorizes: field elements become limb lanes in Montgomery form
(CIOS reduction, canonical < p after every op so lane equality tests are
exact), points become lane arrays, and the sum is a pad-to-power-of-two
Jacobian tree reduction whose pairwise-add round is one vectorized kernel.

Limb geometry is per backend: numpy runs 15x26-bit limbs in int64; the jax
variant runs 30x13-bit limbs in int32 because the device plane (like the
ed25519 kernels) stays inside 32-bit integers — column sums of 30 products
of 2^26 peak at 30*2^26 < 2^31.  R = 2^390 for both, so the Montgomery
constants are shared.

Routing mirrors crypto/batch.py exactly: the device attempt sits behind
`device_breaker`, raises through the armed `crypto.bls_verify` fault site,
records a phase Segment per dispatch, and on ANY failure re-runs on the
host scalar path with byte-identical verdicts while the breaker counts the
strike.  Backend selection: TMTPU_BLS_BACKEND = scalar (default) | numpy |
jax;  TMTPU_BLS_JIT=0 runs the jax backend eagerly (debug only — per-op
dispatch makes it orders of magnitude slower than the jitted rounds).

Honesty note (measured on this host, CPU XLA): per-op dispatch overhead
makes both vector backends *slower* than the scalar Python path at every
realistic validator count — they exist as the device on-ramp and are gated
off by default.
"""

from __future__ import annotations

import os

import numpy as np

from ...libs.faults import faults
from .. import phases as _phases
from ..breaker import classify_device_error, device_breaker
from . import DST_SIG, decompress_pubkey
from .curve import g2_to_affine, hash_to_g1
from .field import P
from .pairing import NEG_G2_AFF, multi_pairing_check

R_BITS = 390
R_MONT = pow(2, R_BITS, P)
R2 = pow(2, 2 * R_BITS, P)
NPRIME = (-pow(P, -1, 1 << R_BITS)) % (1 << R_BITS)

FAULT_SITE = "crypto.bls_verify"

stats = {"device_calls": 0, "host_vec_calls": 0, "scalar_calls": 0,
         "device_errors": 0, "breaker_rejections": 0}


def reset_stats() -> None:
    for k in stats:
        stats[k] = 0


class LimbCfg:
    """One limb geometry: `nlimbs` limbs of `limb` bits in `dtype` lanes."""

    def __init__(self, nlimbs: int, limb: int, dtype):
        assert nlimbs * limb == R_BITS
        self.nlimbs = nlimbs
        self.limb = limb
        self.mask = (1 << limb) - 1
        self.dtype = dtype
        self.p_limbs = self.to_limbs_np(P)
        self.nprime_limbs = self.to_limbs_np(NPRIME)
        self.r2_limbs = self.to_limbs_np(R2)

    def to_limbs_np(self, x: int) -> np.ndarray:
        return np.array([(x >> (self.limb * i)) & self.mask
                         for i in range(self.nlimbs)], dtype=self.dtype)


CFG_NP = LimbCfg(15, 26, np.int64)   # products 2^52, sums < 2^56 in int64
CFG_JAX = LimbCfg(30, 13, np.int32)  # products 2^26, sums < 2^31 in int32


def _cfg_for(backend: str) -> LimbCfg:
    return CFG_JAX if backend == "jax" else CFG_NP


def _get_xp(backend: str):
    if backend == "jax":
        import jax.numpy as jnp

        return jnp
    return np


def _acc(xp, arr, sl, val):
    if xp is np:
        arr[sl] += val
        return arr
    return arr.at[sl].add(val)


def _setrow(xp, arr, i, val):
    if xp is np:
        arr[i] = val
        return arr
    return arr.at[i].set(val)


# --- limb vectors: shape (nlimbs, n), canonical (< p), Montgomery form -----

def int_to_vl(xp, cfg, values):
    out = np.zeros((cfg.nlimbs, len(values)), dtype=cfg.dtype)
    for j, v in enumerate(values):
        for i in range(cfg.nlimbs):
            out[i, j] = (v >> (cfg.limb * i)) & cfg.mask
    return out if xp is np else xp.asarray(out)


def vl_to_int(cfg, limbs) -> list:
    a = np.asarray(limbs)
    return [sum(int(a[i, j]) << (cfg.limb * i) for i in range(cfg.nlimbs)) % P
            for j in range(a.shape[1])]


def _carry(xp, cfg, cols):
    rows = cols.shape[0]
    for i in range(rows - 1):
        c = cols[i] >> cfg.limb  # arithmetic shift: floors negatives too
        cols = _setrow(xp, cols, i, cols[i] - (c << cfg.limb))
        cols = _acc(xp, cols, i + 1, c)
    return cols


def _cond_sub_p(xp, cfg, r):
    """r < 2p, carried -> canonical r mod p (lane-wise select)."""
    pl = cfg.p_limbs[:, None] if xp is np else xp.asarray(cfg.p_limbs)[:, None]
    d = _carry(xp, cfg, r - pl)
    neg = d[cfg.nlimbs - 1] < 0
    return xp.where(neg[None, :], r, d)


def mont_mul(xp, cfg, a, b):
    n = a.shape[1]
    nl = cfg.nlimbs
    pl = cfg.p_limbs if xp is np else xp.asarray(cfg.p_limbs)
    npr = cfg.nprime_limbs if xp is np else xp.asarray(cfg.nprime_limbs)
    cols = xp.zeros((2 * nl + 1, n), dtype=cfg.dtype)
    for i in range(nl):
        cols = _acc(xp, cols, slice(i, i + nl), a[i] * b)
    cols = _carry(xp, cfg, cols)
    tlo = cols[:nl]
    mcols = xp.zeros((nl, n), dtype=cfg.dtype)
    for i in range(nl):
        mcols = _acc(xp, mcols, slice(i, nl), tlo[i] * npr[:nl - i, None])
    # carry mod 2^390: the top carry drops
    for i in range(nl - 1):
        c = mcols[i] >> cfg.limb
        mcols = _setrow(xp, mcols, i, mcols[i] - (c << cfg.limb))
        mcols = _acc(xp, mcols, i + 1, c)
    mcols = _setrow(xp, mcols, nl - 1, mcols[nl - 1] & cfg.mask)
    for i in range(nl):
        cols = _acc(xp, cols, slice(i, i + nl), mcols[i] * pl[:, None])
    cols = _carry(xp, cfg, cols)
    return _cond_sub_p(xp, cfg, cols[nl:2 * nl])


def vl_add(xp, cfg, a, b):
    return _cond_sub_p(xp, cfg, _carry(xp, cfg, a + b))


def vl_sub(xp, cfg, a, b):
    pl = cfg.p_limbs[:, None] if xp is np else xp.asarray(cfg.p_limbs)[:, None]
    d = _carry(xp, cfg, a - b)
    neg = d[cfg.nlimbs - 1] < 0
    d2 = _carry(xp, cfg, d + pl)
    return xp.where(neg[None, :], d2, d)


def to_mont(xp, cfg, a):
    r2 = cfg.r2_limbs[:, None] if xp is np else xp.asarray(cfg.r2_limbs)[:, None]
    return mont_mul(xp, cfg, a, r2 * xp.ones((1, a.shape[1]), dtype=cfg.dtype))


def from_mont(xp, cfg, a):
    one = xp.zeros_like(a)
    one = _setrow(xp, one, 0, one[0] + 1)
    return mont_mul(xp, cfg, a, one)


# --- Fq2 / G2 lanes --------------------------------------------------------
# Fq2 element = (c0, c1) limb arrays; point = (X, Y, Z) of Fq2.

def _f2mul(xp, cfg, x, y):
    a, b = x
    c, d = y
    ac = mont_mul(xp, cfg, a, c)
    bd = mont_mul(xp, cfg, b, d)
    cross = mont_mul(xp, cfg, vl_add(xp, cfg, a, b), vl_add(xp, cfg, c, d))
    return (vl_sub(xp, cfg, ac, bd),
            vl_sub(xp, cfg, vl_sub(xp, cfg, cross, ac), bd))


def _f2sqr(xp, cfg, x):
    return _f2mul(xp, cfg, x, x)


def _f2add(xp, cfg, x, y):
    return (vl_add(xp, cfg, x[0], y[0]), vl_add(xp, cfg, x[1], y[1]))


def _f2sub(xp, cfg, x, y):
    return (vl_sub(xp, cfg, x[0], y[0]), vl_sub(xp, cfg, x[1], y[1]))


def _f2dbl(xp, cfg, x):
    return _f2add(xp, cfg, x, x)


def _f2zero_mask(xp, x):
    return xp.all(x[0] == 0, axis=0) & xp.all(x[1] == 0, axis=0)


def _f2where(xp, cond, x, y):
    c = cond[None, :]
    return (xp.where(c, x[0], y[0]), xp.where(c, x[1], y[1]))


def g2_add_vec(xp, cfg, p, q):
    """Lane-wise complete Jacobian addition on E'/Fq2 (Montgomery limbs).
    Handles infinity lanes (Z == 0), doubling lanes, and P == -Q lanes."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = _f2sqr(xp, cfg, Z1)
    Z2Z2 = _f2sqr(xp, cfg, Z2)
    U1 = _f2mul(xp, cfg, X1, Z2Z2)
    U2 = _f2mul(xp, cfg, X2, Z1Z1)
    S1 = _f2mul(xp, cfg, _f2mul(xp, cfg, Y1, Z2), Z2Z2)
    S2 = _f2mul(xp, cfg, _f2mul(xp, cfg, Y2, Z1), Z1Z1)
    H = _f2sub(xp, cfg, U2, U1)
    Rr = _f2sub(xp, cfg, S2, S1)
    HH = _f2sqr(xp, cfg, H)
    HHH = _f2mul(xp, cfg, H, HH)
    V = _f2mul(xp, cfg, U1, HH)
    X3 = _f2sub(xp, cfg, _f2sub(xp, cfg, _f2sqr(xp, cfg, Rr), HHH),
                _f2dbl(xp, cfg, V))
    Y3 = _f2sub(xp, cfg, _f2mul(xp, cfg, Rr, _f2sub(xp, cfg, V, X3)),
                _f2mul(xp, cfg, S1, HHH))
    Z3 = _f2mul(xp, cfg, _f2mul(xp, cfg, Z1, Z2), H)

    # doubling lanes (H == 0, R == 0)
    A = _f2sqr(xp, cfg, X1)
    B = _f2sqr(xp, cfg, Y1)
    S = _f2dbl(xp, cfg, _f2dbl(xp, cfg, _f2mul(xp, cfg, X1, B)))
    M = _f2add(xp, cfg, _f2dbl(xp, cfg, A), A)
    Xd = _f2sub(xp, cfg, _f2sqr(xp, cfg, M), _f2dbl(xp, cfg, S))
    B2 = _f2sqr(xp, cfg, B)
    B8 = _f2dbl(xp, cfg, _f2dbl(xp, cfg, _f2dbl(xp, cfg, B2)))
    Yd = _f2sub(xp, cfg, _f2mul(xp, cfg, M, _f2sub(xp, cfg, S, Xd)), B8)
    Zd = _f2dbl(xp, cfg, _f2mul(xp, cfg, Y1, Z1))

    p_inf = _f2zero_mask(xp, Z1)
    q_inf = _f2zero_mask(xp, Z2)
    h_zero = _f2zero_mask(xp, H)
    r_zero = _f2zero_mask(xp, Rr)
    both = (~p_inf) & (~q_inf)
    dbl = both & h_zero & r_zero
    cancel = both & h_zero & (~r_zero)

    X3 = _f2where(xp, dbl, Xd, X3)
    Y3 = _f2where(xp, dbl, Yd, Y3)
    Z3 = _f2where(xp, dbl, Zd, Z3)
    zero = (xp.zeros_like(Z3[0]), xp.zeros_like(Z3[1]))
    Z3 = _f2where(xp, cancel, zero, Z3)
    X3 = _f2where(xp, q_inf, X1, X3)
    Y3 = _f2where(xp, q_inf, Y1, Y3)
    Z3 = _f2where(xp, q_inf, Z1, Z3)
    X3 = _f2where(xp, p_inf, X2, X3)
    Y3 = _f2where(xp, p_inf, Y2, Y3)
    Z3 = _f2where(xp, p_inf, Z2, Z3)
    return (X3, Y3, Z3)


_jit_add_cache: dict = {}


def _g2_add_round(backend: str, p, q, jit: bool):
    if backend == "jax" and jit:
        import jax

        lanes = int(np.asarray(p[0][0]).shape[1])
        fn = _jit_add_cache.get(lanes)
        if fn is None:
            import jax.numpy as jnp

            fn = jax.jit(lambda a, b: g2_add_vec(jnp, CFG_JAX, a, b))
            _jit_add_cache[lanes] = fn
        return fn(p, q)
    return g2_add_vec(_get_xp(backend), _cfg_for(backend), p, q)


def _points_to_lanes(xp, cfg, affs):
    """Affine int points -> Montgomery limb lanes, padded to a power of 2
    with infinity lanes."""
    n = len(affs)
    lanes = 1
    while lanes < n:
        lanes *= 2
    pad = lanes - n
    xs0 = [a[0][0] for a in affs] + [0] * pad
    xs1 = [a[0][1] for a in affs] + [0] * pad
    ys0 = [a[1][0] for a in affs] + [0] * pad
    ys1 = [a[1][1] for a in affs] + [0] * pad
    zs0 = [1] * n + [0] * pad
    zs1 = [0] * lanes

    def mk(vals):
        return to_mont(xp, cfg, int_to_vl(xp, cfg, vals))

    return ((mk(xs0), mk(xs1)), (mk(ys0), mk(ys1)), (mk(zs0), mk(zs1)))


def aggregate_pubkeys_vec(pks, backend: str = "numpy", jit: bool = True):
    """Sum the (decompressed, subgroup-checked) pubkeys with the lane engine.
    Returns the affine aggregate, or None on any invalid key / zero sum."""
    affs = []
    for pk in pks:
        q = decompress_pubkey(pk)
        if q is None:
            return None
        affs.append(q)
    if not affs:
        return None
    if len(affs) == 1:
        if backend == "jax":
            # still produce real device evidence (a breaker half-open probe
            # must not re-close on work that never touched the device): one
            # Montgomery roundtrip of the x-coordinate through device limbs
            xp = _get_xp(backend)
            cfg = _cfg_for(backend)
            x0 = affs[0][0][0]
            rt = vl_to_int(cfg, from_mont(xp, cfg, to_mont(
                xp, cfg, int_to_vl(xp, cfg, [x0]))))[0]
            if rt != x0:
                raise RuntimeError("bls device limb roundtrip mismatch")
        return affs[0]
    xp = _get_xp(backend)
    cfg = _cfg_for(backend)
    pt = _points_to_lanes(xp, cfg, affs)
    lanes = int(np.asarray(pt[0][0]).shape[1])
    while lanes > 1:
        half = lanes // 2
        left = tuple(tuple(c[:, :half] for c in comp) for comp in pt)
        right = tuple(tuple(c[:, half:] for c in comp) for comp in pt)
        pt = _g2_add_round(backend, left, right, jit)
        lanes = half
    X, Y, Z = [tuple(vl_to_int(cfg, from_mont(xp, cfg, c))[0] for c in comp)
               for comp in pt]
    if Z == (0, 0):
        return None
    return g2_to_affine((X, Y, Z))


# --- routed fast-aggregate-verify (the consensus-plane entry point) --------

def backend_from_env() -> str:
    b = os.environ.get("TMTPU_BLS_BACKEND", "scalar").strip().lower()
    return b if b in ("scalar", "numpy", "jax") else "scalar"


def _pairing_verdict(apk, msg: bytes, sig: bytes, dst: bytes) -> bool:
    from . import _decompress_sig

    s = _decompress_sig(sig)
    if apk is None or s is None:
        return False
    return multi_pairing_check([(s, NEG_G2_AFF), (hash_to_g1(msg, dst), apk)])


def fast_aggregate_verify_routed(pks, msg: bytes, sig: bytes,
                                 dst: bytes = DST_SIG,
                                 backend=None, mode: str = "full") -> bool:
    """fast_aggregate_verify with backend routing.  The jax backend is the
    device path: breaker-gated, chaos-injectable at `crypto.bls_verify`,
    phase-recorded; any failure falls back to the host scalar engine with
    an identical verdict.

    ``mode`` labels which verify_commit* entry point asked (full / light /
    trusting) — it never changes the verdict, only the telemetry: the call
    is timed into ``crypto_pairing_seconds{plane}``, counted into
    ``crypto_aggregate_verify_total{scheme,mode}``, and wrapped in a
    height-tagged ``agg_verify`` tracer span so trace_merge/stage
    breakdowns can split ed25519 vs bls12381 commits."""
    import time as _time

    from ...libs.trace import tracer

    plane, height = _phases.context()
    span_args = {"scheme": "bls12381", "mode": mode, "n_signers": len(pks)}
    if height is not None:
        span_args["height"] = height
    t0 = _time.perf_counter()
    try:
        with tracer.span("agg_verify", **span_args):
            return _routed(pks, msg, sig, dst, backend)
    finally:
        m = _phases.metrics
        if m is not None:
            try:
                m.pairing_seconds.labels(plane or "aggsig").observe(
                    _time.perf_counter() - t0)
                m.aggregate_verify_total.labels("bls12381", mode).inc()
            except Exception:
                pass


def _routed(pks, msg: bytes, sig: bytes, dst: bytes, backend) -> bool:
    from . import fast_aggregate_verify  # scalar reference path

    if backend is None:
        backend = backend_from_env()
    if not pks:
        return False
    if backend == "jax" and not device_breaker.allow():
        stats["breaker_rejections"] += 1
        backend = "scalar"
    if backend == "jax":
        jit = os.environ.get("TMTPU_BLS_JIT", "1") != "0"
        n = len(pks)
        rec = _phases.Segment(sigs=n, chunk=n, device="bls-apk",
                              plane="aggsig")
        try:
            faults.inject(FAULT_SITE)
            rec.begin().pack_done()
            apk = aggregate_pubkeys_vec(pks, backend="jax", jit=jit)
            rec.dispatched().fetched()
            stats["device_calls"] += 1
            device_breaker.record_success()
        except Exception as e:
            rec.abandon()
            classify_device_error(e)  # normalizes the strike class for logs
            device_breaker.record_failure()
            stats["device_errors"] += 1
            _phases.count_host("aggsig", n)
            return fast_aggregate_verify(pks, msg, sig, dst=dst)
        return _pairing_verdict(apk, msg, sig, dst)
    if backend == "numpy":
        stats["host_vec_calls"] += 1
        return _pairing_verdict(aggregate_pubkeys_vec(pks, backend="numpy"),
                                msg, sig, dst)
    stats["scalar_calls"] += 1
    return fast_aggregate_verify(pks, msg, sig, dst=dst)


def _self_check(n: int = 5) -> bool:
    """numpy lane engine agrees with the scalar spec on an n-key aggregate."""
    from . import aggregate_pubkeys, sk_from_seed, sk_to_pk

    pks = [sk_to_pk(sk_from_seed(bytes([i]) * 4)) for i in range(1, n + 1)]
    return aggregate_pubkeys(pks) == aggregate_pubkeys_vec(pks,
                                                           backend="numpy")
