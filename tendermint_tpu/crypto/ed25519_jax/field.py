"""GF(2^255-19) arithmetic for TPU, in radix-2^15 with 17 uint32 limbs.

Design notes (why this representation):

* TPU int32 multiply returns the low 32 bits only — no widening multiply and
  no fast int64. So limb products must fit in 32 bits *exactly*: with 15-bit
  limbs (plus redundancy up to 2^15+57 after the parallel carry), products
  are < 2^31.
* 17 limbs x 15 bits = 255 bits exactly, so the modular fold is aligned:
  2^255 ≡ 19 (mod p) means column j+17 of a product folds into column j with
  a single multiply by 19 — no sub-limb shifting.
* Field elements are shaped ``(17, *batch)``; the verify kernel uses
  ``(17, N//128, 128)`` so per-limb slices land on full (8,128) vregs —
  a flat ``(17, N)`` layout wastes 7/8 of every sublane on per-limb ops.
* Carries are TWO data-parallel passes over all limbs (mask/shift/roll/add),
  not a 17-step sequential chain: after column sums < 2^26, pass one leaves
  limbs < 2^16.4, pass two < 2^15+57 — inside the mul input invariant.
* A multiplication is a few large fused operations, not many small ones: on
  the v5e the time of the verify programs is the number of passes over
  (17..34, *batch) arrays in memory, so :func:`mul` sums its columns in
  registers from statically padded operands (see there for what the earlier
  in-place build cost).

Invariant: limbs entering :func:`mul` are ``<= 2^15 + 57`` (guaranteed by
:func:`carry`); products then stay < 2^31 and split column sums < 2^22.

This replaces the scalar big-int arithmetic inside Go's x/crypto ed25519
(reference crypto/ed25519/ed25519.go:148-155 → filippo.io/edwards25519 field)
with a batched formulation; semantics are tested differentially against
tendermint_tpu.crypto.ed25519.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

NLIMBS = 17
RADIX = 15
MASK = (1 << RADIX) - 1  # 0x7FFF

P_INT = 2**255 - 19

# p in limb form: limb0 = 2^15-19, limbs 1..16 = 2^15-1
P_LIMBS = np.array([MASK - 18] + [MASK] * 16, dtype=np.uint32)
# 2p in per-limb form with headroom for lazy subtraction: a + TWO_P - b >= 0
# whenever b is carry-normalized (limbs <= 2^15+57 < 2^16-38).
TWO_P_LIMBS = (P_LIMBS * 2).astype(np.uint32)


# --- host-side packing helpers (numpy) ------------------------------------

def int_to_limbs(x: int) -> np.ndarray:
    out = np.zeros(NLIMBS, dtype=np.uint32)
    for i in range(NLIMBS):
        out[i] = (x >> (RADIX * i)) & MASK
    return out


def limbs_to_int(a) -> int:
    a = np.asarray(a, dtype=np.uint64)
    return sum(int(a[i]) << (RADIX * i) for i in range(len(a)))


def bytes_to_limbs(b: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 little-endian -> (17, N) uint32 limbs of the low 255 bits.

    The caller strips/keeps bit 255 (the x-sign bit) beforehand.
    """
    b = np.asarray(b, dtype=np.uint8)
    n = b.shape[0]
    padded = np.zeros((n, 34), dtype=np.uint32)
    padded[:, :32] = b
    out = np.zeros((NLIMBS, n), dtype=np.uint32)
    for i in range(NLIMBS):
        o = RADIX * i
        byte, shift = o // 8, o % 8
        word = padded[:, byte] | (padded[:, byte + 1] << 8) | (padded[:, byte + 2] << 16)
        out[i] = (word >> shift) & MASK
    out[16] &= (1 << 15) - 1
    return out


def limbs_to_bytes(a: np.ndarray) -> np.ndarray:
    """(17, N) canonical limbs -> (N, 32) uint8 little-endian."""
    a = np.asarray(a, dtype=np.uint64)
    n = a.shape[1]
    vals = np.zeros((n, 32), dtype=np.uint8)
    acc = np.zeros(n, dtype=object)
    for i in range(NLIMBS - 1, -1, -1):
        acc = (acc << RADIX) | a[i]
    for j in range(32):
        vals[:, j] = (acc & 0xFF).astype(np.uint8)
        acc >>= 8
    return vals


# --- device constants ------------------------------------------------------

def const(x: int, batch_ndim: int = 1) -> jnp.ndarray:
    """A field constant shaped (17, 1, ..) broadcasting over the batch dims."""
    shape = (NLIMBS,) + (1,) * batch_ndim
    return jnp.asarray(int_to_limbs(x % P_INT).reshape(shape))


def _bcast(limbs_1d: np.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    shape = (NLIMBS,) + (1,) * (like.ndim - 1)
    return jnp.asarray(limbs_1d.reshape(shape))


# --- core ops --------------------------------------------------------------

def carry(c: jnp.ndarray) -> jnp.ndarray:
    """Parallel carry: column sums (< 2^26 per limb) -> limbs <= 2^15+57.

    Each pass: split every limb into low 15 bits + carry, shift the carries up
    one limb (top carry folds into limb 0 via x19). Two passes bound the
    result: pass 1 leaves limbs < 2^15 + 19*2^11; pass 2 < 2^15 + 57.
    All ops are full-width vector ops over (17, *batch) — no sequential chain.
    """
    c = c.astype(jnp.uint32)
    for _ in range(2):
        lo = c & MASK
        hi = c >> RADIX
        hi_rolled = jnp.concatenate([hi[NLIMBS - 1:] * 19, hi[:NLIMBS - 1]], axis=0)
        c = lo + hi_rolled
    return c


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return carry(a + b)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    two_p = _bcast(TWO_P_LIMBS, a)
    return carry(a + two_p - b)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    two_p = _bcast(TWO_P_LIMBS, a)
    return carry(two_p - a)


def _shifted(x: jnp.ndarray, lead: int) -> jnp.ndarray:
    """(17, *batch) -> (34, *batch): ``x`` behind ``lead`` zero limbs."""
    pad = [(lead, NLIMBS - lead, 0)] + [(0, 0, 0)] * (x.ndim - 1)
    return jax.lax.pad(x, jnp.uint32(0), pad)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field multiply. Inputs carry-normalized (limbs <= 2^15+57).

    The 34 columns of the schoolbook product are ONE fused sum: row i is
    ``a_i`` times ``b`` shifted i limbs up (a static zero-pad of the operand,
    never of a product), its low 15 bits taken in place and the rest from the
    same product one limb further; the 17 rows are added with plain ``+``.
    The compiler keeps all of it in registers, drops the padded zeros and
    spends the second multiplication more cheaply than a shifted copy of an
    intermediate: on the v5e a multiplication reads 0.55 us at 256 lanes and
    0.78 us at 2,048 (PERF.md §6, PR 33).

    It replaced an in-place build (``cols.at[i:i+17].add(lo[i])``, 34
    updates a multiplication), which compiled to a device operation per
    update, each a pass over a (34, *batch) array in memory: 33 of the 43
    operations of a multiplication, 1.44 / 3.29 us. The same lo/hi split,
    the same sums in another order (uint32 addition is associative and the
    column sums stay under 2^22), the same fold and carry: every limb of
    every product is bit-identical to that build's
    (tests/test_field_jax.py keeps it as the reference).

    That build was itself a work-around: round 1's ``jnp.roll`` column build
    miscompiled inside ``lax.fori_loop`` on the TPU backend of the time
    (valid signatures rejected on-device while CPU agreed with the host
    spec). ``roll`` is still not used, here or in :func:`carry`; what guards
    the build on the chip is tests/test_tpu_device.py (the field chain under
    ``fori_loop`` and the differential corpora) and chip_smoke.py, and
    tests/test_chip_compile.py holds the operations a multiplication and a
    whole verify program execute on the described v5e.
    """
    cols = None
    for i in range(NLIMBS):
        ai = a[i][None]
        row = ((ai * _shifted(b, i)) & MASK) + ((ai * _shifted(b, i + 1)) >> RADIX)
        cols = row if cols is None else cols + row
    # fold columns 17.. back with x19 (2^255 ≡ 19): c_j += 19*c_{j+17}
    return carry(cols[:NLIMBS] + 19 * cols[NLIMBS:])


def sqr(a: jnp.ndarray) -> jnp.ndarray:
    """Field square: ``mul(a, a)``.

    The symmetric form (153 products: a_i^2 and 2 a_i a_j for i < j) saves
    multiplications the v5e does not miss and costs rows of unequal length
    it does: fused, its variants read 0.91-7.9 us at 256 lanes where
    :func:`mul` reads 0.55, and the in-place symmetric build read 1.21
    (PERF.md §6, PR 33). The value is the same; the redundant limbs differ from the
    symmetric build's in about one squaring in a million (lo(2p) + hi(2p)
    against 2 lo(p) + 2 hi(p) before the carry), inside the same invariant,
    and every verdict reads a field element through :func:`freeze`.
    """
    return mul(a, a)


def mul_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small constant (k < 2^15)."""
    prod = a * jnp.uint32(k)
    lo = prod & MASK
    hi = prod >> RADIX
    hi_rolled = jnp.concatenate([hi[NLIMBS - 1:] * 19, hi[:NLIMBS - 1]], axis=0)
    return carry(lo + hi_rolled)


def _seq_carry(a: jnp.ndarray) -> jnp.ndarray:
    """Exact 17-step sequential carry; top carry folds into limb 0 with x19."""
    limbs = list(jnp.split(a, NLIMBS, axis=0))
    for i in range(NLIMBS - 1):
        c = limbs[i] >> RADIX
        limbs[i] = limbs[i] & MASK
        limbs[i + 1] = limbs[i + 1] + c
    top = limbs[16] >> RADIX
    limbs[16] = limbs[16] & MASK
    limbs[0] = limbs[0] + top * 19
    return jnp.concatenate(limbs, axis=0)


def freeze(a: jnp.ndarray) -> jnp.ndarray:
    """Reduce to the canonical representative in [0, p); limbs strictly 15-bit."""
    # Two parallel passes settle the bulk redundancy, then exact sequential
    # passes guarantee strictly-15-bit limbs (a purely parallel chain can
    # leave a limb >= 2^15 when a carry must walk through a run of 0x7fff
    # limbs — representation-dependent eq()/is_zero() otherwise).
    a = carry(carry(a))
    a = _seq_carry(a)
    a = _seq_carry(a)
    a = _seq_carry(a)
    a = _seq_carry(a)
    # now limbs strictly 15-bit, value < 2^255 < 2p: conditionally subtract p
    # once (sequential borrow chain, but freeze runs only a handful of times)
    p = _bcast(P_LIMBS, a)
    d = list(jnp.split(a.astype(jnp.int32) - p.astype(jnp.int32), NLIMBS, axis=0))
    for i in range(NLIMBS - 1):
        borrow = (d[i] >> 31) & 1          # 1 if negative
        d[i] = d[i] + (borrow << RADIX)
        d[i + 1] = d[i + 1] - borrow
    final_borrow = (d[16] >> 31) & 1
    d[16] = d[16] + (final_borrow << RADIX)
    diff = jnp.concatenate(d, axis=0)
    ge_p = (final_borrow == 0)             # a >= p
    return jnp.where(ge_p, diff.astype(jnp.uint32), a)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    """(*batch,) bool: a ≡ 0 (mod p)."""
    return jnp.all(freeze(a) == 0, axis=0)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(*batch,) bool: a ≡ b (mod p)."""
    return jnp.all(freeze(a) == freeze(b), axis=0)


def parity(a: jnp.ndarray) -> jnp.ndarray:
    """(*batch,) uint32: low bit of the canonical representative."""
    return freeze(a)[0] & 1


# --- exponentiation chains -------------------------------------------------

def _sqr_n(a: jnp.ndarray, n: int) -> jnp.ndarray:
    return jax.lax.fori_loop(0, n, lambda _, x: sqr(x), a)


def _pow_2250_minus_1(z: jnp.ndarray):
    """z^(2^250 - 1) plus intermediates needed by callers (ref10 chain)."""
    z2 = sqr(z)                            # 2
    z9 = mul(_sqr_n(z2, 2), z)             # 9
    z11 = mul(z9, z2)                      # 11
    z_5_0 = mul(sqr(z11), z9)              # 2^5 - 1
    z_10_0 = mul(_sqr_n(z_5_0, 5), z_5_0)  # 2^10 - 1
    z_20_0 = mul(_sqr_n(z_10_0, 10), z_10_0)
    z_40_0 = mul(_sqr_n(z_20_0, 20), z_20_0)
    z_50_0 = mul(_sqr_n(z_40_0, 10), z_10_0)
    z_100_0 = mul(_sqr_n(z_50_0, 50), z_50_0)
    z_200_0 = mul(_sqr_n(z_100_0, 100), z_100_0)
    z_250_0 = mul(_sqr_n(z_200_0, 50), z_50_0)
    return z_250_0, z11


def inverse(z: jnp.ndarray) -> jnp.ndarray:
    """z^(p-2) = z^(2^255 - 21); returns 0 for z = 0."""
    z_250_0, z11 = _pow_2250_minus_1(z)
    return mul(_sqr_n(z_250_0, 5), z11)


def pow_p58(z: jnp.ndarray) -> jnp.ndarray:
    """z^((p-5)/8) = z^(2^252 - 3)."""
    z_250_0, _ = _pow_2250_minus_1(z)
    return mul(_sqr_n(z_250_0, 2), z)
