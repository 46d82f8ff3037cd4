"""Differential tests: JAX GF(2^255-19) limb arithmetic vs Python ints."""

import random

import numpy as np
import pytest

from tendermint_tpu.crypto.ed25519_jax import field as F

P = F.P_INT


def _pack(vals):
    """list[int] -> (17, N) device array."""
    import jax.numpy as jnp

    arr = np.stack([F.int_to_limbs(v % P) for v in vals], axis=1)
    return jnp.asarray(arr)


def _unpack(a):
    arr = np.asarray(a)
    return [F.limbs_to_int(arr[:, i]) for i in range(arr.shape[1])]


# values that stress carries, folds and the canonical boundary
EDGE = [0, 1, 2, 19, 38, 2**15 - 1, 2**15, 2**255 - 20, P - 1, P - 2,
        2**254, 2**255 - 1 - 19, 12345678901234567890]


def _rand_vals(n, seed):
    rng = random.Random(seed)
    return [rng.randrange(P) for _ in range(n)]


def test_pack_roundtrip():
    vals = EDGE + _rand_vals(50, 1)
    assert _unpack(_pack(vals)) == [v % P for v in vals]


def test_bytes_to_limbs_roundtrip():
    vals = EDGE + _rand_vals(50, 2)
    b = np.stack([
        np.frombuffer((v % P).to_bytes(32, "little"), dtype=np.uint8) for v in vals
    ])
    limbs = F.bytes_to_limbs(b)
    assert [F.limbs_to_int(limbs[:, i]) for i in range(len(vals))] == [v % P for v in vals]
    back = F.limbs_to_bytes(limbs)
    assert np.array_equal(back, b)


@pytest.mark.parametrize("op,pyop", [
    (F.add, lambda a, b: (a + b) % P),
    (F.sub, lambda a, b: (a - b) % P),
    (F.mul, lambda a, b: (a * b) % P),
])
def test_binary_ops(op, pyop):
    avals = EDGE + _rand_vals(64, 3)
    bvals = list(reversed(EDGE)) + _rand_vals(64, 4)
    out = _unpack(F.freeze(op(_pack(avals), _pack(bvals))))
    assert out == [pyop(a, b) for a, b in zip(avals, bvals)]


def test_mul_chain_stays_normalized():
    # repeated muls/adds/subs must preserve the limb invariant
    vals = _rand_vals(32, 5)
    a = _pack(vals)
    acc = [v for v in vals]
    x = a
    for i in range(20):
        x = F.mul(x, a) if i % 3 else F.sub(F.add(x, x), a)
        acc = [((v * w) if i % 3 else (2 * v - w)) % P for v, w in zip(acc, vals)]
    assert _unpack(F.freeze(x)) == acc
    assert int(np.asarray(x).max()) <= 2**15 + 2


def test_neg_sqr_mul_small():
    vals = EDGE + _rand_vals(20, 6)
    a = _pack(vals)
    assert _unpack(F.freeze(F.neg(a))) == [(-v) % P for v in vals]
    assert _unpack(F.freeze(F.sqr(a))) == [v * v % P for v in vals]
    assert _unpack(F.freeze(F.mul_small(a, 121666))) == [v * 121666 % P for v in vals]


def test_freeze_canonical_unique():
    # adversarial: limb patterns with redundancy (value >= p, limbs near 2^15)
    import jax.numpy as jnp

    raws = [
        np.full(17, 2**15 - 1, dtype=np.uint32),        # 2^255 - 1
        F.int_to_limbs(P - 1) + np.array([19] + [0] * 16, dtype=np.uint32),  # == p+18
        np.full(17, 2**20, dtype=np.uint32),            # big columns
        F.P_LIMBS.copy(),                               # exactly p
        F.TWO_P_LIMBS.copy(),                           # exactly 2p
    ]
    arr = jnp.asarray(np.stack(raws, axis=1))
    out = np.asarray(F.freeze(arr))
    expect = [F.limbs_to_int(r) % P for r in raws]
    assert [F.limbs_to_int(out[:, i]) for i in range(len(raws))] == expect
    assert out.max() < 2**15


def test_inverse_and_pow():
    vals = [1, 2, P - 1] + _rand_vals(20, 7)
    a = _pack(vals)
    inv = _unpack(F.freeze(F.inverse(a)))
    assert inv == [pow(v, P - 2, P) for v in vals]
    p58 = _unpack(F.freeze(F.pow_p58(a)))
    assert p58 == [pow(v, (P - 5) // 8, P) for v in vals]


def test_eq_is_zero_parity():
    a = _pack([0, 5, P - 1])
    z = np.asarray(F.is_zero(a))
    assert list(z) == [True, False, False]
    assert list(np.asarray(F.parity(a))) == [0, 1, 0]


# --- the fused column build against the in-place build it replaced ----------
#
# field.mul used to accumulate its 34 columns with 34 in-place updates
# (``cols.at[i:i+17].add``), and field.sqr had a symmetric build of its own.
# The references below keep both, in numpy. The fused mul must give every
# limb of every product bit for bit: at the edges of the input invariant, at
# the batch shapes the kernels use, and inside ``lax.fori_loop`` (where round
# 1's roll-based build went wrong on the TPU; tests/test_tpu_device.py
# repeats the check on the chip). sqr is now mul(a, a): limb for limb the
# in-place PRODUCT of a with itself, and the same field element as the
# symmetric build (whose redundant limbs differ in about one squaring in a
# million: field.sqr says why).

LIMB_MAX = 2**15 + 57            # what carry() guarantees, and mul() accepts


def _carry_np(c):
    c = c.astype(np.uint32)
    for _ in range(2):
        lo = c & np.uint32(F.MASK)
        hi = c >> np.uint32(F.RADIX)
        c = lo + np.concatenate([hi[F.NLIMBS - 1:] * np.uint32(19),
                                 hi[:F.NLIMBS - 1]], axis=0)
    return c


def _fold_np(cols):
    return _carry_np(cols[:F.NLIMBS] + np.uint32(19) * cols[F.NLIMBS:])


def _mul_inplace(a, b):
    n = F.NLIMBS
    prod = a[:, None] * b[None]
    lo, hi = prod & np.uint32(F.MASK), prod >> np.uint32(F.RADIX)
    cols = np.zeros((2 * n,) + a.shape[1:], dtype=np.uint32)
    for i in range(n):
        cols[i:i + n] += lo[i]
        cols[i + 1:i + 1 + n] += hi[i]
    return _fold_np(cols)


def _sqr_inplace(a):
    n = F.NLIMBS
    a2 = a + a
    cols = np.zeros((2 * n,) + a.shape[1:], dtype=np.uint32)
    for i in range(n):
        row = np.concatenate([a[i:i + 1] * a[i:i + 1], a2[i:i + 1] * a[i + 1:]])
        width = n - i
        cols[2 * i:2 * i + width] += row & np.uint32(F.MASK)
        cols[2 * i + 1:2 * i + 1 + width] += row >> np.uint32(F.RADIX)
    return _fold_np(cols)


def _operands(kind, batch, seed):
    """Two (17, *batch) uint32 operands inside mul's input invariant."""
    rng = np.random.default_rng(seed)
    shape = (F.NLIMBS,) + batch
    loose = lambda: rng.integers(0, LIMB_MAX + 1, size=shape, dtype=np.uint32)
    if kind == "all_limbs_max":
        a = np.full(shape, LIMB_MAX, dtype=np.uint32)
        b = a.copy()
    elif kind == "zeros_and_ones":
        a = rng.integers(0, 2, size=shape, dtype=np.uint32)
        b = rng.integers(0, 2, size=shape, dtype=np.uint32)
        a[:, 0, 0] = 0                      # 0 · x
        b[:, 0, 1] = 0
        a[:, 0, 2] = [1] + [0] * 16         # 1 · x
        b[:, 0, 3] = 1                      # every limb 1
    elif kind == "frozen_times_loose":
        a = rng.integers(0, 2**15, size=shape, dtype=np.uint32)
        a = np.asarray(F.freeze(a))         # canonical: limbs strictly 15-bit
        b = loose()
        b[:, -1, -1] = LIMB_MAX
    else:
        assert kind == "loose"
        a, b = loose(), loose()
        a[:, 0, 0] = LIMB_MAX
        b[:, 0, 0] = LIMB_MAX
    return a, b


def _lanes_as_ints(x, k=48):
    flat = np.asarray(x).reshape(F.NLIMBS, -1)
    return [F.limbs_to_int(flat[:, i]) for i in range(min(k, flat.shape[1]))]


@pytest.mark.parametrize("batch", [(2, 128), (16, 128)],
                         ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("kind", ["all_limbs_max", "zeros_and_ones",
                                  "frozen_times_loose", "loose"])
@pytest.mark.parametrize("op", ["mul", "sqr"])
def test_fused_columns_match_inplace_build(op, kind, batch):
    import jax

    a, b = _operands(kind, batch, seed=len(kind) + batch[0])
    if op == "mul":
        got = np.asarray(jax.jit(F.mul)(a, b))
        want = _mul_inplace(a, b)
        ints = [x * y % P for x, y in zip(_lanes_as_ints(a), _lanes_as_ints(b))]
    else:
        got = np.asarray(jax.jit(F.sqr)(b))
        want = _mul_inplace(b, b)
        ints = [x * x % P for x in _lanes_as_ints(b)]
        assert np.array_equal(np.asarray(F.freeze(got)),
                              np.asarray(F.freeze(_sqr_inplace(b))))
    assert got.dtype == np.uint32 and got.shape == want.shape
    assert np.array_equal(got, want), np.argwhere(got != want)[:4]
    assert int(got.max()) <= LIMB_MAX
    assert [v % P for v in _lanes_as_ints(got)] == ints


@pytest.mark.parametrize("batch", [(2, 128), (16, 128)],
                         ids=lambda b: "x".join(map(str, b)))
def test_fused_columns_inside_fori_loop(batch):
    """Squarings (and one multiply a trip) under lax.fori_loop: the shape in
    which round 1's roll-based build miscompiled on the TPU."""
    import jax

    trips = 6
    a, b = _operands("loose", batch, seed=33)

    @jax.jit
    def chain(x, y):
        return jax.lax.fori_loop(0, trips, lambda _, v: F.mul(F.sqr(v), y), x)

    want, squares = a, a
    for _ in range(trips):
        want = _mul_inplace(_mul_inplace(want, want), b)
        squares = _mul_inplace(squares, squares)
    assert np.array_equal(np.asarray(chain(a, b)), want)
    assert np.array_equal(np.asarray(F._sqr_n(a, trips)), squares)
