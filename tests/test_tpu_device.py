"""On-device differential suite: the batch verifier vs the host spec on the
REAL accelerator backend.

Run with ``TM_ON_DEVICE=1 python -m pytest tests/test_tpu_device.py -q`` on a
machine with the chip (``python chip_smoke.py`` runs the same corpora there
as part of the end-to-end smoke).
The default suite pins CPU (see conftest.py); these tests exist because the
round-1 kernel returned *wrong answers only on the TPU backend* (a roll-based
column build in field.mul miscompiled under fori_loop) while the CPU suite was
green. Byte-identical accept/reject vs the host spec
(tendermint_tpu/crypto/ed25519.py, mirroring reference
crypto/ed25519/ed25519.go:148-155) is the framework's core claim; it must be
proven per-backend, at many batch shapes, against adversarial inputs.
"""

import os

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as host
from tendermint_tpu.crypto.ed25519_jax import batch_verify

# ONE corpus generator, shared with the chip smoke (repo root on sys.path:
# the suite runs as `python -m pytest` from there)
from chip_smoke import (
    adversarial_corpus,
    edge_encodings,
    votelike_stream_corpus,
)

ON_DEVICE = os.environ.get("TM_ON_DEVICE") == "1"

pytestmark = pytest.mark.skipif(
    not ON_DEVICE, reason="set TM_ON_DEVICE=1 to run the on-device suite"
)


def _device_is_accelerator():
    import jax

    return jax.default_backend() != "cpu"


@pytest.mark.parametrize("n", [1, 16, 20, 127, 128, 129, 1024])
def test_device_matches_host_spec(n):
    assert _device_is_accelerator(), "suite must run on the accelerator backend"
    pks, msgs, sigs = adversarial_corpus(n, seed=n)
    got = np.asarray(batch_verify(pks, msgs, sigs))
    want = np.array(
        [host.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)], dtype=bool
    )
    mismatch = np.nonzero(got != want)[0]
    assert mismatch.size == 0, f"n={n}: device disagrees at indices {mismatch[:8]}"


def test_device_rejects_x0_sign1_and_noncanonical_y():
    assert _device_is_accelerator()
    bad_pks, msgs, sigs = edge_encodings()
    got = np.asarray(batch_verify(bad_pks, msgs, sigs))
    want = np.array(
        [host.verify(p, m, s_) for p, m, s_ in zip(bad_pks, msgs, sigs)], dtype=bool
    )
    assert not got.any()
    assert (got == want).all()


def test_device_field_mul_matches_bigint():
    """Differential field-level check on-device: random mul/freeze vs python ints."""
    assert _device_is_accelerator()
    from tendermint_tpu.crypto.ed25519_jax import field as F

    rng = np.random.default_rng(3)
    n = 128
    a_int = [int(rng.integers(0, 2**63)) ** 4 % F.P_INT for _ in range(n)]
    b_int = [int(rng.integers(0, 2**63)) ** 4 % F.P_INT for _ in range(n)]
    a = np.stack([F.int_to_limbs(x) for x in a_int], axis=1).reshape(F.NLIMBS, 1, n)
    b = np.stack([F.int_to_limbs(x) for x in b_int], axis=1).reshape(F.NLIMBS, 1, n)
    out = np.asarray(F.freeze(F.mul(a, b))).reshape(F.NLIMBS, n)
    for i in range(n):
        assert F.limbs_to_int(out[:, i]) == a_int[i] * b_int[i] % F.P_INT


@pytest.mark.parametrize("sublanes", [2, 16])
def test_device_field_chain_in_fori_loop_matches_bigint(sublanes):
    """Squarings and multiplies under lax.fori_loop on the chip, at the
    kernels' batch shapes: where round 1's roll-based column build went
    wrong, and what the fused build (field.mul) has to get right."""
    assert _device_is_accelerator()
    import jax
    from tendermint_tpu.crypto.ed25519_jax import field as F

    rng = np.random.default_rng(sublanes)
    shape = (F.NLIMBS, sublanes, 128)
    a = rng.integers(0, 2**15 + 58, size=shape, dtype=np.uint32)
    b = rng.integers(0, 2**15 + 58, size=shape, dtype=np.uint32)
    a[:, 0, 0] = b[:, 0, 0] = 2**15 + 57        # the invariant's edge
    trips = 40

    @jax.jit
    def chain(x, y):
        x = jax.lax.fori_loop(0, trips, lambda _, v: F.mul(F.sqr(v), y), x)
        return F.freeze(x)

    out = np.asarray(chain(a, b)).reshape(F.NLIMBS, -1)
    af, bf = a.reshape(F.NLIMBS, -1), b.reshape(F.NLIMBS, -1)
    for i in range(0, af.shape[1], 37):
        want, y = F.limbs_to_int(af[:, i]), F.limbs_to_int(bf[:, i])
        for _ in range(trips):
            want = want * want * y % F.P_INT
        assert F.limbs_to_int(out[:, i]) == want, i


def test_device_segmented_pipeline_matches_host():
    """The segmented double-buffered stream path (the flagship 10k
    optimization) on the real chip: verdicts must be byte-identical to the
    host spec, including rejects that straddle segment boundaries."""
    assert _device_is_accelerator()
    from tendermint_tpu.crypto.ed25519_jax import verify as V

    n = max(2 * V.SEG_MIN_SIGS, 4 * 2048)
    # rejects at every real segment boundary (derived from _segment_sizes,
    # so env overrides of SEG_CHUNKS/SEG_MIN_SIGS keep the coverage honest)
    pks, msgs, sigs, bad = votelike_stream_corpus(n, seed=41)
    got = np.asarray(V.batch_verify_stream(pks, msgs, sigs, chunk=2048))
    want = np.ones(n, dtype=bool)
    want[list(bad)] = False
    mismatch = np.nonzero(got != want)[0]
    assert mismatch.size == 0, f"segmented disagrees at {mismatch[:8]}"
