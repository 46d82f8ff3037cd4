"""BatchVerifier seam + regression tests for review findings."""

import pytest

from tendermint_tpu.crypto import Ed25519PrivKey
from tendermint_tpu.crypto import merkle
from tendermint_tpu.crypto.batch import BatchVerifier


def _signed(n, seed=0):
    out = []
    for i in range(n):
        pk = Ed25519PrivKey.generate(bytes([seed * 31 + i % 251 + 1]) * 32)
        msg = f"msg {i}".encode()
        out.append((pk.pub_key(), msg, pk.sign(msg)))
    return out


@pytest.mark.parametrize("backend", ["jax", "host"])
def test_batch_verifier_backends_agree(backend, device_standin):
    bv = BatchVerifier(backend=backend)
    cases = _signed(20)
    for pub, msg, sig in cases:
        bv.add(pub, msg, sig)
    ok, per = bv.verify()
    assert ok and per.all() and len(per) == 20
    # corrupt one
    for i, (pub, msg, sig) in enumerate(cases):
        bv.add(pub, msg, sig if i != 7 else sig[:-1] + bytes([sig[-1] ^ 1]))
    ok, per = bv.verify()
    assert not ok and per.sum() == 19 and not per[7]
    # verifier reset after verify()
    assert len(bv) == 0
    ok, per = bv.verify()
    assert ok and per.shape == (0,)
    # the jax backend reached the device seam (stood in), the host never
    assert device_standin.calls == ([20, 20] if backend == "jax" else [])


def test_standin_refuses_a_planted_signature_only_by_the_host_spec(
        device_standin):
    """The stand-in's default verdicts are the host spec's over the rows it
    unpacks from the kernel's own inputs — made to answer all-true, the
    same planted signature gets through: every test that plants one
    through ``device_standin`` leans on that default."""
    cases = _signed(20)
    bad = cases[7][2][:-1] + bytes([cases[7][2][-1] ^ 1])

    def verdicts():
        bv = BatchVerifier(backend="jax")
        for i, (pub, msg, sig) in enumerate(cases):
            bv.add(pub, msg, bad if i == 7 else sig)
        return bv.verify()

    ok, per = verdicts()
    assert not ok and per.sum() == 19 and not per[7]
    device_standin.rule = lambda pk, msg, sig: True
    ok, per = verdicts()
    assert ok and per.all()


def test_merkle_adversarial_proof_returns_false():
    """Regression (DoS): huge total/aunts must be rejected, not recurse."""
    items = [b"leaf"]
    root = merkle.hash_from_byte_slices(items)
    evil = merkle.Proof(
        total=2**5000, index=0, leaf_hash=merkle.leaf_hash(b"leaf"),
        aunts=[b"\x00" * 32] * 5000,
    )
    assert evil.verify(root, b"leaf") is False


def test_batch_verify_length_mismatch_raises():
    from tendermint_tpu.crypto.ed25519_jax import batch_verify

    with pytest.raises(ValueError):
        batch_verify([b"\x00" * 32], [], [b"\x00" * 64])
