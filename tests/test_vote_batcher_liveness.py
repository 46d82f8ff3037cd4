"""Vote-batcher liveness: a device flush that stalls (cold XLA compile on a
fresh node, device hang) must NOT wedge consensus — the batch re-verifies on
the host within device_timeout_s and later flushes stay host-side until the
device call completes. Found via a SIGUSR1 stack dump of a localnet node
stuck at one height with every _preverify_and_forward task pending."""

import asyncio
import threading
import time

import numpy as np
import pytest

pytest.importorskip("cryptography", reason="needs the optional 'cryptography' package (absent in slim containers)")

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from tendermint_tpu import crypto
from tendermint_tpu.crypto import vote_batcher


def _mk_votes(n, seed=5):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        sk = Ed25519PrivateKey.from_private_bytes(
            bytes(rng.integers(0, 256, 32, dtype=np.uint8)))
        pub = crypto.Ed25519PubKey(sk.public_key().public_bytes_raw())
        msg = b"vote-%d" % i
        out.append((pub, msg, sk.sign(msg)))
    return out


def test_stalled_device_flush_falls_back_to_host(monkeypatch):
    release = threading.Event()
    calls = []

    def stuck_kernel(pks, msgs, sigs, chunk=2048):
        calls.append(len(pks))
        release.wait(30)  # simulates a cold compile: far beyond the timeout
        return np.ones(len(pks), dtype=bool)

    import tendermint_tpu.crypto.ed25519_jax as ed_jax

    monkeypatch.setattr(ed_jax, "batch_verify_stream", stuck_kernel)

    async def run():
        bv = vote_batcher.BatchVoteVerifier(
            min_device_batch=4, deadline_s=0.005, device_timeout_s=0.3)
        votes = _mk_votes(8)
        t0 = time.monotonic()
        results = await asyncio.gather(
            *(bv.preverify(p, m, s) for p, m, s in votes))
        elapsed = time.monotonic() - t0
        assert all(results)
        assert elapsed < 5, f"preverify blocked {elapsed:.1f}s on the stall"
        assert bv.stats["device_timeouts"] == 1
        assert bv.stats["host_sigs"] == 8
        assert bv._device_warming  # device path parked until the call ends

        # while warming, new flushes go straight to host (no second stall)
        more = _mk_votes(8, seed=6)
        results = await asyncio.gather(
            *(bv.preverify(p, m, s) for p, m, s in more))
        assert all(results) and len(calls) == 1

        # device call completes -> the device path re-arms
        release.set()
        for _ in range(100):
            if not bv._device_warming:
                break
            await asyncio.sleep(0.05)
        assert not bv._device_warming

    asyncio.run(run())


def test_fast_device_flush_still_rides_device(monkeypatch):
    def instant_kernel(pks, msgs, sigs, chunk=2048):
        from tendermint_tpu.crypto import ed25519 as host

        return np.array([host.verify(p, m, s)
                         for p, m, s in zip(pks, msgs, sigs)])

    import tendermint_tpu.crypto.ed25519_jax as ed_jax

    monkeypatch.setattr(ed_jax, "batch_verify_stream", instant_kernel)

    async def run():
        bv = vote_batcher.BatchVoteVerifier(
            min_device_batch=4, deadline_s=0.005, device_timeout_s=3.0)
        votes = _mk_votes(6, seed=9)
        bad = list(votes[0])
        bad[2] = bytes(64)  # one invalid signature: verdict must be False
        votes[0] = tuple(bad)
        results = await asyncio.gather(
            *(bv.preverify(p, m, s) for p, m, s in votes))
        assert results[0] is False or results[0] == False  # noqa: E712
        assert all(results[1:])
        assert bv.stats["device_batches"] == 1
        assert bv.stats["device_timeouts"] == 0

    asyncio.run(run())
