"""Device circuit breaker (crypto/breaker.py): the state machine alone,
then threaded through BatchVerifier and the vote micro-batcher under
injected device faults — the PR's acceptance assertion: after N injected
consecutive failures there are ZERO device-route attempts while OPEN
(proved via metrics), the host path keeps producing identical verdicts,
and a half-open probe restores the device route once injection stops.
"""

import asyncio

import numpy as np
import pytest

from tendermint_tpu.crypto import Ed25519PrivKey
from tendermint_tpu.crypto import batch as batch_mod
from tendermint_tpu.crypto import breaker as breaker_mod
from tendermint_tpu.crypto.batch import BatchVerifier
from tendermint_tpu.crypto.breaker import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    classify_device_error,
    device_breaker,
)
from tendermint_tpu.libs.faults import InjectedFault, faults
from tendermint_tpu.libs.metrics import CryptoMetrics, Registry


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


# -- state machine -----------------------------------------------------------

def test_trips_open_after_threshold_consecutive_failures():
    cb = CircuitBreaker("t", failure_threshold=3, cooldown_s=30.0,
                        clock=FakeClock())
    assert cb.state == CLOSED
    cb.record_failure()
    cb.record_failure()
    assert cb.state == CLOSED and cb.allow()
    cb.record_failure()
    assert cb.state == OPEN
    assert not cb.allow() and cb.stats["rejections"] == 1


def test_success_resets_consecutive_count():
    cb = CircuitBreaker("t", failure_threshold=2, clock=FakeClock())
    cb.record_failure()
    cb.record_success()  # streak broken
    cb.record_failure()
    assert cb.state == CLOSED  # 1+1 non-consecutive != threshold 2


def test_half_open_single_probe_and_verdicts():
    clock = FakeClock()
    cb = CircuitBreaker("t", failure_threshold=1, cooldown_s=10.0,
                        clock=clock)
    cb.record_failure()
    assert cb.state == OPEN and not cb.allow()
    clock.t += 10.0
    # cooldown elapsed: exactly ONE probe admitted
    assert cb.allow() and cb.state == HALF_OPEN
    assert not cb.allow()  # second caller mid-probe: rejected
    # failed probe: straight back to OPEN for a fresh cooldown
    cb.record_failure()
    assert cb.state == OPEN and not cb.allow()
    clock.t += 10.0
    assert cb.allow() and cb.state == HALF_OPEN
    cb.record_success()
    assert cb.state == CLOSED and cb.allow()


def test_transition_metrics_and_state_gauge():
    m = CryptoMetrics(Registry())
    breaker_mod.set_breaker_metrics(m)
    try:
        clock = FakeClock()
        cb = CircuitBreaker("mtest", failure_threshold=1, cooldown_s=5.0,
                            clock=clock)
        cb.record_failure()
        assert m.breaker_state.value("mtest") == 1.0  # open
        clock.t += 5.0
        cb.allow()
        assert m.breaker_state.value("mtest") == 2.0  # half-open
        cb.record_success()
        assert m.breaker_state.value("mtest") == 0.0  # closed
        assert m.breaker_transitions_total.value("mtest", "closed", "open") == 1.0
        assert m.breaker_transitions_total.value("mtest", "open", "half_open") == 1.0
        assert m.breaker_transitions_total.value("mtest", "half_open", "closed") == 1.0
    finally:
        breaker_mod.set_breaker_metrics(None)


def test_classify_device_error_taxonomy():
    assert classify_device_error(InjectedFault("s")) == "injected"
    assert classify_device_error(RuntimeError("XLA compilation failed")) == \
        "compile_error"
    assert classify_device_error(RuntimeError("device wedged")) == \
        "runtime_error"


# -- BatchVerifier integration ----------------------------------------------

def _signed(n, seed=0):
    out = []
    for i in range(n):
        pk = Ed25519PrivKey.generate(bytes([(seed * 29 + i) % 251 + 1]) * 32)
        msg = f"breaker msg {i}".encode()
        out.append((pk.pub_key(), msg, pk.sign(msg)))
    return out


def _verify_cases(bv, cases, corrupt=None):
    for i, (pub, msg, sig) in enumerate(cases):
        if corrupt is not None and i == corrupt:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        bv.add(pub, msg, sig)
    return bv.verify()


@pytest.fixture
def breaker_knobs():
    """Shrink the singleton's trip/cooldown knobs for the test and restore
    them after (conftest's autouse fixture resets STATE, not tuning)."""
    thr, cd = device_breaker.failure_threshold, device_breaker.cooldown_s
    m = CryptoMetrics(Registry())
    batch_mod.set_crypto_metrics(m)
    breaker_mod.set_breaker_metrics(m)
    try:
        device_breaker.failure_threshold = 3
        device_breaker.cooldown_s = 60.0  # tests rewind _opened_at instead
        yield m
    finally:
        device_breaker.failure_threshold, device_breaker.cooldown_s = thr, cd
        batch_mod.set_crypto_metrics(None)
        breaker_mod.set_breaker_metrics(None)


def test_batch_verifier_breaker_cycle(breaker_knobs, device_standin):
    """Injected device faults → host fallback with identical verdicts →
    breaker opens (zero device attempts, via metrics) → half-open probe
    restores the device route when injection stops."""
    m = breaker_knobs
    cases = _signed(8)
    bv = BatchVerifier(backend="jax", plane="votes")
    faults.configure("device.batch_verify")  # every device attempt raises

    # 3 consecutive device failures: each falls back to host with correct
    # verdicts (one corrupted sig per batch must still be caught)
    for k in range(3):
        ok, per = _verify_cases(bv, cases, corrupt=k)
        assert not ok and per.sum() == 7 and not per[k]
        assert m.device_fallbacks_total.value("injected") == float(k + 1)
    assert device_breaker.state == OPEN

    # OPEN: zero device-route attempts — no new device routing decisions,
    # no new injected-fault fallbacks (the site is never evaluated), only
    # breaker_open fallbacks; verdicts stay correct on host
    injected_fires = faults.fires("device.batch_verify")
    for k in range(4):
        ok, per = _verify_cases(bv, cases)
        assert ok and per.all()
    assert m.routing_decisions_total.value("device", "votes") == 0.0
    assert faults.fires("device.batch_verify") == injected_fires
    assert m.device_fallbacks_total.value("breaker_open") == 4.0
    assert device_breaker.state == OPEN

    # injection stops, cooldown elapses (rewound deterministically rather
    # than slept): the half-open probe rides the device and CLOSES the
    # breaker; the device route is live again
    faults.reset()
    device_breaker._opened_at = (device_breaker._clock()
                                 - device_breaker.cooldown_s - 1.0)
    ok, per = _verify_cases(bv, cases)
    assert ok and per.all()
    assert device_breaker.state == CLOSED
    assert m.routing_decisions_total.value("device", "votes") == 1.0
    ok, per = _verify_cases(bv, cases, corrupt=2)
    assert not ok and per.sum() == 7
    assert m.routing_decisions_total.value("device", "votes") == 2.0


def test_batch_verifier_host_backend_never_touches_breaker():
    faults.configure("device.batch_verify")
    bv = BatchVerifier(backend="host")
    ok, per = _verify_cases(bv, _signed(4))
    assert ok and per.all()
    assert faults.fires("device.batch_verify") == 0
    assert device_breaker.state == CLOSED


# -- vote micro-batcher integration ------------------------------------------

def test_vote_batcher_injected_flush_falls_back_and_feeds_breaker():
    """An armed device.vote_flush site fails the flush ON the executor
    thread; every pending preverify future still resolves with the right
    verdict (host re-verify), and the shared breaker counts the failure."""
    from tendermint_tpu.crypto.vote_batcher import BatchVoteVerifier

    thr = device_breaker.failure_threshold
    device_breaker.failure_threshold = 2
    try:
        faults.configure("device.vote_flush")
        verifier = BatchVoteVerifier(min_device_batch=2, deadline_s=0.01,
                                     device_timeout_s=600.0)

        async def run():
            # fresh signatures per round — the batcher's verdict cache
            # would otherwise serve round 2 without a flush
            for round_ in range(2):
                cases = _signed(4, seed=5 + round_)
                results = await asyncio.gather(*(
                    verifier.preverify(pub, msg,
                                       sig if i != 1 else
                                       sig[:-1] + bytes([sig[-1] ^ 1]))
                    for i, (pub, msg, sig) in enumerate(cases)))
                assert results == [True, False, True, True], (round_, results)

        asyncio.run(run())
        assert verifier.stats["device_errors"] == 2
        assert verifier.stats["device_batches"] == 0
        assert device_breaker.state == OPEN
        # OPEN: the next flush never evaluates the device site
        fires = faults.fires("device.vote_flush")

        async def run_open():
            results = await asyncio.gather(*(
                verifier.preverify(pub, msg, sig)
                for pub, msg, sig in _signed(3, seed=9)))
            assert all(results)

        asyncio.run(run_open())
        assert faults.fires("device.vote_flush") == fires
        assert verifier.stats["breaker_rejections"] >= 1
    finally:
        device_breaker.failure_threshold = thr
