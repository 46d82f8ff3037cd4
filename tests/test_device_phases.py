"""Device-plane phase telemetry (crypto/phases.py + the ed25519_jax
dispatcher wiring): per-segment pack/dispatch/fetch stamps tile the segment
span exactly, host-routed batches count with zero device phases, the live
plane's flushes land with plane="live", and height tags ride the seg_*
tracer spans.
The device seam is conftest's ``device_standin``: no program is built here
(the mesh's per-device series are read where the mesh program is built,
tests/test_sharded_verify.py)."""

import asyncio

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as host
from tendermint_tpu.crypto import phases
from tendermint_tpu.crypto.ed25519_jax import verify as V


def _workload(n, seed=3):
    rng = np.random.default_rng(seed)
    pks = [rng.bytes(32) for _ in range(n)]
    msgs = [rng.bytes(40) for _ in range(n)]
    sigs = [rng.bytes(63) + b"\x00" for _ in range(n)]  # s < L
    return pks, msgs, sigs


@pytest.fixture
def accept_all(device_standin):
    """The rows of these tests are not signatures: every verdict True, so
    what the assertions read is the phase plumbing."""
    device_standin.rule = lambda pk, msg, sig: True
    return device_standin


def test_segment_phases_tile_the_span(monkeypatch, device_metrics,
                                      accept_all):
    """pack_s + dispatch_s + fetch_s equals the segment's end-to-end span
    (monotonic stamps, no gaps), per-phase histograms observe once per
    segment, and the pipeline-overlap gauge lands in (0, 1]."""
    accept_all.pack_s, accept_all.dispatch_s = 0.005, 0.002
    monkeypatch.setattr(V, "SEG_MIN_SIGS", 256)
    n, chunk = 512, V.LANE  # 4 chunks -> segments [2, 2]
    out = V._verify_segmented([b"\x01" * 32] * n, [b"m"] * n,
                              [b"\x02" * 64] * n, chunk)
    assert out.all()
    recs = phases.recent_segments()
    assert len(recs) == 2
    for r in recs:
        span = r["t_end"] - r["t0"]
        assert abs(r["pack_s"] + r["dispatch_s"] + r["fetch_s"] - span) < 1e-6
        assert r["pack_s"] >= 0.004  # the stand-in's sleeps are attributed
        assert r["dispatch_s"] >= 0.001
        assert r["plane"] == "sync" and r["height"] is None
        assert r["sigs"] == 256 and r["n_segs"] == 2
    m = device_metrics
    for phase in ("pack", "dispatch", "fetch"):
        assert m.segment_phase_seconds.count_value(phase, "sync") == 2
    assert m.segment_sigs.count_value("sync") == 2
    ratio = m.pipeline_overlap_ratio.value()
    assert 0.0 < ratio <= 1.0
    tot = phases.phase_totals()
    assert tot["segments"] == 2 and tot["sigs"] == n
    assert tot["pack_s"] >= 0.008


def test_real_device_batch_records_segment(device_metrics, device_standin):
    """A one-call batch (batch_verify's own pack, dispatch and fetch, the
    kernel stood in) records one segment with nonzero pack and fetch
    phases and the real device label; in-flight drains."""
    pks, msgs, sigs = _workload(4)
    out = V.batch_verify(pks, msgs, sigs)
    # garbage sigs: verdicts False (the host spec's), phases real
    assert out.shape == (4,) and not out.any()
    assert device_standin.calls == [4]
    recs = phases.recent_segments()
    assert len(recs) == 1
    r = recs[0]
    assert r["sigs"] == 4 and r["pack_s"] > 0 and r["fetch_s"] > 0
    assert r["device"] != "host"
    m = device_metrics
    assert m.device_dispatch_total.value(r["device"]) == 1
    assert m.device_inflight.value(r["device"]) == 0


def test_height_tag_rides_tracer_spans(monkeypatch, device_metrics,
                                       accept_all):
    from tendermint_tpu.libs.trace import tracer

    monkeypatch.setattr(V, "SEG_MIN_SIGS", 256)
    tracer.clear()
    tracer.enable()
    try:
        with phases.telemetry(height=42):
            V._verify_segmented([b"\x01" * 32] * 512, [b"m"] * 512,
                                [b"\x02" * 64] * 512, V.LANE)
    finally:
        tracer.disable()
    by_name = {}
    for ev in tracer.events():
        by_name.setdefault(ev["name"], []).append(ev)
    for name in ("seg_pack", "seg_dispatch", "seg_fetch"):
        assert len(by_name.get(name, [])) == 2, name
        assert all(e["args"]["height"] == 42 for e in by_name[name])
    # spans abut: pack end == dispatch start == fetch start - dispatch dur
    ev_p, ev_d = by_name["seg_pack"][0], by_name["seg_dispatch"][0]
    assert abs(ev_p["ts"] + ev_p["dur"] - ev_d["ts"]) < 1.0  # us
    assert recs_height_all_42(phases.recent_segments())


def recs_height_all_42(recs):
    return all(r["height"] == 42 for r in recs)


def test_scalar_batches_count_with_zero_device_phases(device_metrics):
    """Host-routed (route=scalar) batches record no phase observations but
    land on the device plane's ledger as device="host"."""
    from tendermint_tpu.crypto import Ed25519PubKey
    from tendermint_tpu.crypto.batch import BatchVerifier

    pk = host.pubkey_from_seed(b"\x07" * 32)
    bv = BatchVerifier(backend="host", plane="light")
    bv.add(Ed25519PubKey(pk), b"msg", b"\x00" * 64)
    all_ok, out = bv.verify()
    assert not all_ok and not out[0]
    m = device_metrics
    assert m.device_dispatch_total.value("host") == 1
    for phase in ("pack", "dispatch", "fetch"):
        for plane in ("sync", "live", "light"):
            assert m.segment_phase_seconds.count_value(phase, plane) == 0
    tot = phases.phase_totals()
    assert tot["host_batches"] == 1 and tot["host_sigs"] == 1
    assert tot["segments"] == 0


def test_vote_flush_lands_on_live_plane(device_metrics, device_standin):
    """The vote micro-batcher's device flush routes through the same phase
    instrumentation with plane="live" (set inside the executor thunk —
    contextvars don't cross run_in_executor)."""
    from tendermint_tpu.crypto import Ed25519PubKey
    from tendermint_tpu.crypto.vote_batcher import BatchVoteVerifier

    seeds = [bytes([i]) * 32 for i in range(4)]
    items = []
    for sd in seeds:
        pk = host.pubkey_from_seed(sd)
        msg = b"vote-" + sd[:4]
        items.append((Ed25519PubKey(pk), msg, host.sign(sd + pk, msg)))

    async def run():
        bvv = BatchVoteVerifier(min_device_batch=2, deadline_s=0.005)
        futs = [asyncio.ensure_future(bvv.preverify(pub, m, s))
                for pub, m, s in items]
        return await asyncio.gather(*futs)

    assert all(asyncio.run(run()))
    m = device_metrics
    assert m.segment_phase_seconds.count_value("pack", "live") >= 1
    assert m.segment_sigs.count_value("live") >= 1
    recs = [r for r in phases.recent_segments() if r["plane"] == "live"]
    assert recs and recs[-1]["sigs"] == 4


def test_host_vote_flush_counts_live(device_metrics):
    """A sub-threshold (host) flush records zero device phases but counts
    as a live-plane host batch."""
    from tendermint_tpu.crypto import Ed25519PubKey
    from tendermint_tpu.crypto.vote_batcher import BatchVoteVerifier

    sd = b"\x09" * 32
    pk = host.pubkey_from_seed(sd)
    sig = host.sign(sd + pk, b"m")

    async def run():
        bvv = BatchVoteVerifier(min_device_batch=64, deadline_s=0.005)
        return await bvv.preverify(Ed25519PubKey(pk), b"m", sig)

    assert asyncio.run(run())
    assert device_metrics.segment_phase_seconds.count_value(
        "pack", "live") == 0
    assert device_metrics.device_dispatch_total.value("host") == 1


def test_failed_fetch_drains_inflight_gauge(monkeypatch, device_metrics,
                                            accept_all):
    """A fetch raising after a successful dispatch must not leave
    crypto_device_inflight stuck above zero for already-dispatched
    segments (the gauge's only decrement used to live in fetched())."""

    class _BrokenDev:
        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("device dropped the fetch")

    dispatch = accept_all.dispatch_stream
    monkeypatch.setattr(
        V, "_dispatch_stream",
        lambda *a, **kw: (_BrokenDev(), dispatch(*a, **kw)[1]))
    monkeypatch.setattr(V, "SEG_MIN_SIGS", 256)
    with pytest.raises(RuntimeError, match="device dropped"):
        # every segment dispatches (gauge +1 each); segment 0's fetch blows
        V._verify_segmented([b"\x01" * 32] * 512, [b"m"] * 512,
                            [b"\x02" * 64] * 512, V.LANE)
    assert device_metrics.device_inflight.value(V._device_label()) == 0
    assert phases.recent_segments() == []  # no phase rows from garbage


def test_abandon_before_dispatch_blocks_late_increment(device_metrics):
    """A segment abandoned while its worker is still packing (sibling
    fetch raised) must reject the worker's LATE dispatched() — otherwise
    the gauge increments with nobody left to drain it."""
    rec = phases.Segment(sigs=1, chunk=128, device="cpu:0").begin()
    rec.abandon()          # call aborted pre-dispatch
    rec.dispatched()       # orphaned worker finishes packing anyway
    m = device_metrics
    assert m.device_inflight.value("cpu:0") == 0
    assert m.device_dispatch_total.value("cpu:0") == 0
    rec.fetched()          # and a late fetch is a no-op too
    assert m.segment_sigs.count_value("sync") == 0


def test_segments_get_distinct_trace_tracks():
    """Concurrent calls (live flush under a sync window) must not share a
    synthetic span track — overlapping slices on one track render as
    mis-nested garbage in Perfetto."""
    a = phases.Segment(sigs=1, chunk=128)
    b = phases.Segment(sigs=1, chunk=128)
    assert a.track != b.track
    assert a.track >= phases._SEG_TRACK_BASE


def test_phase_breakdown_interval_union_math():
    """Hand-computable two-segment pipeline: exposed pack + exposed
    dispatch + in-flight union tile the wall exactly; overlapped host work
    is excluded from the exposed shares but kept in the raw totals."""
    recs = [
        # seg 0: pack [0,1], dispatch [1,1.5], in-flight [1.5,5]
        {"t0": 0.0, "pack_s": 1.0, "dispatch_s": 0.5, "fetch_s": 3.5,
         "t_end": 5.0, "wait_s": 3.0, "sigs": 10},
        # seg 1: pack [1.5,2.5] (hidden behind seg 0's flight),
        # dispatch [2.5,3.0] (hidden), in-flight [3,8]
        {"t0": 1.5, "pack_s": 1.0, "dispatch_s": 0.5, "fetch_s": 5.0,
         "t_end": 8.0, "wait_s": 2.0, "sigs": 10},
    ]
    bd = phases.phase_breakdown(recs, 0.0, 8.0)
    assert abs(bd["device_share"] - 6.5 / 8.0) < 1e-9
    assert abs(bd["pack_share_exposed"] - 1.0 / 8.0) < 1e-9
    assert abs(bd["dispatch_share_exposed"] - 0.5 / 8.0) < 1e-9
    assert abs(bd["accounted_share"] - 1.0) < 1e-9
    assert abs(bd["overlap_ratio"] - 6.5 / 8.5) < 1e-9
    assert bd["pack_s"] == 2.0 and bd["sigs"] == 20
    assert abs(bd["pack_share_total"] - 2.0 / 8.0) < 1e-9


def test_stream_single_dispatch_also_records(device_metrics, accept_all):
    """batch_verify_stream's non-segmented leaf (chunk < n < SEG_MIN_SIGS)
    records exactly one segment."""
    out = V.batch_verify_stream([b"\x01" * 32] * 200, [b"m"] * 200,
                                [b"\x02" * 64] * 200, chunk=V.LANE)
    assert out.all()
    recs = phases.recent_segments()
    assert len(recs) == 1 and recs[0]["sigs"] == 200
    assert recs[0]["n_segs"] == 1
