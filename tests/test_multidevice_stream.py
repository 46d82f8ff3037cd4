"""The multi-device sharded streaming verifier
(crypto/ed25519_jax/multidevice.py) on the forced 8-way host CPU mesh
(tests/conftest.py pins ``--xla_force_host_platform_device_count=8``):

* deterministic shard planning (pure function of batch/lane geometry);
* byte-parity of verdicts vs the single-device ``batch_verify_stream``
  layout on mixed valid/invalid batches — including under a one-lane
  breaker-open degradation, where the sick lane's segments re-shard to
  healthy peers with zero dropped signatures;
* per-device ``crypto_device_dispatch_total`` series and phase records;
* the per-lane fault-site family (``device.lane.<label>``) and the lane
  breaker registry;
* the columnar sign-bytes fast path (types/canonical
  vote_sign_bytes_columns_batch -> crypto/signcols.SignColumns ->
  prepare_sparse_stream), differentially against the row-materialized
  encoder and the dense packer's preimage bytes.

Device work runs through shape-identical STUB kernels
(tools/stub_kernels.install_stub_kernels): per-device-ordinal executables
of the real ed25519 kernel take minutes to compile on CPU, and the stub
verdict is a deterministic PER-ITEM function of the packed wire bytes — so
verdict parity across sharding layouts exercises exactly the packing,
sharding, ordering, and re-sharding machinery the real kernels would see.
(Real-kernel byte-parity of the sparse/dense wire formats is covered by
tests/test_sparse_verify.py on the default device.)
"""

import numpy as np
import pytest

import jax

from tendermint_tpu.crypto import phases
from tendermint_tpu.crypto.breaker import (
    OPEN,
    lane_breaker,
    lane_breakers,
    reset_lane_breakers,
)
from tendermint_tpu.crypto.ed25519_jax import multidevice as MD
from tendermint_tpu.crypto.ed25519_jax import verify as V
from tendermint_tpu.libs.faults import faults
from tendermint_tpu.libs.toolbox import load_tool

install_stub_kernels = load_tool("stub_kernels").install_stub_kernels


@pytest.fixture
def stub_kernels():
    restore = install_stub_kernels(V)
    yield
    restore()


def _workload(n, seed=7, invalid_every=11):
    """Dissimilar equal-length messages (dense wire format — the stub
    dense kernel's verdict is per-item, so it is invariant to segment
    layout) with host-invalid rows mixed in: bad lengths, non-canonical
    s — the ok-mask plane rides along with the kernel verdicts."""
    rng = np.random.default_rng(seed)
    pks = [rng.bytes(32) for _ in range(n)]
    msgs = [rng.bytes(120) for _ in range(n)]
    sigs = [rng.bytes(63) + b"\x00" for _ in range(n)]  # s < L
    for i in range(0, n, invalid_every):
        sigs[i] = sigs[i][:32] + b"\xff" * 32  # s >= L: host reject
    pks[3] = pks[3][:31]                       # bad pk length
    sigs[5] = sigs[5][:63]                     # bad sig length
    return pks, msgs, sigs


def _single_device(pks, msgs, sigs, chunk=V.LANE, columns=None):
    """Single-device segmented reference verdicts (pool not engaged)."""
    if columns is not None:
        return V._verify_segmented(pks, msgs, sigs, chunk, columns=columns)
    return V._verify_segmented(pks, msgs, sigs, chunk)


# -- planning -----------------------------------------------------------------

def test_plan_segments_deterministic_and_exact():
    for k, lanes, sc in [(16, 8, 10), (100, 8, 10), (3, 8, 10), (8, 4, 2),
                         (1, 2, 10), (64, 7, 5)]:
        plan = MD.plan_segments(k, lanes, sc)
        assert plan == MD.plan_segments(k, lanes, sc)  # pure
        sizes = [s for s, _ in plan]
        assert sum(sizes) == k
        assert all(1 <= s <= sc for s in sizes)
        assert [l for _, l in plan] == [i % lanes for i in range(len(plan))]
        if k >= 2 * lanes:
            # every lane gets at least two segments: per-lane pipelining
            assert len(plan) >= 2 * lanes
    assert MD.plan_segments(0, 4, 10) == []


def test_pool_disabled_by_env(monkeypatch):
    monkeypatch.setenv(MD.ENV_DEVICES, "1")
    MD.reset_pool()
    assert MD.pool() is None
    monkeypatch.setenv(MD.ENV_DEVICES, "4")
    MD.reset_pool()
    p = MD.pool()
    assert p is not None and len(p.lanes) == 4
    MD.reset_pool()


# -- verdict parity -----------------------------------------------------------

def test_parity_mixed_batch_vs_single_device(stub_kernels):
    pks, msgs, sigs = _workload(1024)
    want = _single_device(pks, msgs, sigs)
    assert 0 < want.sum() < len(pks)  # genuinely mixed accept/reject
    md = MD.MultiDeviceStream(devices=jax.devices()[:4], min_sigs=0)
    got = md.verify(pks, msgs, sigs, chunk=V.LANE)
    np.testing.assert_array_equal(got, want)


def test_parity_through_windowed_submission(stub_kernels):
    """More segments than the 2-per-lane submission window (seg_chunks=1,
    2 lanes, 10 chunks -> 10 segments > window 4): the refill path must
    reassemble in order with the same verdicts."""
    pks, msgs, sigs = _workload(1280, seed=29)
    want = _single_device(pks, msgs, sigs)
    md = MD.MultiDeviceStream(devices=jax.devices()[:2], min_sigs=0,
                              seg_chunks=1)
    got = md.verify(pks, msgs, sigs, chunk=V.LANE)
    np.testing.assert_array_equal(got, want)
    assert sum(r["sigs"] for r in phases.recent_segments()) >= 1280


def test_stream_entry_routes_through_pool(monkeypatch, stub_kernels,
                                          device_metrics):
    pks, msgs, sigs = _workload(768, seed=9)
    want = _single_device(pks, msgs, sigs)
    monkeypatch.setattr(V, "SEG_MIN_SIGS", 256)
    monkeypatch.setenv(MD.ENV_DEVICES, "4")
    MD.reset_pool()
    try:
        got = V.batch_verify_stream(pks, msgs, sigs, chunk=V.LANE)
        np.testing.assert_array_equal(got, want)
        used = [i for i in range(8)
                if device_metrics.device_dispatch_total.value(f"cpu:{i}")]
        assert len(used) >= 2, "segments never sharded across devices"
        for i in used:
            assert device_metrics.device_inflight.value(f"cpu:{i}") == 0
    finally:
        MD.reset_pool()


def test_columns_ride_the_pool(stub_kernels):
    """SignColumns slices follow their segments through the lanes and the
    verdicts stay identical to the single-device layout of the SAME
    columnar representation."""
    from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
    from tendermint_tpu.types.canonical import (
        vote_sign_bytes_batch,
        vote_sign_bytes_columns_batch,
    )

    n = 512
    bid = BlockID(b"\xaa" * 32, PartSetHeader(1, b"\xbb" * 32))
    # constant seconds, nanos varints of equal width (5 bytes)
    ts = [1_700_000_000_500_000_000 + 1000 * i for i in range(n)]
    cols = vote_sign_bytes_columns_batch(
        "chain-md", SignedMsgType.PRECOMMIT, 7, 0, [bid] * n, ts)
    assert cols is not None
    msgs = vote_sign_bytes_batch(
        "chain-md", SignedMsgType.PRECOMMIT, 7, 0, [bid] * n, ts)
    rng = np.random.default_rng(5)
    pks = [rng.bytes(32) for _ in range(n)]
    sigs = [rng.bytes(63) + b"\x00" for _ in range(n)]
    want = _single_device(pks, msgs, sigs, columns=cols)
    md = MD.MultiDeviceStream(devices=jax.devices()[:3], min_sigs=0)
    got = md.verify(pks, msgs, sigs, chunk=V.LANE, columns=cols)
    np.testing.assert_array_equal(got, want)


# -- degradation --------------------------------------------------------------

def test_one_sick_lane_degrades_and_resharding_drops_nothing(
        monkeypatch, stub_kernels, device_metrics):
    monkeypatch.setenv("TMTPU_DEVICE_BREAKER_THRESHOLD", "2")
    reset_lane_breakers()
    pks, msgs, sigs = _workload(1280, seed=13)
    want = _single_device(pks, msgs, sigs)
    faults.configure(MD.LANE_SITE_PREFIX + "cpu:1")  # every dispatch fails
    md = MD.MultiDeviceStream(devices=jax.devices()[:4], min_sigs=0)
    got = md.verify(pks, msgs, sigs, chunk=V.LANE)
    np.testing.assert_array_equal(got, want)  # zero dropped signatures
    assert md.stats["resharded_segments"] >= 1
    assert faults.fires(MD.LANE_SITE_PREFIX + "cpu:1") >= 2
    assert lane_breaker("cpu:1").state == OPEN
    # the sick lane never dispatched (its site raises before packing)
    assert device_metrics.device_dispatch_total.value("cpu:1") == 0
    healthy = [i for i in (0, 2, 3)
               if device_metrics.device_dispatch_total.value(f"cpu:{i}")]
    assert len(healthy) >= 2
    for i in range(4):
        assert device_metrics.device_inflight.value(f"cpu:{i}") == 0

    # second call: the OPEN breaker excludes the lane up front — no new
    # fault evaluations, verdicts still byte-identical
    fired = faults.fires(MD.LANE_SITE_PREFIX + "cpu:1")
    got2 = md.verify(pks, msgs, sigs, chunk=V.LANE)
    np.testing.assert_array_equal(got2, want)
    assert faults.fires(MD.LANE_SITE_PREFIX + "cpu:1") == fired


def test_all_lanes_sick_raises_and_batchverifier_survives(
        monkeypatch, stub_kernels):
    monkeypatch.setenv("TMTPU_DEVICE_BREAKER_THRESHOLD", "1")
    reset_lane_breakers()
    labels = [f"cpu:{i}" for i in range(3)]
    faults.configure(",".join(MD.LANE_SITE_PREFIX + l for l in labels))
    md = MD.MultiDeviceStream(devices=jax.devices()[:3], min_sigs=0)
    pks, msgs, sigs = _workload(512, seed=17)
    with pytest.raises(MD.AllLanesFailed):
        md.verify(pks, msgs, sigs, chunk=V.LANE)

    # ...and through BatchVerifier the same failure is a host fallback,
    # never a caller-visible error — byte-identical verdicts
    from tendermint_tpu.crypto import Ed25519PrivKey
    from tendermint_tpu.crypto.batch import BatchVerifier, stats

    reset_lane_breakers()  # breakers tripped above; fresh pool health
    monkeypatch.setenv(MD.ENV_DEVICES, "3")
    monkeypatch.setattr(V, "SEG_MIN_SIGS", 64)
    MD.reset_pool()
    try:
        n = 2304  # > the 2048-chunk so the stream path engages
        bv = BatchVerifier(backend="jax", plane="votes")
        for i in range(n):
            sk = Ed25519PrivKey.generate(i.to_bytes(4, "big") * 8)
            m = b"md-fallback-%d" % i
            bv.add(sk.pub_key(), m, sk.sign(m))
        before = stats["device_errors"]
        ok, per = bv.verify()
        assert ok and per.all()  # host fallback, byte-identical verdicts
        assert stats["device_errors"] == before + 1
    finally:
        MD.reset_pool()


def test_lane_breaker_registry(monkeypatch):
    monkeypatch.setenv("TMTPU_DEVICE_BREAKER_THRESHOLD", "5")
    monkeypatch.setenv("TMTPU_DEVICE_BREAKER_COOLDOWN_S", "0.25")
    reset_lane_breakers()
    b = lane_breaker("tpu:3")
    assert lane_breaker("tpu:3") is b  # per-label singleton
    assert b.failure_threshold == 5 and b.cooldown_s == 0.25
    assert b.name == "device:tpu:3"
    # peek() is read-only: repeated peeks on OPEN never admit a probe
    for _ in range(5):
        b.record_failure()
    assert b.state == OPEN
    b._opened_at = b._clock() - 1.0  # cooldown elapsed
    assert b.peek() and b.peek()
    assert b.state == OPEN and not b._probe_in_flight
    assert "tpu:3" in lane_breakers()
    reset_lane_breakers()
    assert "tpu:3" not in lane_breakers()


def test_lane_fault_sites_are_known_family(caplog):
    import logging

    from tendermint_tpu.libs.faults import FaultPlane, is_known_site

    assert is_known_site("device.lane.tpu:7")
    assert is_known_site("device.batch_verify")
    assert not is_known_site("device.lanes.tpu:7")
    plane = FaultPlane()
    with caplog.at_level(logging.WARNING, logger="tmtpu.faults"):
        plane.configure_from_env(
            {"TMTPU_FAULTS": "device.lane.cpu:2@0.5"})
    assert not any("no production code consults" in r.message
                   for r in caplog.records)


# -- columnar sign-bytes ------------------------------------------------------

def test_sign_columns_match_row_encoder():
    from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
    from tendermint_tpu.types.canonical import (
        vote_sign_bytes_batch,
        vote_sign_bytes_columns_batch,
    )

    bid = BlockID(b"\x11" * 32, PartSetHeader(3, b"\x22" * 32))
    # timestamps straddling a second boundary but with equal varint widths
    ts = [1_700_000_001_000_000_500 + 7 * i for i in range(300)]
    rows = vote_sign_bytes_batch(
        "col-chain", SignedMsgType.PRECOMMIT, 42, 1, [bid] * 300, ts)
    cols = vote_sign_bytes_columns_batch(
        "col-chain", SignedMsgType.PRECOMMIT, 42, 1, [bid] * 300, ts)
    assert cols is not None and len(cols) == 300
    assert cols.rows() == rows                       # bulk materialization
    assert [cols[i] for i in (0, 7, 299)] == \
        [rows[i] for i in (0, 7, 299)]               # row indexing
    sub = cols.subset([5, 0, 123])
    assert list(sub) == [rows[5], rows[0], rows[123]]
    assert list(cols.slice(10, 13)) == rows[10:13]

    # ragged structures bail to None instead of producing a wrong template
    nil_bid = BlockID(b"", PartSetHeader(0, b""))
    assert vote_sign_bytes_columns_batch(
        "col-chain", SignedMsgType.PRECOMMIT, 42, 1, [bid, nil_bid],
        ts[:2]) is None                              # nil vote mixes in
    assert vote_sign_bytes_columns_batch(
        "col-chain", SignedMsgType.PRECOMMIT, 42, 1, [bid] * 2,
        [1_700_000_000_000_000_000, 5]) is None      # varint widths differ


def test_commit_columns_memo_and_verify_commit_light(monkeypatch):
    """The VerifyCommitLight plane hands the commit's SignColumns to the
    verifier, and the outcome matches the row path exactly."""
    from tendermint_tpu.crypto import Ed25519PrivKey
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    from tendermint_tpu.types import validator_set as VS

    monkeypatch.setenv("TMTPU_BATCH_BACKEND", "host")  # no kernel compiles
    # the entry hands a commit over as columns above one stream chunk
    # (2,048): lowered, so that 40 validators take that way
    monkeypatch.setattr(VS, "STREAM_CHUNK", 32)
    n = 40
    keys = [Ed25519PrivKey.generate(bytes([i + 1]) * 32) for i in range(n)]
    vals = [Validator(k.pub_key().address(), k.pub_key(), 10, 0)
            for k in keys]
    vs = ValidatorSet(vals)
    bid = BlockID(b"\x77" * 32, PartSetHeader(1, b"\x88" * 32))
    commit = Commit(height=9, round=0, block_id=bid, signatures=[
        CommitSig(BlockIDFlag.COMMIT, v.address,
                  1_700_000_000_500_000_000 + 1000 * i, b"")
        for i, v in enumerate(vs.validators)])
    chain = "cols-commit"
    sb = commit.vote_sign_bytes_all(chain)
    by_addr = {k.pub_key().address(): k for k in keys}
    for i, cs in enumerate(commit.signatures):
        cs.signature = by_addr[cs.validator_address].sign(sb[i])

    cols = commit.vote_sign_bytes_columns(chain)
    assert cols is not None
    assert commit.vote_sign_bytes_columns(chain) is cols  # memoized
    assert cols.rows() == sb                              # byte parity

    seen = {}
    orig = B.BatchVerifier.verify

    def spy(self):
        seen["columns"] = self._columns
        return orig(self)

    monkeypatch.setattr(B.BatchVerifier, "verify", spy)
    vs.verify_commit_light(chain, bid, 9, commit)  # must not raise
    assert seen["columns"] is not None and len(seen["columns"]) == n


def test_sparse_from_columns_matches_dense_blocks():
    """The columnar sparse wire format must assemble the SAME SHA preimage
    message bytes as the dense packer — checked with a numpy mirror of the
    on-device _assemble_blocks, no kernel involved."""
    from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
    from tendermint_tpu.types.canonical import (
        vote_sign_bytes_batch,
        vote_sign_bytes_columns_batch,
    )

    n, chunk = 300, 128
    bid = BlockID(b"\x09" * 32, PartSetHeader(2, b"\x0a" * 32))
    # constant seconds, nanos varints of equal width (5 bytes)
    ts = [1_700_000_000_500_000_000 + 1_000_000 * i for i in range(n)]
    msgs = vote_sign_bytes_batch(
        "dense-chain", SignedMsgType.PRECOMMIT, 5, 0, [bid] * n, ts)
    cols = vote_sign_bytes_columns_batch(
        "dense-chain", SignedMsgType.PRECOMMIT, 5, 0, [bid] * n, ts)
    assert cols is not None
    rng = np.random.default_rng(11)
    pks = [rng.bytes(32) for _ in range(n)]
    sigs = [rng.bytes(63) + b"\x00" for _ in range(n)]

    built = V._sparse_from_columns(cols, chunk)
    assert built is not None
    templates, ccols, diff_vals, mlens, k, pad = built
    assert templates.shape[0] == k and diff_vals.shape[0] == pad

    # numpy mirror of _assemble_blocks: template + diff scatter, mlen
    # mask, 0x80 pad marker, BE bitlen in the last 8 bytes
    mlen_max = templates.shape[1]
    m = np.repeat(templates, chunk, axis=0).astype(np.uint8)   # (pad, MLEN)
    m[np.arange(pad)[:, None], ccols[None, :]] = diff_vals
    full_mlens = np.zeros(pad, np.int64)
    full_mlens[:n] = mlens
    iota = np.arange(mlen_max)[None, :]
    m = np.where(iota < full_mlens[:, None], m, 0).astype(np.uint8)
    m[np.arange(pad), full_mlens] = 0x80
    bitlen = (full_mlens + 64) * 8
    nblk = (64 + full_mlens + 17 + 127) // 128
    last = nblk * 128 - 64
    for b_i in range(8):
        m[np.arange(pad), last - 1 - b_i] = (bitlen >> (8 * b_i)) & 0xFF

    # dense reference for the REAL rows: bytes 64.. of each row's padded
    # preimage are exactly the assembled message region
    blocks_w, _nblk, _s, _ok = V.prepare_batch(pks, msgs, sigs)
    dense = np.frombuffer(blocks_w.astype(">u4").tobytes(),
                          dtype=np.uint8).reshape(n, -1)
    np.testing.assert_array_equal(m[:n, :dense.shape[1] - 64],
                                  dense[:, 64:])


def test_pack_scratch_reuse_is_stateless():
    """Repacking different batches through the same worker's scratch must
    never leak bytes between calls (shrink after grow is the risky case)."""
    big = _workload(512, seed=1)
    small = _workload(256, seed=2)
    first = V._pack_stream_dense(*big, 128)
    ref_small = V._pack_stream_dense(*small, 128)
    again_big = V._pack_stream_dense(*big, 128)
    for a, b in zip(first[0], again_big[0]):
        np.testing.assert_array_equal(a, b)
    fresh_small = V._pack_stream_dense(*small, 128)
    for a, b in zip(ref_small[0], fresh_small[0]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(first[1], again_big[1])


def test_phase_records_carry_lane_labels(stub_kernels, device_metrics):
    pks, msgs, sigs = _workload(512, seed=23)
    md = MD.MultiDeviceStream(devices=jax.devices()[:2], min_sigs=0)
    md.verify(pks, msgs, sigs, chunk=V.LANE)
    recs = phases.recent_segments()
    assert recs, "no phase records from a multi-device call"
    labels = {r["device"] for r in recs}
    assert labels <= {"cpu:0", "cpu:1"} and len(labels) == 2
    assert sum(r["sigs"] for r in recs) == 512
    tot = phases.phase_totals()
    assert tot["pipelined_calls"] >= 1
