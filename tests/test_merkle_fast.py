"""Vectorized merkle fast path (crypto/merkle_fast.py + merkle.py):
byte-parity with the hashlib spec, incremental dirty-leaf mode, routing
thresholds, and the MerkleKVStoreApplication integration."""

import hashlib
import os
import random

import pytest

from tendermint_tpu.crypto import merkle_fast as mf
from tendermint_tpu.crypto.merkle import (IncrementalMerkle,
                                          fast_hash_from_byte_slices,
                                          hash_from_byte_slices)

# every sha256 block-boundary edge: empty, 1, 55/56 (length spill into the
# second block), 63/64/65, 119/120/121 (two-block spill), plus a big one
EDGE_LENGTHS = [0, 1, 31, 54, 55, 56, 63, 64, 65, 119, 120, 121, 300, 4096]


def test_sha256_many_np_matches_hashlib():
    for n in EDGE_LENGTHS:
        msgs = [bytes([i % 256]) * n for i in range(5)]
        got = mf.sha256_many_np(msgs)
        want = [hashlib.sha256(m).digest() for m in msgs]
        assert got == want, f"np sha256 diverges at length {n}"


def test_sha256_many_np_bulk_random():
    rng = random.Random(11)
    msgs = [bytes(rng.randrange(256) for _ in range(65)) for _ in range(200)]
    assert mf.sha256_many_np(msgs) == \
        [hashlib.sha256(m).digest() for m in msgs]


def _device_sha256_matches_hashlib():
    if not mf.device_ready():
        pytest.skip("no jax device")
    msgs = [bytes([i % 256]) * 65 for i in range(64)]
    assert mf.sha256_many_device(msgs) == \
        [hashlib.sha256(m).digest() for m in msgs]


@pytest.mark.slow
def test_sha256_many_device_matches_hashlib():
    """The jitted program. ``slow`` because XLA:CPU never ends it: the
    compile takes seconds, but the EXECUTION time of the fused rounds grows
    ~2.2x a round (16 rounds 3 ms, 20 rounds 30 ms, 24 rounds 0.8 s,
    measured PR 26; SHA-256 has 64), at any lane count and block count: it
    held a worker from the first second of a tier-1 run to the cut at
    1,470 s. A compiler that ends it (the chip's) runs it; tier-1 holds the
    same bytes in the twin below."""
    _device_sha256_matches_hashlib()


def test_sha256_many_device_matches_hashlib_op_by_op():
    """Tier-1 twin of the test above: the same call, the same 64 two-block
    messages, the same bytes held against hashlib — with jit off, so the
    jax.numpy rounds run op by op and no program is built."""
    import jax

    with jax.disable_jit():
        _device_sha256_matches_hashlib()


@pytest.mark.parametrize("n", [0, 1, 2, 3, 4, 5, 7, 8, 9, 64, 65, 127, 128,
                               129, 1000])
def test_fast_tree_matches_spec(n):
    items = [f"leaf-{i}".encode() * (1 + i % 4) for i in range(n)]
    assert fast_hash_from_byte_slices(items) == hash_from_byte_slices(items)


def test_fast_tree_crosses_np_threshold(monkeypatch):
    # force the numpy batch path on for even tiny trees
    monkeypatch.setenv("TMTPU_MERKLE_NP_MIN", "1")
    items = [f"x{i}".encode() for i in range(37)]
    assert fast_hash_from_byte_slices(items) == hash_from_byte_slices(items)


def test_incremental_merkle_differential():
    """Random update/insert/delete schedule: the incremental root equals
    the spec recomputed from scratch at every step."""
    rng = random.Random(7)
    state = {}
    imt = IncrementalMerkle()

    def leaf_item(k):
        return k.encode() + b"\x00" + state[k].encode()

    for step in range(120):
        dirty = set()
        for _ in range(rng.randrange(1, 6)):
            op = rng.random()
            k = f"k{rng.randrange(40)}"
            if op < 0.70 or not state:
                state[k] = f"v{step}-{rng.random()}"
                dirty.add(k)
            else:
                victim = rng.choice(sorted(state))
                del state[victim]
        keys = sorted(state)
        got = imt.root(keys, leaf_item, dirty)
        want = hash_from_byte_slices([leaf_item(k) for k in keys])
        assert got == want, f"incremental root diverged at step {step}"
    assert imt.patches > 0 and imt.rebuilds > 0  # both paths exercised


def test_incremental_merkle_patch_vs_rebuild_thresholds():
    state = {f"k{i:03d}": "v" for i in range(200)}
    imt = IncrementalMerkle()

    def leaf_item(k):
        return k.encode() + b"\x00" + state[k].encode()

    keys = sorted(state)
    imt.root(keys, leaf_item, None)
    rebuilds0 = imt.rebuilds
    # a small dirty set patches
    state["k000"] = "v2"
    imt.root(keys, leaf_item, {"k000"})
    assert imt.patches == 1 and imt.rebuilds == rebuilds0
    # a huge dirty set (>= n/4) rebuilds
    big = {k for k in keys[:60]}
    for k in big:
        state[k] = "v3"
    imt.root(keys, leaf_item, big)
    assert imt.rebuilds == rebuilds0 + 1


def test_incremental_merkle_empty_and_reset():
    imt = IncrementalMerkle()
    assert imt.root([], lambda k: b"", None) == hash_from_byte_slices([])
    imt.reset()
    assert imt.root(["a"], lambda k: b"a=1", None) == \
        hash_from_byte_slices([b"a=1"])


def test_merkle_kvstore_app_incremental_matches_spec():
    """Commit-by-commit: the app's (incremental) hash equals the spec
    recomputed from the full store, and the kill switch takes the same
    bytes through the hashlib path."""
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.example.kvstore import MerkleKVStoreApplication

    app = MerkleKVStoreApplication(interval=1)
    spec = MerkleKVStoreApplication(interval=1)
    os.environ["TMTPU_MERKLE_FAST"] = "1"
    try:
        rng = random.Random(13)
        for h in range(1, 8):
            for i in range(rng.randrange(1, 9)):
                tx = f"k{rng.randrange(12)}=v{h}.{i}".encode()
                app.deliver_tx(abci.RequestDeliverTx(tx=tx))
                spec.deliver_tx(abci.RequestDeliverTx(tx=tx))
            fast_hash = app.commit().data
            os.environ["TMTPU_MERKLE_FAST"] = "0"
            try:
                spec_hash = spec.commit().data
            finally:
                os.environ["TMTPU_MERKLE_FAST"] = "1"
            assert fast_hash == spec_hash, f"app hash diverged at height {h}"
    finally:
        os.environ.pop("TMTPU_MERKLE_FAST", None)
