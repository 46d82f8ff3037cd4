"""The serving plane's cache of loaded objects (``LightServePlane.loaded``).

A verify request loads the signed header and the validator set at its
trusted height and at its height; the plane keeps what the stores gave,
keyed by (kind, height), and hands the same object to the next request.
Held here: the answers are those of a plane that keeps nothing, a crowd
at one tip loads each (kind, height) once, a pruned height stays refused,
a falling tip forgets what lies at or above it, the row bound holds LRU
first, a cache that keeps nothing loads no more than the stores alone, and
the counters ``status()`` carries."""

import asyncio
import collections
import dataclasses

import pytest

from tendermint_tpu.config import LightServeConfig
from tendermint_tpu.light.serve import LOADED_ROWS, HeaderCache, LightServePlane
from tendermint_tpu.types import ValidatorSet
from tendermint_tpu.types.block import Commit

from tests.test_light_client import CHAIN, _keys, _mk_chain

N_VALS = 4
HEIGHTS = 16


class _Stores:
    """A node's BlockStore and StateStore over a ``_mk_chain`` chain: the
    tip and the base settable, a seen commit apart from the canonical one
    where ``seen`` says, every load counted by (kind, height) and decoded
    anew from bytes, as the node's stores do."""

    def __init__(self, blocks, seen=None):
        self.blocks = blocks
        self.seen = seen or {}
        self.tip = max(blocks)
        self.floor = min(blocks)
        self.loads = collections.Counter()

    def height(self):
        return self.tip

    def base(self):
        return self.floor

    def _known(self, h):
        return self.floor <= h <= self.tip and h in self.blocks

    def load_block_meta(self, h):
        from types import SimpleNamespace

        if not self._known(h):
            return None
        return SimpleNamespace(header=self.blocks[h].signed_header.header)

    def load_block_commit(self, h):
        self.loads["canonical", h] += 1
        if not self._known(h) or h == self.tip:
            return None  # the next block's LastCommit is not stored yet
        return Commit.decode(self.blocks[h].signed_header.commit.encode())

    def load_seen_commit(self, h):
        self.loads["seen", h] += 1
        if not self._known(h):
            return None
        c = self.seen.get(h, self.blocks[h].signed_header.commit)
        return Commit.decode(c.encode())

    def load_validators(self, h):
        self.loads["vals", h] += 1
        if not self._known(h):
            return None
        return ValidatorSet.decode(self.blocks[h].validator_set.encode())


def _tampered(commit, row):
    """``commit`` with one bit of row ``row``'s signature flipped."""
    sigs = list(commit.signatures)
    bad = bytearray(sigs[row].signature)
    bad[0] ^= 1
    sigs[row] = dataclasses.replace(sigs[row], signature=bytes(bad))
    return dataclasses.replace(commit, signatures=sigs)


@pytest.fixture(scope="module")
def chain():
    return _mk_chain([_keys(0x30, N_VALS)], HEIGHTS)


def _plane(stores, rows=None):
    """A plane over ``stores``; ``rows`` bounds its ``loaded`` in place of
    ``LOADED_ROWS``."""
    cfg = LightServeConfig()
    cfg.trusting_period_s = 10 * 365 * 24 * 3600.0  # chain fixture is 2023
    plane = LightServePlane(block_store=stores, state_store=stores,
                            chain_id=CHAIN, config=cfg)
    if rows is not None:
        plane.loaded = HeaderCache(capacity=rows)
    return plane


def _answer(res):
    return None if res is None else (type(res).__name__, str(res))


def _round(plane, stores, tip, trusted):
    """One round: a client for each trusted height, all at once at ``tip``."""
    stores.tip = tip

    async def run():
        return await asyncio.gather(*[
            plane.serve_verify(tip, th, client_id=f"c{i}")
            for i, th in enumerate(trusted)])

    return [_answer(r) for r in asyncio.run(run())]


#: tips in the order the rounds see them: height 9's seen commit is
#: tampered, 9 is later a trusted height, and the tip falls back to 9
TIPS = [6, 7, 8, 9, 10, 11, 12, 9, 10, 13]


def test_answers_match_a_plane_that_keeps_nothing(chain):
    """Over rounds with advancing tips, a tampered seen commit, that height
    later trusted, and a tip that falls back onto it: every answer equals
    the answer of a plane whose bound holds no load."""
    seen = {9: _tampered(chain[9].signed_header.commit, 0)}
    kept, bare = _Stores(chain, seen), _Stores(chain, seen)
    p_kept, p_bare = _plane(kept), _plane(bare, rows=1)
    try:
        for tip in TIPS:
            trusted = sorted({tip - 1, tip - 2, tip - 3, max(1, tip - 7),
                              1, 9 if tip > 9 else 1})
            got = _round(p_kept, kept, tip, trusted)
            assert got == _round(p_bare, bare, tip, trusted), tip
            if tip == 9:   # the tampered seen commit is what was verified
                assert all(a is not None for a in got)
            else:
                assert got == [None] * len(trusted)
    finally:
        p_kept.stop()
        p_bare.stop()
    assert p_bare.loaded.stats["hits"] == 0 and len(p_bare.loaded) == 0
    assert p_kept.loaded.stats["hits"] > p_kept.loaded.stats["misses"]
    # a trusted height below the tip carries its canonical commit, the tip
    # its seen one, whatever was loaded before
    kept.tip = 13
    req = p_kept._build_request(9, 13, (1, 3), 13)
    assert req.trusted_sh.commit == chain[9].signed_header.commit
    kept.tip = 9
    req = p_kept._build_request(8, 9, (1, 3), 9)
    assert req.untrusted_sh.commit == seen[9]


def test_a_crowd_at_one_tip_loads_each_height_once(chain):
    """32 concurrent requests at one tip over 12 trusted heights: one store
    load per distinct (kind, height), and every request shares the objects
    of its heights."""
    stores = _Stores(chain)
    plane = _plane(stores)
    tip = HEIGHTS
    trusted = [tip - 1 - (i % 12) for i in range(32)]
    reqs = []
    built = plane._build_request

    def keep(*args):
        reqs.append(built(*args))
        return reqs[-1]

    plane._build_request = keep
    try:
        assert _round(plane, stores, tip, trusted) == [None] * 32
    finally:
        plane.stop()
    want = ({("seen", tip), ("vals", tip)}
            | {(k, h) for h in set(trusted) for k in ("canonical", "vals")})
    assert set(stores.loads) == want
    assert set(stores.loads.values()) == {1}
    assert plane.loaded.stats["misses"] == len(want)
    for a, b in zip(reqs, reqs[1:]):
        assert a.untrusted_sh is b.untrusted_sh
        assert a.untrusted_vals is b.untrusted_vals
    by_height = {}
    for r in reqs:
        h = r.trusted_sh.header.height
        assert by_height.setdefault(h, r.trusted_vals) is r.trusted_vals


def test_a_pruned_height_is_refused_though_loaded(chain):
    stores = _Stores(chain)
    plane = _plane(stores)
    try:
        assert _round(plane, stores, 10, [2, 3, 9]) == [None] * 3
        assert ("vals", 2) in plane.loaded._entries
        stores.floor = 3
        with pytest.raises(KeyError, match="no header at height 2"):
            asyncio.run(plane.serve_verify(10, 2))
        assert _round(plane, stores, 10, [3]) == [None]
    finally:
        plane.stop()


def test_a_falling_tip_forgets_what_lies_at_or_above_it(chain):
    stores = _Stores(chain)
    plane = _plane(stores)
    try:
        for tip in (12, 13, 14):
            _round(plane, stores, tip, [tip - 1, 8, 5])
        keys = set(plane.loaded._entries)
        assert {("vals", 14), ("seen", 14), ("canonical", 13)} <= keys
        # a seen commit is kept only while its height is the tip
        assert ("seen", 13) not in keys and ("seen", 12) not in keys
        before = stores.loads["seen", 8]
        assert _round(plane, stores, 8, [7, 5]) == [None] * 2
        left = {h for _, h in plane.loaded._entries}
        assert max(left) == 8
        # the tip and what lies below it come back from the stores
        assert ("canonical", 8) not in plane.loaded._entries
        assert stores.loads["seen", 8] == before + 1
        assert stores.loads["vals", 8] == 2    # loaded again after the fall
        assert stores.loads["vals", 5] == 1    # below the new tip: kept
    finally:
        plane.stop()


def test_the_row_bound_evicts_least_recent_first():
    c = HeaderCache(capacity=10)
    c.put("a", 1, weight=4)
    c.put("b", 2, weight=4)
    assert c.get("a") == 1             # "b" is now the least recent
    c.put("c", 3, weight=4)
    assert c.peek("b") is None and c.peek("a") == 1 and c.weight == 8
    c.put("a", 4, weight=2)            # replaced: its new weight counts
    assert c.weight == 6 and c.stats["evictions"] == 1
    c.put("big", 5, weight=11)         # heavier than the bound: not kept
    assert len(c) == 0 and c.weight == 0
    c.put("d", 6)
    c.drop_where(lambda k: k == "d")
    assert len(c) == 0 and c.weight == 0


def test_the_plane_holds_its_rows_under_the_bound(chain):
    """At 4 rows an object and a bound of 20 rows, five objects stay: the
    ones used last, and never more rows than the bound."""
    stores = _Stores(chain)
    plane = _plane(stores, rows=5 * N_VALS)
    try:
        for tip in range(8, HEIGHTS + 1):
            for th in (tip - 1, tip - 4, 2):
                _round(plane, stores, tip, [th])
                assert plane.loaded.weight <= 5 * N_VALS
                assert len(plane.loaded) <= 5
        # the last request loaded, in order: header and set at 2 (the
        # trusted height), then the tip's seen header and set
        assert len(plane.loaded) == 5
        assert list(plane.loaded._entries)[-4:] == [
            ("canonical", 2), ("vals", 2), ("seen", HEIGHTS),
            ("vals", HEIGHTS)]
        assert plane.loaded.stats["evictions"] > 0
    finally:
        plane.stop()


def test_hits_and_misses_count_every_load(chain):
    stores = _Stores(chain)
    plane = _plane(stores)
    assert plane.loaded.capacity == LOADED_ROWS
    try:
        for tip in (10, 11, 12):
            _round(plane, stores, tip, [tip - 1, tip - 2, 3, 3])
    finally:
        plane.stop()
    st = plane.status()
    loaded = st["loaded"]
    assert loaded["hits"] + loaded["misses"] == (
        4 * st["served"]["verifies_served"]) == 48
    assert loaded["misses"] == sum(stores.loads.values())
    assert loaded["hits"] > 0
    assert loaded["resident"] == len(plane.loaded)
    assert loaded["rows"] == N_VALS * loaded["resident"]


@pytest.mark.parametrize("rows", [1, N_VALS, 2 * N_VALS])
def test_a_cache_that_keeps_little_loads_no_more_than_the_stores(chain, rows):
    """One client a round, each at a height no earlier round touched: every
    load misses. The stores are asked four times a verify at most, as
    without the cache, and the answers are the same."""
    stores = _Stores(chain)
    plane = _plane(stores, rows=rows)
    try:
        for tip in range(3, HEIGHTS + 1):
            assert _round(plane, stores, tip, [tip - 2]) == [None]
            assert plane.loaded.weight <= rows
    finally:
        plane.stop()
    verifies = plane.stats["verifies_served"]
    assert verifies == HEIGHTS - 2
    assert sum(stores.loads.values()) == plane.loaded.stats["misses"]
    assert plane.loaded.stats["misses"] <= 4 * verifies
    if rows < N_VALS:   # nothing fits: every load goes to the stores
        assert plane.loaded.stats["misses"] == 4 * verifies
