"""The benchmark's light-serving cell (``light1000.serve``) at a small size.

The serving plane (``light/serve.py``), driven the way the cell's driver
drives it, against the cell's plain reference (``benchmarks/reference/
light_spec.py``, OpenSSL over the benchmark's own sign-bytes): 64
validators, a 24-block chain, 8 clients a round. Every flush carries one
commit of 64 signatures, so the one-call kernel's stand-in answers
(conftest.py's rule). Also: the cell's files at their own size, the plane's
three new counters, and the driver and reader against a plane that lacks
those counters (the parent side of a comparison).
"""

import asyncio
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402
from drivers import light_serve_rounds as L  # noqa: E402
from reference import light_spec  # noqa: E402

#: the cell at a small size: heights 16-23 are the tips, trusted heights
#: reach down to 1; three of the eight tips are tampered
SMALL = {"validators": 64, "chain_blocks": 24, "pool_heights": 8,
         "clients": 8,
         "gap_classes": [{"clients": 2, "gaps": [1, 1]},
                         {"clients": 4, "gaps": [2, 8]},
                         {"clients": 2, "gaps": [9, 15]}]}
SEED = 2 ** 31 + 4242


def _files():
    config = harness.load_json("configs", "light1000.json")
    traffic = harness.load_json("traffic", "serve.json")
    return config, traffic


def _small_files():
    config, traffic = _files()
    for k, v in SMALL.items():
        (config if k in config else traffic)[k] = v
    return config, traffic


@pytest.fixture(scope="module")
def cell():
    """The driver's data at the small size, and the reference over it."""
    config, traffic = _small_files()
    data = L.build(config, traffic, SEED)
    return data, light_spec.Spec(data["vals"], data["chain"])


def _ask(data, tip, gaps):
    answers, failed = asyncio.run(L._round(data, tip, gaps))
    assert not failed, answers
    return answers


#: what each tampered row region makes of a request, by path
EXPECTED = {
    ("inside_one_third", "adjacent"): "ErrInvalidHeader",
    ("inside_one_third", "skipping"): "ErrWrongSignature",
    ("one_third_to_two_thirds", "adjacent"): "ErrInvalidHeader",
    ("one_third_to_two_thirds", "skipping"): "ErrInvalidHeader",
    ("past_two_thirds", "adjacent"): "accept",
    ("past_two_thirds", "skipping"): "accept",
}


@pytest.mark.parametrize("region,path", sorted(EXPECTED))
def test_plane_answers_as_light_spec_at_a_tampered_tip(cell, device_standin,
                                                       region, path):
    """One round at the tip whose seen commit is tampered in ``region``:
    8 clients on the one path, each answer the reference's, and the error
    type and row the v0.34 verifier gives there."""
    data, spec = cell
    tip = next(h for h, r in data["regions"].items() if r == region)
    gaps = [1] * 8 if path == "adjacent" else list(range(2, 10))
    answers = _ask(data, tip, gaps)
    want = [spec.answer(tip, g) for g in gaps]
    assert answers == want
    row = data["tampered"][tip]
    name = EXPECTED[(region, path)]
    assert set(answers) == ({("accept",)} if name == "accept"
                            else {(name, row)})
    # one flush, one device batch of the commit's 64 signatures
    assert device_standin.calls[-1] == 64


def test_plane_accepts_every_gap_class_at_a_sound_tip(cell, device_standin):
    data, spec = cell
    tip = next(h for h in data["pool"] if h not in data["tampered"])
    gaps = L.gaps_of_round(data, 5)
    answers = _ask(data, tip, gaps)
    assert answers == [("accept",)] * 8
    assert answers == [spec.answer(tip, g) for g in gaps]
    assert {g == 1 for g in gaps} == {True, False}


def test_driver_data_is_what_its_files_say():
    """The cell's own files: 1,000 validators at power 10, a 129-block
    chain, 32 clients in three gap classes, 64 tips (65-128), three
    tampered tips drawn among all of them, one row in each power region,
    and the plane's defaults; ``sigs`` a round is the validator count."""
    from tendermint_tpu.config import LightServeConfig

    import data as D

    config, traffic = _files()
    assert (config["validators"], config["power"],
            config["chain_blocks"]) == (1000, 10, 129)
    assert config["reduced"] == [] and config["reference"] == "light_spec"
    serving, defaults = config["serving"], LightServeConfig()
    assert tuple(serving["trust_level"]) == (1, 3)
    assert (serving["trusting_period_s"], serving["flush_max"],
            serving["flush_deadline_ms"]) == (
        defaults.trusting_period_s, defaults.flush_max,
        defaults.flush_deadline_ms) == (14 * 24 * 3600.0, 64, 2.0)
    assert traffic["clients"] == 32 and traffic["pool_heights"] == 64
    assert [(c["clients"], c["gaps"]) for c in traffic["gap_classes"]] == [
        (8, [1, 1]), (16, [2, 16]), (8, [17, 64])]
    assert traffic["tampered_rows"] == list(L.REGIONS)
    assert set(traffic) == {"name", "driver", "why", "chain_id", "clients",
                            "pool_heights", "gap_classes", "tampered_rows",
                            "trace_after_ticks", "trace_ticks"}
    assert (traffic["trace_after_ticks"], traffic["trace_ticks"]) == (2, 4)

    vals = D.make_validators(1000, SEED, power=10)
    bounds = [(L._row_bound(vals, *L.REGIONS[r][0]),
               L._row_bound(vals, *L.REGIONS[r][1])) for r in L.REGIONS]
    assert bounds == [(0, 334), (334, 667), (667, 1000)]
    data = {"seed": SEED, "traffic": traffic}
    for k in range(200):
        gaps = L.gaps_of_round(data, k)
        assert gaps[:8] == [1] * 8
        assert all(2 <= g <= 16 for g in gaps[8:24])
        assert all(17 <= g <= 64 for g in gaps[24:])
    assert L.gaps_of_round(data, 3) == L.gaps_of_round(dict(data), 3)
    assert L.gaps_of_round(data, 3) != L.gaps_of_round(
        {"seed": SEED + 1, "traffic": traffic}, 3)


def test_small_cell_runs_correct_and_its_control_does_not(device_standin):
    """The harness's whole run at the small size: every answer the
    reference's, every tampered tip reached, every signature an answer
    relies on counted on the device; the control (every signature taken
    on trust) is not correct."""
    overrides = dict(SMALL)
    out = harness.run_cell("light1000.serve", SEED, 0.5, False,
                           time.perf_counter(), overrides=overrides,
                           control="also")
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 3 and out["failed"] == 0
    assert out["slowest"][0][2] == 64 + 8      # sigs + headers a round
    ctl = out["compared_control"]
    assert ctl["answer_mismatches"]["value"] > 0
    assert ctl["tampered_heights_unseen"]["value"] == 0


def test_forced_true_verdicts_are_not_correct(device_standin):
    """A program whose device says yes to every signature answers the two
    refused tampered tips wrongly."""
    device_standin.rule = lambda pk, msg, sig: True
    out = harness.run_cell("light1000.serve", SEED, 0.5, False,
                           time.perf_counter(), overrides=dict(SMALL))
    assert not out["correct"]
    assert out["compared"]["answer_mismatches"]["value"] > 0


def test_refusing_any_bad_row_is_not_correct(cell, device_standin,
                                             monkeypatch):
    """A plane that refuses a header for a wrong signature anywhere in its
    commit (no early exit) answers the past-two-thirds tip wrongly."""
    from tendermint_tpu.types.validator_set import ValidatorSet

    data, spec = cell

    def every_row(self, chain_id, block_id, height, commit):
        self.verify_commit(chain_id, block_id, height, commit)

    monkeypatch.setattr(ValidatorSet, "verify_commit_light", every_row)
    tip = next(h for h, r in data["regions"].items()
               if r == "past_two_thirds")
    answers = _ask(data, tip, [1, 2])
    assert answers != [spec.answer(tip, 1), spec.answer(tip, 2)]


def test_new_counters_grow_with_every_flush_and_keep_status(cell,
                                                           device_standin):
    """build_s, collect_s and replay_s grow with every round; every key the
    plane's status() returned before them is there, beside them and the
    coalescer's commit counters."""
    data, _ = cell
    plane = data["plane"]
    before = plane.status()
    old = {"served": {"headers_served", "verifies_served", "prefetched"},
           "coalescer": {"requests", "flushes", "largest_flush",
                         "coalesced_dupes", "verdict_cache_hits", "sheds",
                         "batched_sigs", "verified_requests"},
           "cache": {"hits", "misses", "evictions", "resident", "pinned"},
           "limiter": {"admitted", "rate_sheds", "ban_sheds"}}
    assert {k: set(v) for k, v in before.items()} == {
        "served": old["served"] | {"build_s"},
        "coalescer": old["coalescer"] | {"collect_s", "replay_s",
                                         "commits_collected",
                                         "commits_reused"},
        "cache": old["cache"], "limiter": old["limiter"],
        "loaded": {"hits", "misses", "evictions", "resident", "rows"}}
    tip = data["pool"][-1]
    for _ in range(2):
        last = L.program_counters(plane)
        flushes = plane.coalescer.stats["flushes"]
        _ask(data, tip, L.gaps_of_round(data, 0))
        now = L.program_counters(plane)
        assert plane.coalescer.stats["flushes"] == flushes + 1
        assert all(now[k] > last[k] > -1 for k in L.COUNTERS), (last, now)
    after = plane.status()
    assert after["coalescer"]["verified_requests"] == (
        before["coalescer"]["verified_requests"] + 16)
    assert after["served"]["verifies_served"] == (
        before["served"]["verifies_served"] + 16)
    # each round's requests share the tip's loaded commit: one collection
    # a flush, every other request of it reuses that
    assert after["coalescer"]["commits_collected"] == (
        before["coalescer"]["commits_collected"] + 2)
    assert after["coalescer"]["commits_reused"] == (
        before["coalescer"]["commits_reused"] + 14)


#: the per-layer metric that reads each of the plane's new counters
METRIC_OF = {"build_s": "store_load_ms.light",
             "collect_s": "sign_bytes_ms.light",
             "replay_s": "replay_ms.light"}


class _PlaneWithoutCounters:
    """The plane as the parent commit has it: no build_s, collect_s or
    replay_s in its stats, every request accepted."""

    def __init__(self):
        self.stats = {"headers_served": 0, "verifies_served": 0,
                      "prefetched": 0}
        self.coalescer = types.SimpleNamespace(
            stats={"requests": 0, "flushes": 0})
        self.stopped = False

    async def serve_verify(self, height, trusted_height, trust_level=(1, 3),
                           client_id=""):
        self.coalescer.stats["requests"] += 1
        return None

    def stop(self):
        self.stopped = True


def test_parent_side_reads_nothing_and_never_fails(cell):
    data, _ = cell
    fake = dict(data, plane=_PlaneWithoutCounters(),
                extras={"device_sigs": 0})
    assert L.program_counters(fake["plane"]) == dict.fromkeys(L.COUNTERS)
    requests = L.window(fake, 0.0, harness.Probe())
    # a window past its deadline runs on to the last tampered tip
    last = max(data["pool"].index(h) for h in data["tampered"])
    assert len(requests) == last + 1
    assert not any(r["failed"] for r in requests)
    assert fake["plane"].stopped
    assert all(fake["extras"][k] is None for k in L.COUNTERS)
    win = harness.Window({}, {}, {}, {}, 0.0, 0.0, 1.0, requests, {}, {},
                         [], extras=fake["extras"])
    for key, metric in METRIC_OF.items():
        spec = harness.load_json("metrics", metric + ".json")
        assert spec == {"reader": "extras_ms_per_request",
                        "args": {"key": key}}
        assert harness.read_metric(metric, win) is None
    win.extras = dict(fake["extras"], build_s=0.5)
    assert harness.read_metric("store_load_ms.light", win) == pytest.approx(
        1e3 * 0.5 / len(requests))


def test_tampered_tips_are_drawn_among_the_whole_pool():
    """Three distinct tips a seed, each in its own region; over seeds every
    part of the 64-tip pool is reached, its last quarter too, so a window
    runs on past its deadline to the last of them."""
    import data as D

    _, traffic = _files()
    pool = list(range(65, 129))
    vals = D.make_validators(64, SEED, power=10)
    seen = set()
    for seed in range(SEED, SEED + 60):
        regions, rows = L.draw_tampered(vals, pool, traffic["tampered_rows"],
                                        seed)
        assert len(regions) == 3 and set(regions) <= set(pool)
        assert sorted(regions.values()) == sorted(L.REGIONS)
        for h, region in regions.items():
            lo, hi = (L._row_bound(vals, *f) for f in L.REGIONS[region])
            assert lo <= rows[h] < hi
        seen |= set(regions)
    assert min(seen) < 70 and max(seen) > 120
    assert L.draw_tampered(vals, pool, traffic["tampered_rows"], SEED) == (
        L.draw_tampered(vals, pool, traffic["tampered_rows"], SEED))


def test_reference_hashes_each_header_as_its_commit_names_it(cell):
    """The reference's own Header.Hash over the plain fields is the hash
    every seen commit of the chain signs."""
    data, _ = cell
    chain = data["chain"]
    for h, header in chain["headers"].items():
        assert light_spec.header_hash(header) == chain["seen"][h].block_id.hash


#: a header field the reference holds to its own hashes: (which header,
#: field), for a tip asked about with a gap of 1 and of 5
ALTERED = [("new", "app_hash"), ("new", "validators_hash"),
           ("trusted", "next_validators_hash"), ("trusted", "validators_hash")]


@pytest.mark.parametrize("which,field", ALTERED)
def test_reference_refuses_a_header_it_does_not_hash_to(cell, which, field):
    """A header whose commit names another hash, or a trusted header that
    names another validator set, is refused as an invalid header on both
    paths, whatever the program's own hashes said."""
    data, spec = cell
    tip = next(h for h in data["pool"] if h not in data["tampered"])
    for gap in (1, 5):
        assert spec.answer(tip, gap) == ("accept",)
        at = tip if which == "new" else tip - gap
        headers = dict(data["chain"]["headers"])
        headers[at] = dict(headers[at], **{field: bytes(32)})
        altered = light_spec.Spec(data["vals"],
                                  dict(data["chain"], headers=headers))
        assert altered.answer(tip, gap) == ("ErrInvalidHeader", None)
