"""Every cell of ``BENCHMARK.json`` finds its files by name; the new
deployment's data is what its files say."""

import importlib.util

import pytest

import harness

BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_file_of_a_cell_is_found_by_name(name):
    cell = harness.find_cell(BENCH, name)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    config = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    assert entry["file"] == f"benchmarks/configs/{cell['config']}.json"
    assert (config["name"], traffic["name"]) == (cell["config"],
                                                 cell["traffic"])
    assert config["chips"] == cell["chips"]
    assert config["reduced"] == entry["reduced"]
    assert importlib.util.find_spec("drivers." + traffic["driver"])
    assert importlib.util.find_spec("reference." + config["reference"])
    e2e = harness.metrics_of(BENCH, name, "end_to_end")
    per_layer = harness.metrics_of(BENCH, name, "per_layer")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert per_layer
    reported = {m["name"] for m in e2e}
    for m in e2e + per_layer:
        spec = harness.load_json("metrics", m["name"] + ".json")
        assert importlib.util.find_spec("readers." + spec["reader"]), m
        # a per-layer metric moves an end-to-end metric this cell reports
        assert m.get("moves", m["name"]) in reported, m


def test_unknown_names_give_no_result():
    with pytest.raises(harness.BenchmarkError):
        harness.find_cell(BENCH, "commit150.dead")
    with pytest.raises(harness.BenchmarkError):
        harness.load_json("configs", "vals151.json")


def test_vals150_is_vals10000_key_for_key_at_its_own_size():
    big = harness.load_json("configs", "vals10000.json")
    small = harness.load_json("configs", "vals150.json")
    assert list(small) == list(big)
    assert list(small["assumed"]) == list(big["assumed"])
    same = ("reference", "key_type", "sign_bytes", "routing", "power",
            "heavy_power", "reduced", "guarantees", "chips")
    assert {k: small[k] for k in same} == {k: big[k] for k in same}
    assert (small["validators"], small["heavy_validators"]) == (150, 0)
    assert len(small["source"]) <= 200 and small["source"] != big["source"]


@pytest.mark.parametrize("seed", [30, 2 ** 31 + 77])
def test_commit150_data_is_what_its_files_say(seed):
    """150 equal-power validators, 32 commits at successive heights, one of
    them with one signature tampered at a row past the 2/3-power prefix
    (rows 101-149), and the reference refuses exactly that row while its
    control, the 2/3 early exit, accepts."""
    from drivers import closed_loop_commits
    from reference import commit_spec

    config = harness.load_json("configs", "vals150.json")
    traffic = harness.load_json("traffic", "live.json")
    data = closed_loop_commits.build(config, traffic, seed)
    vals, plain = data["vals"], data["plain"]
    assert len(vals) == 150 and set(vals.powers) == {10}
    assert [c.height for c in plain] == list(range(100, 132))
    assert all(len(c.signatures) == 150 for c in plain)
    bad = [c for c in plain if c.tampered_rows]
    assert len(bad) == 1 and len(bad[0].tampered_rows) == 1
    row = bad[0].tampered_rows[0]
    assert 101 <= row <= 149
    assert commit_spec.verify_commit(vals, bad[0]) == ("wrong_signature", row)
    assert commit_spec.control(vals, bad[0]) == ("accept",)
    sound = next(c for c in plain if not c.tampered_rows)
    assert commit_spec.verify_commit(vals, sound) == ("accept",)
    assert len(data["commits"]) == 32
    assert all(len(c.signatures) == 150 for c in data["commits"])
