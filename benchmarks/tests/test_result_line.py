"""The result line stays short whatever the window held, is strict JSON,
and a line that cannot be read is refused where it was made."""

import json

import numpy as np
import pytest

import harness

CONTRACT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def _strict(line: str) -> dict:
    def refuse(name):
        raise ValueError(f"not strict JSON: {name}")

    return json.loads(line, parse_constant=refuse)


def _result(n_requests: int, seed: int = 30) -> tuple:
    """A result object as ``run_cell`` puts it together, over a synthetic
    window of ``n_requests`` back-to-back requests of ~8 ms."""
    rng = np.random.default_rng(seed)
    lengths = rng.uniform(0.0078, 0.0089, n_requests)
    lengths[rng.integers(0, n_requests, 3)] = [3.852, 0.231, 0.229]
    starts = np.concatenate([[0.0], np.cumsum(lengths)[:-1]]) + 100.0
    requests = [{"t0": float(a), "t1": float(a + b), "units": {"sigs": 150}}
                for a, b in zip(starts, lengths)]
    rows = harness.request_rows(requests, 100.0)
    compared = {k: {"value": 0, "limit": 0} for k in (
        "verdict_mismatches", "tampered_entries_unseen", "requests_unchecked",
        "compiles_in_window", "device_errors", "breaker_rejections",
        "open_breakers", "segments_off_device", "host_routed_sigs")}
    out = {"correct": True, "attempted": n_requests, "failed": 0,
           "metrics": {"request_p50_ms": {"value": 8.0205, "unit": "ms"},
                       "setup_s": {"value": 58.2, "unit": "s"}},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 32649216},
           "workload": "commit150.live", "seed": 2 ** 31 + 5,
           "window_s": float(starts[-1] + lengths[-1] - 100.0),
           "slowest": harness.slowest(rows), "compared": compared}
    return out, rows


@pytest.mark.parametrize("n_requests", [9, 6200, 100_000])
def test_line_is_short_and_strict_whatever_the_window_held(n_requests, capsys):
    out, rows = _result(n_requests)
    harness.print_result(out)
    printed = capsys.readouterr()
    lines = printed.out.splitlines()
    assert len(lines) == 1
    assert len(lines[0].encode()) < 4000 < harness.RESULT_LINE_LIMIT
    back = _strict(lines[0])
    assert all(k in back for k in CONTRACT_KEYS)
    assert "requests" not in back and list(back)[-1] == "compared"
    # the 8 longest, longest first, each [start_s, length_s, units]
    want = sorted(rows, key=lambda r: r[1], reverse=True)[:8]
    assert back["slowest"] == want
    assert back["slowest"][0][1] == pytest.approx(3.852)
    assert [r[1] for r in want] == sorted((r[1] for r in want), reverse=True)
    # each number compared beside its limit, as the last lines of stderr
    assert printed.err.splitlines()[-1] == (
        "compared host_routed_sigs: value 0 limit 0")


@pytest.mark.parametrize("spoil", [
    lambda out, rows: out.update(requests=rows),          # 100,000 triples
    lambda out, rows: out.update(padding="x" * harness.RESULT_LINE_LIMIT),
    lambda out, rows: out["metrics"]["request_p50_ms"].update(
        value=float("nan")),
    lambda out, rows: out["metrics"]["setup_s"].update(value=float("inf")),
])
def test_a_line_that_cannot_be_read_is_refused_and_nothing_printed(
        spoil, capsys):
    out, rows = _result(100_000)
    spoil(out, rows)
    with pytest.raises(harness.BenchmarkError):
        harness.print_result(out)
    assert capsys.readouterr().out == ""


def test_a_line_at_the_limit_passes_and_one_byte_more_does_not(capsys):
    out, _ = _result(9)
    out["note"] = ""
    out["note"] = "x" * (harness.RESULT_LINE_LIMIT - len(json.dumps(out)))
    harness.print_result(out)
    line = capsys.readouterr().out.rstrip("\n")
    assert len(line.encode()) == harness.RESULT_LINE_LIMIT
    out["note"] += "x"
    with pytest.raises(harness.BenchmarkError, match="16385 bytes"):
        harness.print_result(out)
    assert capsys.readouterr().out == ""
