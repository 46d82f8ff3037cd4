"""The trace reduction: on hand-made planes (exact numbers), and on the
small trace recorded on a v5e chip and checked in under data/."""

import json
import os

import pytest

from readers import device_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_pair_executions_runs_the_queue_in_order():
    # the second program is handed over while the first still runs: it is
    # no burst's first
    assert device_trace.pair_executions([1.0, 1.2, 5.0], [2.0, 2.5, 5.5]) == [
        (1.0, 2.0, True), (2.0, 2.5, False), (5.0, 5.5, True)]
    with pytest.raises(ValueError):
        device_trace.pair_executions([1.0, 1.2], [2.0])
    with pytest.raises(ValueError):
        device_trace.pair_executions([3.0], [2.0])


def _planes():
    # trace clock = perf_counter + 100 s; window 100.0 .. 101.0
    return {
        "devices": [{"name": "tpu core 0", "handed": [100.10, 100.50],
                     "finished": [100.30, 100.60]}],
        "host": [(100.0, 101.0, "bench.traced_window"),
                 (100.05, 100.65, "bench.request")],
    }


def _segments():
    return [
        {"plane": "votes", "sigs": 6144, "chunk": 2048, "t0": 0.06,
         "pack_s": 0.03, "dispatch_s": 0.01, "fetch_s": 0.22, "t_end": 0.32},
        {"plane": "votes", "sigs": 4000, "chunk": 2048, "t0": 0.33,
         "pack_s": 0.10, "dispatch_s": 0.05, "fetch_s": 0.13, "t_end": 0.61},
    ]


def test_reduce_planes_exact():
    spans = [{"name": "request", "t0": 0.05, "t1": 0.65}]
    tr = device_trace.reduce_planes(_planes(), spans, (0.0, 1.0), _segments(),
                                    calibrated=False)
    assert tr["window_s"] == pytest.approx(1.0)
    assert tr["busy_s"] == pytest.approx(0.20 + 0.10)
    assert tr["sigs"] == 10144 and tr["lanes"] == 10240
    assert tr["device_ops"] == [
        ["verify kernel K=3 (votes)", pytest.approx(0.20)],
        ["verify kernel K=2 (votes)", pytest.approx(0.10)]]
    idle = dict((k, v) for k, v in tr["idle_gaps"])
    # idle: 100.00-.10, .30-.50, .60-101.0
    assert sum(idle.values()) == pytest.approx(1.0 - 0.30)
    assert idle["pack"] == pytest.approx(0.03 + 0.10)      # .06-.09, .33-.43
    assert idle["dispatch"] == pytest.approx(0.01 + 0.05)  # .09-.10, .43-.48
    assert idle["in_flight"] == pytest.approx(0.02 + 0.02 + 0.01)
    assert idle["in_request_other"] == pytest.approx(0.01 + 0.01 + 0.04)
    assert idle["between_requests"] == pytest.approx(0.05 + 0.35)
    k = device_trace.kernel(tr, "verify.*kernel")
    assert k == {"seconds": pytest.approx(0.30), "executions": 2,
                 "sigs": 10144}
    assert device_trace.kernel(tr, "no_such_program") is None
    assert device_trace.kernel(None, "verify") is None


def test_executions_the_segments_do_not_account_for_give_no_result():
    with pytest.raises(ValueError, match="cannot be told apart"):
        device_trace.reduce_planes(_planes(), [], (0.0, 1.0),
                                   _segments()[:1], calibrated=False)


def test_reduce_planes_needs_the_window_mark_and_an_execution():
    p = _planes()
    p["host"] = []
    with pytest.raises(ValueError):
        device_trace.reduce_planes(p, [], (0.0, 1.0), [], calibrated=False)
    p = _planes()
    p["devices"][0].update(handed=[], finished=[])
    with pytest.raises(ValueError):
        device_trace.reduce_planes(p, [], (0.0, 1.0), [], calibrated=False)


def test_hand_over_latency_comes_off_the_first_of_every_burst():
    p = _planes()
    # a second burst of two: the later one queues behind the earlier one;
    # then, after the window, three calibration runs of 10, 12 and 30 ms
    p["devices"][0]["handed"] += [100.70, 100.72, 101.10, 101.20, 101.30]
    p["devices"][0]["finished"] += [100.80, 100.90, 101.11, 101.212, 101.33]
    segs = _segments() + [dict(_segments()[0], t0=0.69, t_end=0.81),
                          dict(_segments()[1], t0=0.70, t_end=0.91)]
    tr = device_trace.reduce_planes(p, [], (0.0, 1.0), segs)
    assert tr["handover_s"] == pytest.approx(0.012)
    # three bursts in the window, 12 ms off each; the queued one keeps all
    assert tr["busy_s"] == pytest.approx(0.20 + 0.10 + 0.20 - 3 * 0.012)
    assert [round(x[2], 6) for x in tr["executions"]] == [
        0.188, 0.088, 0.088, 0.1]
    # a trace without the calibration gives nothing
    with pytest.raises(ValueError, match="calibration"):
        device_trace.reduce_planes(_planes(), [], (0.0, 1.0), _segments())


def test_recorded_v5e_trace():
    """Six executions of an 8.4 us toy program, two a request, and the
    harness's calibration runs after the window, recorded on a v5e chip with
    the device's own plane on. The plane says when the chip really ran each
    program: the runtime's events have to bracket every one of them, the
    calibrated hand-over latency has to be the latency these six show, and
    the reduction's device seconds have to come out at the chip's own."""
    path = os.path.join(DATA, "v5e_small.xplane.pb")
    with open(os.path.join(DATA, "v5e_small.json")) as f:
        rec = json.load(f)
    tr = device_trace.reduce(path, rec["spans"], tuple(rec["traced"]),
                             rec["segments"])
    want = rec["expect"]
    assert tr["devices"] == ["tpu core 0"]
    assert tr["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert tr["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert tr["handover_s"] == pytest.approx(want["handover_s"], rel=1e-9)
    assert 0 < tr["busy_s"] < tr["window_s"]
    assert tr["sigs"] == 6 * 2048
    k = device_trace.kernel(tr, "verify.*kernel")
    assert k["executions"] == 6
    assert k["seconds"] == pytest.approx(tr["busy_s"])

    # the device's own record of the same programs
    import statistics

    import jax

    import harness

    pd = jax.profiler.ProfileData.from_file(path)
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    mods = list(next(ln for ln in dev.lines
                     if ln.name == "XLA Modules").events)
    toy = [e.duration_ns * 1e-9 for e in mods if "toy_kernel" in e.name]
    assert len(toy) == 6
    assert len(mods) == 6 + harness.Probe.CALIBRATION_RUNS
    planes = device_trace.load(path)["devices"][0]
    raw = device_trace.pair_executions(planes["handed"],
                                       planes["finished"])[:6]
    latency = [(e - s) - on_chip for (s, e, _first), on_chip in zip(raw, toy)]
    assert all(0.2e-3 < late < 1.5e-3 for late in latency), latency
    assert tr["handover_s"] == pytest.approx(statistics.median(latency),
                                             abs=0.3e-3)
    # what is left on a burst after the correction: under 0.35 ms each
    assert tr["busy_s"] == pytest.approx(sum(toy), abs=6 * 0.35e-3)
