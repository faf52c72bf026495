"""The reduction from a trace to numbers, on a small recorded trace kept as
a fixture (a cut of a real v5e trace of ``train_dp4``'s kind, PR 23) and on
intervals small enough to count by hand."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import spec, trace_reduce  # noqa: E402

TABLE = spec.load_trace_table(REPO)
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_small.json")


def _hand_trace():
    """One device, one host thread, named as a v5e trace names them. Times
    in ns. Core: [0,100) fusion, [100,150) all-reduce, [120,140) fusion
    (hides 20 of the all-reduce), [300,400) a Pallas kernel; an asynchronous
    all-gather [380,420); module jit_window [0,400)."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_window(123)", 0.0, 400.0]]},
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-reduce.9)", 0.0, 100.0],
                ["%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1)", 100.0, 50.0],
                ["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 120.0, 20.0],
                ["%jvp__.4 = bf16[4]{0} custom-call(bf16[4]{0} %q), "
                 "custom_call_target=\"tpu_custom_call\"", 300.0, 100.0]]},
            {"name": "Async XLA Ops", "events": [
                ["%all-gather-start.7 = f32[8]{0} all-gather-start(f32[2]{0} %x)",
                 380.0, 40.0]]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench/fetch_loss", 140.0, 170.0], ["Thread", 0.0, 500.0]]},
        ]},
    ]}


def test_interval_arithmetic():
    assert trace_reduce.merge([[5, 7], [0, 2], [1, 3]]) == [[0, 3], [5, 7]]
    assert trace_reduce.total([[0, 3], [5, 7]]) == 5
    assert trace_reduce.subtract([[0, 10]], [[2, 3], [5, 7]]) == [
        [0, 2], [3, 5], [7, 10]]
    assert trace_reduce.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]


def test_hand_trace_busy_idle_programs_and_exposed_collective():
    r = trace_reduce.reduce_trace(_hand_trace(), TABLE)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["busy_s"] == pytest.approx(250e-9)  # [0,150) and [300,400)
    assert r["idle_pct"] == pytest.approx(50.0)
    assert r["programs"]["train_window"]["total_s"] == pytest.approx(400e-9)
    assert r["programs"]["train_window"]["count"] == 1
    # the all-reduce [100,150) and the asynchronous all-gather [380,420)
    assert r["collective_s"] == pytest.approx(90e-9)
    # hidden: [120,140) under fusion.3 and [380,400) under the kernel
    assert r["collective_exposed_s"] == pytest.approx(50e-9)
    assert r["kernels"]["flash"] == {"count": 1, "total_s": pytest.approx(100e-9)}
    assert r["device_ops"][0][1] == pytest.approx(100e-9)
    assert r["device_ops"][0][0].startswith(("fusion.1 f32[8]", "jvp__.4 bf16[4]"))
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["bench/fetch_loss"] == pytest.approx(150e-9)  # [150,300)
    assert gaps["Thread"] == pytest.approx(100e-9)  # [400,500)


def test_an_unknown_program_is_reported_not_dropped():
    trace = _hand_trace()
    trace["planes"][0]["lines"][0]["events"].append(["jit_mystery(9)", 410.0, 20.0])
    r = trace_reduce.reduce_trace(trace, TABLE)
    assert r["unknown_programs"] == {"jit_mystery(9)": pytest.approx(20e-9)}


def test_a_trace_with_no_device_plane_reads_nothing():
    trace = {"planes": [p for p in _hand_trace()["planes"] if "host" in p["name"]]}
    assert trace_reduce.reduce_trace(trace, TABLE) == {"devices": 0}


def _busy_by_sweep(events):
    """The union of intervals by counting open intervals at sorted ends:
    another way than ``merge``."""
    ends = sorted([(s, 1) for _, s, d in events] + [(s + d, -1) for _, s, d in events],
                  key=lambda p: (p[0], -p[1]))
    busy, depth, since = 0.0, 0, None
    for t, step in ends:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_trace():
    """A slice of a real v5e trace (see the fixture's ``what``): the
    numbers the reducer read when it was recorded, the busy union counted
    another way, and what must hold of any trace."""
    with open(FIXTURE) as f:
        fixture = json.load(f)
    r = trace_reduce.reduce_trace(fixture["trace"], TABLE)
    want = fixture["expected"]
    assert r["devices"] == want["devices"] == 2
    for key in ("window_s", "busy_s", "idle_pct", "collective_s",
                "collective_exposed_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-6), key
    for fam, numbers in want["programs"].items():
        assert r["programs"][fam]["total_s"] == pytest.approx(numbers["total_s"])
    per_device = [
        _busy_by_sweep([e for l in p["lines"] if l["name"] == "XLA Ops"
                        for e in l["events"]])
        for p in fixture["trace"]["planes"] if p["name"].startswith("/device")]
    assert r["busy_s"] == pytest.approx(sum(per_device) / 2 / 1e9, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["collective_exposed_s"] <= r["collective_s"] < r["busy_s"]
    assert r["programs"]["train_window"]["count"] >= 1
    assert any(name.startswith("all-reduce") for name, _ in r["device_ops"])
    assert not r["unknown_programs"]
