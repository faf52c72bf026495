"""The four per-layer metrics that read the CPU clocks on the serving
program's spans, the loop's park and the stream threads' sends (PR 37), and
the helper they share: on intervals small enough to count by hand, and on a
cut of a real v5e trace of ``serve_backlog`` kept as a fixture
(``fixtures/thread_spans_small.json``: ``plain`` is the helper's plain form)."""

import copy
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import readers, spec  # noqa: E402
from benchmark.layer_metrics import _program_spans, _thread_spans  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
METRICS = ("step_call_cpu_ms", "sched_offcpu_ms", "stream_send_in_call_pct",
           "loop_busy_pct")
A, B, C = "/host:CPU:3", "/host:CPU:7", "/host:CPU:8"


def _c(cpu, proc=None, **more):
    return {"cpu_ns": cpu, "proc_cpu_ns": cpu if proc is None else proc, **more}


def _hand_spans():
    """The scheduler's thread A and two connection threads B and C, times in
    ns, the window [0, 4000).

    Iteration 0 began before the window (its emit at 50 is the one before
    iteration 1's call). Iteration 1 [300,1300) ran 450 of its 1000 ns: it
    stood still 550, of which 150 in its collect and 40 in its prefill chunk
    are the device's by design, so 360 are not; its call [500,900) ran 100 of
    400 ns while the others burned 250, and two sends that overlap,
    [600,750) and [700,800), cover half of it. Iteration 2 [1400,2400) ran
    600: still 400, 300 of them in its collect, 100 not; its call [1500,1700)
    ran 150 of 200 and C's send [1650,1720) covers 50 of it; B's send at 1750
    lies in no call. Two parks, [2450,2950) ran out and [2950,3100) was woken.
    Iteration 3 dispatched no step; iteration 4 runs past the window's end."""
    return {"window": [0.0, 4000.0], "spans": [
        ["serving/iter", -200.0, 400.0, A, _c(300, iter=0)],
        ["serving/emit", 50.0, 100.0, A, _c(100, emitted=2)],
        ["serving/iter", 300.0, 1000.0, A, _c(450, 900, iter=1, ahead=1, discarded=0)],
        ["serving/admit", 300.0, 100.0, A, _c(60, admitted=1)],
        ["serving/prefill_chunk", 320.0, 60.0, A, _c(20, host_arg_bytes=100)],
        ["serving/mask", 400.0, 20.0, A, _c(20)],
        ["serving/step_args", 420.0, 80.0, A, _c(60, 70)],
        ["serving/step", 500.0, 400.0, A, _c(100, 350, host_arg_bytes=100)],
        ["serving/collect", 900.0, 200.0, A, _c(50)],
        ["serving/emit", 1100.0, 150.0, A, _c(150, emitted=2)],
        ["serving/stream_send", 600.0, 150.0, B, _c(40, req=7, tokens=1)],
        ["serving/stream_send", 700.0, 100.0, C, _c(30, req=8, tokens=1)],
        ["serving/iter", 1400.0, 1000.0, A, _c(600, 800, iter=2, ahead=0, discarded=3)],
        ["serving/admit", 1400.0, 50.0, A, _c(50, admitted=0)],
        ["serving/mask", 1450.0, 20.0, A, _c(20)],
        ["serving/step_args", 1470.0, 30.0, A, _c(30, 30)],
        ["serving/step", 1500.0, 200.0, A, _c(150, 170, host_arg_bytes=100)],
        ["serving/collect", 1700.0, 400.0, A, _c(100)],
        ["serving/emit", 2100.0, 250.0, A, _c(250, emitted=2)],
        ["serving/stream_send", 1650.0, 70.0, C, _c(20, req=8, tokens=1)],
        ["serving/stream_send", 1750.0, 50.0, B, _c(50, req=7, tokens=1)],
        ["serving/wait", 2450.0, 500.0, A, _c(5, woken=0, queue_depth=0, held=0)],
        ["serving/wait", 2950.0, 150.0, A, _c(5, woken=1, queue_depth=1, held=0)],
        ["serving/iter", 3100.0, 400.0, A, _c(380, iter=3)],
        ["serving/admit", 3100.0, 300.0, A, _c(290, admitted=1)],
        ["serving/iter", 3700.0, 600.0, A, _c(500, iter=4)],
        ["serving/step", 3800.0, 100.0, A, _c(90, host_arg_bytes=100)],
    ]}


def _without_clocks(plain):
    plain = copy.deepcopy(plain)
    for row in plain["spans"]:
        row[4].pop("cpu_ns", None)
        row[4].pop("proc_cpu_ns", None)
    return plain


def _read(name, plain, monkeypatch, trace=True):
    monkeypatch.setattr(_thread_spans, "run_profile", lambda: plain)
    m = {"name": name, **spec.load_layer_metric(name, REPO)}
    return readers.read(m, {"trace": {"devices": 1} if trace else None,
                            "operands": {}})


def test_the_view_picks_the_scheduler_s_thread_and_its_counted_iterations():
    v = _thread_spans.view(_hand_spans())
    assert [it["args"]["iter"] for it in v["its"]] == [1, 2]
    assert set(v["others"]) == {B, C}
    assert all(n != "serving/stream_send" for _s, _d, n, _a in v["sched"])
    assert _thread_spans.view(_without_clocks(_hand_spans())) is None
    assert _thread_spans.view({"window": [None, None], "spans": []}) is None
    assert _thread_spans.view(None) is None


def test_covered_counts_what_a_union_covers_of_an_interval():
    union = [[0.0, 10.0], [20.0, 30.0], [50.0, 60.0]]
    assert _thread_spans.covered(union, 5.0, 25.0) == 10.0
    assert _thread_spans.covered(union, 30.0, 50.0) == 0.0
    assert _thread_spans.covered(union, -5.0, 100.0) == 30.0
    assert _thread_spans.covered([], 0.0, 1.0) == 0.0


@pytest.mark.parametrize("name,by_hand,said", [
    ("step_call_cpu_ms", 125e-6,
     ["serving/step at the means: wall 0.000 cpu 0.000", "(n=2)"]),
    ("sched_offcpu_ms", 230e-6,
     ["n=2 iterations", "ahead in 1 of them (50.0%)", "discarded slot-steps 3"]),
    ("stream_send_in_call_pct", 100.0 * 250 / 600,
     ["n=2 calls, 4 sends on 2 threads (2.0 an iteration)",
      "wall 0.1 us at the median, 0.1 at the mean", "call's end in 17.2%"]),
    ("loop_busy_pct", 72.5,
     ["in 2 waits", "woken 1, with slots held 0", "(longest 0.00 ms)"]),
])
def test_each_metric_on_the_hand_spans(name, by_hand, said, monkeypatch, capsys):
    assert _read(name, _hand_spans(), monkeypatch) == pytest.approx(by_hand)
    out = capsys.readouterr().out
    assert all(words in out for words in said), out


def test_the_parts_of_loop_busy_add_up_to_the_window(monkeypatch, capsys):
    _read("loop_busy_pct", _hand_spans(), monkeypatch)
    out = capsys.readouterr().out
    # 2900 ns in an iteration, 650 parked, 450 under no span, of 4000
    assert "in an iteration 0.0000 s" in out
    v = _thread_spans.view(_hand_spans())
    busy = _thread_spans.union_of(v["sched"], "serving/iter")
    assert sum(min(e, 4000.0) - max(s, 0.0) for s, e in busy) == 2900.0
    parked = _thread_spans.union_of(v["sched"], "serving/wait")
    assert sum(e - s for s, e in parked) == 650.0


@pytest.mark.parametrize("name", METRICS)
def test_spans_without_the_clocks_read_nothing(name, monkeypatch):
    """The parent's program opens the same spans without ``cpu_ns``; PR 25's
    fixture was recorded from such a program; a training cell has no span; an
    untraced run has no trace to look in."""
    assert _read(name, _without_clocks(_hand_spans()), monkeypatch) is None
    with open(os.path.join(FIXTURES, "program_spans_small.json")) as f:
        assert _read(name, json.load(f), monkeypatch) is None
    assert _read(name, {"window": [None, None], "spans": []}, monkeypatch) is None
    assert _read(name, None, monkeypatch) is None
    assert _read(name, _hand_spans(), monkeypatch, trace=False) is None


def test_no_stream_reads_no_send_share_and_leaves_the_others(monkeypatch):
    plain = _hand_spans()
    plain["spans"] = [r for r in plain["spans"] if r[0] != "serving/stream_send"]
    assert _read("stream_send_in_call_pct", plain, monkeypatch) is None
    assert _read("step_call_cpu_ms", plain, monkeypatch) == pytest.approx(125e-6)


def test_the_helper_parses_the_profile_once_through_program_spans(monkeypatch):
    calls = []
    monkeypatch.setattr(_program_spans, "run_profile",
                        lambda: calls.append(1) or _hand_spans())
    assert _thread_spans.of_run({"trace": {"devices": 1}})["its"]
    assert calls == [1]
    assert _thread_spans.of_run({"trace": None}) is None and calls == [1]


def test_the_benchmark_names_the_four_metrics_for_the_serving_cells():
    bench = spec.load_benchmark(REPO)
    assert tuple(m["name"] for m in bench["per_layer"][-4:]) == METRICS
    serving = ["serve_backlog", "serve_backlog_kanana", "serve_backlog_longcat",
               "serve_backlog_laguna"]
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == serving and m["source"] == "program_span"
        assert m["moves"] == "serve_tokens_per_s" and m["unit"] in ("ms", "%")
        got = spec.load_layer_metric(m["name"], REPO)
        assert got["reader"] == "python" and callable(got["read"])
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["sched_offcpu_ms"] == layers["loop_busy_pct"] == layers["sched_iter_ms"]
    assert layers["step_call_cpu_ms"] == layers["step_call_ms"]
    assert layers["stream_send_in_call_pct"] == "server streams (serving/server.py)"
    train = {m["name"] for m in spec.load_cell("train_seq2048", REPO)["per_layer"]}
    assert not set(METRICS) & train


def test_the_recorded_cut_of_a_v5e_trace(monkeypatch, capsys):
    """A send lies inside a call and the loop's thread stood still in it, so
    each of the four reads above 0 on the cut, and what they read is what a
    sweep over the interval ends gave when the cut was made."""
    with open(os.path.join(FIXTURES, "thread_spans_small.json")) as f:
        cut = json.load(f)
    plain, by_hand = cut["plain"], cut["by_hand"]
    v = _thread_spans.view(plain)
    assert len(v["its"]) == by_hand["iterations"] >= 4
    # fewer than the cut's serving/iter spans: its edges cost two
    assert len(v["its"]) < sum(n == "serving/iter" for _s, _d, n, _a in v["sched"])
    assert len(v["others"]) >= by_hand["send_threads"] > 1
    for name, _start, dur, _thread, args in plain["spans"]:
        if name == "serving/stream_send":  # a plain span: no clock of its own
            assert "cpu_ns" not in args and args["tokens"] >= 1
            continue
        # a tick of the host's CPU clocks is 10 ms: a span reads 0 or whole ticks
        assert args["cpu_ns"] % 10_000_000 == 0 <= args["cpu_ns"]
        assert args["proc_cpu_ns"] >= args["cpu_ns"]
    for name in METRICS:
        value = _read(name, plain, monkeypatch)
        assert value == pytest.approx(by_hand[name], rel=1e-9) and value > 0
    out = capsys.readouterr().out
    assert f"{by_hand['sends']} sends on {by_hand['send_threads']} threads" in out
    assert f"in {by_hand['waits']} waits" in out
    # the call's CPU time is no more than its wall time, in the cut as a whole
    assert by_hand["step_call_cpu_ms"] <= by_hand["step_call_ms"]
    monkeypatch.setattr(_program_spans, "run_profile", lambda: plain)
    m = {"name": "step_call_ms", **spec.load_layer_metric("step_call_ms", REPO)}
    assert readers.read(m, {"trace": {"devices": 1}, "operands": {}}) == \
        pytest.approx(by_hand["step_call_ms"])
