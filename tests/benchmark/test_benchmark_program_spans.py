"""The six per-layer metrics that read the serving program's own spans
(PR 25) and the helper they share: on intervals small enough to count by
hand, and on a cut of a real v5e trace of ``serve_backlog`` kept as a
fixture (``fixtures/program_spans_small.json``, the helper's plain form)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import readers, spec  # noqa: E402
from benchmark.layer_metrics import _program_spans  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "program_spans_small.json")
METRICS = ("sched_iter_ms", "sched_host_ms", "step_call_ms", "step_args_ms",
           "step_host_arg_mb", "kv_pages_in_use_pct")
A, B = "/host:CPU:3", "/host:CPU:4"


def _pool(used, waits, **more):
    return {"iter": 1, "active": 32, "prefilling": 0, "queue_depth": 32,
            "pages_in_use": used, "pages_total": 100, "page_waits": waits,
            **more}


def _hand_spans():
    """One scheduler thread (A), times in ns, the window [-300, 3500).

    Iteration 1 [0,1000): admission [0,300) with a prefill chunk [100,250),
    collect [300,500), emit, mask, step_args [650,700), step [700,950): its
    calls cover 150 + 200 + 50 + 250 = 650, so 350 are the scheduler's own.
    Iteration 2 [1000,2200): two prefill chunks that overlap, [1050,1250) and
    [1200,1350) (300 together), collect [1400,1700), step_args [1850,1900),
    step [1900,2150): 900 covered, 300 its own; admission waited for pages.
    Iteration 3 dispatched no step; iteration 4 runs past the window's end;
    a step and a collect before the first iteration have lost their parent
    to the trace's start; thread B's step lies inside iteration 1's
    interval and is none of its children."""
    return {"window": [-300.0, 3500.0], "spans": [
        ["serving/step", -250.0, 30.0, A, {"host_arg_bytes": 7}],
        ["serving/collect", -200.0, 150.0, A, {}],
        ["serving/iter", 0.0, 1000.0, A, _pool(50, 0)],
        ["serving/admit", 0.0, 300.0, A, {"admitted": 1}],
        ["serving/prefill_chunk", 100.0, 150.0, A, {"host_arg_bytes": 1000}],
        ["serving/collect", 300.0, 200.0, A, {}],
        ["serving/emit", 500.0, 100.0, A, {"emitted": 32}],
        ["serving/mask", 600.0, 50.0, A, {}],
        ["serving/step_args", 650.0, 50.0, A, {}],
        ["serving/step", 700.0, 250.0, A, {"host_arg_bytes": 1_000_000}],
        ["serving/step", 700.0, 100.0, B, {"host_arg_bytes": 9}],
        ["serving/iter", 1000.0, 1200.0, A, _pool(80, 1, iter=2)],
        ["serving/admit", 1000.0, 400.0, A, {"admitted": 0}],
        ["serving/prefill_chunk", 1050.0, 200.0, A, {"host_arg_bytes": 1000}],
        ["serving/prefill_chunk", 1200.0, 150.0, A, {"host_arg_bytes": 1000}],
        ["serving/collect", 1400.0, 300.0, A, {}],
        ["serving/emit", 1700.0, 100.0, A, {"emitted": 32}],
        ["serving/mask", 1800.0, 50.0, A, {}],
        ["serving/step_args", 1850.0, 50.0, A, {}],
        ["serving/step", 1900.0, 250.0, A, {"host_arg_bytes": 3_000_000}],
        ["serving/iter", 2200.0, 400.0, A, _pool(99, 0, iter=3, active=0)],
        ["serving/admit", 2200.0, 300.0, A, {"admitted": 0}],
        ["serving/mask", 2500.0, 50.0, A, {}],
        ["serving/iter", 2600.0, 1000.0, A, _pool(99, 0, iter=4)],
        ["serving/admit", 2600.0, 100.0, A, {"admitted": 0}],
        ["serving/step_args", 2700.0, 50.0, A, {}],
        ["serving/step", 2750.0, 250.0, A, {"host_arg_bytes": 5_000_000}],
    ]}


def _read(name, plain, monkeypatch, trace=True):
    monkeypatch.setattr(_program_spans, "run_profile", lambda: plain)
    m = {"name": name, **spec.load_layer_metric(name, REPO)}
    return readers.read(m, {"trace": {"devices": 1} if trace else None,
                            "operands": {}})


def test_iterations_nest_by_thread_and_leave_out_what_the_edge_cut():
    its = _program_spans.iterations(_hand_spans())
    assert [it["args"]["iter"] for it in its] == [1, 2]
    assert [it["dur_ns"] for it in its] == [1000.0, 1200.0]
    assert [it["self_ns"] for it in its] == [350.0, 300.0]
    assert [len(it["spans"]["serving/step"]) for it in its] == [1, 1]
    assert len(its[1]["spans"]["serving/prefill_chunk"]) == 2
    assert _program_spans.span_values(its, "serving/step", "host_arg_bytes") == [
        1_000_000, 3_000_000]


@pytest.mark.parametrize("name,by_hand", [
    ("sched_iter_ms", 1100e-6), ("sched_host_ms", 325e-6),
    ("step_call_ms", 250e-6), ("step_args_ms", 50e-6),
    ("step_host_arg_mb", 2.0), ("kv_pages_in_use_pct", 65.0)])
def test_each_metric_on_the_hand_spans(name, by_hand, monkeypatch, capsys):
    assert _read(name, _hand_spans(), monkeypatch) == pytest.approx(by_hand)
    said = capsys.readouterr().out
    assert f"{name}: n=2 " in said  # the sample count, on an earlier line
    if name == "kv_pages_in_use_pct":
        assert "waited for pages in 1 of them (50.0%)" in said


@pytest.mark.parametrize("name", METRICS)
def test_a_run_without_iteration_spans_reads_nothing(name, monkeypatch):
    """The parent's program has ``serving/step`` and ``serving/prefill_chunk``
    and no ``serving/iter``; a training cell has none of them; an untraced
    run has no trace to look in."""
    parent = {"window": [0.0, 900.0], "spans": [
        ["serving/prefill_chunk", 10.0, 50.0, A, {}],
        ["serving/step", 100.0, 250.0, A, {}]]}
    assert _read(name, parent, monkeypatch) is None
    assert _read(name, {"window": [None, None], "spans": []}, monkeypatch) is None
    assert _read(name, None, monkeypatch) is None
    assert _read(name, _hand_spans(), monkeypatch, trace=False) is None


def test_no_profile_on_disk_reads_nothing(tmp_path):
    assert _program_spans.run_profile(str(tmp_path)) is None


def test_the_recorded_cut_of_a_v5e_trace():
    with open(FIXTURE) as f:
        plain = json.load(f)
    its = _program_spans.iterations(plain)
    names = {name for name, *_ in plain["spans"]}
    assert {"serving/iter", "serving/admit", "serving/mask", "serving/step_args",
            "serving/step", "serving/collect", "serving/emit"} <= names
    assert len(its) == plain["by_hand"]["iterations"]
    # fewer than the cut's serving/iter spans: its edges cost some
    assert len(its) < sum(name == "serving/iter" for name, *_ in plain["spans"])
    for it in its:
        assert {"serving/step", "serving/collect", "serving/step_args"} <= set(
            it["spans"])
        assert 0 < it["self_ns"] < it["dur_ns"]
        assert it["args"]["pages_total"] == 1407 and it["args"]["active"] <= 32
    assert _program_spans.median_ms(
        "sched_iter_ms", [it["dur_ns"] for it in its], "") == pytest.approx(
            plain["by_hand"]["sched_iter_ms"])
    assert sorted(it["self_ns"] for it in its)[len(its) // 2] / 1e6 == pytest.approx(
        plain["by_hand"]["sched_host_ms_upper_median"])


def test_the_benchmark_names_the_six_metrics_for_the_serving_cell_alone():
    bench = spec.load_benchmark(REPO)
    assert tuple(m["name"] for m in bench["per_layer"][-6:]) == METRICS
    assert len(bench["per_layer"]) == 16
    serve = {m["name"] for m in spec.load_cell("serve_backlog", REPO)["per_layer"]}
    train = {m["name"] for m in spec.load_cell("train_seq2048", REPO)["per_layer"]}
    assert set(METRICS) <= serve and not set(METRICS) & train
    for name in METRICS:
        m = spec.load_layer_metric(name, REPO)
        assert m["reader"] == "python" and callable(m["read"])
    # the helper beside them is no metric, and adds nothing to the trace table
    assert spec.load_trace_table(REPO)["host_span_prefixes"] == ["bench/", "serving/"]
