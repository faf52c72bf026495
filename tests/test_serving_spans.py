"""The serving scheduler's spans on the profiler's timeline (PR 25): one
``serving/iter`` an iteration with its phases inside it, page and
host-argument counts on them, and nothing of it when nobody hands the
batcher a span factory.

The traced tests read the profile back through the benchmark's own helper
(``benchmark/layer_metrics/_program_spans.py``), so the names and the
arguments the six per-layer metrics read are pinned where they are made.
"""

from __future__ import annotations

import ast
import glob
import inspect
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.layer_metrics import _program_spans, _thread_spans  # noqa: E402
from distkeras_tpu.serving import scheduler  # noqa: E402
from distkeras_tpu.serving.scheduler import ContinuousBatcher, ServeRequest  # noqa: E402
from test_serving import FakeStepper  # noqa: E402

PHASES = ("serving/admit", "serving/mask", "serving/step_args",
          "serving/step", "serving/collect", "serving/emit")


def _lm():
    from distkeras_tpu.models import zoo

    return zoo.transformer_lm(vocab_size=61, seq_len=32, d_model=32,
                              num_heads=2, depth=2)


def _traced(tmp_path, drive):
    """``drive()`` under the profiler (host annotations only, as the
    benchmark takes its traces); the plain form of the profile's spans."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = drive()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    _program_spans.load.cache_clear()
    return out, _program_spans.load(path)


def _generate(engine, n_requests=4, steps=6):
    reqs = [engine.submit((np.arange(3 + i, dtype=np.int32) * 7) % 61, steps)
            for i in range(n_requests)]
    return [list(r.result(120)) for r in reqs]


@pytest.fixture(scope="module")
def paged_engine():
    from distkeras_tpu.serving import ServingEngine

    engine = ServingEngine(_lm(), num_slots=2, paged=True, page_size=4,
                           prefill_chunk=4)
    engine.start()
    _generate(engine, 2)  # compiles, off the traced drives
    yield engine
    engine.stop()


def test_every_iteration_span_holds_its_phases_on_its_thread(
        paged_engine, tmp_path):
    _, plain = _traced(tmp_path, lambda: _generate(paged_engine))
    its = _program_spans.iterations(plain)
    assert len(its) >= 6
    alloc = paged_engine._stepper._kv_alloc
    for it in its:
        # a child lies inside its parent on the parent's thread: that is
        # how ``iterations`` found it; here, that each phase is there
        assert set(PHASES) - {"serving/collect", "serving/emit"} <= set(it["spans"])
        assert it["self_ns"] <= it["dur_ns"]
        a = it["args"]
        assert a["pages_total"] == alloc.total_pages
        assert 0 < a["pages_in_use"] <= a["pages_total"]
        assert 1 <= a["active"] <= 2 and a["page_waits"] in (0, 1)
        assert a["iter"] > 0 and a["queue_depth"] >= 0 and a["prefilling"] >= 0
    # the overlapped loop collects, then emits, the step the iteration
    # before dispatched: every iteration but the first after an idle bank
    assert sum("serving/collect" in it["spans"] for it in its) >= len(its) - 2
    assert all(("serving/collect" in it["spans"]) == ("serving/emit" in it["spans"])
               for it in its)
    iters = [it["args"]["iter"] for it in its]
    assert iters == sorted(set(iters))
    emitted = sum(a["emitted"] for it in its
                  for _s, _d, a in it["spans"].get("serving/emit", []))
    assert 0 < emitted <= 4 * 6
    admitted = sum(a["admitted"] for it in its
                   for _s, _d, a in it["spans"]["serving/admit"])
    assert admitted <= 4
    # a prefill chunk is a child of admission
    for it in its:
        for s, d, a in it["spans"].get("serving/prefill_chunk", []):
            assert any(s >= s0 and s + d <= s0 + d0
                       for s0, d0, _a in it["spans"]["serving/admit"])
            assert a["host_arg_bytes"] > 0


def test_pages_in_use_on_the_span_is_the_allocator_s(paged_engine, tmp_path):
    """One request alone, so that the pool stands still while it decodes:
    what admission reserved is what the span and the allocator both say."""
    def drive():
        req = paged_engine.submit(np.arange(5, dtype=np.int32), 8)
        req.result(120)
        return paged_engine._stepper.pages_for(5, 8)

    need, plain = _traced(tmp_path, drive)
    its = _program_spans.iterations(plain)
    held = {it["args"]["pages_in_use"] for it in its if it["args"]["active"]}
    index_only = paged_engine._stepper.prefix_index.reclaimable()
    assert held and all(need <= h <= need + index_only for h in held)
    assert paged_engine.stats()["paged"]["pages_in_use"] <= max(held)


_KEYS_OF_64 = {"indexer_num_heads": 2, "indexer_head_dim": 64,
               "indexer_num_kv_heads": 1, "topk": 8}


@pytest.mark.parametrize("selector,attention,sa,page_size,head_dim", [
    ("gather: selector pages of 4 keys of 8", "gather: heads of 16", None, 4,
     16),
    ("kernel", "gather: heads of 16", _KEYS_OF_64, 16, 16),
    ("kernel", "kernel", _KEYS_OF_64, 16, 128),
], ids=["gather", "kernel", "streamed"])
def test_collect_span_carries_the_selection_s_counters(
        tmp_path, selector, attention, sa, page_size, head_dim):
    """A block whose keys an indexer selects: every ``serving/collect`` span
    of a traced drive carries ``keys_cached`` and ``keys_selected`` beside
    the routing counters, in the profile as the benchmark's reader finds
    them, and ``stats()["select"]`` sums the same steps; every
    ``serving/step`` span says beside ``attention`` (how the step reads K
    and V: the selected rows gathered by token, or with heads of 128 the
    slot's pages where they lie under the selection's mask: ``"kernel"``)
    how the step scored the selector keys (``selector``: gathered, or by the
    kernel in place), the words of ``stats()["paged"]``; the counters are
    the same whichever body attends."""
    from distkeras_tpu.models import zoo
    from distkeras_tpu.serving import ServingEngine

    engine = ServingEngine(
        zoo.keye_lm(vocab_size=61, seq_len=64, hidden_size=32, sa_config=sa,
                    head_dim=head_dim),
        num_slots=2, paged=True, page_size=page_size, prefill_chunk=8)
    engine.start()
    try:
        _generate(engine, 2)  # compiles, off the traced drive
        before = engine.stats()["select"]
        _, plain = _traced(tmp_path, lambda: [
            list(engine.submit(np.arange(1, 20 + i, dtype=np.int32) % 61,
                               5).result(120)) for i in range(2)])
        after = engine.stats()["select"]
        paged = engine.stats()["paged"]
        grouped = engine.stats()["moe"]["grouped"]
    finally:
        engine.stop()
    its = _program_spans.iterations(plain)
    # how the expert layers' grouped products multiply (hidden 32: the plain
    # form), on the step's and the chunk's spans as ``stats()["moe"]`` says
    assert grouped == "ragged_dot"
    assert set(_program_spans.span_values(
        its, "serving/step", "grouped")) == {grouped}
    chunks = [a for n, _s, _d, _t, a in plain["spans"]
              if n == "serving/prefill_chunk"]
    assert chunks and {a["grouped"] for a in chunks} == {grouped}
    assert paged["selector"].startswith(selector)
    assert set(_program_spans.span_values(
        its, "serving/step", "selector")) == {paged["selector"]}
    (said,) = set(_program_spans.span_values(
        its, "serving/step", "attention"))
    # a span's argument ends at its first comma (the annotation's syntax)
    assert paged["attention"].startswith(said) and said.startswith(
        attention)
    rows = [a for n, _s, _d, _t, a in plain["spans"]
            if n == "serving/collect" and "keys_cached" in a]
    assert len(rows) == after["steps"] - before["steps"] == 10
    assert all("experts_hit" in a for a in rows)
    # one request at a time: 19..23 and 20..24 cached positions, 8 read
    assert sorted(a["keys_cached"] for a in rows) == sorted(
        list(range(19, 24)) + list(range(20, 25)))
    assert {a["keys_selected"] for a in rows} == {8}
    assert after["keys_cached"] - before["keys_cached"] == sum(
        a["keys_cached"] for a in rows)
    assert after["keys_selected"] - before["keys_selected"] == 80


def test_step_span_says_how_the_step_attended(paged_engine, tmp_path):
    """``attention`` on every ``serving/step`` span is the word
    ``stats()["paged"]["attention"]`` gives: the fixture's heads of 16
    keep the gather body, and the span says why."""
    def drive():
        paged_engine.submit(np.arange(6, dtype=np.int32), 4).result(120)

    _, plain = _traced(tmp_path, drive)
    said = set(_program_spans.span_values(
        _program_spans.iterations(plain), "serving/step", "attention"))
    word = paged_engine.stats()["paged"]["attention"]
    assert word.startswith("gather: heads of 16") and said == {word}


def test_step_span_carries_the_host_bytes_of_the_call(tmp_path):
    """The stepper places a NumPy tree when it binds it (PR 26), so only
    the small per-step arrays ride a step's span; host arrays bound to
    ``_params`` afterwards, the fault the counter is there to show, ride
    every call with them."""
    import jax

    from distkeras_tpu.serving import ServingEngine

    lm = _lm()
    lm.params = jax.tree_util.tree_map(np.asarray, lm.params)
    tree_bytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(lm.params))
    engine = ServingEngine(lm, num_slots=2, paged=True, page_size=4,
                           prefill_chunk=4)
    engine.start()
    try:
        stepper = engine._stepper
        assert stepper._params_host_bytes == 0 < tree_bytes
        _generate(engine, 1)
        _, plain = _traced(tmp_path / "device", lambda: _generate(engine, 2))
        on_device = _program_spans.span_values(
            _program_spans.iterations(plain), "serving/step", "host_arg_bytes")
        # lens, mask, the (2, bucket) page table, five sampler arrays
        small = {2 * 4 + 2 + 2 * b * 4 + 5 * 2 * 4 for b in (1, 2, 4, 8)}
        assert on_device and set(on_device) <= small
        assert engine.stats()["paged"]["host_arg_bytes_step"] == on_device[-1]

        stepper._params = lm.params
        assert stepper._params_host_bytes == tree_bytes
        _, plain = _traced(tmp_path / "host", lambda: _generate(engine, 2))
        on_host = _program_spans.span_values(
            _program_spans.iterations(plain), "serving/step", "host_arg_bytes")
        assert on_host and {v - tree_bytes for v in on_host} <= small
        assert engine.stats()["paged"]["host_arg_bytes_step"] == on_host[-1]
    finally:
        engine.stop()


def test_a_request_that_waits_for_pages_is_counted(tmp_path):
    """A pool that holds one request's reservation and not two: the second
    waits at the head of the queue while a slot stands free, and the
    counters and the iteration's span say so."""
    from distkeras_tpu.serving import ServingEngine

    # 12 + 12 tokens reserve 6 pages of 4; 8 pages hold one such request
    engine = ServingEngine(_lm(), num_slots=2, paged=True, page_size=4,
                           num_pages=9, prefill_chunk=4, prefix_cache=False)
    engine.start()
    try:
        def drive():
            reqs = [engine.submit((np.arange(12, dtype=np.int32) + 5 * i) % 61, 12)
                    for i in range(2)]
            return [len(r.result(120)) for r in reqs]

        lens, plain = _traced(tmp_path, drive)
        assert lens == [24, 24]
        stats = engine.stats()
        assert stats["page_wait_requests"] == 1
        assert stats["page_waits"] >= 1 and stats["pool_exhausted"] == 0
        its = _program_spans.iterations(plain)
        waited = [it for it in its if it["args"]["page_waits"]]
        assert waited and len(waited) <= stats["page_waits"]
        assert all(it["args"]["active"] == 1 and it["args"]["queue_depth"] == 1
                   for it in waited)
        events = [e for e in engine.recorder.snapshot()
                  if e["kind"] == "scheduler.iteration"]
        assert any(e.get("page_waits") == 1 for e in events)
    finally:
        engine.stop()


# ------------------------------------------------- the batcher without JAX


class _Recorded:
    """A span factory that keeps what it is given."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **args):
        self.spans.append((name, args))
        return self

    def __enter__(self):
        self._open = self.spans[-1]
        return _Closing(self._open[1])

    def __exit__(self, *exc):
        return False


class _Closing:
    def __init__(self, args):
        self._args = args

    def set_metadata(self, **args):
        self._args.update(args)


def _serve(span, overlap):
    b = ContinuousBatcher(FakeStepper(), overlap=overlap, span=span)
    reqs = [b.submit(ServeRequest(np.arange(2 + i), 3 + i)) for i in range(3)]
    for _ in range(40):
        b.step()
    return [list(r.result(1)) for r in reqs]


def test_the_scheduler_module_imports_no_jax():
    tree = ast.parse(inspect.getsource(scheduler))
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert "jax" not in names and "jaxlib" not in names
    assert not any(getattr(v, "__name__", "").split(".")[0] == "jax"
                   for v in vars(scheduler).values() if inspect.ismodule(v))


@pytest.mark.parametrize("overlap", [False, True], ids=["sequential", "overlapped"])
def test_tokens_are_the_same_with_the_spans_on_and_off(overlap, monkeypatch):
    import jax

    def refuse(*a, **kw):
        raise AssertionError("the default span factory reached the profiler")

    rec = _Recorded()
    with_spans = _serve(rec, overlap)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert _serve(None, overlap) == with_spans
    names = [n for n, _ in rec.spans]
    assert set(names) == {"serving/iter", "serving/admit", "serving/mask",
                          "serving/emit"}
    # no span for a pass over an idle bank: 40 calls, far fewer iterations
    iters = [a for n, a in rec.spans if n == "serving/iter"]
    assert 4 <= len(iters) <= 12
    assert [a["iter"] for a in iters] == list(range(1, len(iters) + 1))
    assert sum(a["emitted"] for n, a in rec.spans if n == "serving/emit") == 3 + 4 + 5
    assert sum(a["admitted"] for n, a in rec.spans if n == "serving/admit") == 3
    assert "pages_in_use" not in iters[0]  # a dense bank has no pool


# --------------------- PR 37: CPU clocks, the loop's wait, the stream threads


def test_traced_spans_carry_cpu_clocks_and_the_streams_and_waits_show(tmp_path):
    """Under a trace every ``serving/*`` span of the scheduler's thread says
    how long the thread ran (``cpu_ns`` <= its duration) and what the whole
    process burned meanwhile (``proc_cpu_ns`` >= ``cpu_ns``); each streamed
    chunk's send is a plain span, one a frame, all on the server's one
    sender thread (PR 38) inside that thread's ``serving/stream_flush`` a
    wake, and the loop's park once the load stops is a span on the
    scheduler's."""
    import threading
    import time

    from distkeras_tpu.serving import ServingClient, ServingEngine, ServingServer

    # the server stops its engine with itself: one of this test's own
    engine = ServingEngine(_lm(), num_slots=2, paged=True, page_size=4,
                           prefill_chunk=4)
    server = ServingServer(engine, host="127.0.0.1", port=0).start()
    try:
        _generate(engine, 2)  # compiles, off the traced drive
        def drive():
            got = [None] * 3

            def client(i):
                cli = ServingClient("127.0.0.1", server.port)
                try:
                    stream = cli.generate_stream(
                        (np.arange(4 + i, dtype=np.int32) * 5) % 61, 6)
                    got[i] = [t for chunk in stream for t in chunk]
                finally:
                    cli.close()

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not any(t.is_alive() for t in threads)
            time.sleep(0.12)  # the bank is idle: the loop parks, 50 ms a time
            return got

        wakes0 = engine.stats()["streams"]["sender_wakes"]
        got, plain = _traced(tmp_path, drive)
        wakes = engine.stats()["streams"]["sender_wakes"] - wakes0
    finally:
        server.shutdown()
    assert [len(g) for g in got] == [6, 6, 6]
    for name, _start, dur, _thread, args in plain["spans"]:
        if name == "serving/stream_send":  # a plain span: no clock of its own
            assert "cpu_ns" not in args
            continue
        assert 0 <= args["cpu_ns"] <= dur, (name, args, dur)
        assert args["proc_cpu_ns"] >= args["cpu_ns"], (name, args)
    threads = _thread_spans.threads(plain)  # rows of [start, dur, name, args]
    sched = [t for t, rows in threads.items()
             if any(n == "serving/iter" for _s, _d, n, _a in rows)]
    assert len(sched) == 1
    sends = {t: [r for r in rows if r[2] == "serving/stream_send"]
             for t, rows in threads.items()}
    assert not sends[sched[0]]
    # every send sits on one thread, which is not the scheduler's: a span a
    # frame, a stream's sends under its request's identifier
    (sender,) = [t for t, rows in sends.items() if rows]
    by_req = {}
    for _s, _d, _n, a in sends[sender]:
        by_req[a["req"]] = by_req.get(a["req"], 0) + a["tokens"]
    assert sorted(by_req.values()) == [6, 6, 6]
    assert len(sends[sender]) == sum(len(g) for g in got)  # a token a frame
    # a flush a wake of the sender (two wakes may share a pass), each send
    # inside one, and the frames on the flushes are the sends
    flushes = [r for r in threads[sender] if r[2] == "serving/stream_flush"]
    assert 1 <= len(flushes) <= wakes
    assert sum(a["frames"] for _s, _d, _n, a in flushes) == len(sends[sender])
    assert all(a["streams"] >= 1 for _s, _d, _n, a in flushes)
    assert all(any(fs <= s and s + d <= fs + fd for fs, fd, _n, _a in flushes)
               for s, d, _n, _a in sends[sender])
    assert not any(r[2] == "serving/stream_flush"
                   for t, rows in threads.items() if t != sender for r in rows)
    waits = [r for r in threads[sched[0]] if r[2] == "serving/wait"]
    assert waits and all(
        a["woken"] in (0, 1) and a["held"] == 0 and a["queue_depth"] == 0
        for _s, _d, _n, a in waits[-1:])
    # a park that ran its 50 ms out stood still for nearly all of them
    timed_out = [(d, a) for _s, d, _n, a in waits if a["woken"] == 0]
    assert timed_out and all(d > 40e6 and a["cpu_ns"] < d / 4
                             for d, a in timed_out)
    last_iter_end = max(s + d for s, d, n, _a in threads[sched[0]]
                        if n == "serving/iter")
    assert any(s >= last_iter_end for s, _d, _n, _a in waits)


@pytest.mark.parametrize("overlap", [False, True], ids=["sequential", "overlapped"])
def test_with_no_trace_running_the_factory_reads_no_clock(overlap, monkeypatch):
    from distkeras_tpu.utils import profiling

    def refuse(*a, **kw):
        raise AssertionError("an untraced span read a CPU clock")

    with_recorded = _serve(_Recorded(), overlap)
    monkeypatch.setattr(profiling, "thread_time_ns", refuse)
    monkeypatch.setattr(profiling, "process_time_ns", refuse)
    assert _serve(profiling.span, overlap) == with_recorded
    with profiling.span("serving/wait") as sp:
        sp.set_metadata(woken=1)


class _SlowOnce(FakeStepper):
    """A device call that takes ``seconds`` once, at its ``at``-th step."""

    def __init__(self, at, seconds, **kw):
        super().__init__(**kw)
        self._at, self._seconds, self._calls = at, seconds, 0

    def step(self, active):
        import time

        self._calls += 1
        if self._calls == self._at:
            time.sleep(self._seconds)
        return super().step(active)


@pytest.mark.parametrize("overlap", [False, True], ids=["sequential", "overlapped"])
def test_a_long_iteration_is_no_stall(overlap):
    b = ContinuousBatcher(_SlowOnce(2, 0.6), overlap=overlap)
    req = b.submit(ServeRequest(np.arange(3), 5))
    while not b.idle:
        b.step()
    assert len(req.result(1)) == 3 + 5
    loop = b.loop_stats()
    assert loop["longest_iter_s"] > 0.6
    assert loop["longest_iter_cpu_s"] < 0.1  # it slept in there: no CPU time
    assert loop["stalls"] == 0 and loop["longest_gap_s"] < 0.1
    assert b.stats()["loop"]["iterations"] == loop["iterations"] > 0


@pytest.mark.parametrize("overlap", [False, True], ids=["sequential", "overlapped"])
def test_a_bank_that_only_prefills_is_no_stall(overlap):
    """Twelve iterations of 50 ms that each spend a chunk of one prompt's
    prefill and emit nothing: a gap is taken between iterations, not between
    tokens."""
    import time

    class SlowChunks(FakeStepper):
        def prefill_chunk(self, slot, budget):
            time.sleep(0.05)
            return super().prefill_chunk(slot, budget)

    st = SlowChunks(num_slots=1, max_len=64)
    b = ContinuousBatcher(st, overlap=overlap, prefill_chunk=2)
    req = b.submit(ServeRequest(np.arange(25), 2))
    t0 = time.monotonic()
    while not b.idle:
        b.step()
    assert time.monotonic() - t0 > 0.6 and len(st.chunks) == 12
    assert len(req.result(1)) == 25 + 2
    loop = b.loop_stats()
    assert loop["stalls"] == 0 and loop["longest_gap_s"] < 0.1
    assert loop["iterations"] >= 12


@pytest.mark.parametrize("overlap", [False, True], ids=["sequential", "overlapped"])
def test_a_bank_left_idle_between_two_requests_is_no_stall(overlap):
    import time

    b = ContinuousBatcher(FakeStepper(), overlap=overlap)
    b.submit(ServeRequest(np.arange(3), 2))
    while not b.idle:
        b.step()
    before = b.loop_stats()
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.6:  # the engine's loop over an idle bank
        assert b.step() is False
        b.wait_for_work()
    b.submit(ServeRequest(np.arange(3), 2))
    while not b.idle:
        b.step()
    loop = b.loop_stats()
    assert loop["waits"] - before["waits"] >= 10
    assert loop["wait_s"] - before["wait_s"] > 0.5
    assert loop["idle_passes"] - before["idle_passes"] >= 10
    assert loop["stalls"] == 0 and loop["longest_gap_s"] < 0.1


@pytest.mark.parametrize("overlap", [False, True], ids=["sequential", "overlapped"])
def test_a_held_bank_that_is_not_stepped_for_0_6_s_is_one_stall(overlap, caplog):
    """The batcher alone: a gap between two working iterations with a tenant
    in a slot. The thread slept, so the stall says it neither waited for work
    nor ran."""
    import logging
    import time

    from distkeras_tpu.obs import FlightRecorder

    rec = FlightRecorder(capacity=64)
    b = ContinuousBatcher(FakeStepper(), overlap=overlap, recorder=rec)
    req = b.submit(ServeRequest(np.arange(3), 6))
    b.step()
    b.step()
    with caplog.at_level(logging.WARNING, logger=scheduler.logger.name):
        time.sleep(0.6)
        while not b.idle:
            b.step()
    assert len(req.result(1)) == 3 + 6
    loop = b.loop_stats()
    assert loop["stalls"] == 1 and 0.6 <= loop["longest_gap_s"] < 1.0
    (line,) = rec.events("scheduler.stall")
    assert line["gap_s"] == pytest.approx(loop["longest_gap_s"], abs=1e-3)
    assert line["waits"] == 0 and line["idle_passes"] == 0
    assert line["cpu_s"] < 0.1 and line["held"] == 1 and line["queue_depth"] == 0
    assert line["in_air"] is overlap
    warned = [r for r in caplog.records if "scheduler stall" in r.getMessage()]
    assert len(warned) == 1 and warned[0].levelno == logging.WARNING
    assert "'gap_s': " in warned[0].getMessage()


@pytest.mark.parametrize("overlap", [False, True], ids=["sequential", "overlapped"])
def test_the_engine_s_loop_records_a_stall_and_its_counts_add_up(overlap):
    """The ``scheduler.loop`` fault seam sleeps 0.6 s once between two
    iterations with a slot held: one stall, with no wait inside it and no CPU
    time; ``stats()["loop"]`` and ``health()["loop"]`` carry the block."""
    from distkeras_tpu.faults import FaultPlan
    from distkeras_tpu.serving import ServingEngine

    engine = ServingEngine(_lm(), num_slots=2, paged=True, page_size=4,
                           prefill_chunk=4, overlap=overlap)
    engine.start()
    try:
        _generate(engine, 1)  # compiles
        before = engine.stats()["loop"]
        assert before["stalls"] == 0
        plan = FaultPlan().arm(
            "scheduler.loop", action="delay", delay=0.6, times=1, after=3,
            when=lambda ctx: ctx["busy"])
        with plan:
            out = _generate(engine, 2, steps=10)
        assert plan.fired("scheduler.loop") == 1
        assert [len(o) for o in out] == [3 + 10, 4 + 10]
        loop = engine.stats()["loop"]
        assert loop["stalls"] == 1 and 0.6 <= loop["longest_gap_s"] < 1.5
        (line,) = engine.recorder.events("scheduler.stall")
        assert line["waits"] == 0 and line["cpu_s"] < 0.1
        assert line["held"] >= 1 and line["gap_s"] >= 0.6
        # the counts add up: every step() call is a working iteration or
        # an idle pass (or both, where a working one made no progress), and
        # the loop parks only after a call that made no progress
        assert loop["iterations"] > before["iterations"]
        assert engine.health()["loop"]["stalls"] == 1
    finally:
        engine.stop()
    # the counts add up once the thread has ended: every step() call was a
    # working iteration or an idle pass (both, where a working one made no
    # progress), and the loop parks only after a call that made no progress
    batcher = engine.batcher
    loop = batcher.loop_stats()
    calls = batcher._sched_iters
    assert loop["iterations"] + loop["idle_passes"] >= calls > loop["iterations"]
    assert loop["waits"] <= loop["idle_passes"]
    assert loop["wait_s"] <= 0.05 * loop["waits"] + 0.5


def test_a_stall_s_warning_reaches_stderr_with_no_handler_installed():
    """Neither the package nor the benchmark installs a log handler: Python's
    last-resort handler prints a WARNING, so an untraced benchmark run shows a
    stall on its standard error."""
    import subprocess

    code = (
        "import time, numpy as np, sys\n"
        f"sys.path.insert(0, {os.path.join(REPO, 'tests')!r})\n"
        "from test_serving import FakeStepper\n"
        "from distkeras_tpu.serving.scheduler import ContinuousBatcher, ServeRequest\n"
        "b = ContinuousBatcher(FakeStepper())\n"
        "b.submit(ServeRequest(np.arange(3), 4))\n"
        "b.step(); time.sleep(0.55)\n"
        "while not b.idle: b.step()\n"
        "print(b.loop_stats()['stalls'])\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "1"
    assert "scheduler stall: {'gap_s': 0.5" in done.stderr
