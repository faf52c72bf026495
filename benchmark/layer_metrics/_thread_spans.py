"""Every thread's ``serving/`` spans in the profile a traced run has just
written, and the CPU clocks on them (PR 37): what the four metrics share that
tell the scheduler thread's waits from its work. No entry of BENCHMARK.json
names this file, so it is no metric.

Since PR 37 the program's span factory (``distkeras_tpu.utils.profiling.span``)
sets ``cpu_ns`` and ``proc_cpu_ns`` on every ``serving/`` span that opens
while a trace runs: its thread's and its process's CPU time across it. So a
span's duration less its ``cpu_ns`` is how long its thread stood still (the
interpreter lock, a lock, a system call, the device), and ``proc_cpu_ns`` less
``cpu_ns`` what the other threads of the process burned meanwhile. The loop's
park is a span (``serving/wait``), and each streamed chunk's send is one on
its connection's thread (``serving/stream_send``). The scheduler's thread is
the one that holds ``serving/iter``.

The plain form is ``_program_spans``'s, parsed once a process there. A
program without the clocks (the parent of PR 37, the old fixture) reads as
nothing: ``of_run`` returns None.

The CPU clocks are ``clock_gettime``'s, and under the sandboxed kernel of the
TPU hosts they advance in ticks of 10 ms (my chip runs, PR 37): one span's
``cpu_ns`` is 0 or a multiple of 1e7 there. A tick lands on whichever thread
runs when it falls, so sums over the hundreds of spans of a traced window are
sound and a single span's value is not: every CPU time here is a mean.
"""

from __future__ import annotations

from bisect import bisect_right

from benchmark.layer_metrics import _program_spans
from benchmark.trace_reduce import merge

ITER, SEND, WAIT = "serving/iter", "serving/stream_send", "serving/wait"


def run_profile() -> dict | None:
    """The plain form of the run's profile: ``_program_spans``'s own, so one
    parse a process; a test hands its fixture here."""
    return _program_spans.run_profile()


def threads(plain: dict) -> dict:
    """``{thread: [[start, dur, name, args], ...]}``, each sorted by start."""
    out = {}
    for name, start, dur, thread, args in plain["spans"]:
        out.setdefault(thread, []).append([start, dur, name, args])
    for rows in out.values():
        rows.sort(key=lambda r: (r[0], -r[1]))
    return out


def view(plain: dict | None) -> dict | None:
    """What the four readers share of a plain form: ``window``, ``sched`` (the
    scheduler thread's rows), ``others`` (every other thread's, by thread) and
    ``its`` (``_program_spans.iterations`` on the scheduler's thread: whole
    inside the window, with a decode step). None where no ``serving/iter``
    carries ``cpu_ns``."""
    if not plain or not plain.get("spans"):
        return None
    by_thread = threads(plain)
    iters = {t: sum(r[2] == ITER for r in rows) for t, rows in by_thread.items()}
    thread = max(iters, key=iters.get)
    sched = by_thread.pop(thread)
    if not any(r[2] == ITER and "cpu_ns" in r[3] for r in sched):
        return None
    its = _program_spans.iterations({
        "window": plain["window"],
        "spans": [[n, s, d, thread, a] for s, d, n, a in sched]})
    return {"window": plain["window"], "sched": sched, "its": its,
            "others": by_thread}


def of_run(ctx: dict) -> dict | None:
    """``view`` of the run whose ``ctx`` this is; None for an untraced run, a
    training cell, a program whose spans carry no clocks."""
    if not ctx.get("trace"):
        return None
    return view(run_profile())


def clocks(rows: list) -> list:
    """``[wall, cpu, stood still, the others' cpu]`` in ns of each
    ``[start, dur, args]`` row that carries the clocks."""
    return [[d, a["cpu_ns"], d - a["cpu_ns"], a["proc_cpu_ns"] - a["cpu_ns"]]
            for _s, d, a in rows if "cpu_ns" in a]


def means_ms(rows: list) -> str:
    """The four columns of ``clocks`` at their means, as words. Means and
    not medians: the CPU clocks of a TPU host's kernel tick every 10 ms, so
    one span's ``cpu_ns`` reads 0 or a whole tick and only a sum over many
    spans says how long their thread ran."""
    if not rows:
        return "none"
    wall, cpu, still, others = (sum(c) / len(rows) / 1e6 for c in zip(*rows))
    return (f"wall {wall:.3f} cpu {cpu:.3f} stood still {still:.3f} the "
            f"others' cpu {others:.3f} ms (n={len(rows)})")


def covered(union: list, a: float, b: float) -> float:
    """How much of [a, b) the sorted disjoint intervals ``union`` cover."""
    i = max(bisect_right(union, [a, float("inf")]) - 1, 0)
    out = 0.0
    while i < len(union) and union[i][0] < b:
        out += max(0.0, min(b, union[i][1]) - max(a, union[i][0]))
        i += 1
    return out


def union_of(rows: list, name: str) -> list:
    """The union of the ``name`` spans among ``[start, dur, name, args]``."""
    return merge([[s, s + d] for s, d, n, _a in rows if n == name])
