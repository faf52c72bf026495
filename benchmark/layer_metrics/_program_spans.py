"""The program's own spans in the profile a traced run has just written:
what the six metrics that read the serving scheduler's iteration share.
No entry of BENCHMARK.json names this file, so it is no metric.

The serving program opens ``serving/<phase>`` spans on the profiler's
timeline (``jax.profiler.TraceAnnotation``: the device trace's clock), one
``serving/iter`` an iteration of the scheduler with its phases inside it on
the same thread (PERF.md has the table). On this installation the profiler
keeps a span's keyword arguments as the event's stats and its bare name as
the event's name (found by looking at one trace by hand, PR 25). ``ctx``
holds the reduced trace only, so the profile is found where
``harness.Profile.path()`` finds it and its host planes are read once a
process. The arithmetic is pure Python over plain data, so that a small
recorded fixture checks it (tests/benchmark).

Plain form: ``{"window": [t0, t1], "spans": [[name, start_ns,
duration_ns, thread, {argument: value}], ...]}``; ``window`` spans every
event of every plane that is not one of these spans.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from statistics import median

from benchmark import spec
from benchmark.harness import log
from benchmark.trace_reduce import merge, total

PREFIX = "serving/"
ITER = "serving/iter"
# the descendants of an iteration that are not the scheduler's own work:
# building a program call's arguments, the call, and the wait for its result
CALLS = ("serving/step_args", "serving/step", "serving/collect",
         "serving/prefill_chunk")


def run_profile(root: str = spec.ROOT) -> dict | None:
    """The plain form of the profile the run has just written under
    ``root``, where ``harness.Profile.path()`` finds it; None without one."""
    found = glob.glob(os.path.join(
        root, ".bench_trace", "plugins", "profile", "*", "*.xplane.pb"))
    return load(found[0]) if found else None


@functools.lru_cache(maxsize=1)
def load(path: str) -> dict:
    """The plain form of an ``.xplane.pb``'s ``serving/`` spans."""
    import jax

    host = re.compile(spec.load_trace_table()["host_plane"])
    spans, t0, t1 = [], None, None
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                start, dur = float(e.start_ns), float(e.duration_ns)
                if e.name.startswith(PREFIX) and host.search(plane.name):
                    spans.append([e.name, start, dur,
                                  f"{plane.name}:{thread}", dict(e.stats)])
                    continue
                t0 = start if t0 is None else min(t0, start)
                t1 = start + dur if t1 is None else max(t1, start + dur)
    return {"window": [t0, t1], "spans": spans}


def iterations(plain: dict) -> list:
    """The scheduler iterations that count: each ``serving/iter`` that lies
    wholly inside the window and dispatched a decode step (has a
    ``serving/step`` inside it on its thread). One dict an iteration:
    ``dur_ns``, ``args``, ``spans`` ({name: [[start, dur, args], ...]} of
    what lies inside it) and ``self_ns``, its duration less what its
    ``CALLS`` descendants cover. A span whose iteration the trace's edge
    cut has no parent here and is left out."""
    t0, t1 = plain["window"]
    by_thread = {}
    for name, start, dur, thread, args in plain["spans"]:
        by_thread.setdefault(thread, []).append((start, dur, name, args))
    out = []
    for rows in by_thread.values():
        rows.sort(key=lambda r: (r[0], -r[1]))
        for start, dur, name, args in rows:
            if name != ITER or (t0 is not None and (
                    start < t0 or start + dur > t1)):
                continue
            inside = {}
            for s, d, n, a in rows:
                if n != ITER and s >= start and s + d <= start + dur:
                    inside.setdefault(n, []).append([s, d, a])
            if "serving/step" not in inside:
                continue
            covered = total(merge([
                [s, s + d] for n in CALLS for s, d, _a in inside.get(n, [])]))
            out.append({"start_ns": start, "dur_ns": dur, "args": args,
                        "spans": inside, "self_ns": dur - covered})
    out.sort(key=lambda it: it["start_ns"])
    return out


def of_run(ctx: dict) -> list | None:
    """The iterations of the run whose ``ctx`` this is; none (None or an
    empty list) for an untraced run, a training cell, a program that opens
    no ``serving/iter``."""
    if not ctx.get("trace"):
        return None
    plain = run_profile()
    return iterations(plain) if plain else None


def median_ms(metric: str, values_ns: list, what: str) -> float | None:
    """The median of durations in ns as ms, with the line that says what it
    was taken from."""
    if not values_ns:
        return None
    ms = sorted(v / 1e6 for v in values_ns)
    log(f"{metric}: n={len(ms)} {what}; min {ms[0]:.3f} median "
        f"{median(ms):.3f} max {ms[-1]:.3f} ms")
    return median(ms)


def span_values(its: list, name: str, key=None) -> list:
    """Of every ``name`` span inside the counted iterations, its duration
    in ns, or its argument ``key`` where it carries one."""
    rows = [r for it in its for r in it["spans"].get(name, [])]
    if key is None:
        return [d for _s, d, _a in rows]
    return [a[key] for _s, _d, a in rows if key in a]
