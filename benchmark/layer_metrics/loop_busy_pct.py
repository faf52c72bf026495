"""``loop_busy_pct``: the share of the traced window in which a
``serving/iter`` span is open on the scheduler's thread: the loop is inside a
working iteration. Its log line splits the rest into the loop's park
(``serving/wait``, PR 37, with how many were woken and how many ran their
50 ms out with slots held) and time under no span, and gives the longest
stretch of each: a traced run that met one of the holes of PERF.md section 7
reads near 50 here, and the line says which of the two the hole was."""

from benchmark.harness import log
from benchmark.layer_metrics import _thread_spans
from benchmark.trace_reduce import subtract, total


def _clip(intervals: list, t0: float, t1: float) -> list:
    return [[max(s, t0), min(e, t1)] for s, e in intervals
            if min(e, t1) > max(s, t0)]


def read(ctx):
    v = _thread_spans.of_run(ctx)
    if not v or v["window"][0] is None:
        return None
    t0, t1 = v["window"]
    busy = _clip(_thread_spans.union_of(v["sched"], _thread_spans.ITER), t0, t1)
    parked = _clip(_thread_spans.union_of(v["sched"], _thread_spans.WAIT), t0, t1)
    bare = subtract(subtract([[t0, t1]], busy), parked)
    waits = [a for _s, _d, n, a in v["sched"] if n == _thread_spans.WAIT]
    log(f"loop_busy_pct: window {(t1 - t0) / 1e9:.4f} s; in an iteration "
        f"{total(busy) / 1e9:.4f} s (longest "
        f"{max((e - s for s, e in busy), default=0.0) / 1e6:.2f} ms); parked "
        f"in serving/wait {total(parked) / 1e9:.4f} s in {len(waits)} waits "
        f"(longest {max((e - s for s, e in parked), default=0.0) / 1e6:.2f} "
        f"ms; woken {sum(a.get('woken', 0) for a in waits)}, with slots held "
        f"{sum(a.get('held', 0) > 0 for a in waits)}); under no span "
        f"{total(bare) / 1e9:.4f} s (longest "
        f"{max((e - s for s, e in bare), default=0.0) / 1e6:.2f} ms)")
    return 100.0 * total(busy) / (t1 - t0)
