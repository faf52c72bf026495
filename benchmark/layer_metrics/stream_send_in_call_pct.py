"""``stream_send_in_call_pct``: of the wall time of the counted iterations'
``serving/step`` spans (the decode step's program call on the scheduler's
thread), the share during which a ``serving/stream_send`` span (PR 37: a
connection thread packing and sending one streamed chunk) is open on another
thread. Its log line gives the sends an iteration, a send's duration at the
median and the mean, the threads seen, and the same share for the interval
from the ``serving/emit`` before the call to the call's end. The sends carry
no CPU clocks: four system calls a send, 32-128 sends an iteration, cost a
traced run a fifth of its pace (PR 37)."""

from bisect import bisect_left
from statistics import median

from benchmark.harness import log
from benchmark.layer_metrics import _thread_spans


def read(ctx):
    v = _thread_spans.of_run(ctx)
    if not v:
        return None
    by_thread = [[r for r in rows if r[2] == _thread_spans.SEND]
                 for rows in v["others"].values()]
    sends = [r for rows in by_thread for r in rows]
    steps = [r for it in v["its"] for r in it["spans"].get("serving/step", [])]
    if not sends or not steps:
        return None
    open_ = _thread_spans.union_of(sends, _thread_spans.SEND)
    in_call = sum(_thread_spans.covered(open_, s, s + d) for s, d, _a in steps)
    call = sum(d for _s, d, _a in steps)
    # from the emit that woke the streams to the end of the call after it
    emits = sorted(s for s, _d, n, _a in v["sched"] if n == "serving/emit")
    spans = []
    for s, d, _a in steps:
        i = bisect_left(emits, s)
        if i:
            spans.append([emits[i - 1], s + d])
    from_emit = sum(_thread_spans.covered(open_, a, b) for a, b in spans)
    threads = sum(map(bool, by_thread))
    t0 = v["its"][0]["start_ns"]
    t1 = v["its"][-1]["start_ns"] + v["its"][-1]["dur_ns"]
    inside = sum(t0 <= s < t1 for s, _d, _n, _a in sends)
    log(f"stream_send_in_call_pct: n={len(steps)} calls, {len(sends)} sends "
        f"on {threads} threads ({inside / len(steps):.1f} an iteration); a "
        f"send's wall {median(d for _s, d, _n, _a in sends) / 1e3:.1f} us at "
        f"the median, {sum(d for _s, d, _n, _a in sends) / len(sends) / 1e3:.1f}"
        f" at the mean; a send open in {in_call / 1e6:.2f} of {call / 1e6:.2f} "
        f"ms of the calls; from the emit before a call to the call's end in "
        f"{100.0 * from_emit / max(sum(b - a for a, b in spans), 1.0):.1f}%")
    return 100.0 * in_call / call
