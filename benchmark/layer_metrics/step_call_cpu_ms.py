"""``step_call_cpu_ms``: the mean CPU time of the scheduler's thread inside
the decode step's program call (``cpu_ns`` of the program's ``serving/step``
span, PR 37) over the counted iterations; a mean because the clock ticks
(``_thread_spans``). Beside ``step_call_ms``, the same span's wall time, it
says whether the call works or waits: its log line gives the call's wall, CPU,
stood-still time and the CPU the process's other threads burned meanwhile,
each at its mean, and the same four for ``serving/step_args``."""

from benchmark.harness import log
from benchmark.layer_metrics import _thread_spans


def read(ctx):
    v = _thread_spans.of_run(ctx)
    if not v:
        return None
    rows = {name: _thread_spans.clocks(
        [r for it in v["its"] for r in it["spans"].get(name, [])])
        for name in ("serving/step", "serving/step_args")}
    if not rows["serving/step"]:
        return None
    for name, got in rows.items():
        log(f"step_call_cpu_ms: {name} at the means: "
            f"{_thread_spans.means_ms(got)}")
    return sum(r[1] for r in rows["serving/step"]) / len(rows["serving/step"]) / 1e6
