"""``sched_host_ms``: the median, an iteration, of the serving scheduler's
self time: its ``serving/iter`` span less what the program calls inside it
cover (``serving/step_args``, ``serving/step``, ``serving/collect``,
``serving/prefill_chunk``). Admission, the mask, emission and the stream
pushes are what is left."""

from statistics import median

from benchmark.harness import log
from benchmark.layer_metrics import _program_spans


def read(ctx):
    its = _program_spans.of_run(ctx)
    if not its:
        return None
    phases = {
        name: median(sum(d for _s, d, _a in it["spans"].get(name, [])) / 1e6
                     for it in its)
        for name in ("serving/admit", "serving/preempt", "serving/mask",
                     "serving/emit") + _program_spans.CALLS}
    log(f"sched_host_ms: median ms an iteration by span (admit holds its "
        f"prefill chunks): {phases}")
    return _program_spans.median_ms(
        "sched_host_ms", [it["self_ns"] for it in its],
        "iterations, serving/iter less its program calls")
