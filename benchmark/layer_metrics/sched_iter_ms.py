"""``sched_iter_ms``: the median duration of one iteration of the serving
scheduler (the program's ``serving/iter`` span), over the iterations of the
traced seconds that dispatched a decode step."""

from benchmark.layer_metrics import _program_spans


def read(ctx):
    its = _program_spans.of_run(ctx)
    if not its:
        return None
    collected = sum("serving/collect" in it["spans"] for it in its)
    return _program_spans.median_ms(
        "sched_iter_ms", [it["dur_ns"] for it in its],
        f"serving/iter spans with a serving/step inside ({collected} with a "
        f"serving/collect too)")
