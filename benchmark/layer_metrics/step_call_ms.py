"""``step_call_ms``: the median host time inside the decode step's program
call (the program's ``serving/step`` span, the jitted call alone): dispatch,
and the upload of every argument that is not on the device."""

from benchmark.layer_metrics import _program_spans


def read(ctx):
    its = _program_spans.of_run(ctx)
    if not its:
        return None
    return _program_spans.median_ms(
        "step_call_ms", _program_spans.span_values(its, "serving/step"),
        "serving/step spans")
