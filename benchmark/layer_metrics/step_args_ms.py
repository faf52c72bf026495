"""``step_args_ms``: the median time the decode step's host arguments take
to build in NumPy (the program's ``serving/step_args`` span: the grammar
mask, the sampler's arrays, the lengths and the page tables)."""

from benchmark.layer_metrics import _program_spans


def read(ctx):
    its = _program_spans.of_run(ctx)
    if not its:
        return None
    return _program_spans.median_ms(
        "step_args_ms", _program_spans.span_values(its, "serving/step_args"),
        "serving/step_args spans")
