"""``step_host_arg_mb``: the median of what one decode-step call hands over
from the host, in millions of bytes (the ``host_arg_bytes`` argument of the
program's ``serving/step`` span: the bytes of the call's arguments that are
NumPy arrays and not ``jax.Array``s)."""

from statistics import median

from benchmark.harness import log
from benchmark.layer_metrics import _program_spans


def read(ctx):
    its = _program_spans.of_run(ctx)
    if not its:
        return None
    values = _program_spans.span_values(its, "serving/step", "host_arg_bytes")
    if not values:
        return None
    log(f"step_host_arg_mb: n={len(values)} serving/step spans; bytes min "
        f"{min(values)} median {median(values)} max {max(values)}")
    return median(values) / 1e6
