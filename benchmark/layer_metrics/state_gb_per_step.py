"""``state_gb_per_step``: the median of what one decode step reads and writes
of the layers' states, in billions of bytes (the ``state_bytes`` argument of
the program's ``serving/step`` span: decoding slots x layers that hold a
state x 2 x the state's bytes, from the host's own counts). It DESCRIBES THE
WINDOW'S OCCUPANCY (a full bank moves the most) and the state's precision;
"lower" says only that a step has less to move."""

from statistics import median

from benchmark.harness import log
from benchmark.layer_metrics import _ssm_ops


def read(ctx):
    plain = _ssm_ops.of_run(ctx)
    values = (plain or {}).get("step_state_bytes")
    if not values:
        return None
    log(f"state_gb_per_step: n={len(values)} serving/step spans; bytes min "
        f"{min(values)} median {median(values)} max {max(values)}")
    return median(values) / 1e9
