"""``ssm_step_share_pct``: of the decode steps' device time in the traced
seconds, the share under the program's ``ssm/proj`` and ``ssm/update`` scopes
(the union of their operations inside ``decode_step`` programs over those
programs' durations): whether the state-space layers do most of a step's
work, which is what the cell is for. It has no direction of its own to
optimise ("higher" says that the rest of the step got out of the way): read
it beside ``ssm_decode_roofline``, which says how well that share is spent."""

from benchmark.harness import log
from benchmark.layer_metrics import _ssm_ops


def read(ctx):
    plain = _ssm_ops.of_run(ctx)
    share = plain and _ssm_ops.step_share(plain)
    if not share:
        return None
    log(f"ssm_step_share_pct: n={len(plain['programs']['decode_step'])} "
        f"decode steps; {len(plain['ops'])} operations under ssm/*")
    return share
