"""``keys_selected_pct``: of the cached positions the active slots' queries
could see in a decode step, the share they read: the sum of the program's
``keys_selected`` (``min(cached, topk)`` a slot) over the sum of its
``keys_cached`` on the ``serving/collect`` spans of the traced seconds: how
sparse the cell really ran (100: nothing was left out).

It DESCRIBES THE WINDOW'S MIX and has no direction to optimise: the counters
come from the host's own lengths and the configuration's ``topk``, so no
change to the program moves it, and it would read the same if the step
gathered every row (``BENCHMARK.json`` has to give a ``better``; "lower"
says only that a sparser window asks less of the step). It is there to be
read beside ``sparse_attn_decode_roofline`` and ``index_decode_roofline``,
whose counted bytes follow it. What the device really reads is held by a
test on the lowered program and not by this metric:
``tests/test_keye.py::test_the_lowered_step_gathers_topk_rows_a_slot_whatever_the_table``."""

from benchmark.harness import log
from benchmark.layer_metrics import _select_ops


def read(ctx):
    plain = _select_ops.of_run(ctx)
    rows = [r for r in (plain or {}).get("collect", []) if r["keys_cached"]]
    if not rows:
        return None
    cached = sum(r["keys_cached"] for r in rows)
    read_ = sum(r["keys_selected"] for r in rows)
    log(f"keys_selected_pct: n={len(rows)} serving/collect spans; "
        f"keys_cached a step mean {cached / len(rows):.1f}, keys_selected "
        f"{read_ / len(rows):.1f}")
    return 100.0 * read_ / cached
