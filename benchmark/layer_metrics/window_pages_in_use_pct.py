"""``window_pages_in_use_pct``: the mean, over the iterations of the traced
seconds, of the window layers' pool in use over its pages
(``window_pages_in_use`` and ``window_pages_total`` on the program's
``serving/iter`` span). The pool holds a ring of ``window / page + 1`` pages a
slot, so a slot whose request is longer than the window holds its whole ring
and no more, whatever its length."""

from benchmark.harness import log
from benchmark.layer_metrics import _gqa_ops


def read(ctx):
    plain = _gqa_ops.of_run(ctx)
    rows = (plain or {}).get("iterations") or []
    if not rows:
        return None
    shares = [100.0 * r["window_pages_in_use"] / r["window_pages_total"]
              for r in rows]
    log(f"window_pages_in_use_pct: n={len(rows)} iterations; "
        f"window_pages_total {rows[0]['window_pages_total']}, in use min "
        f"{min(r['window_pages_in_use'] for r in rows)} max "
        f"{max(r['window_pages_in_use'] for r in rows)}")
    return sum(shares) / len(shares)
