"""``zero_pick_pct``: of the picks the active slots' tokens made in a decode
step (tokens x experts a token x expert layers), the share that took a
zero-compute identity expert: the mean, over the ``serving/collect`` spans of
the traced seconds, of the program's ``zero_picks`` over ``picks``. It is the
model's property under its routing (the identity experts' share of the
router's outputs under even routing) and moves only with the routing: a pick
of an identity expert costs the step no byte and no product. Its log line
gives the picks that reached a held routed expert and the picks a token."""

from benchmark.harness import log
from benchmark.layer_metrics import _shortcut_ops


def read(ctx):
    plain = _shortcut_ops.of_run(ctx)
    rows = [r for r in (plain or {}).get("collect", [])
            if r.get("picks") and "zero_picks" in r]
    if not rows:
        return None
    shares = [100.0 * r["zero_picks"] / r["picks"] for r in rows]
    held = [100.0 * r["held_picks"] / r["picks"] for r in rows]
    tokens = sum(r.get("routed_tokens", 0.0) for r in rows)
    log(f"zero_pick_pct: n={len(rows)} serving/collect spans; picks a token "
        f"{sum(r['picks'] for r in rows) / tokens if tokens else None}; "
        f"zero_picks min {min(shares):.2f}% max {max(shares):.2f}%; held_picks "
        f"mean {sum(held) / len(held):.3f}% of the picks (the rest chose a "
        f"routed expert that is not held here)")
    return sum(shares) / len(shares)
