"""``experts_hit_pct``: of the routed experts an expert layer holds, the share
that some active slot's token reached in a decode step: the mean, over the
``serving/collect`` spans of the traced seconds, of the program's
``experts_hit`` (itself a mean over the expert layers) over ``experts_total``.
Its log line gives the largest load on one expert against the even share."""

from benchmark.harness import log
from benchmark.layer_metrics import _scoped_ops


def read(ctx):
    plain = _scoped_ops.of_run(ctx)
    rows = [r for r in (plain or {}).get("collect", []) if r.get("experts_total")]
    if not rows:
        return None
    shares = [100.0 * r["experts_hit"] / r["experts_total"] for r in rows]
    loads = [r["expert_load_max"] for r in rows]
    tokens = [r.get("routed_tokens", 0.0) for r in rows]
    top_k = ctx["widths"].get("top_k")
    even = (sum(tokens) / len(tokens)) * top_k / rows[0]["experts_total"] \
        if top_k else None
    log(f"experts_hit_pct: n={len(rows)} serving/collect spans; experts_total "
        f"{rows[0]['experts_total']:.0f}; experts_hit min "
        f"{min(r['experts_hit'] for r in rows):.2f} max "
        f"{max(r['experts_hit'] for r in rows):.2f}; expert_load_max mean "
        f"{sum(loads) / len(loads):.2f} max {max(loads):.0f} against an even "
        f"share of {even if even is None else round(even, 2)} tokens an expert")
    return sum(shares) / len(shares)
