"""``kv_pages_in_use_pct``: the mean, over the iterations of the traced
seconds, of the KV pool's pages in use over its pages (``pages_in_use`` and
``pages_total`` on the program's ``serving/iter`` span, read once admission
has run). In use are the pages requests hold and those the device prefix
index alone still holds."""

from benchmark.harness import log
from benchmark.layer_metrics import _program_spans


def read(ctx):
    its = _program_spans.of_run(ctx)
    rows = [it["args"] for it in its or [] if it["args"].get("pages_total")]
    if not rows:
        return None
    shares = [100.0 * a["pages_in_use"] / a["pages_total"] for a in rows]
    waited = sum(a.get("page_waits", 0) for a in rows)
    log(f"kv_pages_in_use_pct: n={len(rows)} iterations; pages_total "
        f"{rows[0]['pages_total']}, pages_in_use min "
        f"{min(a['pages_in_use'] for a in rows)} max "
        f"{max(a['pages_in_use'] for a in rows)}; admission waited for "
        f"pages in {waited} of them ({100.0 * waited / len(rows):.1f}%)")
    return sum(shares) / len(shares)
