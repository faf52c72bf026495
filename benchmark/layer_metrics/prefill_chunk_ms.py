"""``prefill_chunk_ms``: the median device duration of the paged
prefill-chunk program in the traced seconds (``XLA Modules`` events of the
``prefill_chunk`` family of ``programs.json``)."""

from benchmark.harness import log
from benchmark.layer_metrics import _scoped_ops


def read(ctx):
    plain = _scoped_ops.of_run(ctx)
    seconds = plain and _scoped_ops.program_median_s(plain, "prefill_chunk")
    if not seconds:
        return None
    runs = sorted(d / 1e6 for _s, d in plain["programs"]["prefill_chunk"])
    log(f"prefill_chunk_ms: n={len(runs)} prefill-chunk programs; min "
        f"{runs[0]:.3f} median {1000.0 * seconds:.3f} max {runs[-1]:.3f} ms")
    return 1000.0 * seconds
