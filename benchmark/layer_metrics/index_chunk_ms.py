"""``index_chunk_ms``: device milliseconds a prefill chunk spends under the
program's ``attn/index`` scope (all layers: the scores a tile of queries at a
time and the exact selection a query, where up to 2,048 queries select at
once), the mean over the chunk programs of the traced seconds. Its log line
gives the same for ``attn/sparse`` and the chunk's whole device time."""

from benchmark.harness import log
from benchmark.layer_metrics import _scoped_ops, _select_ops


def read(ctx):
    plain = _select_ops.of_run(ctx)
    ms = plain and _select_ops.chunk_ms(plain, "index")
    if not ms:
        return None
    whole = _scoped_ops.program_median_s(plain, "prefill_chunk")
    log(f"index_chunk_ms: n={len(plain['programs']['prefill_chunk'])} "
        f"prefill-chunk programs; attn/index {ms:.3f} ms a chunk, attn/sparse "
        f"{_select_ops.chunk_ms(plain, 'sparse')} ms, a chunk's median "
        f"{None if whole is None else 1000.0 * whole} ms")
    return ms
