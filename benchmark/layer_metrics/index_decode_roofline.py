"""``index_decode_roofline``: what the indexer of one decode step has to compute
and move at the window's mean batch and cached length (the ``index`` part of
the family's ``decode_step``: its three matrices a layer and EVERY cached
selector key of every active slot) against the device time a step spends under
the program's ``attn/index`` scope (projections, the selector key's write, the
gather of the selector keys, the scores, the exact top-k) in the traced
seconds."""

from benchmark.layer_metrics import _select_ops


def read(ctx):
    return _select_ops.roofline(ctx, "index_decode_roofline", "index", "index")
