"""``sparse_attn_decode_roofline``: what the attention of one decode step has
to compute and move once the selection is made (the ``attn`` part of the
family's ``decode_step``: four matrices a layer and ``min(cached, topk)`` key
and value rows a slot and layer) against the device time a step spends under
the program's ``attn/sparse`` scope (norm to ``wo``, the gather of the selected
rows, the attention over them) in the traced seconds."""

from benchmark.layer_metrics import _select_ops


def read(ctx):
    return _select_ops.roofline(
        ctx, "sparse_attn_decode_roofline", "sparse", "attn")
