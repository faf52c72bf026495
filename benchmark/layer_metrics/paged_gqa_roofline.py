"""``paged_gqa_roofline``: what the paged kernel's calls of one decode step
have to move and compute alone (the ``kernel`` entry of the family's
``decode_step``: for every layer the whole pages that hold the positions in a slot's reach, keys
and values of every K/V head, the queries in and the outputs back) against the
device time of the kernel's own custom calls (``paged_decode_attention``)
inside the step program in the traced seconds."""

from benchmark.layer_metrics import _gqa_ops


def read(ctx):
    return _gqa_ops.roofline(
        ctx, "paged_gqa_roofline", "kernel", lambda count: count.get("kernel"))
