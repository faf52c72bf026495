"""``attn_decode_roofline``: what the attention of one decode step has to
compute and move at the window's mean batch and cached length (the ``attn``
part of the family's ``decode_step``: every layer's five matrices and the keys
and values in its reach, a window layer's counted at ``min(cached, window)``)
against the device time a step spends under the program's ``attn/full`` and
``attn/window`` scopes in the traced seconds."""

from benchmark.layer_metrics import _gqa_ops


def read(ctx):
    return _gqa_ops.roofline(
        ctx, "attn_decode_roofline", "attn",
        lambda count: count.get("parts", {}).get("attn"))
