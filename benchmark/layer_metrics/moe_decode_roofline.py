"""``moe_decode_roofline``: what the expert layers of one decode step have to
compute and move at the window's mean batch (the ``moe`` part of the family's
``decode_step``: router, the routed experts some token reaches, the shared
experts) against the device time a step spends under the program's ``moe/``
scopes in the traced seconds."""

from benchmark.layer_metrics import _part_roofline


def read(ctx):
    return _part_roofline.read(ctx, "moe_decode_roofline", "moe")
