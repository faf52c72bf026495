"""``mla_decode_roofline``: what the latent attention of one decode step has
to compute and move at the window's mean batch and cached length (the ``mla``
part of the family's ``decode_step``: its four matrices a layer and the
latent cache) against the device time a step spends under the program's
``mla`` scope in the traced seconds."""

from benchmark.layer_metrics import _part_roofline


def read(ctx):
    return _part_roofline.read(ctx, "mla_decode_roofline", "mla")
