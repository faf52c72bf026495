"""``ssm_decode_roofline``: what the Mamba layers' mixers of one decode step
have to compute and move at the window's mean batch (the ``ssm`` part of the
family's ``decode_step``: each layer's two matrices, and every decoding slot's
state and convolution tail read once and written once) against the device time
a step spends under the program's ``ssm/proj`` and ``ssm/update`` scopes in the
traced seconds. XLA's fusion of the update is the kernel: there is no custom
call of its own."""

from benchmark.layer_metrics import _ssm_ops


def read(ctx):
    return _ssm_ops.decode_roofline(ctx, "ssm_decode_roofline")
