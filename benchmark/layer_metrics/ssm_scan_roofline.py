"""``ssm_scan_roofline``: what the Mamba layers' mixers of one prefill chunk
have to compute (the family's ``scan``: a real token's two matrices and the
chunk form's products, the causal half of a block counted) and move (the
matrices and one slot's state in and out) at the traced chunks' mean real
tokens (``tokens`` on ``serving/prefill_chunk``) against the device time a
chunk spends under the program's ``ssm/proj`` and ``ssm/scan`` scopes. The
program's products that touch the state are float32 at ``HIGHEST`` (six
passes of the MXU) and it computes the whole pow2 bucket: both show here as a
share below what bfloat16 products over the real tokens alone would read."""

from benchmark.layer_metrics import _ssm_ops


def read(ctx):
    return _ssm_ops.scan_roofline(ctx, "ssm_scan_roofline")
