"""``flash_attn_roofline``: what the flash kernels' forward and backward
passes of one step need (the family's ``flash_attention_train``; a family that
runs no such kernel has no such count, and the metric is left out) against
their time a step in the traced steps."""

from benchmark import flops
from benchmark.harness import log


def read(ctx):
    kernels = (ctx.get("trace") or {}).get("kernels") or {}
    steps = ctx["counters"].get("traced_steps")
    count = getattr(ctx["family"], "flash_attention_train", None)
    if not steps or count is None or "flash" not in kernels:
        return None
    need = count(ctx["widths"], ctx["counters"]["batch"])
    share = flops.roofline_share(
        need["flops"], need["bytes_fwd"] + need["bytes_bwd"],
        kernels["flash"]["total_s"] / steps,
        ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"])
    operands = {**need, **share, "traced_steps": steps,
                "kernel_calls_a_step": kernels["flash"]["count"] / steps}
    ctx["operands"]["flash_attn_roofline"] = operands
    log(f"flash_attn_roofline: {share['bound']}-bound; {operands}")
    return share["pct"]
