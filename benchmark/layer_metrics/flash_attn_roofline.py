"""``flash_attn_roofline``: what the flash kernels' forward and backward
passes of one step need (``flops.flash_attention_train``) against their time
a step in the traced steps."""

from benchmark import flops
from benchmark.harness import log


def read(ctx):
    kernels = (ctx.get("trace") or {}).get("kernels") or {}
    steps = ctx["counters"].get("traced_steps")
    if not steps or "flash" not in kernels:
        return None
    need = flops.flash_attention_train(ctx["widths"], ctx["counters"]["batch"])
    share = flops.roofline_share(
        need["flops"], need["bytes_fwd"] + need["bytes_bwd"],
        kernels["flash"]["total_s"] / steps,
        ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"])
    operands = {**need, **share, "traced_steps": steps,
                "kernel_calls_a_step": kernels["flash"]["count"] / steps}
    ctx["operands"]["flash_attn_roofline"] = operands
    log(f"flash_attn_roofline: {share['bound']}-bound; {operands}")
    return share["pct"]
