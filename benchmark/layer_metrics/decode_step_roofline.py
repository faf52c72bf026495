"""``decode_step_roofline``: what one decode step has to compute and move at
the window's mean batch and mean cached length (the family's ``decode_step``)
against the median device time of the step program in the traced seconds."""

from benchmark import flops
from benchmark.harness import log


def read(ctx):
    step = ((ctx.get("trace") or {}).get("programs") or {}).get("decode_step")
    c = ctx["counters"]
    count = getattr(ctx["family"], "decode_step", None)
    if step is None or count is None or not c.get("mean_batch") \
            or not c.get("mean_cached"):
        return None
    serving = ctx["config"]["serving"]
    need = count(
        ctx["widths"], c["mean_batch"], c["mean_cached"],
        weight_bytes=serving["weight_bytes"], kv_bytes=serving["kv_bytes"])
    share = flops.roofline_share(
        need["flops"], need["bytes"], step["median_s"],
        ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"])
    operands = {**need, **share, "mean_batch": c["mean_batch"],
                "mean_cached": c["mean_cached"]}
    ctx["operands"]["decode_step_roofline"] = operands
    log(f"decode_step_roofline: {share['bound']}-bound; {operands}")
    return share["pct"]
