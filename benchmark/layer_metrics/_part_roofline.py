"""What ``moe_decode_roofline`` and ``mla_decode_roofline`` share: one part
of the family's ``decode_step`` count (its ``parts``) against the device time
a decode step spends under that part's named scope. No entry of
BENCHMARK.json names this file, so it is no metric."""

from benchmark import flops
from benchmark.harness import log
from benchmark.layer_metrics import _scoped_ops


def read(ctx, metric: str, part: str):
    plain = _scoped_ops.of_run(ctx)
    c = ctx["counters"]
    count = getattr(ctx["family"], "decode_step", None)
    if not plain or count is None or not c.get("mean_batch") \
            or not c.get("mean_cached"):
        return None
    seconds = _scoped_ops.scope_seconds_a_step(plain, part)
    serving = ctx["config"]["serving"]
    need = count(
        ctx["widths"], c["mean_batch"], c["mean_cached"],
        weight_bytes=serving["weight_bytes"], kv_bytes=serving["kv_bytes"],
    ).get("parts", {}).get(part)
    if seconds is None or need is None:
        return None
    share = flops.roofline_share(
        need["flops"], need["bytes"], seconds,
        ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"])
    operands = {**need, **share, "mean_batch": c["mean_batch"],
                "mean_cached": c["mean_cached"],
                "decode_steps_traced": len(plain["programs"]["decode_step"])}
    ctx["operands"][metric] = operands
    log(f"{metric}: {share['bound']}-bound; {operands}")
    return share["pct"]
