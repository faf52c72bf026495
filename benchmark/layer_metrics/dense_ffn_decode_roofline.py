"""``dense_ffn_decode_roofline``: what the dense MLPs of one decode step have
to compute and move at the window's mean batch (the ``dense`` part of the
family's ``decode_step``: two gated MLPs a layer, each matrix read once)
against the device time a step spends under the program's ``ffn/dense`` scope
in the traced seconds."""

from benchmark import flops
from benchmark.harness import log
from benchmark.layer_metrics import _scoped_ops, _shortcut_ops


def read(ctx):
    plain = _shortcut_ops.of_run(ctx)
    c = ctx["counters"]
    count = getattr(ctx["family"], "decode_step", None)
    if not plain or count is None or not c.get("mean_batch") \
            or not c.get("mean_cached"):
        return None
    seconds = _scoped_ops.scope_seconds_a_step(plain, "dense")
    serving = ctx["config"]["serving"]
    need = count(
        ctx["widths"], c["mean_batch"], c["mean_cached"],
        weight_bytes=serving["weight_bytes"], kv_bytes=serving["kv_bytes"],
    ).get("parts", {}).get("dense")
    if seconds is None or need is None:
        return None
    share = flops.roofline_share(
        need["flops"], need["bytes"], seconds,
        ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"])
    operands = {**need, **share, "mean_batch": c["mean_batch"],
                "decode_steps_traced": len(plain["programs"]["decode_step"])}
    ctx["operands"]["dense_ffn_decode_roofline"] = operands
    log(f"dense_ffn_decode_roofline: {share['bound']}-bound; {operands}")
    return share["pct"]
