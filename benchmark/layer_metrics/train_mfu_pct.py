"""``train_mfu_pct``: the operations the forward and backward passes need per
token (the family's ``train_flops_per_token``, from shapes; recomputed work
is not counted) times the run's end-to-end tokens a second a chip, over the
chip's bfloat16 peak."""


def read(ctx):
    rate = ctx["e2e"].get("train_tokens_per_s_per_chip")
    count = getattr(ctx["family"], "train_flops_per_token", None)
    if rate is None or count is None:
        return None
    per_token = count(ctx["widths"])
    peak = ctx["peaks"]["bf16_flops_per_s"]
    ctx["operands"]["train_mfu_pct"] = {
        "tokens_per_s_per_chip": rate, **per_token, "peak_flops_per_s": peak}
    return 100.0 * rate * per_token["flops"] / peak
