"""FLOPs the Update's forward and backward require for the batch's live
tokens (bench/flops.py), over the Update span's time and the chips' bf16
peak, in %."""


def read(ctx):
    spans = ctx.reduction.span_s.get("update")
    if not spans or len(spans) != len(ctx.step_flops):
        return None
    flops = sum(f["update"] for f in ctx.step_flops)
    return 100.0 * flops / (sum(spans) * ctx.chips * ctx.peak_flops)
