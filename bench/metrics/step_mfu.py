"""FLOPs one whole step requires (Rollout forward, reference forward,
Update forward and backward, over live tokens; bench/flops.py), over the
step's host-clock time and the chips' bf16 peak, in %."""


def read(ctx):
    if not ctx.step_s:
        return None
    flops = sum(f["total"] for f in ctx.step_flops)
    return 100.0 * flops / (sum(ctx.step_s) * ctx.chips * ctx.peak_flops)
