"""Device idle time inside the Rollout spans over their length, in %:
the host's round trip per sampled token shows here."""


def read(ctx):
    spans = ctx.reduction.span_s.get("rollout")
    if not spans:
        return None
    return 100.0 * ctx.reduction.span_idle_s["rollout"] / sum(spans)
