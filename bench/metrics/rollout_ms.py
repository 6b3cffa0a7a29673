"""Mean Rollout span per step (host clock, span `bench.rollout`), in ms."""


def read(ctx):
    spans = ctx.reduction.span_s.get("rollout")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
