"""Mean Update span per step (host clock, span `bench.update`), in ms."""


def read(ctx):
    spans = ctx.reduction.span_s.get("update")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
