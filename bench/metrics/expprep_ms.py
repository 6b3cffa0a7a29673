"""Mean ExpPrep span per step (host clock, span `bench.expprep`), in ms."""


def read(ctx):
    spans = ctx.reduction.span_s.get("expprep")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
