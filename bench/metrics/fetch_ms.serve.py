"""Mean length of the scheduler's ``fetch`` span, the last part of
``sweep``: copying the predictions and the two per-lane meters from the
device to the host."""


def read(ctx):
    spans = ctx.spans.get("fetch", []) if ctx.spans else []
    if not spans:
        return None
    return sum(b - a for a, b, _ in spans) / len(spans) * 1e3
