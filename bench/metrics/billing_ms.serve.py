"""Mean length of the scheduler's ``billing`` span: building request
records, per-sweep percentiles and the step's energy report, after the
sweep returned."""


def read(ctx):
    spans = ctx.spans.get("billing", []) if ctx.spans else []
    if not spans:
        return None
    return sum(b - a for a, b, _ in spans) / len(spans) * 1e3
