"""Mean length of the scheduler's ``sweep`` span: one
``InferenceSession.infer_step`` call, from dispatch until its
predictions and per-lane meters are on the host."""


def read(ctx):
    spans = ctx.spans.get("sweep", []) if ctx.spans else []
    if not spans:
        return None
    return sum(b - a for a, b, _ in spans) / len(spans) * 1e3
