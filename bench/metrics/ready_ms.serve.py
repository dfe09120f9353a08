"""Mean length of the scheduler's ``ready`` span, the middle part of
``sweep``: waiting until the device has computed the predictions."""


def read(ctx):
    spans = ctx.spans.get("ready", []) if ctx.spans else []
    if not spans:
        return None
    return sum(b - a for a, b, _ in spans) / len(spans) * 1e3
