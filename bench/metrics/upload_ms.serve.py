"""Mean length of the scheduler's ``upload`` span: the slot table's
literal rows copied to the device and its valid mask built, before the
sweep."""


def read(ctx):
    spans = ctx.spans.get("upload", []) if ctx.spans else []
    if not spans:
        return None
    return sum(b - a for a, b, _ in spans) / len(spans) * 1e3
