"""Mean length of the scheduler's ``dispatch`` span, the first part of
``sweep``: ``InferenceSession.infer_step`` uploading the valid mask and
enqueueing the compiled executable."""


def read(ctx):
    spans = ctx.spans.get("dispatch", []) if ctx.spans else []
    if not spans:
        return None
    return sum(b - a for a, b, _ in spans) / len(spans) * 1e3
