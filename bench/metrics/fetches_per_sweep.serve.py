"""Mean number of device->host transfers per scheduler ``sweep`` span,
from the ``fetches`` count the zoo records on it: 1 where a sweep's
predictions and per-lane meters come back as one buffer.  A program
whose sweep spans carry no count reads as no value."""


def read(ctx):
    spans = ctx.spans.get("sweep", []) if ctx.spans else []
    counts = [args.get("fetches") for _, _, args in spans]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
