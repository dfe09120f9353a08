"""Mean ``step`` span less the ``upload``, ``sweep`` and ``billing``
spans inside it: admission into the slot table, the firing decision and
the release of the swept lanes."""
import bisect


def read(ctx):
    spans = ctx.spans or {}
    steps = sorted(spans.get("step", []), key=lambda s: s[0])
    if not steps:
        return None
    starts = [a for a, _, _ in steps]
    inner = 0.0
    for name in ("upload", "sweep", "billing"):
        for a, b, _ in spans.get(name, []):
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and b <= steps[i][1]:
                inner += b - a
    total = sum(b - a for a, b, _ in steps)
    return (total - inner) / len(steps) * 1e3
