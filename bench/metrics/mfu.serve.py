"""The serving step's share of the chip's peak: live operations of the
occupied lanes over the time of the ``sweep`` spans, at peak FLOP/s."""
import work


def read(ctx):
    spans = (ctx.spans or {}).get("sweep", [])
    if not spans:
        return None
    ops = sum(args["n_valid"] for _, _, args in spans) * work.ops_per_row(
        ctx.cfg)
    secs = sum(b - a for a, b, _ in spans)
    return ops / (secs * ctx.peak["flops_per_s"]) * 100.0
