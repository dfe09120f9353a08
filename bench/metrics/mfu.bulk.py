"""The bulk step's share of the chip's peak: rows scored per second in
the traced window times live operations per row, at peak FLOP/s."""
import work


def read(ctx):
    calls = ctx.calls
    if calls is None or calls["n"] == 0:
        return None
    rows_per_s = calls["n"] * calls["batch"] / calls["seconds"]
    return rows_per_s * work.ops_per_row(ctx.cfg) / ctx.peak[
        "flops_per_s"] * 100.0
