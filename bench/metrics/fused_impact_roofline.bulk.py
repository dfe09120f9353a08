"""Share of the roofline reached by the Pallas kernel in bulk calls: the
least time each call's live work needs (``work.bound_s``) over the
kernel's device time in the trace."""
import work


def read(ctx):
    dev = ctx.device
    if dev is None or dev.kernel_calls == 0 or ctx.calls is None:
        return None
    bound, _ = work.bound_s(ctx.cfg, [ctx.calls["batch"]], ctx.peak)
    return bound * dev.kernel_calls / dev.kernel_s * 100.0
