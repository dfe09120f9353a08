"""Device time per bulk call of the fused metered kernel, read from the
trace under the name its ``pallas_call`` gives it; a trace without a
kernel of that name reads as no value."""

KERNEL = "fused_impact_metered"


def read(ctx):
    dev = ctx.device
    if dev is None or dev.kernel_calls == 0 or KERNEL not in dev.op_s:
        return None
    return dev.op_s[KERNEL] / dev.devices / dev.kernel_calls * 1e3
