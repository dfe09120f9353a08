"""Device time per call outside the Pallas kernel: the executable's
relayouts, pads and reductions around it."""


def read(ctx):
    dev = ctx.device
    if dev is None or dev.kernel_calls == 0:
        return None
    return dev.nonkernel_s / dev.kernel_calls * 1e3
