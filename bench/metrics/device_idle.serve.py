"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    if ctx.device is None:
        return None
    return ctx.device.idle_share * 100.0
