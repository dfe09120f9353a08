"""Share of the roofline reached by the Pallas kernel in the serving
sweeps: the least time the live work of each sweep's occupied lanes needs
(``work.bound_s``) over the kernel's device time in the trace."""
import work


def read(ctx):
    dev, spans = ctx.device, (ctx.spans or {}).get("sweep", [])
    if dev is None or dev.kernel_calls == 0 or not spans:
        return None
    lanes = [args["n_valid"] for _, _, args in spans]
    bound, _ = work.bound_s(ctx.cfg, lanes, ctx.peak)
    bound *= dev.kernel_calls / len(lanes)
    return bound / dev.kernel_s * 100.0
