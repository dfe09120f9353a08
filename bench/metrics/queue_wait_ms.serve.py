"""Mean time a request waited between its due time and its admission
into a slot-table lane (``RequestRecord.admitted`` - due), over the
requests admitted in the traced window."""


def read(ctx):
    if ctx.requests is None or len(ctx.requests["due"]) == 0:
        return None
    return float((ctx.requests["admitted"] - ctx.requests["due"]).mean()
                 * 1e3)
