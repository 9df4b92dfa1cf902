"""Share of the traced interval in which no operation ran on the device,
in percent: 1 - (union of device-op intervals) / interval."""


def read(ctx):
    if ctx.trace is None:
        return None
    tr = ctx.trace
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
