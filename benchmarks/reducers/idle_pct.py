"""Share of the traced window in which no operation ran on the device:
1 - union of the device-op intervals over the window, averaged over chips."""


def reduce(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.trace.window()
    if hi <= lo:
        return None
    busy = [ctx.trace.busy_s(d) for d in ctx.trace.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
