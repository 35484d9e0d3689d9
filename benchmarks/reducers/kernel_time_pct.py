"""The named kernels' share of one registered program's device time."""


def reduce(ctx, kernels, program: str):
    if ctx.trace is None:
        return None
    shares = []
    for dev in ctx.trace.devices:
        whole = sum(d for _, _, d in ctx.program_events(dev, program))
        part = sum(ctx.trace.op_seconds(dev, k)[0] for k in kernels)
        if whole > 0 and part > 0:
            shares.append(part / whole)
    return 100.0 * sum(shares) / len(shares) if shares else None
