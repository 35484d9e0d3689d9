"""A kernel's share of its roofline: the least time the chip could take for
the work (the larger of operations / peak FLOP/s and bytes / peak bytes/s,
from the ops/bytes function `cost` and `peaks.json`) over the kernel's device
time in the trace. First chip of the cell."""

from benchmarks.harness.layers import reducer


def reduce(ctx, kernel: str, cost: str):
    if ctx.trace is None or not ctx.trace.devices or not ctx.peaks:
        return None
    seconds, calls = ctx.trace.op_seconds(ctx.trace.devices[0], kernel)
    if calls == 0 or seconds <= 0:
        return None
    work = reducer(cost).total(ctx, calls)
    if work is None:
        return None
    ops, nbytes = work
    least = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
