"""The named kernels' share of one registered program's device time,
counting only the kernel events that ran INSIDE an execution of that program
(`kernel_time_pct` counts a kernel wherever it ran: right where one program
alone calls it, wrong for a kernel that two programs share)."""

import bisect

from benchmarks.harness.trace import base_name


def reduce(ctx, kernels, program: str):
    if ctx.trace is None:
        return None
    shares = []
    for dev in ctx.trace.devices:
        runs = sorted((s, s + d)
                      for _, s, d in ctx.program_events(dev, program))
        starts = [a for a, _ in runs]
        whole = sum(b - a for a, b in runs)
        part = 0.0
        for name, s, d in ctx.trace.ops[dev]:
            if base_name(name) in kernels:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s + d <= runs[i][1] + 1e-9:
                    part += d
        if whole > 0 and part > 0:
            shares.append(part / whole)
    return 100.0 * sum(shares) / len(shares) if shares else None
