"""Time of the named collectives during which nothing else ran on that
device, as a share of the registered program's device time; mean over chips.
An asynchronous collective shows on the core's line as a short `-start` and
a `-done` that lasts as long as the core had to wait: both count."""

from benchmarks.harness import stats
from benchmarks.harness.trace import base_name


def reduce(ctx, collectives, program: str):
    if ctx.trace is None:
        return None
    shares = []
    for dev in ctx.trace.devices:
        whole = sum(d for _, _, d in ctx.program_events(dev, program))
        coll, other = [], []
        for name, s, d in ctx.trace.leaf_ops(dev):
            (coll if base_name(name).startswith(tuple(collectives))
             else other).append((s, s + d))
        if whole <= 0 or not coll:
            continue
        both = stats.union_seconds(coll + other)
        exposed = both - stats.union_seconds(other)
        shares.append(exposed / whole)
    return 100.0 * sum(shares) / len(shares) if shares else None
