"""Share of the traced window in which no PROGRAM was running on the device:
the time the device waits for the host between one program's end and the
next one's start (gaps between operations inside a program do not count;
`idle_pct` has those too). First chip."""

from benchmarks.harness import stats


def reduce(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    dev = ctx.trace.devices[0]
    mods = ctx.trace.modules.get(dev)
    lo, hi = ctx.trace.window()
    if not mods or hi <= lo:
        return None
    covered = stats.union_seconds(
        [(max(s, lo), min(s + d, hi)) for _, s, d in mods if s + d > lo
         and s < hi])
    return 100.0 * (1.0 - covered / (hi - lo))
