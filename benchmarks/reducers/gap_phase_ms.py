"""What the gap between two serving programs is made of, in ms: each gap is
split by INTERSECTION with the program's own spans, which lie in the host
plane of the same `.xplane.pb` as the `XLA Modules` line. Reads only
`ctx.trace.modules` (first chip) and `ctx.trace.host`; no trace, None.

A gap is an interval between the first and the last program execution of
the traced window in which no module runs on the first chip: the union
`program_gap_pct` takes, so `mean x gaps / window` is that share. Of the host
events named `serving/...`, D is the `.../dispatch` span with the latest
begin at or before the gap's end (it enqueued the program that ends the
gap). A gap [g0, g1] is then, in this order of claim:

  `enqueue`  its overlap with D: the jitted call, operands transferred
             and the program handed to the runtime;
  `launch`   [max(D's end, g0), g1] where D ended inside the gap, else 0:
             the call has returned and the device has not started;
  `wake`     the overlap of [g0, min(g1, D's begin)] with the `.../fetch`
             spans: the program has ended and the host still waits in
             `np.asarray`;
  `host`     the rest: apply, the iteration's tail and head, lock wait,
             admit, prepare, `serving/idle`.

A gap with no D (nothing was enqueued before it ended), or whose D ended
before it began (the program that ends it had no dispatch span of its own:
a copy-on-write), is all `host`. The four parts of a gap add up to it, and
`mean` is over gaps, so the four metrics add up to `part: gap` exactly.

**The two clocks.** A gap's length is the device's alone and `host` (what
lies between a fetch's end and the next dispatch's begin) the host's alone;
only the split of the rest into `wake` and `enqueue` + `launch` reads one
against the other. The capture puts both on one clock, but the FIRST capture
on a machine had the device early by 0.9-1.4 ms in five calls of five (PR
40's chip runs; the later twenty-eight agree to 0.1 ms): the programs then
began while `DevicePut` was still putting their operands. So the device's
times are first moved LATER by the least that such a capture needs
(`clock_shift`): the median, over the gaps, of how long before its operands
were on the device a program began; 0 where it did not (in a sound capture
a program begins as the last `DevicePut` inside its dispatch ends, to 0.1
ms). The shift goes to stderr with the number of gaps. A device LATE against
the host has not been seen and is not looked for.

Arguments: `part` (`gap`, `wake`, `host`, `enqueue`, `launch`); `stat`
(`mean`, `max`); `before` (optional, a name under `programs/`): only the
gaps that end at an execution of that program. `trace.load` drops host
events under 50 us: a dispatch that short would leave its gap to the
dispatch before it (all `host`), and a fetch that short adds nothing to
`wake`; on the chip a dispatch takes a millisecond."""

import bisect
import statistics
import sys

from benchmarks.harness import stats, trace as trace_mod
from benchmarks.reducers import program_spans

PARTS = ("gap", "wake", "host", "enqueue", "launch")


def _overlap(lo, hi, spans):
    """Seconds of [lo, hi] that the (start, end) `spans` cover."""
    return stats.union_seconds([(max(s, lo), min(e, hi)) for s, e in spans
                                if e > lo and s < hi]) if hi > lo else 0.0


def split(g0, g1, dispatches, fetches):
    """{part: seconds} of the gap [g0, g1]. `dispatches` and `fetches` are
    (start, end) pairs."""
    gap = g1 - g0
    began = [d for d in dispatches if d[0] <= g1]
    if not began:
        return dict(gap=gap, wake=0.0, host=gap, enqueue=0.0, launch=0.0)
    d0, d1 = max(began)
    enqueue = _overlap(g0, g1, [(d0, d1)])
    launch = g1 - max(d1, g0) if g0 <= d1 <= g1 else 0.0
    wake = _overlap(g0, min(g1, d0), fetches)
    return dict(gap=gap, wake=wake, enqueue=enqueue, launch=launch,
                host=gap - enqueue - launch - wake)


def clock_shift(gaps, dispatches, puts):
    """Seconds to move the device's times later by: a program cannot begin
    before the operands of its call are on the device, which is when the
    last `DevicePut` inside its dispatch ends (the dispatch's begin where it
    put none). Each gap is held against the dispatch whose operands were
    ready nearest its end, so that a clock out by more than a call still
    finds its own; the median over the gaps, or 0 where that is negative
    (the programs began after their operands were there, as they must)."""
    if not dispatches or not gaps:
        return 0.0
    puts = sorted(puts)
    begins = [s for s, _ in puts]
    ready = sorted(
        max([e for _, e in puts[bisect.bisect_left(begins, d0):
                                bisect.bisect_right(begins, d1)] if e <= d1],
            default=d0) for d0, d1 in dispatches)
    early = []
    for _, g1 in gaps:
        i = bisect.bisect_left(ready, g1)
        early.append(min((r - g1 for r in ready[max(i - 1, 0):i + 1]),
                         key=abs))
    return max(0.0, statistics.median(early))


def reduce(ctx, part: str, stat: str = "mean", before: str = None):
    if part not in PARTS:
        raise ValueError(f"unknown part '{part}' (one of {PARTS})")
    if stat not in ("mean", "max"):
        raise ValueError(f"unknown statistic '{stat}'")
    if ctx.trace is None or not ctx.trace.devices:
        return None
    mods = ctx.trace.modules.get(ctx.trace.devices[0])
    if not mods:
        return None
    lo = min(s for _, s, _ in mods)
    hi = max(s + d for _, s, d in mods)
    ends_at = {}
    for name, s, _ in mods:
        ends_at.setdefault(s, set()).add(trace_mod.base_name(name))
    only = ctx.module_of(before) if before else None
    host = [(n, s, s + d) for evs in ctx.trace.host.values()
            for n, s, d in evs]
    named = [e for e in host if e[0].startswith("serving/")]
    dispatches = [(s, e) for n, s, e in named if n.endswith("/dispatch")]
    fetches = [(s, e) for n, s, e in named if n.endswith("/fetch")]
    gaps = stats.gaps([(s, s + d) for _, s, d in mods], lo, hi)
    shift = clock_shift(gaps, dispatches,
                        [(s, e) for n, s, e in host if n == "DevicePut"])
    values = [split(g0 + shift, g1 + shift, dispatches, fetches)[part]
              for g0, g1 in gaps
              if only is None or only in ends_at.get(g1, ())]
    program_spans.note_samples(
        "gap_phase_ms", part + (f" before {before}" if before else ""),
        len(values))
    print(f"gap_phase_ms clock: device moved {1e3 * shift:+.3f} ms over "
          f"{len(gaps)} gaps", file=sys.stderr, flush=True)
    if not values:
        return None
    return 1e3 * (max(values) if stat == "max" else statistics.fmean(values))
