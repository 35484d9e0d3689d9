"""The spans the PROGRAM recorded (`deepspeed_tpu.observability.spans`: it
records them in memory while a profiler capture is open), for the `span_ms`
and `span_count` reductions. Each record is a dict with `name`, `id`,
`parent_id` (where it has a parent), `start_s` and `end_s` in
`time.perf_counter` seconds — the clock `ctx.traced` is on — `thread`, and its
counts under `attrs`."""

import statistics
import sys


def recorded(ctx, span: str, has: str = None):
    """(every recorded span inside the traced seconds, those named `span`).
    Where `ctx.traced` is not set (a rehearsal on the CPU) every recorded
    span counts. With `has`, only the events that carry a count of that name
    above 0 are kept of those named `span`: the program writes `rows` on a
    decode step and `tokens` on a prefill chunk once the work is known to
    run, so a step that found no row ready, or a chunk the pool could not
    place, is no sample of the work. A program that has no span record (a
    commit before it was added) gives two empty lists, as does a run that
    recorded nothing."""
    try:
        from deepspeed_tpu import observability
        spans = observability.recorded_spans()
    except (ImportError, AttributeError):
        return [], []
    if ctx.traced is not None:
        lo, hi = ctx.traced
        spans = [s for s in spans if lo <= s["start_s"] and s["end_s"] <= hi]
    return spans, [s for s in spans if s["name"] == span
                   and (has is None or s.get("attrs", {}).get(has, 0) > 0)]


def statistic(values, stat: str) -> float:
    if stat == "mean":
        return statistics.fmean(values)
    if stat == "median":
        return statistics.median(values)
    if stat == "last":
        return values[-1]
    raise ValueError(f"unknown statistic '{stat}'")


def note_samples(reducer: str, what: str, n: int) -> None:
    print(f"{reducer} {what}: {n} samples", file=sys.stderr, flush=True)
