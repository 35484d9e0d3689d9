"""A statistic of one count that a program span carries, over the span's
events inside the traced seconds that have it: `mean`, `median`, `last` (the
newest event's: a cumulative count), or `rate` (the sum over the traced
seconds; needs `ctx.traced`). With `over`, each event gives the ratio of two
of its counts (`rows` over `max_rows`). `scale` turns the result into the
metric's unit (100 for a share in %, 0.001 from us to ms). `has` keeps only
the events that carry that count above 0 (`program_spans.recorded`). The
sample count goes to stderr."""

from benchmarks.reducers import program_spans


def reduce(ctx, span: str, count: str, stat: str = "mean", over: str = None,
           scale: float = 1.0, has: str = None):
    _, events = program_spans.recorded(ctx, span, has)
    values = []
    for e in sorted(events, key=lambda e: e["end_s"]):
        attrs = e.get("attrs", {})
        if count not in attrs or (over and not attrs.get(over)):
            continue
        values.append(attrs[count] / attrs[over] if over else attrs[count])
    program_spans.note_samples("span_count", f"{span}.{count}", len(values))
    if not values:
        return None
    if stat == "rate":
        if ctx.traced is None:
            return None
        return scale * sum(values) / (ctx.traced[1] - ctx.traced[0])
    return scale * program_spans.statistic(values, stat)
