"""The largest value of one count that a program span carries, over the
span's events inside the traced seconds that have it (`span_count` has the
mean, the median, the newest and the rate; a pause that must be read beside
the longest gap needs the largest). `scale` turns it into the metric's unit;
`has` keeps only the events that carry that count above 0. The sample count
goes to stderr."""

from benchmarks.reducers import program_spans


def reduce(ctx, span: str, count: str, scale: float = 1.0, has: str = None):
    _, events = program_spans.recorded(ctx, span, has)
    values = [e["attrs"][count] for e in events
              if count in e.get("attrs", {})]
    program_spans.note_samples("span_max", f"{span}.{count}", len(values))
    return scale * max(values) if values else None
