"""A statistic (`mean` or `median`) of the time one program span takes, in
ms, over its events inside the traced seconds. `less` takes child spans out
of each event first: a list of names (the time the event's direct children of
those names cover), or `"children"` for the span's self time, its duration
less what all its direct children cover. `has` keeps only the events that
carry that count above 0 (`program_spans.recorded`). The sample count goes to
stderr."""

from benchmarks.harness import stats
from benchmarks.reducers import program_spans


def reduce(ctx, span: str, stat: str = "mean", less=None, has: str = None):
    spans, events = program_spans.recorded(ctx, span, has)
    program_spans.note_samples("span_ms", span, len(events))
    if not events:
        return None
    children = {}
    if less:
        for s in spans:
            if "parent_id" in s and (less == "children" or s["name"] in less):
                children.setdefault(s["parent_id"], []).append(s)
    times = []
    for e in events:
        covered = stats.union_seconds(
            [(max(c["start_s"], e["start_s"]), min(c["end_s"], e["end_s"]))
             for c in children.get(e["id"], ())])
        times.append(e["end_s"] - e["start_s"] - covered)
    return 1e3 * program_spans.statistic(times, stat)
