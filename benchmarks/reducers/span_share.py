"""The share of a program span's events whose count `count` EQUALS `equals`
(a name the program gives: which rule held a step), over the events that
carry any of the counts `among` above 0 or as a name, inside the traced
seconds, times `scale` (100 for a share in %). The two sets are counted each
for itself: a decode step is dispatched in one `serving/decode` span and may
be fetched in another, so the span that says why no successor went ahead
(`held_by`) need not be the one that carries the step's `rows`; every step is
dispatched once and fetched once, so the ratio is still a share of the
steps. None where no event carries `count` at all (the records of a commit
before it was added, or a window in which no rule held any step), or where
the denominator is empty. The sample counts go to stderr."""

from benchmarks.reducers import program_spans


def reduce(ctx, span: str, count: str, equals: str, among,
           scale: float = 1.0):
    _, events = program_spans.recorded(ctx, span)
    attrs = [e.get("attrs", {}) for e in events]
    base = sum(1 for a in attrs if any(a.get(k) for k in among))
    named = sum(1 for a in attrs if count in a)
    program_spans.note_samples("span_share", f"{span}.{count}={equals}",
                               base)
    if not base or not named:
        return None
    return scale * sum(1 for a in attrs if a.get(count) == equals) / base
