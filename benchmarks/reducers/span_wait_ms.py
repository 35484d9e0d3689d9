"""A statistic (`mean` or `median`) of the OFF-CPU self time of one program
span, in ms, over its events inside the traced seconds: what the span's
thread WAITED there and did not run. A span the program opened with `cpu`
carries `cpu_us`, the time its thread ran inside it (`time.thread_time_ns`
at both ends; the serving iteration, its program children and their
`.../fetch` are opened so), and then

    wait = (duration - children's duration) - (cpu_us - children's cpu_us)

over the event's direct children ON THE SAME THREAD: for `serving/iteration`
less its program children and its `.../lock_wait` that is the driver
thread's wait for the interpreter, chiefly. The arguments are `span_ms`'s:
`less` is a list of child names, or `"children"` for all direct children;
`has` keeps only the events that carry that count above 0. A child on
another thread is no part of this thread's time and is ignored. Where an
event, or a child that is taken out, carries no `cpu_us` (the records of a
commit before it was added) there is nothing to read: None. The sample count
goes to stderr."""

from benchmarks.reducers import program_spans


def reduce(ctx, span: str, stat: str = "mean", less=None, has: str = None):
    spans, events = program_spans.recorded(ctx, span, has)
    program_spans.note_samples("span_wait_ms", span, len(events))
    if not events:
        return None
    children = {}
    if less:
        for s in spans:
            if "parent_id" in s and (less == "children" or s["name"] in less):
                children.setdefault(s["parent_id"], []).append(s)
    waits = []
    for e in events:
        mine = [c for c in children.get(e["id"], ())
                if c.get("thread") == e.get("thread")]
        if any("cpu_us" not in s.get("attrs", {}) for s in [e] + mine):
            return None
        wall = e["end_s"] - e["start_s"] - sum(
            min(c["end_s"], e["end_s"]) - max(c["start_s"], e["start_s"])
            for c in mine)
        ran = e["attrs"]["cpu_us"] - sum(c["attrs"]["cpu_us"] for c in mine)
        waits.append(wall - 1e-6 * ran)
    return 1e3 * program_spans.statistic(waits, stat)
