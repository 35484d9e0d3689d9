"""Device time of one registered program per execution, in ms: `stat` is
`mean` or `median` over the executions in the trace, averaged over chips.
`must_run` false is for a program that a short trace may well not hold (a
prefill chunk in a cell that mostly decodes): the metric is then left out."""

import statistics


def reduce(ctx, program: str, stat: str = "mean", must_run: bool = True):
    if ctx.trace is None:
        return None
    per_dev = []
    for dev in ctx.trace.devices:
        durs = [d for _, _, d in ctx.program_events(dev, program, must_run)]
        if durs:
            per_dev.append(statistics.median(durs) if stat == "median"
                           else statistics.fmean(durs))
    return 1e3 * statistics.fmean(per_dev) if per_dev else None
