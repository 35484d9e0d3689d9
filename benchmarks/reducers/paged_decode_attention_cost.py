"""Ops and bytes of `paged_decode_attention` over the traced window: every
token after a request's first that the harness saw arrive inside the traced
interval was one decode row, attending to the prompt and what was generated
before it; times the layers."""

from benchmarks.reducers.paged_attention_cost import decode_row


def total(ctx, calls: int):
    if ctx.traced is None:
        return None
    t0, t1 = ctx.traced
    ops = nbytes = 0.0
    for r in ctx.records:
        for i, t in enumerate(r.token_times):
            if i > 0 and t0 <= t <= t1:
                o, b = decode_row(ctx, r.prompt_len + i)
                ops, nbytes = ops + o, nbytes + b
    layers = ctx.model_config.num_layers
    return (ops * layers, nbytes * layers) if ops else None
