"""Ops and bytes of `paged_prefill_attention` over the traced window. The
harness sees a request's submit and its first token, not each chunk; so a
request's chunks are counted by the share of its submit-to-first-token
interval that lies inside the traced interval (an estimate: queueing before
the first chunk is spread over the chunks); times the layers."""

from benchmarks.reducers.paged_attention_cost import prefill_chunk


def total(ctx, calls: int):
    if ctx.traced is None:
        return None
    t0, t1 = ctx.traced
    chunk = int(ctx.cell.config["serving"]["prefill_chunk"])
    ops = nbytes = 0.0
    for r in ctx.records:
        if not r.token_times or r.token_times[0] <= r.submit_time:
            continue
        a, b = r.submit_time, r.token_times[0]
        share = max(0.0, min(b, t1) - max(a, t0)) / (b - a)
        if share <= 0:
            continue
        for start in range(0, r.prompt_len, chunk):
            o, by = prefill_chunk(ctx, start, min(chunk, r.prompt_len - start))
            ops, nbytes = ops + share * o, nbytes + share * by
    layers = ctx.model_config.num_layers
    return (ops * layers, nbytes * layers) if ops else None
