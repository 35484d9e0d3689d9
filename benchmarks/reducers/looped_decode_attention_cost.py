"""Ops and bytes of `paged_decode_attention` in a LOOPED stack over the
traced interval: what `paged_decode_attention_cost` counts for a stack that
runs once (every token after a request's first that the harness saw arrive
inside the interval was one decode row, attending to the prompt and what was
generated before it; times the layers), times the passes: the kernel walks a
row's pages once a (pass, layer), each pass over its own pool, so the walks
are `loop_passes` x `num_layers` of the model's config, 4 x 48 for the
published model."""

from benchmarks.reducers import paged_decode_attention_cost


def total(ctx, calls: int):
    once = paged_decode_attention_cost.total(ctx, calls)
    if once is None:
        return None
    passes = ctx.model_config.loop_passes
    return once[0] * passes, once[1] * passes
