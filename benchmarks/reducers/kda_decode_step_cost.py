"""Ops and bytes of `kda_decode_step` over the traced window, from the count
the PROGRAM puts on its span `serving/decode`: `recurrent_rows`, the (row,
linear-attention layer) pairs whose state the step advanced, real rows only
(the chunk program advances states too, but not through this kernel).

For each pair and head the kernel reads the float32 state (d x d) once and
writes it once, reads one 8 x d float32 tile of vectors (decay, key, query,
beta, value) and writes d float32 of output; it does 7 d^2 operations (the
decay's product, and a multiply and an add each for `S'^T k`, the rank-one
update and `S^T q`). A row that holds nothing is sent to the scratch slot
and costs time, not bytes that the algorithm needs.

A program that writes no such count (a commit before it, a model with no
such layer) gives None, and the metric is left out."""

from benchmarks.reducers import program_spans


def total(ctx, calls: int):
    _, events = program_spans.recorded(ctx, "serving/decode",
                                       "recurrent_rows")
    pairs = sum(e["attrs"]["recurrent_rows"] for e in events)
    heads = getattr(ctx.model_config, "kda_num_heads", 0)
    d = getattr(ctx.model_config, "kda_head_dim", 0)
    if not pairs or not heads:
        return None
    ops = pairs * heads * 7 * d * d
    nbytes = pairs * heads * 4 * (2 * d * d + 8 * d + d)
    return ops, nbytes
