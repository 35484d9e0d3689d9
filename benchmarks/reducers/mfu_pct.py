"""Model FLOP/s utilization of a training cell: the operations the forward
and backward passes require per token (`cost`, recomputation not counted)
times the traced run's tokens per second, over chips times peak."""

from benchmarks.harness.layers import reducer


def reduce(ctx, cost: str, rate: str):
    tok_s = ctx.counters.get(rate)
    if tok_s is None or not ctx.peaks:
        return None
    per_token = reducer(cost).per_token(ctx)
    return 100.0 * per_token * tok_s / (ctx.chips * ctx.peaks["bf16_flops"])
