"""Ops and bytes of `mamba1_chunk_scan` over the traced window, from the
count the PROGRAM puts on its span `serving/prefill_chunk`: `tokens`, a
chunk's real tokens, times the Mamba-1 layers. A token does 6 x state x
inner width operations (as `mamba1_decode_step_cost` counts them) and moves
its own `x`, `dt` and `y` rows (inner width float32 each) and `B` and `C`;
a CALL moves the float32 state in and out once and reads `A` once (state x
inner width each). The rows of a chunk past its real tokens are walked too:
time, not work the algorithm needs. The recurrence has no matmul form, so
against the chip's matmul peak its share is small by nature."""

from benchmarks.reducers import phi4flash_costs as costs
from benchmarks.reducers import program_spans


def total(ctx, calls: int):
    sizes = costs.sizes(ctx)
    _, events = program_spans.recorded(ctx, "serving/prefill_chunk",
                                       "tokens")
    tokens = sum(e["attrs"]["tokens"] for e in events)
    if sizes is None or not tokens:
        return None
    _, _, layers, inner, n = sizes
    ops = tokens * layers * 6 * n * inner
    nbytes = 4 * (tokens * layers * (3 * inner + 2 * n)
                  + calls * 3 * n * inner)
    return ops, nbytes
