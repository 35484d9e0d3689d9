"""Ops and bytes of `mamba1_decode_step` over the traced window, from the
count the PROGRAM puts on its span `serving/decode`: `ssm_rows`, the (row,
Mamba-1 layer) pairs whose state the step advanced, real rows only (the
chunk program advances states too, through `mamba1_chunk_scan`).

For each pair the kernel reads the float32 state (state x inner width) once
and writes it once, reads `dt x` and `dt` (inner width float32 each) and `B`
and `C` (state float32 each) and writes inner width float32 of output; it
does 6 x state x inner width operations (the decay's argument and product,
a multiply and an add for the rank-one update and for `S C`; the
exponential counts as one of them). `A` (state x inner width float32) is
read once a CALL and stays in fast memory between its rows. The six rows of
zeros that fill the operand's sublane tile, and a row that holds nothing and
is sent to the scratch slot, cost time, not bytes the algorithm needs."""

from benchmarks.reducers import phi4flash_costs as costs
from benchmarks.reducers import program_spans


def total(ctx, calls: int):
    sizes = costs.sizes(ctx)
    _, events = program_spans.recorded(ctx, "serving/decode", "ssm_rows")
    pairs = sum(e["attrs"]["ssm_rows"] for e in events)
    if sizes is None or not pairs:
        return None
    _, _, _, inner, n = sizes
    ops = pairs * 6 * n * inner
    nbytes = 4 * (pairs * (2 * n * inner + 3 * inner + 2 * n)
                  + calls * n * inner)
    return ops, nbytes
