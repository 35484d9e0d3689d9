"""Ops and bytes of `window_decode_attention` (the paged decode walk of a
window layer, started at the window's first page) over the traced interval:
each decode row over the last `attention_window` keys of its context, times
the window layers. The bytes do not grow with the row's length."""

from benchmarks.reducers import phi4flash_costs as costs


def total(ctx, calls: int):
    return costs.decode_walks(ctx, windowed=True)
