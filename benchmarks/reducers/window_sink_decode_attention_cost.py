"""Ops and bytes of `window_decode_attention` as the `mimo_v2_flash` family
runs it (the paged decode walk of a window layer over its row's ring, 8
key-value heads of keys 192 and values 128 wide, a learned sink a query
head) over the traced interval: each decode row over the last
`attention_window` keys of its context, times the WINDOW layers. The bytes
do not grow with the row's length."""

from benchmarks.reducers import mimo_v2_flash_costs as costs


def total(ctx, calls: int):
    return costs.decode_walks(ctx, "window")
