"""Ops and bytes of `full_kv_decode_attention` (the paged decode walk of a
full layer of the `mimo_v2_flash` family: 4 key-value heads of keys 192 and
values 128 wide, no sink, every layer its own pool) over the traced
interval: each decode row over its whole context, times the FULL layers."""

from benchmarks.reducers import mimo_v2_flash_costs as costs


def total(ctx, calls: int):
    return costs.decode_walks(ctx, "full")
