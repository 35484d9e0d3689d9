"""Ops and bytes of `shared_kv_decode_attention` (the paged decode walk of
the full layer and of every cross layer, all over the full layer's ONE pool)
over the traced interval: each decode row over its whole context, times the
layers that read the pool (the pool's pages are read once a layer)."""

from benchmarks.reducers import phi4flash_costs as costs


def total(ctx, calls: int):
    return costs.decode_walks(ctx, windowed=False)
