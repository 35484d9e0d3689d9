"""Ops and bytes of `flash_attention_bwd_dq` over the traced window: calls x
per call (flash_attention_cost.py)."""

from benchmarks.reducers.flash_attention_cost import per_call


def total(ctx, calls: int):
    ops, nbytes = per_call(ctx, "bwd_dq")
    return ops * calls, nbytes * calls
