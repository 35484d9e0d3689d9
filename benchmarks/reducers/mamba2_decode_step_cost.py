"""Ops and bytes of `mamba2_decode_step` over the traced window, from the
count the PROGRAM puts on its span `serving/decode`: `ssm_rows`, the (row,
Mamba-2 layer) pairs whose state the step advanced, real rows only (the chunk
program advances states too, but not through this kernel).

For each pair the kernel reads every head's float32 state (head dim x
state) once and writes it once, reads the step's vectors (`dt x` and the
decay across the head's lanes, head dim float32 each a head, and `B` and `C`,
state float32 each a GROUP: the heads of a group share them) and writes head
dim float32 of output a head; it does 5 x head dim x state operations a head
(the decay's product, and a multiply and an add each for the rank-one update
and for `S C`). The six rows of zeros that fill each operand's sublane tile,
and a row that holds nothing and is sent to the scratch slot, cost time, not
bytes that the algorithm needs.

A program that writes no such count (a commit before it, a model with no
such layer) gives None, and the metric is left out."""

from benchmarks.reducers import program_spans


def total(ctx, calls: int):
    _, events = program_spans.recorded(ctx, "serving/decode", "ssm_rows")
    pairs = sum(e["attrs"]["ssm_rows"] for e in events)
    cfg = ctx.model_config
    heads = getattr(cfg, "mamba_num_heads", 0)
    if not pairs or not heads:
        return None
    p, n = cfg.mamba_head_dim, cfg.mamba_state_size
    ops = pairs * heads * 5 * p * n
    nbytes = pairs * 4 * (heads * (2 * p * n + 3 * p)
                          + cfg.mamba_n_groups * 2 * n)
    return ops, nbytes
