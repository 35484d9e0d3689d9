"""Operations and bytes of the three causal flash-attention kernels of a
training step, per call, on one chip: q, k, v of shape (B, N, S, D) in bf16,
B the rows per chip. Causal masking halves the S x S work. Matmuls of
2*S*S*D operations each, per (row, head):

- fwd:     s = q k^T, o = p v                       -> 2 matmuls
- bwd_dq:  s = q k^T, dp = do v^T, dq = ds k        -> 3 matmuls
- bwd_dkv: s, dp as above, dv = p^T do, dk = ds^T q -> 4 matmuls

(the two backward kernels each recompute s and dp: that is what the split
costs, and the kernel cannot do its job with less). Bytes: every operand
read once and every result written once; log-sum-exp and delta rows are
float32 of shape (B, N, S)."""

MATMULS = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}
#            bf16 (B,N,S,D) tensors moved, float32 (B,N,S) rows moved
MOVED = {"fwd": (4, 1), "bwd_dq": (5, 2), "bwd_dkv": (6, 2)}


def per_call(ctx, which: str):
    cfg, c = ctx.model_config, ctx.counters
    b, s = int(c["rows_per_chip"]), int(c["sequence"])
    n, d = cfg.num_heads, cfg.head_dim
    ops = MATMULS[which] * 2 * b * n * s * s * d / 2
    tensors, rows = MOVED[which]
    return ops, tensors * b * n * s * d * 2 + rows * b * n * s * 4
