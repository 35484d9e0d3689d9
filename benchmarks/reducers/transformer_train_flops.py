"""Operations a training step requires per token, forward and backward,
recomputation not counted: 6 per parameter that a token's activations are
multiplied by (the position table and the embedding lookup are not; the
tied output head is), plus attention: 12 * layers * S * D * heads / 2
(causal) for the q k^T and p v matmuls, forward (2x) and backward (4x)."""


def per_token(ctx) -> float:
    cfg = ctx.model_config
    h, f, layers = cfg.hidden_size, cfg.ffn_hidden_size, cfg.num_layers
    kv = cfg.num_kv_heads * cfg.head_dim
    per_layer = h * h + 2 * h * kv + h * h + 2 * h * f   # q, k+v, o, up+down
    matmul_params = layers * per_layer + cfg.vocab_size * h    # + output head
    s = int(ctx.counters["sequence"])
    attn = 12 * layers * s * cfg.num_heads * cfg.head_dim / 2
    return 6.0 * matmul_params + attn
