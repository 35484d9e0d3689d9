"""Operations and bytes of the paged attention kernels, per layer, from the
lengths the harness itself recorded. Bytes are the RESIDENT pages only, as
the kernels' contract says: keys and values of ceil(T / block) * block
tokens, K * D wide, in bf16, plus the queries in and the outputs out."""


def _page_bytes(ctx, tokens: int) -> float:
    cfg = ctx.model_config
    block = int(ctx.cell.config["serving"]["block_size"])
    resident = -(-tokens // block) * block
    return 2 * resident * cfg.num_kv_heads * cfg.head_dim * 2


def decode_row(ctx, context: int):
    """One decode row attending to `context` tokens."""
    cfg = ctx.model_config
    nd = cfg.num_heads * cfg.head_dim
    return 4 * nd * context, _page_bytes(ctx, context) + 2 * nd * 2


def prefill_chunk(ctx, start: int, valid: int):
    """One chunk of `valid` prompt tokens at positions start..start+valid-1:
    query i sees start + i + 1 keys."""
    cfg = ctx.model_config
    nd = cfg.num_heads * cfg.head_dim
    seen = valid * start + valid * (valid + 1) // 2
    return 4 * nd * seen, _page_bytes(ctx, start + valid) + 2 * valid * nd * 2
