"""What the ops-and-bytes functions of the `phi4flash` family's kernels
share: the model's sizes as the program's config states them, and the
decode rows of the traced interval with the tokens each had in its cache.

A program whose config has no such layers (a commit before them, another
family) gives None everywhere, and the metrics are left out."""


def sizes(ctx):
    """(window layers, layers that read the shared pool, Mamba-1 layers,
    inner width, state) or None."""
    cfg = ctx.model_config
    kinds = tuple(getattr(cfg, "layer_pattern", ()) or ())
    if "swa" not in kinds or "mamba1" not in kinds:
        return None
    inner = getattr(cfg, "mamba_expand", 2) * cfg.hidden_size
    return (kinds.count("swa"), kinds.count("full") + kinds.count("cross"),
            kinds.count("mamba1"), inner, cfg.mamba_state_size)


def decode_contexts(ctx):
    """The keys each decode row of the traced interval attended to, its own
    included: every token after a request's first that the harness saw
    arrive inside the interval was one decode row."""
    if ctx.traced is None:
        return []
    t0, t1 = ctx.traced
    return [r.prompt_len + i for r in ctx.records
            for i, t in enumerate(r.token_times) if i > 0 and t0 <= t <= t1]


def walk(ctx, context: int, window=None):
    """(operations, bytes) of ONE layer's differential attention for one
    decode row over `context` keys, or over the last `window` of them: the
    two softmax maps of every pair (a query head's 64-wide product with its
    key and its 128-wide weighing of the group's value), and the bytes of
    the pages the walk touches (whole pages, K * D lanes of bfloat16 for
    keys and for values: a pair's keys 64 and its values 128 wide are the
    same lanes), the queries in and the outputs out."""
    cfg = ctx.model_config
    block = int(ctx.cell.config["serving"]["block_size"])
    seen = context if window is None else min(context, window)
    first = context - seen
    pages = (context - 1) // block - first // block + 1
    heads, d = cfg.num_heads, cfg.head_dim
    ops = heads * 2 * seen * (d + 2 * d)
    nbytes = (2 * pages * block * cfg.num_kv_heads * d * 2
              + heads * (2 * d) * 2 * 2)
    return ops, nbytes


def decode_walks(ctx, windowed: bool):
    """(operations, bytes) of every decode row's walk in the traced
    interval, times the layers that make it: the window layers over the last
    `attention_window` keys, or the readers of the shared pool over the
    whole context. None where there is nothing to read."""
    found, rows = sizes(ctx), decode_contexts(ctx)
    if found is None or not rows:
        return None
    window = ctx.model_config.attention_window if windowed else None
    layers = found[0] if windowed else found[1]
    ops = nbytes = 0
    for context in rows:
        o, b = walk(ctx, context, window)
        ops, nbytes = ops + o, nbytes + b
    return ops * layers, nbytes * layers
