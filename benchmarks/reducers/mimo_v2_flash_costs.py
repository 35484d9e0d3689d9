"""What the ops-and-bytes functions of the `mimo_v2_flash` family's two decode
walks share: the sizes of each FORM of its softmax layers as the program's
config states them (`models/transformer.attn_shape`'s fields: a window layer
has its own count of key-value heads), the layers OF THAT FORM (never
`num_layers`), and the decode rows of the traced interval with the tokens
each had in its cache.

A program whose config has no such layers (a commit before them, another
family: no `window_sink`, or cross layers that share the full layer's pool)
gives None everywhere, and the metrics are left out."""


def forms(ctx):
    """{"window" | "full": (layers of the form, key-value heads, a sink or
    not)} and (query heads, a head's keys, a head's values), or None."""
    cfg = ctx.model_config
    kinds = tuple(getattr(cfg, "layer_pattern", ()) or ())
    if not getattr(cfg, "window_sink", False) or "cross" in kinds \
            or "swa" not in kinds:
        return None
    full = sum(k in ("full", "full_dense") for k in kinds)
    sizes = (cfg.num_heads, cfg.head_dim, cfg.v_head_dim or cfg.head_dim)
    return ({"window": (kinds.count("swa"),
                        cfg.window_kv_heads or cfg.num_kv_heads, True),
             "full": (full, cfg.num_kv_heads, False)}, sizes)


def decode_contexts(ctx):
    """The keys each decode row of the traced interval attended to, its own
    included: every token after a request's first that the harness saw
    arrive inside the interval was one decode row."""
    if ctx.traced is None:
        return []
    t0, t1 = ctx.traced
    return [r.prompt_len + i for r in ctx.records
            for i, t in enumerate(r.token_times) if i > 0 and t0 <= t <= t1]


def decode_walks(ctx, form: str):
    """(operations, bytes) of every decode row's walk in the traced
    interval over the layers of `form`: a window layer over the last
    `attention_window` keys of the row, a full layer over all of them. A
    query head's score against a key is a product over the key's width and
    its weighing of the value one over the value's: `heads x 2 x seen x
    (keys + values)`. The bytes are the whole pages the walk touches, `K x
    keys` and `K x values` lanes of bfloat16 with K the FORM's own, the
    sinks (a float a head, where the form has them), the queries in and the
    outputs out. None where there is nothing to read."""
    found, rows = forms(ctx), decode_contexts(ctx)
    if found is None or not rows:
        return None
    (layers, kv_heads, sink), (heads, keys, values) = found[0][form], found[1]
    if not layers:
        return None
    block = int(ctx.cell.config["serving"]["block_size"])
    window = ctx.model_config.attention_window if form == "window" else None
    ops = nbytes = 0
    for context in rows:
        seen = context if window is None else min(context, window)
        first = context - seen
        pages = (context - 1) // block - first // block + 1
        ops += heads * 2 * seen * (keys + values)
        nbytes += (pages * block * kv_heads * (keys + values) * 2
                   + heads * (keys + values) * 2 + 4 * heads * sink)
    return ops * layers, nbytes * layers
