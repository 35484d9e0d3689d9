"""Ops and bytes of `latent_decode_attention` over the traced interval:
what the MATHEMATICS of an absorbed latent read needs, whatever implements
it. Every token after a request's first that the harness saw arrive inside
the interval was one decode row attending to the prompt and what was
generated before it; a pool (a sublayer, two a weight layer):

- bytes: each resident token's latent and roped key, `kv_lora_rank +
  rotary_dim` values in the model's dtype (1,152 B as published), read ONCE,
  whole pages; the row's queries in (heads x that width) and its mixed
  latents out (heads x `kv_lora_rank`);
- operations: a head's score against a token is a product over the latent
  and the roped key, and its weighted sum one over the latent: 2 x heads x
  ((kv_lora_rank + rotary_dim) + kv_lora_rank) a token, 139,264 as
  published.

The lanes a page is padded with and a second copy of a page (the first
form's keys AND values) cost time, not bytes or operations that the
algorithm needs, and are not counted: a walk that reads a page once moves
the share with the yardstick left alone. A model with no latent pool, or no
traced interval, gives None."""


def total(ctx, calls: int):
    cfg = ctx.model_config
    latent = getattr(cfg, "kv_lora_rank", 0)
    if ctx.traced is None or not latent:
        return None
    import jax.numpy as jnp

    itemsize = jnp.dtype(ctx.cell.config["model"]["dtype"]).itemsize
    width = latent + cfg.rotary_dim
    block = int(ctx.cell.config["serving"]["block_size"])
    t0, t1 = ctx.traced
    ops = nbytes = 0.0
    for r in ctx.records:
        for i, t in enumerate(r.token_times):
            if i > 0 and t0 <= t <= t1:
                context = r.prompt_len + i
                resident = -(-context // block) * block
                ops += 2 * cfg.num_heads * (width + latent) * context
                nbytes += itemsize * (resident * width
                                      + cfg.num_heads * (width + latent))
    pools = 2 * cfg.num_layers
    return (ops * pools, nbytes * pools) if ops else None
