"""Plain reference for the GPT-NeoX family (Black et al. 2022, GPT-NeoX-20B,
arXiv:2204.06745; `EleutherAI/gpt-neox-20b` config.json), kept as a test
fixture: the family that `test_new_family_arrives_as_files` brings into a
temporary copy of the benchmark as files. No cell of BENCHMARK.json runs it.

A decoder-only transformer with no position table: rotary embeddings on the
first `rotary_pct` of each head's dimensions (halves rotated against each
other, base `rotary_emb_base`), multi-head causal attention with biases, a
GELU feed-forward of 4x width (the 20B model's `gelu_fast`: the tanh form),
both read from the block's INPUT through a LayerNorm each and added to it in
one sum (`use_parallel_residual`), a final LayerNorm and an output head of
its own (`tie_word_embeddings` false, no bias).

Straightforward `jax.numpy` in float32, no kernels, no cache; callers wrap it
in `jax.default_matmul_precision("highest")`. It reads the parameter tree
the program builds (leaves stacked over layers) and shares no code with it.

Departure from the published model, noted: Hugging Face keeps query, key and
value in one matrix, interleaved by head; the program keeps three, and so
does this. With weights drawn from a seed the two are the same model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rotate_half(x):
    x1, x2 = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotary(x, rotary_ndims, base):
    """x: (B, S, heads, D); the first `rotary_ndims` of D turn with the
    position, the rest pass."""
    S = x.shape[1]
    inv_freq = 1.0 / base ** (jnp.arange(0, rotary_ndims, 2,
                                         dtype=jnp.float32) / rotary_ndims)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    rot, rest = x[..., :rotary_ndims], x[..., rotary_ndims:]
    rot = rot * jnp.cos(angles) + _rotate_half(rot) * jnp.sin(angles)
    return jnp.concatenate([rot, rest], axis=-1)


def logits(params, input_ids, *, num_heads: int, rotary_pct: float,
           rotary_emb_base: float, layer_norm_eps: float):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    B, S = input_ids.shape
    x = params["embed"]["tokens"].astype(jnp.float32)[input_ids]
    H = x.shape[-1]
    D = H // num_heads
    rotary_ndims = int(D * rotary_pct)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(x, layer):
        layer = f32(layer)
        a, m = layer["attn"], layer["mlp"]
        h = _layer_norm(x, layer["ln1"], layer_norm_eps)
        q = (h @ a["wq"] + a["bq"]).reshape(B, S, num_heads, D)
        k = (h @ a["wk"] + a["bk"]).reshape(B, S, num_heads, D)
        v = (h @ a["wv"] + a["bv"]).reshape(B, S, num_heads, D)
        q = _rotary(q, rotary_ndims, rotary_emb_base)
        k = _rotary(k, rotary_ndims, rotary_emb_base)
        s = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)
        attn = o.reshape(B, S, H) @ a["wo"] + a["bo"]
        h = _layer_norm(x, layer["ln2"], layer_norm_eps)
        u = h @ m["w_up"] + m["b_up"]
        u = 0.5 * u * (1.0 + jnp.tanh(0.7978845608 * u
                                      * (1.0 + 0.044715 * u * u)))
        return x + attn + (u @ m["w_down"] + m["b_down"]), None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = _layer_norm(x, f32(params["final_norm"]), layer_norm_eps)
    return x @ params["lm_head"].astype(jnp.float32)


def next_token_logprobs(params, input_ids, **reference_args):
    """(B, S) -> (B, S-1): log-probability of token p+1 given tokens 0..p."""
    lp = jax.nn.log_softmax(logits(params, input_ids, **reference_args),
                            axis=-1)[:, :-1]
    return jnp.take_along_axis(lp, input_ids[:, 1:, None], axis=-1)[..., 0]


def loss(params, input_ids, **reference_args):
    """Mean next-token cross entropy over the batch."""
    return -next_token_logprobs(params, input_ids, **reference_args).mean()
