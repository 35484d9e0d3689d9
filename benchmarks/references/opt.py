"""Plain reference for the OPT family (Zhang et al. 2022, arXiv:2205.01068;
`facebook/opt-*` config.json): a decoder-only transformer with learned
absolute positions, pre-LayerNorm blocks, multi-head causal attention with
biases, a ReLU feed-forward of 4x width, a final LayerNorm and an output head
tied to the token embedding.

Straightforward `jax.numpy` in float32 with no kernels, no cache and no
batching tricks; callers wrap it in `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in bf16 passes). It reads the
parameter tree the program's `models/transformer.py` builds (leaves stacked
over layers), and shares no code with it.

Departure from the published model, noted: Hugging Face's OPT offsets
position ids by 2 into a table of 2050 rows; the program keeps a table of
`max_position_embeddings` rows indexed from 0, and so does this.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def logits(params, input_ids, *, num_heads: int, eps: float = 1e-5):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    B, S = input_ids.shape
    emb = params["embed"]["tokens"].astype(jnp.float32)
    x = emb[input_ids] + params["pos"].astype(jnp.float32)[:S][None]
    H = x.shape[-1]
    D = H // num_heads
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(x, layer):
        layer = f32(layer)
        a, m = layer["attn"], layer["mlp"]
        h = _layer_norm(x, layer["ln1"]["scale"], layer["ln1"]["bias"], eps)
        q = (h @ a["wq"] + a["bq"]).reshape(B, S, num_heads, D)
        k = (h @ a["wk"] + a["bk"]).reshape(B, S, num_heads, D)
        v = (h @ a["wv"] + a["bv"]).reshape(B, S, num_heads, D)
        s = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)
        x = x + o.reshape(B, S, H) @ a["wo"] + a["bo"]
        h = _layer_norm(x, layer["ln2"]["scale"], layer["ln2"]["bias"], eps)
        x = x + jax.nn.relu(h @ m["w_up"] + m["b_up"]) @ m["w_down"] \
            + m["b_down"]
        return x, None

    # one layer at a time, so only one layer's float32 copy is alive
    x, _ = jax.lax.scan(block, x, params["layers"])
    fn = f32(params["final_norm"])
    x = _layer_norm(x, fn["scale"], fn["bias"], eps)
    return x @ emb.T


def next_token_logprobs(params, input_ids, *, num_heads: int):
    """(B, S) -> (B, S-1): log-probability of token p+1 given tokens 0..p."""
    lp = jax.nn.log_softmax(logits(params, input_ids, num_heads=num_heads),
                            axis=-1)[:, :-1]
    return jnp.take_along_axis(lp, input_ids[:, 1:, None], axis=-1)[..., 0]


def loss(params, input_ids, *, num_heads: int):
    """Mean next-token cross entropy over the batch, as a training step
    reports it."""
    return -next_token_logprobs(params, input_ids, num_heads=num_heads).mean()
