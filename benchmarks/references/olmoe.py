"""Plain reference for the OLMoE family (Muennighoff et al. 2024, OLMoE: Open
Mixture-of-Experts Language Models, arXiv:2409.02060;
`allenai/OLMoE-1B-7B-0125-Instruct` config.json, `model_type` olmoe; the
semantics of Hugging Face's `modeling_olmoe.py`).

A decoder-only transformer with no position table and no biases. Each block:
RMSNorm, multi-head causal attention whose query and key projections each
pass through an RMSNorm over the WHOLE projection (all heads at once, before
the split into heads) and then rotary embeddings over the whole head (halves
rotated against each other, base `rope_theta`); RMSNorm, then a
mixture-of-experts feed-forward in every layer: the router's logits over all
experts, softmax in float32, the `num_experts_per_tok` most probable, their
probabilities used as they are where `norm_topk_prob` is false (OLMoE) and
divided by their sum where it is true; every expert a SwiGLU MLP
(`down(silu(gate(x)) * up(x))`); no shared expert. A final RMSNorm and an
output head of its own (`tie_word_embeddings` false).

Straightforward `jax.numpy` in float32 with no kernels, no cache, no
dispatch: EVERY expert is computed for EVERY token and the results are mixed
by a dense (tokens, experts) weight matrix that is zero off the chosen
experts. Callers wrap it in `jax.default_matmul_precision("highest")`. It
reads the parameter tree the program's `models/transformer.py` builds (leaves
stacked over layers; `lax.scan` keeps one layer's float32 copy alive at a
time) and shares no code with it.

Departures from the published model, noted: none in the mathematics.
`clip_qkv` is null in the source and is not implemented. `qk_norm=False`
and other values of `num_experts_per_tok` and `norm_topk_prob` exist for the
tests' controls (a wrong model must fail the tolerance).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x: (B, S, heads, D), position = index along S."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return x * jnp.cos(angles) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angles)


def _forward(params, input_ids, *, num_heads, num_experts_per_tok, rope_theta,
             rms_norm_eps, norm_topk_prob, qk_norm=True):
    """(B, S) ids -> ((B, S, V) logits, (L, B, S, E) router probabilities)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    B, S = input_ids.shape
    x = params["embed"]["tokens"].astype(jnp.float32)[input_ids]
    H = x.shape[-1]
    D = H // num_heads
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(x, layer):
        layer = f32(layer)
        a, m = layer["attn"], layer["mlp"]
        h = _rms_norm(x, layer["ln1"]["scale"], rms_norm_eps)
        q, k, v = h @ a["wq"], h @ a["wk"], h @ a["wv"]
        if qk_norm:
            q = _rms_norm(q, a["q_norm"], rms_norm_eps)
            k = _rms_norm(k, a["k_norm"], rms_norm_eps)
        q = _rotary(q.reshape(B, S, num_heads, D), rope_theta)
        k = _rotary(k.reshape(B, S, -1, D), rope_theta)
        v = v.reshape(B, S, -1, D)
        group = num_heads // k.shape[2]         # 1 in OLMoE: 16 of 16
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)
        x = x + o.reshape(B, S, H) @ a["wo"]

        h = _rms_norm(x, layer["ln2"]["scale"], rms_norm_eps)
        probs = jax.nn.softmax(h @ layer["router"], axis=-1)     # (B, S, E)
        top, chosen = jax.lax.top_k(probs, num_experts_per_tok)
        if norm_topk_prob:
            top = top / top.sum(-1, keepdims=True)
        E = probs.shape[-1]
        mix = (jax.nn.one_hot(chosen, E, dtype=jnp.float32)
               * top[..., None]).sum(-2)                         # (B, S, E)
        inner = (jax.nn.silu(jnp.einsum("bsh,ehf->besf", h, m["w_gate"]))
                 * jnp.einsum("bsh,ehf->besf", h, m["w_up"]))
        every = jnp.einsum("besf,efh->besh", inner, m["w_down"])
        return x + jnp.einsum("bse,besh->bsh", mix, every), probs

    # one layer at a time, so only one layer's float32 copy is alive
    x, router = jax.lax.scan(block, x, params["layers"])
    x = _rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                  rms_norm_eps)
    return x @ params["lm_head"].astype(jnp.float32), router


def logits(params, input_ids, **reference_args):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    return _forward(params, input_ids, **reference_args)[0]


def router_probabilities(params, input_ids, **reference_args):
    """(B, S) -> (L, B, S, E): every layer's router softmax, for counting
    how often a lower precision chooses another set of experts."""
    return _forward(params, input_ids, **reference_args)[1]


def next_token_logprobs(params, input_ids, **reference_args):
    """(B, S) -> (B, S-1): log-probability of token p+1 given tokens 0..p."""
    lp = jax.nn.log_softmax(logits(params, input_ids, **reference_args),
                            axis=-1)[:, :-1]
    return jnp.take_along_axis(lp, input_ids[:, 1:, None], axis=-1)[..., 0]


def loss(params, input_ids, **reference_args):
    """Mean next-token cross entropy over the batch, as a training step
    reports it (without the program's load-balancing term)."""
    return -next_token_logprobs(params, input_ids, **reference_args).mean()
