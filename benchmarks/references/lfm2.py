"""Plain reference for the `lfm2_moe` family (`LiquidAI/LFM2-8B-A1B`
config.json; the semantics of the family's published modelling code,
`Lfm2MoeShortConv`, `Lfm2MoeAttention`, `Lfm2MoeSparseMoeBlock`).

With `rms(x; w) = w * x / sqrt(mean(x^2) + norm_eps)` (weight `w`, not
`1 + w`): `x = E[ids]`; for each layer `x = x + mixer(rms(x; operator_norm))`,
then `x = x + ffn(rms(x; ffn_norm))`; `logits = rms(x; embedding_norm) @ E^T`
(the head is tied to the embedding).

* `layer_types[i] == "conv"`, a **gated short convolution** on `h` (S, H):
  `[B | C | u] = h W_in` (H -> 3H, no bias); `z = B * u`;
  `y_t = sum_j k[j] * z_{t - (taps - 1) + j}` (depthwise, causal, taps oldest
  first, `z` before the sequence's first token zero; `taps` is `conv_L_cache`,
  read from the shape); `out = (C * y) W_out`. No activation.
* `"full_attention"`: `q = h W_q` in `num_attention_heads` heads of `d`,
  `k, v` in `num_key_value_heads`; `q = rms(q; q_layernorm)` and
  `k = rms(k; k_layernorm)` over each HEAD's `d` values (one weight of `d`
  for all heads); rope, rotate-half over the whole head, base `rope_theta`;
  causal softmax of `q k^T / sqrt(d)`, each group of query heads on one
  key-value head; `@ W_o`.
* the first `num_dense_layers` layers have a **dense FFN**
  `(silu(h W1) * (h W3)) W2`; every other layer **experts**:
  `s = sigmoid(h W_r)` in float32; `chosen = top_k(s + b)` with the bias used
  for the CHOICE only (`use_expert_bias`); `w = s[chosen]`, over
  `sum(s[chosen]) + 1e-6` where `norm_topk_prob`, times
  `routed_scaling_factor`; `out = sum_e w_e (silu(h W1_e) * (h W3_e)) W2_e`.
  No shared expert.

Straightforward `jax.numpy` in float32: no kernels, no cache, no chunks, no
dispatch. Callers wrap it in `jax.default_matmul_precision("highest")`. It
reads the parameter tree the program builds (`params["layers"]` one stacked
tree a kind of layer, each in layer order: `"conv_dense"` and `"conv"`, a
convolution under a dense FFN and under experts, `"attn"` and `"attn_dense"`
likewise; a block holds `ln1`, its mixer `shortconv` or `attn`, `ln2`, and
`dense` or `router`, `router_bias` and the bank `mlp`) and shares no code
with it. Which layer is which is reckoned HERE, from `layer_types` and
`num_dense_layers` (of a longer published list the first layers are run, as
many as the tree holds).

It has to run beside the bfloat16 parameters it is handed (7.9 GB at the
published widths and 12 layers) on a sequence of 8,320: the layers are walked
under ONE `lax.fori_loop` with a `lax.switch` on the layer's kind, a layer
taken out of its stack by its index and made float32 inside the step;
attention runs a head at a time (`lax.map`), the experts one at a time
(`lax.fori_loop`: EVERY expert computes EVERY token, mixed by a dense weight
that is zero off the chosen ones), and `next_token_logprobs` takes the head
in blocks of positions.

The keyword arguments after `routed_scaling_factor` exist for the controls:
a wrong or cheaper model must fail the tolerance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HEAD_BLOCK = 256            # positions of a block of next_token_logprobs
RENORM_EPS = 1e-6           # the published block's, in the renormalisation
F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, heads, d), position = index along S; halves rotated."""
    S, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    angles = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(angles) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angles)


def _short_conv(w, h, gate_b=True, gate_c=True, tail_cut=None):
    B, S, H = h.shape
    taps = w["conv_w"].shape[0]
    b, c, u = jnp.split(h @ w["w_in"], 3, axis=-1)
    z = b * u if gate_b else u
    past = jnp.concatenate([jnp.zeros((B, taps - 1, H), z.dtype), z], axis=1)
    y = 0.0
    for j in range(taps):
        back = taps - 1 - j         # how far behind its token this tap reads
        rows = past[:, j:j + S]
        if tail_cut:                # a control: no history across a boundary
            seen = (jnp.arange(S) % tail_cut) >= back
            rows = jnp.where(seen[None, :, None], rows, 0.0)
        y = y + w["conv_w"][j] * rows
    return ((c * y) if gate_c else y) @ w["w_out"]


def _attention(w, h, heads, kv_heads, theta, eps, norm_per_head=True):
    B, S, _ = h.shape
    q = (h @ w["wq"]).reshape(B, S, heads, -1)
    k = (h @ w["wk"]).reshape(B, S, kv_heads, -1)
    v = (h @ w["wv"]).reshape(B, S, kv_heads, -1)
    d = q.shape[-1]
    if norm_per_head:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    else:       # a control: one norm over the whole projection
        q = _rms(q.reshape(B, S, -1), jnp.tile(w["q_norm"], heads),
                 eps).reshape(q.shape)
        k = _rms(k.reshape(B, S, -1), jnp.tile(w["k_norm"], kv_heads),
                 eps).reshape(k.shape)
    q, k = _rope(q, theta), _rope(k, theta)
    group = heads // kv_heads
    t = jnp.arange(S)
    causal = t[None, :] <= t[:, None]

    def head(n):
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, n], k[:, :, n // group])
        s = jnp.where(causal[None], s / jnp.sqrt(F32(d)), -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1),
                          v[:, :, n // group])

    o = jax.lax.map(head, jnp.arange(heads))            # (heads, B, S, d)
    return jnp.moveaxis(o, 0, 2).reshape(B, S, heads * d) @ w["wo"]


def _dense_ffn(w, h):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _experts(layer, bank, index, h, top_k, renormalise, use_bias, scale,
             low, bias_in_weights=False):
    """`layer`: the block's float32 leaves (its router and bias); `bank` the
    kind's WHOLE stack of experts in the parameters' own dtype, of which
    expert e of layer `index` is made float32 when its turn comes."""
    scores = jax.nn.sigmoid(h @ layer["router"])                # (B, S, E)
    biased = scores + layer["router_bias"] if use_bias else scores
    _, chosen = jax.lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(biased if bias_in_weights else scores,
                                 chosen, axis=-1)
    if renormalise:
        picked = picked / (picked.sum(-1, keepdims=True) + RENORM_EPS)
    picked = picked * scale
    E = scores.shape[-1]
    mix = (jax.nn.one_hot(chosen, E, dtype=F32) * picked[..., None]).sum(-2)

    def one(e, acc):
        w = {name: low(stack[index, e].astype(F32))
             for name, stack in bank.items()}
        return acc + mix[..., e, None] * _dense_ffn(w, h)

    return jax.lax.fori_loop(0, E, one, jnp.zeros_like(h))


def _kind(layer_type, dense):
    mixer = {"conv": "conv", "full_attention": "attn"}[layer_type]
    return mixer + "_dense" if dense else mixer


def _depth(params):
    return sum(jax.tree.leaves(stack)[0].shape[0]
               for stack in params["layers"].values())


def _hidden(params, input_ids, *, layer_types, num_dense_layers,
            num_attention_heads, num_key_value_heads, num_experts_per_tok,
            rope_theta, norm_eps, norm_topk_prob, use_expert_bias,
            routed_scaling_factor, mantissa_bits=None, bias_in_weights=False,
            gate_b=True, gate_c=True, tail_cut=None, norm_per_head=True,
            experts_in_leading_layers=False):
    """(B, S) int ids -> (B, S, H) float32, after the final norm.

    The controls: `mantissa_bits` (the model in the precision below the one
    it is served in: every matrix and every layer's normed inputs rounded to
    that many bits of mantissa, 3 for float8 e4m3), `bias_in_weights` (the
    expert bias added to the weights too), `norm_topk_prob` False (no
    renormalisation), `gate_b` / `gate_c` False (a gate of the convolution
    left out), `tail_cut` (the convolution's history dropped at every
    multiple of that many positions: a tail not carried from chunk to
    chunk), `norm_per_head` False (q and k normed over the whole
    projection), `experts_in_leading_layers` (a leading layer runs the first
    expert layer's experts in place of its dense FFN)."""
    eps = norm_eps
    stacks = params["layers"]
    L = _depth(params)
    kinds = [_kind(t, i < num_dense_layers)
             for i, t in enumerate(layer_types[:L])]
    assert len(kinds) == L and set(kinds) == set(stacks), (kinds, set(stacks))
    names = sorted(stacks)
    which = jnp.asarray([names.index(k) for k in kinds], jnp.int32)
    place = jnp.asarray([kinds[:i].count(k) for i, k in enumerate(kinds)],
                        jnp.int32)       # a layer's place among its kind
    low = ((lambda a: a) if mantissa_bits is None else
           (lambda a: jax.lax.reduce_precision(a, 8, mantissa_bits)))

    def block(kind, index):
        """Layer `index` of its kind in float32, but for the experts' bank."""
        return jax.tree.map(
            lambda a: (low(a[index].astype(F32)) if a.ndim > 2
                       else a[index].astype(F32)),
            {k: v for k, v in stacks[kind].items() if k != "mlp"})

    def experts(kind, index, layer, h):
        return _experts(layer, stacks[kind]["mlp"], index, h,
                        num_experts_per_tok, norm_topk_prob, use_expert_bias,
                        routed_scaling_factor, low, bias_in_weights)

    def layer_of(kind):
        def run(x, index):
            layer = block(kind, index)
            h = low(_rms(x, layer["ln1"]["scale"], eps))
            if kind.startswith("conv"):
                x = x + _short_conv(layer["shortconv"], h, gate_b, gate_c,
                                    tail_cut)
            else:
                x = x + _attention(layer["attn"], h, num_attention_heads,
                                   num_key_value_heads, rope_theta, eps,
                                   norm_per_head)
            h = low(_rms(x, layer["ln2"]["scale"], eps))
            if not kind.endswith("_dense"):
                return x + experts(kind, index, layer, h)
            if experts_in_leading_layers:
                other = next(k for k in kinds if not k.endswith("_dense"))
                return x + experts(other, 0, block(other, 0), h)
            return x + _dense_ffn(layer["dense"], h)
        return run

    branches = [layer_of(kind) for kind in names]
    x = low(params["embed"]["tokens"].astype(F32))[input_ids]
    x = jax.lax.fori_loop(
        0, L, lambda i, x: jax.lax.switch(which[i], branches, x, place[i]), x)
    return _rms(x, params["final_norm"]["scale"].astype(F32), eps)


def logits(params, input_ids, **reference_args):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    x = _hidden(params, input_ids, **reference_args)
    return x @ params["embed"]["tokens"].astype(F32).T


def next_token_stats(params, input_ids, **reference_args):
    """(B, S) -> three (B, S-1): the log-probability of token p+1 given
    tokens 0..p, the largest logit at p, and the logit of token p+1. The
    head in blocks of `HEAD_BLOCK` positions: at the published size all the
    logits of a long sequence are gigabytes."""
    x = _hidden(params, input_ids, **reference_args)[:, :-1]
    targets = input_ids[:, 1:]
    B, T, H = x.shape
    n = -(-T // HEAD_BLOCK)
    pad = n * HEAD_BLOCK - T
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(B, n, HEAD_BLOCK, H)
    targets = jnp.pad(targets, ((0, 0), (0, pad))).reshape(B, n, HEAD_BLOCK)
    table = params["embed"]["tokens"]

    def block(args):
        xb, tb = args                               # (B, HB, H) (B, HB)
        logits = xb @ table.astype(F32).T
        of_next = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return (of_next - jax.nn.logsumexp(logits, axis=-1),
                logits.max(-1), of_next)

    stats = jax.lax.map(block, (jnp.moveaxis(x, 1, 0),
                                jnp.moveaxis(targets, 1, 0)))
    return tuple(jnp.moveaxis(a, 0, 1).reshape(B, n * HEAD_BLOCK)[:, :T]
                 for a in stats)


def next_token_logprobs(params, input_ids, **reference_args):
    """(B, S) -> (B, S-1): log-probability of token p+1 given tokens 0..p."""
    return next_token_stats(params, input_ids, **reference_args)[0]


def loss(params, input_ids, **reference_args):
    """Mean next-token cross entropy over the batch."""
    return -next_token_logprobs(params, input_ids, **reference_args).mean()
