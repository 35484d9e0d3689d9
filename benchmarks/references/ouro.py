"""Plain reference for the Ouro family (ByteDance/Ouro-2.6B `config.json`,
`model_type` ouro; the LoopLM of "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741).

A decoder-only transformer whose WHOLE stack of layers runs `total_ut_steps`
times over, the same weights in every pass. With `R` an RMSNorm with a scale,
for pass `t` and layer `l`:

    a = R1_l(x);  q, k, v = a Wq_l, a Wk_l, a Wv_l;  q, k = rope(q, k)
    o = softmax(q k^T / sqrt(head size), causal) v     # the keys of THIS pass
    x = x + R2_l(o Wo_l)                               # a norm BEHIND the mixer
    b = R3_l(x);  x = x + R4_l((silu(b Wg_l) * (b Wu_l)) Wd_l)   # and the FFN
    after the last layer:  x = Rf(x);  h_t = x         # the final norm closes
                                                       # EVERY pass and feeds
                                                       # the next
    lam_t = sigmoid(h_t w_e + b_e)                     # the exit gate
    p_t = lam_t prod_{j<t}(1 - lam_j), and the last pass takes what is left
    exit pass = the first t whose sum_{j<=t} p_j >= early_exit_threshold,
                else the last;  logits = h_exit W_head

Rotary embeddings over the whole head (halves rotated against each other,
base `rope_theta`), the same positions in every pass; no biases but the
gate's; an output head of its own (`tie_word_embeddings` false).

Straightforward `jax.numpy` in float32 with no kernels, no cache and no
batching tricks: a pass keeps nothing, the sequence attends to itself in
each, and the passes are a PYTHON loop (the program's are a `lax.scan`
around its layer scan: nothing here could share a fault with it). Callers
wrap it in `jax.default_matmul_precision("highest")`. It reads the parameter
tree the program's `models/transformer.py` builds (leaves stacked over
layers; the layers of a pass are a `lax.scan`, which keeps ONE layer's
float32 copy alive at a time beside the bfloat16 parameters) and shares no
code with it.

Departures from the published code, noted: none in the mathematics as the
configuration's file states it (`assumed` there lists what the catalog's
copy of `config.json` does not carry: where the four norms of a layer stand,
that the final norm closes every pass, what the gate reads). The paper's
decode-time sharing of the last pass's keys among all passes is NOT the
published configuration and is not here. The exit is taken a token, by the
rule above, whatever the threshold: at the published threshold of 1 the
cumulated probability reaches it only at the last pass (or nowhere, in
float32, and the rule's "else the last" says the same). `controls`, a set of
names, exists for the tests and `scripts/check_ouro_on_chip.py`: each makes
one WRONG model, which must fail the tolerance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# wrong models, one mechanism each left out or misplaced
CONTROLS = ("one_pass_fewer",       # T - 1 passes for T
            "previous_pool",        # a query of pass t reads pass t-1's keys
            "last_pool",            # every pass reads the last pass's keys
            "no_post_norms",        # no norm behind the halves
            "final_norm_once")      # the closing norm once, at the end


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


def _one_pass(layers, x, *, num_heads, num_kv_heads, rope_theta, eps,
              post_norms=True, foreign_kv=None, low=lambda a: a):
    """The stack once over `x` (B, S, H) -> (x, (k, v) of every layer,
    (L, B, S, K, D) each). `foreign_kv`: the keys and values of ANOTHER
    pass, which a wrong model reads in place of its own. `low`: what rounds
    a matrix and a half's normed input to a lower precision."""
    B, S, H = x.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(x, layer_and_kv):
        layer, foreign = layer_and_kv
        layer = jax.tree.map(
            lambda a: (low(a.astype(jnp.float32)) if a.ndim > 1
                       else a.astype(jnp.float32)), layer)
        a, m = layer["attn"], layer["mlp"]
        h = low(_rms_norm(x, layer["ln1"]["scale"], eps))
        D = a["wq"].shape[-1] // num_heads
        q = _rotary((h @ a["wq"]).reshape(B, S, num_heads, D), rope_theta)
        k = _rotary((h @ a["wk"]).reshape(B, S, num_kv_heads, D), rope_theta)
        v = (h @ a["wv"]).reshape(B, S, num_kv_heads, D)
        kept = (k, v)
        if foreign is not None:
            k, v = foreign
        group = num_heads // num_kv_heads       # 1 in Ouro: 16 of 16
        kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqnd,bknd->bnqk", q, kk) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), vv)
        o = o.reshape(B, S, num_heads * D) @ a["wo"]
        if post_norms:
            o = _rms_norm(o, layer["ln1_post"]["scale"], eps)
        x = x + o
        h = low(_rms_norm(x, layer["ln2"]["scale"], eps))
        f = (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
        if post_norms:
            f = _rms_norm(f, layer["ln2_post"]["scale"], eps)
        return x + f, kept

    # one layer at a time, so only one layer's float32 copy is alive
    return jax.lax.scan(block, x, (layers, foreign_kv))


def _forward(params, input_ids, *, total_ut_steps, early_exit_threshold,
             rms_norm_eps, rope_theta, num_attention_heads,
             num_key_value_heads, controls=(), mantissa_bits=None):
    """(B, S) ids -> ((B, S, V) logits, (B, S) the pass each token left at).
    `mantissa_bits`: the model in the precision below the one it is served
    in, every matrix and every half's normed input rounded to that many bits
    of mantissa (3 for float8 e4m3)."""
    unknown = set(controls) - set(CONTROLS)
    if unknown:
        raise ValueError(f"unknown controls {sorted(unknown)}")
    f32 = jnp.float32
    low = ((lambda a: a) if mantissa_bits is None else
           (lambda a: jax.lax.reduce_precision(a, 8, mantissa_bits)))
    x = low(params["embed"]["tokens"].astype(f32))[input_ids]
    final = params["final_norm"]["scale"].astype(f32)
    gate_w = params["exit_gate"]["w"].astype(f32)
    gate_b = params["exit_gate"]["b"].astype(f32)
    passes = total_ut_steps - ("one_pass_fewer" in controls)
    once = dict(num_heads=num_attention_heads, num_kv_heads=num_key_value_heads,
                rope_theta=rope_theta, eps=rms_norm_eps,
                post_norms="no_post_norms" not in controls, low=low)

    def own_kv(x_in):
        """The keys and values every pass would make of its own input, with
        nothing foreign read: what the wrong model `last_pool` reads."""
        kv, x = [], x_in
        for _ in range(passes):
            x, kept = _one_pass(params["layers"], x, **once)
            x = _rms_norm(x, final, rms_norm_eps)
            kv.append(kept)
        return kv

    last_kv = own_kv(x)[-1] if "last_pool" in controls else None
    outputs, previous = [], None
    for t in range(passes):                     # a Python loop: no scan
        foreign = None
        if "previous_pool" in controls and t > 0:
            foreign = previous
        if last_kv is not None:
            foreign = last_kv
        x, previous = _one_pass(params["layers"], x, foreign_kv=foreign,
                                **once)
        if "final_norm_once" not in controls or t == passes - 1:
            x = _rms_norm(x, final, rms_norm_eps)
        outputs.append(x)

    # the exit, a token: p_t = lam_t prod_{j<t}(1 - lam_j), the last pass
    # takes what is left; leave at the first pass whose cumulated
    # probability reaches the threshold, else at the last
    left = jnp.ones(input_ids.shape, f32)
    cum = jnp.zeros(input_ids.shape, f32)
    exit_pass = jnp.full(input_ids.shape, passes - 1, jnp.int32)
    for t, h in enumerate(outputs):
        lam = jax.nn.sigmoid(h @ gate_w + gate_b)
        cum = cum + (lam * left if t < passes - 1 else left)
        left = left * (1.0 - lam)
        exit_pass = jnp.minimum(exit_pass, jnp.where(
            cum >= early_exit_threshold, t, passes - 1))
    h_exit = jnp.take_along_axis(
        jnp.stack(outputs), exit_pass[None, ..., None], axis=0)[0]
    return low(h_exit) @ low(params["lm_head"].astype(f32)), exit_pass


def logits(params, input_ids, **reference_args):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    return _forward(params, input_ids, **reference_args)[0]


def exit_passes(params, input_ids, **reference_args):
    """(B, S) -> (B, S) int32: the pass each token's logits are read from."""
    return _forward(params, input_ids, **reference_args)[1]


def next_token_logprobs(params, input_ids, **reference_args):
    """(B, S) -> (B, S-1): log-probability of token p+1 given tokens 0..p."""
    lp = jax.nn.log_softmax(logits(params, input_ids, **reference_args),
                            axis=-1)[:, :-1]
    return jnp.take_along_axis(lp, input_ids[:, 1:, None], axis=-1)[..., 0]


def loss(params, input_ids, **reference_args):
    """Mean next-token cross entropy over the batch."""
    return -next_token_logprobs(params, input_ids, **reference_args).mean()
