"""Plain reference for the `nemotron_h` family
(`nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16` config.json): a decoder whose
every layer is ONE function under one pre-norm and one residual add,
`x <- x + f(RMSNorm(x))`, the function named by the layer's character of
`hybrid_override_pattern`; a final RMSNorm and an output head of its own; no
bias but the convolution's, and no positional term anywhere.

*`M`, Mamba-2* (`mamba_num_heads` heads of `mamba_head_dim`, `n_groups`
groups of `ssm_state_size` that the heads share in equal parts, inner width
I = heads x head dim): `[z | xBC | dt] = u W_in` with widths I | I + 2 x
groups x state | heads; `xBC` through a depthwise causal convolution over time
(`conv_kernel` taps, oldest first, rows before the sequence's start zero)
plus its bias, then SiLU; split into `x` (heads x head dim), `B`, `C` (groups
x state); `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)` a head; a float32
state `S` (head dim x state a head), zero at the start, a token at a time
under `lax.scan`: `S <- exp(dt A) S + dt x B^T`, `y = S C + D x`; then
`y <- RMSNorm_grouped(y * silu(z))` (one mean of squares over each group's
I / groups channels, a scale of I), and `y W_out`.

*`*`, attention*: `q, k, v = u W_q, u W_k, u W_v`; `softmax(q k^T /
sqrt(head_dim))`, causal, each key-value head serving `heads / kv_heads` query
heads, no positional term; `(p v) W_o`.

*`E`, LatentMoE*: `s = sigmoid(u W_r)` over all the router's outputs; the
`num_experts_per_tok` largest of `s + b` (`b`, the published
`e_score_correction_bias`, enters the CHOICE only; `n_group` 1: no group
limit); weights `s_e / sum of the chosen s` (`norm_topk_prob`) times
`routed_scaling_factor`; the routed part runs in a latent: `v = u W_in`,
each chosen expert `relu(v W1_e)^2 W2_e`, their weighted sum back through
`W_out`; one shared expert on the full width, `relu(u W1_s)^2 W2_s`, added
unweighted.
**A share**: where the expert stack holds fewer experts than the router has
outputs, they are the router's FIRST ones: choice and weights are over all
outputs as published, the sum runs over the chosen experts that are held,
and what the absent ones would add is left out before `W_out`. The share is
read from the shapes.
**A share placed** (`place_held_experts`; the configuration's
`share.placement`): as `references/solar_open2.py` says of its own: the
policy is the harness's, handed in as `order`; this file supplies the walk,
layer by layer, and only the columns of `router` and the entries of
`router_bias` move.

Straightforward `jax.numpy` in float32: no chunking, no kernels, no cache,
no dispatch (every held expert is computed for every token, one expert's
float32 copy alive at a time). Callers wrap it in
`jax.default_matmul_precision("highest")`. It reads the parameter tree the
program builds (`params["layers"]` one stacked tree a kind of layer,
`"mamba2_mixer"`, `"attn_mixer"` and `"ffn"`, each in layer order) and shares
no code with it. The layers run are the leading characters of the published
`hybrid_override_pattern`, as many as the stacks hold blocks in all.

Departures from the published model: the multi-token-prediction module
(`num_nextn_predict_layers`) is a drafter beside the forward pass and is not
part of it. The keyword arguments after `hybrid_override_pattern` exist for
the controls: a wrong or cheaper model must fail the tolerance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STACK_OF = {"M": "mamba2_mixer", "*": "attn_mixer", "E": "ffn"}


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _attention(a, h, num_heads, kv_heads, head_dim):
    B, S, _ = h.shape
    q = (h @ a["wq"]).reshape(B, S, num_heads, head_dim)
    k = (h @ a["wk"]).reshape(B, S, kv_heads, head_dim)
    v = (h @ a["wv"]).reshape(B, S, kv_heads, head_dim)
    group = num_heads // kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, S, num_heads * head_dim) @ a["wo"]


def _mamba2(p, h, heads, head_dim, groups, state, taps, eps, state_dtype,
            conv, skip):
    B, S, _ = h.shape
    inner = heads * head_dim
    z, xbc, dt = jnp.split(h @ p["w_in"],
                           [inner, 2 * inner + 2 * groups * state], axis=-1)
    if conv:
        pad = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        xbc = p["conv_b"] + sum(p["conv_w"][j] * pad[:, j:j + S]
                                for j in range(taps))
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :inner].reshape(B, S, heads, head_dim)
    per = heads // groups           # a group's B and C serve `per` heads
    Bm, Cm = (jnp.repeat(m.reshape(B, S, groups, state), per, axis=2)
              for m in (xbc[..., inner:inner + groups * state],
                        xbc[..., inner + groups * state:]))
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (B, S, heads)
    A = -jnp.exp(p["A_log"])

    def token(S_, t):
        x_t, B_t, C_t, dt_t = t      # (B, heads, d) (B, heads, n) (B, heads)
        S_ = (jnp.exp(dt_t * A)[..., None, None] * S_
              + jnp.einsum("bhd,bhn->bhdn", dt_t[..., None] * x_t, B_t))
        # a control keeps the state in a lower precision between tokens (an
        # explicit rounding: a pair of casts is one that XLA may drop)
        if state_dtype != jnp.float32:
            info = jnp.finfo(state_dtype)
            S_ = jax.lax.reduce_precision(S_, info.nexp, info.nmant)
        return S_, jnp.einsum("bhdn,bhn->bhd", S_, C_t)

    time_first = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(
        token, jnp.zeros((B, heads, head_dim, state), jnp.float32),
        tuple(map(time_first, (x, Bm, Cm, dt))))
    y = jnp.moveaxis(y, 0, 1)
    if skip:
        y = y + p["D"][:, None] * x
    y = (y.reshape(B, S, inner) * jax.nn.silu(z)).reshape(
        B, S, groups, inner // groups)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + eps)
    return (y.reshape(B, S, inner) * p["norm"]) @ p["w_out"]


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


def _experts(layer, stacks, h, top_k, norm_topk_prob, scale,
             renorm_over_held, low):
    """`stacks`: this layer's expert matrices still in the program's dtype;
    one expert's float32 copy is made at a time (`low`: a control's rounding
    of it)."""
    scores = jax.nn.sigmoid(h @ layer["router"])              # (B, S, E)
    _, chosen = jax.lax.top_k(scores + layer["router_bias"], top_k)
    E, held = scores.shape[-1], stacks["w_up"].shape[0]
    picked = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(-2)
    if renorm_over_held:        # a control: as if the absent did not exist
        picked = picked * (jnp.arange(E) < held)
    mix = picked * scores
    if norm_topk_prob:
        mix = mix / jnp.maximum(mix.sum(-1, keepdims=True), 1e-20)
    mix = mix * scale
    v = low(h @ layer["latent"]["w_in"])

    def one_expert(acc, e):
        w = jax.tree.map(lambda a: low(a.astype(jnp.float32)), e["w"])
        return acc + e["mix"][..., None] * (_relu2(v @ w["w_up"])
                                            @ w["w_down"]), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(v),
        {"w": stacks, "mix": jnp.moveaxis(mix[..., :held], -1, 0)})
    s = layer["shared"]
    return (routed @ layer["latent"]["w_out"]
            + _relu2(h @ s["w_up"]) @ s["w_down"]), chosen


def _assignments(chosen, E):
    """(..., k) chosen outputs -> (E,) how many times each was chosen."""
    return jnp.zeros((E,), jnp.int32).at[chosen.reshape(-1)].add(1)


def _pattern(params, hybrid_override_pattern):
    """The layers the tree holds: the leading characters of the published
    pattern, as many as its stacks hold blocks in all."""
    depth = sum(jax.tree.leaves(stack)[0].shape[0]
                for stack in params["layers"].values())
    return hybrid_override_pattern[:depth]


def _forward(params, input_ids, *, num_attention_heads, num_key_value_heads,
             head_dim, mamba_num_heads, mamba_head_dim, n_groups,
             ssm_state_size, conv_kernel, num_experts_per_tok,
             norm_topk_prob, routed_scaling_factor, layer_norm_epsilon,
             hybrid_override_pattern, state_dtype=jnp.float32, conv=True,
             skip=True, renorm_over_held=False, mantissa_bits=None,
             order=None):
    """(B, S) int ids -> ((B, S, V) float32 logits, (E layers, B, S, k) the
    experts each expert layer's router chose, of all its outputs, (E layers,
    E) each such layer's outputs in the order `order` gave them: as they
    were without it). `order(load, held)`: `(E,)` this layer's assignments to
    each output -> `(E,)` the outputs in the order that they shall stand in,
    the first `held` this chip's.

    `mantissa_bits` (a control: the model in the precision below the one it
    is served in): every matrix and every layer's normed input rounded to
    that many bits of mantissa, 3 for float8 e4m3, the exponent left as wide
    as a float8 deployment's scales would make it."""
    eps = layer_norm_epsilon
    low = ((lambda a: a) if mantissa_bits is None else
           (lambda a: jax.lax.reduce_precision(a, 8, mantissa_bits)))
    f32 = lambda t: jax.tree.map(
        lambda a: low(a.astype(jnp.float32)) if a.ndim > 1
        else a.astype(jnp.float32), t)
    x = low(params["embed"]["tokens"].astype(jnp.float32))[input_ids]
    stacks = params["layers"]
    count = dict.fromkeys(STACK_OF, 0)
    routed, orders = [], []
    for char in _pattern(params, hybrid_override_pattern):
        mine = jax.tree.map(lambda a: a[count[char]], stacks[STACK_OF[char]])
        count[char] += 1
        if char == "M":
            layer = f32(mine)
            h = low(_rms_norm(x, layer["ln1"]["scale"], eps))
            x = x + _mamba2(layer["mamba2"], h, mamba_num_heads,
                            mamba_head_dim, n_groups, ssm_state_size,
                            conv_kernel, eps, state_dtype, conv, skip)
        elif char == "*":
            layer = f32(mine)
            h = low(_rms_norm(x, layer["ln1"]["scale"], eps))
            x = x + _attention(layer["attn"], h, num_attention_heads,
                               num_key_value_heads, head_dim)
        else:
            experts = mine.pop("mlp")
            layer = f32(mine)
            h = low(_rms_norm(x, layer["ln2"]["scale"], eps))
            E = layer["router"].shape[-1]
            stands = jnp.arange(E)
            if order is not None:
                _, chosen = jax.lax.top_k(
                    jax.nn.sigmoid(h @ layer["router"])
                    + layer["router_bias"], num_experts_per_tok)
                stands = order(_assignments(chosen, E),
                               experts["w_up"].shape[0])
                layer = dict(layer, router=layer["router"][:, stands],
                             router_bias=layer["router_bias"][stands])
            orders.append(stands)
            out, chosen = _experts(layer, experts, h, num_experts_per_tok,
                                   norm_topk_prob, routed_scaling_factor,
                                   renorm_over_held, low)
            x = x + out
            routed.append(chosen)
    x = _rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32), eps)
    return (low(x) @ low(params["lm_head"].astype(jnp.float32)),
            jnp.stack(routed), jnp.stack(orders))


def logits(params, input_ids, **reference_args):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    return _forward(params, input_ids, **reference_args)[0]


def router_choices(params, input_ids, **reference_args):
    """(B, S) -> (E layers, B, S, k): the experts every expert layer's
    router chose, for counting how often a lower precision chooses another
    set."""
    return _forward(params, input_ids, **reference_args)[1]


def place_held_experts(params, input_ids, order, **reference_args):
    """(B, S) calibration ids and the harness's policy `order(load, held)`
    (`_forward`) -> the leaves of `params` that the placement reorders, as a
    tree of `params`' own shape holding those leaves alone (each expert
    layer's `router` columns and `router_bias` entries, in the program's
    dtypes), and `(E layers, E)` the calibration batch's assignments to each
    output in its NEW place (the first `held` of a row are this chip's)."""
    _, routed, orders = _forward(params, input_ids, order=order,
                                 **reference_args)
    E = orders.shape[1]
    load = jax.vmap(lambda chosen: _assignments(chosen, E))(routed)
    stack = params["layers"]["ffn"]
    return {"layers": {"ffn": {
        "router": jnp.take_along_axis(stack["router"], orders[:, None],
                                      axis=2),
        "router_bias": jnp.take_along_axis(stack["router_bias"], orders,
                                           axis=1)}}}, load


def next_token_logprobs(params, input_ids, **reference_args):
    """(B, S) -> (B, S-1): log-probability of token p+1 given tokens 0..p."""
    lp = jax.nn.log_softmax(logits(params, input_ids, **reference_args),
                            axis=-1)[:, :-1]
    return jnp.take_along_axis(lp, input_ids[:, 1:, None], axis=-1)[..., 0]


def loss(params, input_ids, **reference_args):
    """Mean next-token cross entropy over the batch."""
    return -next_token_logprobs(params, input_ids, **reference_args).mean()
