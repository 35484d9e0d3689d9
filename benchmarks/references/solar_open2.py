"""Plain reference for the Solar Open 2 family (`upstage/Solar-Open2-250B`
config.json, `model_type` solar_open2): a decoder of layers of two kinds with
a mixture-of-experts feed-forward in every layer.

Block, every layer: `h = x + mixer(RMSNorm(x))`, `out = h + moe(RMSNorm(h))`;
a final RMSNorm and an output head of its own; no bias and no positional
term anywhere (`use_rope` false). Layer `i` is a softmax layer where
`i % (gqa_interval + 1) == 0` (the published `gqa_layers` 0, 4, 8, ...), else
a linear-attention layer.

*Softmax layer*: `q = xW_q`, `k = xW_k`, `v = xW_v`, `a = xW_a`;
`p = softmax(q k^T / sqrt(head_dim))`, causal, each KV head serving
`heads / kv_heads` query heads; `y = ((p v) * sigmoid(a)) W_o`
(`use_gqa_gate`).

*Linear-attention layer* (the gated delta rule with per-channel decay; H
heads of d for keys and values): `q~, k~, v~ = xW_q, xW_k, xW_v`; on each a
depthwise causal convolution over time (taps oldest first, rows before the
sequence's start zero) and SiLU; `q = l2norm_head(.) / sqrt(d)`,
`k = l2norm_head(.)` (eps 1e-6), `v` as it is; decay
`g = -exp(A_log_h) * softplus((x W_f1) W_f2 + dt_bias)`, `alpha = exp(g)`;
`beta = 2 sigmoid(x W_b)` (the 2 is `kda_allow_neg_eigval`); a float32 state
`S` (d x d a head), zero at the start:
`S' = diag(alpha) S`; `u = beta (v - S'^T k)`; `S = S' + k u^T`; `o = S^T q`;
`y = (RMSNorm_head(o) * sigmoid((x W_g1) W_g2)) W_o`. The recurrence is a
`lax.scan` over the tokens.

*Expert layer*: `s = sigmoid(x W_r)` over all the router's outputs; the
`num_experts_per_tok` largest of `s + b` (`b` enters the CHOICE only);
weights `s_e / sum of the chosen s` (`norm_topk_prob`) times
`routed_scaling_factor`; `moe(x) = shared(x) + sum_{e chosen} w_e E_e(x)`,
every expert and the shared one `down(silu(gate(x)) * up(x))`.
**A share**: where the expert stack holds fewer experts than the router has
outputs, they are the router's FIRST ones (one chip of a deployment whose
chips divide each layer's experts): choice and weights are over all outputs
as published, the sum runs over the chosen experts that are held, and what
the absent ones would add is left out. The share is read from the shapes.
**A share placed** (`place_held_experts`; the configuration's
`share.placement`): WHICH of the router's outputs come first is a labelling
of one draw of exchangeable experts, and the harness relabels them so that
a chip carries its part of the load. The policy is the harness's, handed in
as `order` (`harness/program.py` `balanced_order`); this file supplies the
walk: layer by layer from the first, on a calibration batch, count the
assignments to each of the router's outputs, ask `order` where each output
shall stand, and reorder them so; this layer's result, with the outputs so
reordered and the absent experts' part left out, is the next layer's input.
Only the columns of `router` and the entries of `router_bias` move.

Straightforward `jax.numpy` in float32: no kernels, no cache, no dispatch
(every held expert is computed for every token, one expert's float32 copy
alive at a time, and mixed by a dense weight matrix). Callers wrap it in
`jax.default_matmul_precision("highest")`. It reads the parameter tree the
program builds (`params["layers"]` one stacked tree a kind of layer, `"attn"`
and `"kda"`, each in layer order) and shares no code with it. The sizes
inside the source's `linear_attn_config` come from the shapes (`A_log` one a
head; the taps of `conv_q`).

Departures from the published model: none known in the mathematics; what the
config does not state (the low-rank pairs, the l2norm's eps, the scale on q,
sigmoid scores with a choice-only bias) follows the published layer and
router the keys are named after, and is listed under `assumed` in the
configuration's file. The keyword arguments after `routed_scaling_factor`
exist for the controls: a wrong or cheaper model must fail the tolerance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _softmax_layer(a, h, num_heads, head_dim, gate):
    B, S, _ = h.shape
    q = (h @ a["wq"]).reshape(B, S, num_heads, head_dim)
    k = (h @ a["wk"]).reshape(B, S, -1, head_dim)
    v = (h @ a["wv"]).reshape(B, S, -1, head_dim)
    group = num_heads // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)
    o = o.reshape(B, S, num_heads * head_dim)
    if gate:
        o = o * jax.nn.sigmoid(h @ a["wg"])
    return o @ a["wo"]


def _linear_layer(p, h, eps, beta_scale, state_dtype, decay, conv):
    B, S, _ = h.shape
    heads = p["A_log"].shape[0]
    d = p["wq"].shape[1] // heads

    def short_conv(z, taps):
        if not conv:
            return jax.nn.silu(z)
        n = taps.shape[0]
        z = jnp.pad(z, ((0, 0), (n - 1, 0), (0, 0)))
        return jax.nn.silu(sum(taps[j] * z[:, j:j + S] for j in range(n)))

    def heads_of(z):
        return z.reshape(B, S, heads, d)

    def l2norm(z):
        return z / jnp.sqrt((z * z).sum(-1, keepdims=True) + 1e-6)

    q = l2norm(heads_of(short_conv(h @ p["wq"], p["conv_q"]))) / jnp.sqrt(
        jnp.float32(d))
    k = l2norm(heads_of(short_conv(h @ p["wk"], p["conv_k"])))
    v = heads_of(short_conv(h @ p["wv"], p["conv_v"]))
    g = -jnp.exp(p["A_log"])[:, None] * heads_of(
        jax.nn.softplus((h @ p["wf1"]) @ p["wf2"] + p["dt_bias"]))
    alpha = jnp.exp(g) if decay else jnp.ones_like(g)
    beta = beta_scale * jax.nn.sigmoid(h @ p["wb"])          # (B, S, heads)

    def token(state, x):
        q_t, k_t, v_t, a_t, b_t = x           # (B, heads, d); b_t (B, heads)
        state = a_t[..., :, None] * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        # a control keeps the state in a lower precision between tokens (an
        # explicit rounding: a pair of casts is one that XLA may drop)
        if state_dtype != jnp.float32:
            info = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    time_first = lambda z: jnp.moveaxis(z, 1, 0)
    _, o = jax.lax.scan(token, jnp.zeros((B, heads, d, d), jnp.float32),
                        tuple(map(time_first, (q, k, v, alpha, beta))))
    o = _rms_norm(jnp.moveaxis(o, 0, 1), p["o_norm"], eps)
    gate = jax.nn.sigmoid((h @ p["wg1"]) @ p["wg2"])
    return (o.reshape(B, S, heads * d) * gate) @ p["wo"]


def _experts(layer, stacks, h, top_k, norm_topk_prob, scale, shared,
             renorm_over_held, low=lambda a: a):
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

    def one_expert(acc, e):
        w = jax.tree.map(lambda a: low(a.astype(jnp.float32)), e["w"])
        out = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        return acc + e["mix"][..., None] * out, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        {"w": stacks, "mix": jnp.moveaxis(mix[..., :held], -1, 0)})
    if shared:
        s = layer["shared"]
        out = out + (jax.nn.silu(h @ s["w_gate"]) * (h @ s["w_up"])) \
            @ s["w_down"]
    return out, chosen


def _kind(i, gqa_interval):
    """Layer `i` is a softmax layer at the head of every period."""
    return "attn" if i % (gqa_interval + 1) == 0 else "kda"


def _assignments(chosen, E):
    """(..., k) chosen outputs -> (E,) how many times each was chosen."""
    return jnp.zeros((E,), jnp.int32).at[chosen.reshape(-1)].add(1)


def _forward(params, input_ids, *, num_heads, head_dim, num_experts_per_tok,
             rms_norm_eps, norm_topk_prob, routed_scaling_factor,
             gqa_interval, use_gqa_gate, kda_allow_neg_eigval, use_rope,
             beta_scale=None, state_dtype=jnp.float32, decay=True, conv=True,
             shared=True, renorm_over_held=False, mantissa_bits=None,
             order=None):
    """(B, S) int ids -> ((B, S, V) float32 logits, (L, B, S, k) the experts
    each layer's router chose, of all its outputs, (L, E) each layer's
    outputs in the order `order` gave them: as they were without it).
    `order(load, held)`: `(E,)` this layer's assignments to each output ->
    `(E,)` the outputs in the order that they shall stand in, the first
    `held` this chip's.

    `mantissa_bits` (a control: the model in the precision below the one it
    is served in): every matrix and every layer's normed input rounded to
    that many bits of mantissa, 3 for float8 e4m3, the exponent left as wide
    as a float8 deployment's scales would make it."""
    if use_rope:
        raise NotImplementedError("this family's softmax layers carry no "
                                  "positional term (use_rope false)")
    if beta_scale is None:
        beta_scale = 2.0 if kda_allow_neg_eigval else 1.0
    low = ((lambda a: a) if mantissa_bits is None else
           (lambda a: jax.lax.reduce_precision(a, 8, mantissa_bits)))
    f32 = lambda t: jax.tree.map(
        lambda a: low(a.astype(jnp.float32)) if a.ndim > 1
        else a.astype(jnp.float32), t)
    x = low(params["embed"]["tokens"].astype(jnp.float32))[input_ids]
    stacks = params["layers"]
    depth = sum(t["ln1"]["scale"].shape[0] for t in stacks.values())
    count = {"attn": 0, "kda": 0}
    routed, orders = [], []
    for i in range(depth):
        kind = _kind(i, gqa_interval)
        mine = jax.tree.map(lambda a: a[count[kind]], stacks[kind])
        count[kind] += 1
        experts = mine.pop("mlp")
        layer = f32(mine)
        h = low(_rms_norm(x, layer["ln1"]["scale"], rms_norm_eps))
        if kind == "attn":
            x = x + _softmax_layer(layer["attn"], h, num_heads, head_dim,
                                   use_gqa_gate)
        else:
            x = x + _linear_layer(layer["kda"], h, rms_norm_eps, beta_scale,
                                  state_dtype, decay, conv)
        h = low(_rms_norm(x, layer["ln2"]["scale"], rms_norm_eps))
        E = layer["router"].shape[-1]
        stands = jnp.arange(E)
        if order is not None:
            _, chosen = jax.lax.top_k(
                jax.nn.sigmoid(h @ layer["router"]) + layer["router_bias"],
                num_experts_per_tok)
            stands = order(_assignments(chosen, E),
                           experts["w_up"].shape[0])
            layer = dict(layer, router=layer["router"][:, stands],
                         router_bias=layer["router_bias"][stands])
        orders.append(stands)
        out, chosen = _experts(layer, experts, h, num_experts_per_tok,
                               norm_topk_prob, routed_scaling_factor, shared,
                               renorm_over_held, low)
        x = x + out
        routed.append(chosen)
    x = _rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                  rms_norm_eps)
    return (low(x) @ low(params["lm_head"].astype(jnp.float32)),
            jnp.stack(routed), jnp.stack(orders))


def logits(params, input_ids, **reference_args):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    return _forward(params, input_ids, **reference_args)[0]


def router_choices(params, input_ids, **reference_args):
    """(B, S) -> (L, B, S, k): the experts every layer's router chose, for
    counting how often a lower precision chooses another set."""
    return _forward(params, input_ids, **reference_args)[1]


def place_held_experts(params, input_ids, order, **reference_args):
    """(B, S) calibration ids and the harness's policy `order(load, held)`
    (`_forward`) -> the leaves of `params` that the placement reorders, as a
    tree of `params`' own shape holding those leaves alone (each layer's
    `router` columns and `router_bias` entries, in the program's dtypes),
    and `(L, E)` the calibration batch's assignments to each output in its
    NEW place (the first `held` of a row are this chip's)."""
    _, routed, orders = _forward(params, input_ids, order=order,
                                 **reference_args)
    L, E = orders.shape
    load = jax.vmap(lambda chosen: _assignments(chosen, E))(routed)
    moved = {}
    for kind, stack in params["layers"].items():
        mine = orders[jnp.array([
            i for i in range(L)
            if _kind(i, reference_args["gqa_interval"]) == kind])]
        moved[kind] = {
            "router": jnp.take_along_axis(stack["router"], mine[:, None],
                                          axis=2),
            "router_bias": jnp.take_along_axis(stack["router_bias"], mine,
                                               axis=1)}
    return {"layers": moved}, load


def next_token_logprobs(params, input_ids, **reference_args):
    """(B, S) -> (B, S-1): log-probability of token p+1 given tokens 0..p."""
    lp = jax.nn.log_softmax(logits(params, input_ids, **reference_args),
                            axis=-1)[:, :-1]
    return jnp.take_along_axis(lp, input_ids[:, 1:, None], axis=-1)[..., 0]


def loss(params, input_ids, **reference_args):
    """Mean next-token cross entropy over the batch."""
    return -next_token_logprobs(params, input_ids, **reference_args).mean()
