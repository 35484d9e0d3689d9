"""Plain reference for the `mimo_v2_flash` family (`XiaomiMiMo/MiMo-V2-Flash`
config.json; the semantics of the family's published modelling code).

With `rms(x; w) = w * x / sqrt(mean(x^2) + layernorm_epsilon)`: `x = E[ids]`;
for each layer `x = x + attention(rms(x; ln1))`, then `x = x + ffn(rms(x;
ln2))`; `logits = rms(x; final_norm) @ W_head` (the head is untied).

* **Attention, both forms.** `q = h W_q` as `num_attention_heads` heads of
  `head_dim` (read from the shapes); `k = h W_k` as K heads of `head_dim`;
  `v = attention_value_scale * (h W_v)` as K heads of `v_head_dim`; K is
  `num_key_value_heads` in a full layer and `swa_num_key_value_heads` in a
  window layer; query head n reads key-value head `n // (heads / K)`. Rope on
  the FIRST `int(head_dim * partial_rotary_factor)` values of every q and k
  head, halves rotated (pairs `(j, j + rot / 2)`), base `rope_theta` in a
  full layer and `swa_rope_theta` in a window layer; the other values as they
  came. `s_ij = q_i . k_j / sqrt(head_dim)` for `j <= i`, and in a window
  layer only for `i - sliding_window < j` (`sliding_window` keys, the
  query's own included). No q/k norm, no bias.
* **Full layer** (`hybrid_layer_pattern` 0): `p_ij = softmax_j(s_ij)`
  (`add_full_attention_sink_bias` false).
* **Window layer** (1; `add_swa_attention_sink_bias` true): with the layer's
  learned `sink_n`, one float a query head, `p_ij = exp(s_ij - m) /
  (exp(sink_n - m) + sum_j' exp(s_ij' - m))`, `m = max(sink_n, max_j s_ij)`:
  the sink takes its share of the mass and adds no value.
* `o_i = sum_j p_ij v_j`, `x += o W_o`.
* **The FFN** of a layer whose `moe_layer_freq` is 0: `(silu(h W_g) * (h
  W_u)) W_d`. Of every other layer: `z = sigmoid(h W_r)` in float32 over ALL
  the router's outputs; the `num_experts_per_tok` chosen are the largest of
  `z + b` (`b` the choice-only bias; one group, so no group limit); weights
  `w_e = z_e / (sum of the chosen z + 1e-20)` where `norm_topk_prob`, times 1
  (`routed_scaling_factor` null); `sum_e w_e SwiGLU_e(h)`; no shared expert.
  The expert stack may hold fewer experts than the router has outputs (one
  chip's share of an expert-parallel group): the FIRST outputs are the held
  ones, and what an absent expert would add is left out.

Which layers a tree holds: layer 0 and the pattern's LAST `depth - 1` (whole
periods of five window layers and a full one; every period behind the
published first is such a one), reckoned HERE from `hybrid_layer_pattern`,
`moe_layer_freq` and the depth of the tree.

Straightforward `jax.numpy` in float32: no kernels, no cache, no chunks, no
rings, no dispatch. Callers wrap it in `jax.default_matmul_precision
("highest")`. It reads the parameter tree the program builds
(`params["layers"]` one stacked tree a kind of layer, each in layer order:
`"full_dense"`, `"swa"`, `"full"`; a block holds `ln1`, `attn` (`wq`, `wk`,
`wv`, `wo` and in a window layer `sink`), `ln2`, and `dense` or `router`,
`router_bias` and the bank `mlp`) and shares no code with it.

It has to run beside the bfloat16 parameters it is handed (6.9 GB at the
published widths and 7 layers) on a sequence of 10,240: a layer is taken out
of its stack and made float32 when its turn comes; attention runs a head at a
time and in blocks of `QUERY_BLOCK` positions (`lax.map` over both), the
experts one at a time (`lax.scan`: EVERY held expert computes EVERY token,
mixed by a dense weight that is zero off the chosen ones), and
`next_token_logprobs` takes the head in blocks of positions.

The keyword arguments after `add_full_attention_sink_bias` exist for the
controls: a wrong or cheaper model must fail the tolerance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HEAD_BLOCK = 256            # positions of a block of next_token_logprobs
QUERY_BLOCK = 512           # queries of a block of attention
RENORM_EPS = 1e-20          # the published gate's, in the renormalisation
ABSENT_SINK = 3.0           # a control's sink where a layer has learned none:
#   where the seeded sinks of the layers that have them stand (a zero would
#   be one key more among thousands, and no wrong model at all)
F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, rot, theta):
    """x: (B, S, heads, d), position = index along S; the first `rot` values
    of a head roped, halves rotated, the rest as they came."""
    S = x.shape[1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    angles = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    r = x[..., :rot]
    r1, r2 = r[..., : rot // 2], r[..., rot // 2:]
    roped = r * jnp.cos(angles) + jnp.concatenate([-r2, r1], -1) * jnp.sin(
        angles)
    return jnp.concatenate([roped, x[..., rot:]], axis=-1)


def _attention(w, h, heads, kv_heads, rot, theta, value_scale, window, sink):
    """`window` None: a full layer; `sink` None: no sink in the softmax."""
    B, S, _ = h.shape
    q = (h @ w["wq"]).reshape(B, S, heads, -1)
    d, dv = q.shape[-1], w["wo"].shape[0] // heads
    # the key-value heads as the matrices hold them; `kv_heads` says which
    # of them a query head reads (the two agree but in a control)
    k = (h @ w["wk"]).reshape(B, S, -1, d)
    v = (value_scale * (h @ w["wv"])).reshape(B, S, -1, dv)
    if rot:
        q, k = _rope(q, rot, theta), _rope(k, rot, theta)
    group = heads // kv_heads
    block = min(QUERY_BLOCK, S)
    n_blocks = -(-S // block)
    q = jnp.pad(q, ((0, 0), (0, n_blocks * block - S), (0, 0), (0, 0)))
    t = jnp.arange(S)

    def head(n):
        kn, vn = k[:, :, n // group], v[:, :, n // group]      # (B, S, .)

        def queries(b):
            at = b * block + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(q[:, :, n], b * block, block, 1)
            s = jnp.einsum("bqd,bkd->bqk", qb, kn) / jnp.sqrt(F32(d))
            seen = t[None, :] <= at[:, None]
            if window is not None:
                seen = seen & (t[None, :] > at[:, None] - window)
            s = jnp.where(seen[None], s, -jnp.inf)
            m = s.max(-1, keepdims=True)
            under = 0.0
            if sink is not None:
                m = jnp.maximum(m, sink[n])
                under = jnp.exp(sink[n] - m)
            e = jnp.exp(s - m)
            p = e / (under + e.sum(-1, keepdims=True))
            return jnp.einsum("bqk,bkd->bqd", p, vn)

        o = jax.lax.map(queries, jnp.arange(n_blocks))  # (blocks, B, block, dv)
        return jnp.moveaxis(o, 0, 1).reshape(B, n_blocks * block, -1)[:, :S]

    o = jax.lax.map(head, jnp.arange(heads))            # (heads, B, S, dv)
    return jnp.moveaxis(o, 0, 2).reshape(B, S, -1) @ w["wo"]


def _dense_ffn(w, h):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _assignments(chosen, E):
    """(..., k) chosen outputs -> (E,) how many times each was chosen."""
    return jnp.zeros((E,), jnp.int32).at[chosen.reshape(-1)].add(1)


def _experts(layer, bank, h, top_k, renormalise, low, bias_in_weights=False):
    """`layer`: the block's float32 leaves (its router and bias); `bank` the
    layer's held experts in the parameters' own dtype, each made float32
    when its turn comes. -> (what the HELD experts add, the outputs chosen)."""
    scores = jax.nn.sigmoid(h @ layer["router"])                # (B, S, E)
    biased = scores + layer["router_bias"]
    _, chosen = jax.lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(biased if bias_in_weights else scores,
                                 chosen, axis=-1)
    if renormalise:
        picked = picked / (picked.sum(-1, keepdims=True) + RENORM_EPS)
    E = scores.shape[-1]
    mix = (jax.nn.one_hot(chosen, E, dtype=F32) * picked[..., None]).sum(-2)
    held = bank["w_up"].shape[0]

    def one(acc, e):
        w = jax.tree.map(lambda a: low(a.astype(F32)), e["w"])
        return acc + e["mix"][..., None] * _dense_ffn(w, h), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), {
        "w": bank, "mix": jnp.moveaxis(mix[..., :held], -1, 0)})
    return out, chosen


def _depth(params):
    return sum(jax.tree.leaves(stack)[0].shape[0]
               for stack in params["layers"].values())


def _kinds(hybrid_layer_pattern, moe_layer_freq, depth):
    """The kind of each layer a tree of `depth` layers holds: layer 0 and
    the pattern's last `depth - 1`."""
    n = len(hybrid_layer_pattern)
    layers = [0, *range(n - (depth - 1), n)][:min(depth, n)]
    return [("swa" if hybrid_layer_pattern[i] else "full")
            + ("" if moe_layer_freq[i] else "_dense") for i in layers]


def _forward(params, input_ids, *, hybrid_layer_pattern, moe_layer_freq,
             num_attention_heads, num_key_value_heads,
             swa_num_key_value_heads, partial_rotary_factor, rope_theta,
             swa_rope_theta, sliding_window, attention_value_scale,
             num_experts_per_tok, layernorm_epsilon, norm_topk_prob,
             add_swa_attention_sink_bias, add_full_attention_sink_bias,
             mantissa_bits=None, bias_in_weights=False, rope_all=False,
             order=None):
    """(B, S) int ids -> ((B, S, H) float32 after the final norm, (layers
    with experts, B, S, k) the outputs each router chose, (those layers, E)
    each router's outputs in the order `order` gave them: as they were
    without it). `order(load, held)`: `(E,)` a layer's assignments to each
    output -> `(E,)` the outputs in the order they shall stand in, the
    first `held` this chip's.

    The controls: `mantissa_bits` (the model in the precision below the one
    it is served in: every matrix and every layer's normed inputs rounded to
    that many bits of mantissa, 3 for float8 e4m3), `bias_in_weights` (the
    choice-only bias added to the weights too), `rope_all` (rope over the
    whole head); every other control is a changed argument (a flag flipped,
    another window, head count, base or scale)."""
    eps = layernorm_epsilon
    stacks = params["layers"]
    kinds = _kinds(hybrid_layer_pattern, moe_layer_freq, _depth(params))
    assert set(kinds) == set(stacks), (kinds, set(stacks))
    low = ((lambda a: a) if mantissa_bits is None else
           (lambda a: jax.lax.reduce_precision(a, 8, mantissa_bits)))
    x = low(params["embed"]["tokens"].astype(F32))[input_ids]
    count = dict.fromkeys(stacks, 0)
    routed, orders = [], []
    for kind in kinds:
        mine = jax.tree.map(lambda a: a[count[kind]], stacks[kind])
        count[kind] += 1
        bank = mine.pop("mlp", None)
        layer = jax.tree.map(lambda a: (low(a.astype(F32)) if a.ndim > 1
                                        else a.astype(F32)), mine)
        window = kind.startswith("swa")
        head_dim = layer["attn"]["wq"].shape[-1] // num_attention_heads
        has_sink = (add_swa_attention_sink_bias if window
                    else add_full_attention_sink_bias)
        sink = layer["attn"].get("sink") if has_sink else None
        if has_sink and sink is None:       # a control: sinks a layer lacks
            sink = jnp.full((num_attention_heads,), ABSENT_SINK, F32)
        h = low(_rms(x, layer["ln1"]["scale"], eps))
        x = x + _attention(
            layer["attn"], h, num_attention_heads,
            swa_num_key_value_heads if window else num_key_value_heads,
            head_dim if rope_all else int(head_dim * partial_rotary_factor),
            swa_rope_theta if window else rope_theta, attention_value_scale,
            sliding_window if window else None, sink)
        h = low(_rms(x, layer["ln2"]["scale"], eps))
        if bank is None:
            x = x + _dense_ffn(layer["dense"], h)
            continue
        E = layer["router"].shape[-1]
        stands = jnp.arange(E)
        if order is not None:
            _, chosen = jax.lax.top_k(
                jax.nn.sigmoid(h @ layer["router"]) + layer["router_bias"],
                num_experts_per_tok)
            stands = order(_assignments(chosen, E), bank["w_up"].shape[0])
            layer = dict(layer, router=layer["router"][:, stands],
                         router_bias=layer["router_bias"][stands])
        orders.append(stands)
        out, chosen = _experts(layer, bank, h, num_experts_per_tok,
                               norm_topk_prob, low, bias_in_weights)
        x = x + out
        routed.append(chosen)
    x = _rms(x, params["final_norm"]["scale"].astype(F32), eps)
    return low(x), jnp.stack(routed), jnp.stack(orders)


def logits(params, input_ids, **reference_args):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    mantissa_bits = reference_args.get("mantissa_bits")
    head = params["lm_head"].astype(F32)
    if mantissa_bits is not None:
        head = jax.lax.reduce_precision(head, 8, mantissa_bits)
    return _forward(params, input_ids, **reference_args)[0] @ head


def router_choices(params, input_ids, **reference_args):
    """(B, S) -> (layers with experts, B, S, k): the outputs every router
    chose, for counting how often a lower precision chooses another set."""
    return _forward(params, input_ids, **reference_args)[1]


def place_held_experts(params, input_ids, order, **reference_args):
    """(B, S) calibration ids and the harness's policy `order(load, held)`
    (`_forward`) -> the leaves of `params` that the placement reorders, as a
    tree of `params`' own shape holding those leaves alone (each expert
    layer's `router` columns and `router_bias` entries, in the program's
    dtypes), and `(layers with experts, E)` the calibration batch's
    assignments to each output in its NEW place (the first `held` of a row
    are this chip's)."""
    _, routed, orders = _forward(params, input_ids, order=order,
                                 **reference_args)
    E = orders.shape[1]
    load = jax.vmap(lambda chosen: _assignments(chosen, E))(routed)
    kinds = [k for k in _kinds(reference_args["hybrid_layer_pattern"],
                               reference_args["moe_layer_freq"],
                               _depth(params)) if not k.endswith("_dense")]
    moved = {}
    for kind in sorted(set(kinds)):
        stack = params["layers"][kind]
        mine = orders[jnp.array([i for i, k in enumerate(kinds)
                                 if k == kind])]
        moved[kind] = {
            "router": jnp.take_along_axis(stack["router"], mine[:, None],
                                          axis=2),
            "router_bias": jnp.take_along_axis(stack["router_bias"], mine,
                                               axis=1)}
    return {"layers": moved}, load


def next_token_stats(params, input_ids, **reference_args):
    """(B, S) -> three (B, S-1): the log-probability of token p+1 given
    tokens 0..p, the largest logit at p, and the logit of token p+1. The
    head in blocks of `HEAD_BLOCK` positions: at the published size all the
    logits of a long sequence are gigabytes."""
    x = _forward(params, input_ids, **reference_args)[0][:, :-1]
    targets = input_ids[:, 1:]
    B, T, H = x.shape
    n = -(-T // HEAD_BLOCK)
    pad = n * HEAD_BLOCK - T
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(B, n, HEAD_BLOCK, H)
    targets = jnp.pad(targets, ((0, 0), (0, pad))).reshape(B, n, HEAD_BLOCK)
    head = params["lm_head"]
    mantissa_bits = reference_args.get("mantissa_bits")

    def block(args):
        xb, tb = args                               # (B, HB, H) (B, HB)
        w = head.astype(F32)
        if mantissa_bits is not None:
            w = jax.lax.reduce_precision(w, 8, mantissa_bits)
        logits = xb @ w
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
