"""Plain reference for the LongCat-Flash family (`meituan-longcat/
LongCat-Flash-Chat` config.json, `model_type` longcat_flash; arXiv:2509.01322;
checked line by line against `transformers.models.longcat_flash.
modeling_longcat_flash`, 4.57): a decoder of DOUBLE layers with latent
attention (MLA) and a shortcut-connected mixture of routed and
zero-computation experts.

One published layer (`LongcatFlashDecoderLayer.forward`), input `x`:

    for i in (0, 1):
        a = x + MLA_i(RMSNorm(x; g_in_i))
        h = RMSNorm(a; g_post_i)
        if i == 0: s = MoE(h)                 # the shortcut: read here ...
        x = a + SwiGLU_i(h)                   # dense, ffn_hidden_size wide
    x = x + s                                 # ... joined here

then a final RMSNorm and an output head of its own; the four layer norms and
the final one take `rms_norm_eps`; no bias anywhere.

*MLA(u)*, `num_attention_heads` heads: `cq = RMSNorm(u W_qa; eps 1e-6)`;
`q = (cq W_qb)` a head, times `sqrt(hidden / q_lora_rank)`
(`mla_scale_q_lora`), its first `qk_nope_head_dim` values `q_nope`, its last
`qk_rope_head_dim` `q_rope`. `[ckv | kr] = u W_kva`; `c = RMSNorm(ckv; eps
1e-6) * sqrt(hidden / kv_lora_rank)` (`mla_scale_kv_lora`); a head's keys
`k_nope_h = c W_kb_h` and values `v_h = c W_vb_h` (the published `kv_b_proj`,
so the scale reaches both); `k_rope = rope(kr)`, ONE a token for all heads.
Scores `(q_nope_h . k_nope_h + rope(q_rope_h) . k_rope) * (qk_nope_head_dim
+ qk_rope_head_dim)^-0.5`, causal softmax, `out = concat_h(sum p v_h) W_o`.
Rope: `rope_theta` over the `qk_rope_head_dim` values, NEIGHBOURS paired,
`(x_2j, x_2j+1) -> (x_2j cos - x_2j+1 sin, x_2j sin + x_2j+1 cos)` at angle
`pos * theta^(-2j / qk_rope_head_dim)` (the source's
`apply_rotary_pos_emb_interleave` lays the result out in halves; the scores
are the same). The two inner norms take their class's default eps, 1e-6.

*MoE(h)*: `scores = softmax(h W_r)` over ALL the router's outputs, routed
and zero-computation, in float32; the `moe_topk` chosen are the largest of
`scores + bias` (the bias enters the CHOICE only); their weights are `scores
* routed_scaling_factor`, NOT renormalised. Output `sum_{chosen j routed}
w_j SwiGLU_j(h) + (sum_{chosen j zero} w_j) h`: a zero-computation expert of
type identity gives the token back, under its weight scaled like the others.
**A share**: where the expert stack holds fewer experts than the router has
routed outputs, they are the router's FIRST ones: choice and weights are over
all outputs as published, the sum runs over the chosen routed experts that
are held, what the absent ones would add is left out, and the identity term
is computed in full (every chip computes it for its own tokens; no exchange).
**A share placed** (`place_held_experts`): as in `solar_open2.py`; only the
ROUTED columns of `router` and entries of `router_bias` move, the
zero-computation ones stay where they are.

Straightforward `jax.numpy` in float32: no kernels, no cache, no dispatch.
Sized to run on the chip beside the bfloat16 parameters at the cell's length
(and beside the live arena too, for the placement's calibration batch): the
layers are a `lax.scan` and the held experts a `lax.fori_loop`, so the
compiler holds one layer's (one expert's) matrices at a time, attention runs
a head at a time (`lax.map`), every held expert is computed for every token
and mixed by a dense weight matrix. Callers wrap it
in `jax.default_matmul_precision("highest")`. It reads the parameter tree
the program builds (`params["layers"]`: `ln1`, `mla`, `ln2`, `dense` with the
two sublayers on the axis behind the layers'; `router`, `router_bias`, `mlp`
the layer's one; `wk_b` (heads, nope, latent) and `wv_b` (heads, latent, v)
are `kv_b_proj` in two) and shares no code with it.

Departures from the published model: none known in the mathematics. The
multi-token-prediction module (`model.mtp.*`), which the source's loader
ignores, is not built.
"""

import jax
import jax.numpy as jnp

INNER_NORM_EPS = 1e-6       # q_a_layernorm, kv_a_layernorm: the class default


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x (S, ..., D) at positions 0..S-1, neighbours (2j, 2j+1) paired."""
    S, D = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv         # (S, D/2)
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    x = x.reshape(x.shape[:-1] + (D // 2, 2))
    even, odd = x[..., 0], x[..., 1]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(out.shape[:-2] + (D,))


def _swiglu(h, w, low):
    f32 = lambda a: low(a.astype(jnp.float32))
    inner = jax.nn.silu(h @ f32(w["w_gate"])) * (h @ f32(w["w_up"]))
    return inner @ f32(w["w_down"])


def _mla(p, u, low, *, rope_theta, scale_q, scale_c, rope_all):
    """One sequence: u (S, H) -> (S, H)."""
    f32 = lambda a: low(a.astype(jnp.float32))
    S, H = u.shape
    N, Dn, R = p["wk_b"].shape
    Dr = p["wkv_a"].shape[-1] - R
    cq = _rms_norm(u @ f32(p["wq_a"]), f32(p["q_norm"]), INNER_NORM_EPS)
    q = (cq @ f32(p["wq_b"])).reshape(S, N, Dn + Dr)
    if scale_q:
        q = q * (H / p["wq_a"].shape[-1]) ** 0.5
    kv = u @ f32(p["wkv_a"])
    c = _rms_norm(kv[:, :R], f32(p["kv_norm"]), INNER_NORM_EPS)
    if scale_c:
        c = c * (H / R) ** 0.5
    kr = kv[:, R:]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(args):
        q_h, wk, wv = args                  # (S, Dn + Dr), (Dn, R), (R, Dv)
        k_h = jnp.concatenate([c @ f32(wk).T, kr], axis=-1)
        if rope_all:    # a control: the rotary embedding on a whole head
            q_h, k_h = _rope(q_h, rope_theta), _rope(k_h, rope_theta)
        else:
            q_h = jnp.concatenate(
                [q_h[:, :Dn], _rope(q_h[:, Dn:], rope_theta)], axis=-1)
            k_h = jnp.concatenate(
                [k_h[:, :Dn], _rope(k_h[:, Dn:], rope_theta)], axis=-1)
        s = (q_h @ k_h.T) * (Dn + Dr) ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return pr @ (c @ f32(wv))

    out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), p["wk_b"], p["wv_b"]))
    return jnp.moveaxis(out, 0, 1).reshape(S, -1) @ f32(p["wo"])


def _router(layer, h, moe_topk, routed_scaling_factor, renormalise):
    """h (T, H) -> (chosen (T, k) of all outputs, their weights (T, k))."""
    scores = jax.nn.softmax(h @ layer["router"].astype(jnp.float32), axis=-1)
    _, chosen = jax.lax.top_k(
        scores + layer["router_bias"].astype(jnp.float32), moe_topk)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalise:     # a control: the published weights are not
        w = w / w.sum(-1, keepdims=True)
    return chosen, w * routed_scaling_factor


def _moe(layer, expert, held, h, zero, moe_topk, routed_scaling_factor, low,
         renormalise, zero_weight_scaled, identity=True, routed=True):
    """h (T, H) -> (the layer's expert FFN (T, H), chosen (T, k)).
    `expert(e)`: the matrices of held expert `e`, of `held`."""
    chosen, w = _router(layer, h, moe_topk, routed_scaling_factor,
                        renormalise)
    E_all = layer["router"].shape[-1]
    dense = jnp.zeros((h.shape[0], E_all), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], chosen].add(w)
    out = jnp.zeros_like(h)
    if routed:
        # a loop the compiler keeps: one expert's float32 copy alive at a time
        out = jax.lax.fori_loop(0, held, lambda e, acc: acc + jnp.take(
            dense, e, axis=1)[:, None] * _swiglu(h, expert(e), low), out)
    if identity:
        w_zero = dense[:, E_all - zero:].sum(-1)
        if not zero_weight_scaled:  # a control: the factor on routed alone
            w_zero = w_zero / routed_scaling_factor
        out = out + w_zero[:, None] * h
    return out, chosen


def _assignments(chosen, outputs):
    """(..., k) chosen outputs -> (outputs,) how many assignments each got."""
    return jnp.zeros((outputs,), jnp.float32).at[chosen.reshape(-1)].add(1.0)


def _forward(params, input_ids, *, num_attention_heads, moe_topk,
             routed_scaling_factor, zero_expert_num, zero_expert_type,
             rms_norm_eps, rope_theta, mla_scale_q_lora, mla_scale_kv_lora,
             kv_lora_rank, qk_rope_head_dim, mantissa_bits=None, order=None,
             scale_c=None, scale_q=None, zero_weight_scaled=True,
             join_after=1, renormalise=False, rope_all=False):
    """(B, S) int ids -> ((B, S, V) float32 logits, (L, B, S, k) what each
    layer's router chose, of all its outputs, (L, E) each layer's ROUTED
    outputs in the order `order` gave them: as they were without it).
    `order(load, held)`: `(E,)` this layer's assignments to each routed
    output -> `(E,)` those outputs in the order they shall stand in, the
    first `held` this chip's.

    The keywords behind `order` are controls, each another model: no scale
    on `c` / on `q`, the factor not on zero-computation weights, the
    shortcut joined behind sublayer `join_after` = 0, renormalised weights,
    rope on a whole head. `mantissa_bits`: the model in the precision below
    the one it is served in, every matrix and every normed input rounded to
    that many bits of mantissa (3: float8 e4m3)."""
    if zero_expert_type != "identity":
        raise NotImplementedError(f"zero_expert_type {zero_expert_type!r}: "
                                  "the published one is 'identity'")
    stack = params["layers"]
    mla = stack["mla"]
    assert (mla["wk_b"].shape[2], mla["wk_b"].shape[-1],
            mla["wkv_a"].shape[-1]) == (
        num_attention_heads, kv_lora_rank, kv_lora_rank + qk_rope_head_dim)
    scale_c = mla_scale_kv_lora if scale_c is None else scale_c
    scale_q = mla_scale_q_lora if scale_q is None else scale_q
    low = ((lambda a: a) if mantissa_bits is None else
           (lambda a: jax.lax.reduce_precision(a, 8, mantissa_bits)))
    f32 = lambda a: low(a.astype(jnp.float32))
    x = f32(params["embed"]["tokens"])[input_ids]
    B, S, H = x.shape
    L = stack["router"].shape[0]
    zero = zero_expert_num
    routed_outputs = stack["router"].shape[-1] - zero
    held = stack["mlp"]["w_up"].shape[1]
    halves = {k: stack[k] for k in ("ln1", "mla", "ln2", "dense")}

    def one_layer(x, li):
        """A `lax.scan` over the layers, so that the compiler holds one
        layer's matrices at a time: unrolled, it took every layer's slices
        out of the stacks at once (5 GB beside 10 GB of parameters)."""
        layer = {"router": stack["router"][li],
                 "router_bias": stack["router_bias"][li]}
        expert = lambda e: jax.tree.map(lambda a: a[li, e], stack["mlp"])
        shortcut = None
        for i in (0, 1):
            sub = jax.tree.map(lambda a: a[li, i], halves)
            u = low(_rms_norm(x, sub["ln1"]["scale"].astype(jnp.float32),
                              rms_norm_eps))
            a = x + jax.vmap(lambda s: _mla(
                sub["mla"], s, low, rope_theta=rope_theta, scale_q=scale_q,
                scale_c=scale_c, rope_all=rope_all))(u)
            h = low(_rms_norm(a, sub["ln2"]["scale"].astype(jnp.float32),
                              rms_norm_eps))
            if i == 0:
                flat = h.reshape(B * S, H)
                stands = jnp.arange(routed_outputs)
                if order is not None:
                    chosen, _ = _router(layer, flat, moe_topk,
                                        routed_scaling_factor, False)
                    stands = order(
                        _assignments(chosen, routed_outputs + zero)[
                            :routed_outputs], held)
                    every = jnp.concatenate(
                        [stands, routed_outputs + jnp.arange(zero)])
                    layer = dict(router=layer["router"][:, every],
                                 router_bias=layer["router_bias"][every])
                shortcut, chosen = _moe(
                    layer, expert, held, flat, zero, moe_topk,
                    routed_scaling_factor, low, renormalise,
                    zero_weight_scaled)
                shortcut = shortcut.reshape(B, S, H)
            x = a + _swiglu(h, sub["dense"], low)
            if i == join_after:
                x = x + shortcut
        return x, (chosen.reshape(B, S, -1), stands)

    x, (chosen_all, orders) = jax.lax.scan(one_layer, x, jnp.arange(L))
    x = _rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                  rms_norm_eps)
    return low(x) @ f32(params["lm_head"]), chosen_all, orders


def logits(params, input_ids, **reference_args):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    return _forward(params, input_ids, **reference_args)[0]


def router_choices(params, input_ids, **reference_args):
    """(B, S) -> (L, B, S, k): what every layer's router chose."""
    return _forward(params, input_ids, **reference_args)[1]


def moe_parts(layer, h, *, moe_topk, routed_scaling_factor, zero_expert_num,
              **_):
    """One layer's expert FFN in its two parts, for the test that adds the
    shares up: `layer` (unstacked: `router`, `router_bias`, `mlp`) and `h`
    (T, H) -> (the held routed experts' part, the identity term)."""
    same = lambda a: a
    expert = lambda e: jax.tree.map(lambda a: a[e], layer["mlp"])
    held = layer["mlp"]["w_up"].shape[0]
    routed, _ = _moe(layer, expert, held, h, zero_expert_num, moe_topk,
                     routed_scaling_factor, same, False, True, identity=False)
    identity, _ = _moe(layer, expert, held, h, zero_expert_num, moe_topk,
                       routed_scaling_factor, same, False, True, routed=False)
    return routed, identity


def place_held_experts(params, input_ids, order, **reference_args):
    """(B, S) calibration ids and the harness's policy `order(load, held)`
    (`_forward`) -> the leaves of `params` that the placement reorders, as a
    tree of `params`' own shape holding those leaves alone (each layer's
    `router` columns and `router_bias` entries, in the program's dtypes; the
    zero-computation outputs keep their places behind the routed ones), and
    `(L, E)` the calibration batch's assignments to each ROUTED output in
    its NEW place (the first `held` of a row are this chip's)."""
    zero = reference_args["zero_expert_num"]
    _, chosen, orders = _forward(params, input_ids, order=order,
                                 **reference_args)
    L, E = orders.shape
    every = jnp.concatenate(
        [orders, jnp.broadcast_to(E + jnp.arange(zero), (L, zero))], axis=1)
    load = jax.vmap(lambda c: _assignments(c, E + zero)[:E])(chosen)
    stack = params["layers"]
    moved = {"router": jnp.take_along_axis(stack["router"], every[:, None],
                                           axis=2),
             "router_bias": jnp.take_along_axis(stack["router_bias"], every,
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
