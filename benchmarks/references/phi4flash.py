"""Plain reference for the `phi4flash` family
(`microsoft/Phi-4-mini-flash-reasoning` config.json; the SambaY
decoder-hybrid-decoder of arXiv:2507.06607 with the differential attention of
arXiv:2410.05258): L pre-norm layers, `x <- x + mixer(LN(x))` then
`x <- x + FFN(LN(x))`, LayerNorm with a bias (`layer_norm_eps`), FFN
`down(silu(gate) * up)` without bias, a final LayerNorm, the head tied to the
embedding, and no positional term anywhere. With `mb_per_layer` 2 the mixer
of layer `i` of `0..L-1` is

* `i` even, `i <= L/2`, **Mamba-1** (inner width `I`, state `N`, step rank
  `R`, `T` taps, all read from the shapes): `[x | z] = h W_in`; `x` through a
  depthwise causal convolution over time (taps oldest first, rows before the
  sequence's start zero) plus its bias, then SiLU; `[r | B | C] = x W_x`;
  `dt = softplus(r W_dt + dt_bias)`; `A = -exp(A_log)` (stored `N x I`); a
  float32 state `S` (`N x I`), zero at the start, a token at a time under
  `lax.scan`: `S <- exp(dt A) * S + (dt x) B^T`, `y = S C + D x`;
  `out = (y * silu(z)) W_out`. Layer `L/2` also hands on `m = y`, BEFORE the
  gate.
* `i` even, `i > L/2`, a **gated memory unit**: `out = (silu(h W_1) * m) W_2`,
  `m` layer `L/2`'s for the same token.
* `i` odd, **differential attention**: `q` in `num_attention_heads` heads and
  `k`, `v` in `num_key_value_heads` heads of `d`, each projection with a bias,
  scale `1/sqrt(d)`. Adjacent heads pair: `q1_j, q2_j = q_2j, q_2j+1`;
  `k1_g, k2_g = k_2g, k_2g+1`; `V_g = [v_2g, v_2g+1]`, `2 d` wide; pair `j`
  uses group `j // (pairs / groups)`. `a1 = softmax(q1 k1^T) V`,
  `a2 = softmax(q2 k2^T) V`; `lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0`,
  `lam0 = 0.8 - 0.6 exp(-0.3 i)`; `o_j = RMSNorm(a1_j - lam a2_j; g) *
  (1 - lam0)` (eps `layer_norm_eps`); `out = concat_j(o_j) W_o + b_o`.
  `i < L/2`: a **window**, query `t` sees keys `t - sliding_window + 1 .. t`.
  `i = L/2 + 1`: **full** causal. `i > L/2 + 1`: **cross**: `q` from the
  layer's own `W_q`, `k` and `v` those of layer `L/2 + 1`, causal, no window.

Straightforward `jax.numpy` in float32: no kernels, no cache, no chunks, no
batching tricks. Callers wrap it in `jax.default_matmul_precision("highest")`.
It reads the parameter tree the program builds (`params["layers"]` one
stacked tree a kind of layer: `"mamba1"`, `"swa"`, `"full"`, `"gmu"`,
`"cross"`, each in layer order; a block holds `ln1`, its mixer, `ln2`, `mlp`)
and shares no code with it. The program's leaf `lam_init` is NOT read: `lam0`
is reckoned here from the layer's index.

It has to run beside the bfloat16 parameters it is handed (7.7 GB at the
published size): the layers of one kind are walked under `lax.scan`, a layer
taken out of its stack by its index and made float32 inside the step, so one
layer's float32 copy is alive at a time; `next_token_logprobs` takes the head
in blocks of positions and never holds all the logits.

The keyword arguments after `mb_per_layer` exist for the controls: a wrong
or cheaper model must fail the tolerance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HEAD_BLOCK = 256            # positions of a block of next_token_logprobs


def _layer_norm(x, ln, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * ln["scale"] + ln["bias"]


def _ffn(w, h):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _mamba1(w, h, skip=True, after_gate=False):
    """-> (the mixer's output, m: y before the gate; `after_gate`, a
    control: behind it)."""
    B, S, _ = h.shape
    taps, I = w["conv_w"].shape
    N, R = w["A_log"].shape[0], w["w_dt"].shape[0]
    x, z = jnp.split(h @ w["w_in"], 2, axis=-1)
    past = jnp.concatenate([jnp.zeros((B, taps - 1, I), x.dtype), x], axis=1)
    x = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * past[:, j:j + S] for j in range(taps)))
    r, Bm, Cm = jnp.split(x @ w["w_x"], [R, R + N], axis=-1)
    dt = jax.nn.softplus(r @ w["w_dt"] + w["dt_bias"])
    A = -jnp.exp(w["A_log"])                            # (N, I)

    def token(state, tok):
        x_t, dt_t, B_t, C_t = tok               # (B, I) (B, I) (B, N) (B, N)
        state = (jnp.exp(dt_t[:, None] * A) * state
                 + (dt_t * x_t)[:, None] * B_t[:, :, None])
        return state, (state * C_t[:, :, None]).sum(1)

    _, y = jax.lax.scan(token, jnp.zeros((B, N, I), jnp.float32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1)
    if skip:
        y = y + w["D"] * x
    gated = y * jax.nn.silu(z)
    return gated @ w["w_out"], gated if after_gate else y


def _keys_values(w, h, kv_heads):
    B, S, _ = h.shape
    k = (h @ w["wk"] + w["bk"]).reshape(B, S, kv_heads, -1)
    v = (h @ w["wv"] + w["bv"]).reshape(B, S, kv_heads, -1)
    return k, v


def _diff_attention(w, h, k, v, lam0, heads, eps, window=None,
                    one_minus_lam0=True):
    """`h` (B, S, H) the layer's normed input, `k`, `v` (B, S, K, d) the keys
    and values it attends to; `lam0` this layer's, a scalar."""
    B, S, _ = h.shape
    K, d = k.shape[2:]
    q = (h @ w["wq"] + w["bq"]).reshape(B, S, heads // 2, 2, d)
    pairs, groups = heads // 2, K // 2
    k = jnp.repeat(k.reshape(B, S, groups, 2, d), pairs // groups, axis=2)
    V = jnp.repeat(v.reshape(B, S, groups, 2 * d), pairs // groups, axis=2)
    t = jnp.arange(S)
    seen = t[None, :] <= t[:, None]
    if window is not None:
        seen = seen & (t[None, :] > t[:, None] - window)

    def attend(half):
        s = jnp.einsum("bqjd,bkjd->bjqk", q[:, :, :, half],
                       k[:, :, :, half]) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("bjqk,bkje->bqje", jax.nn.softmax(s, axis=-1), V)

    lam = (jnp.exp(jnp.sum(w["lam_q1"] * w["lam_k1"]))
           - jnp.exp(jnp.sum(w["lam_q2"] * w["lam_k2"])) + lam0)
    o = attend(0) - lam * attend(1)                     # (B, S, pairs, 2d)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) * w["subln"]
    if one_minus_lam0:
        o = o * (1.0 - lam0)
    return o.reshape(B, S, pairs * 2 * d) @ w["wo"] + w["bo"]


def _lam0(i):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(i, jnp.float32))


def _depth(params):
    return sum(jax.tree.leaves(stack)[0].shape[0]
               for stack in params["layers"].values())


def _hidden(params, input_ids, *, sliding_window, layer_norm_eps,
            num_attention_heads, num_key_value_heads, mb_per_layer,
            window=True, window_shift=0, cross_own_kv=False,
            memory_after_gate=False, lam0_shift=0, one_minus_lam0=True,
            skip=True, mantissa_bits=None):
    """(B, S) int ids -> (B, S, H) float32, after the final norm.

    The controls: `window` False (window layers see everything),
    `window_shift` (a window of `sliding_window + window_shift` keys),
    `cross_own_kv` (a cross layer makes keys and values from its OWN input
    with the full layer's matrices), `memory_after_gate` (`m` taken after
    the `z` gate), `lam0_shift` (the `lam0` of the layer that many places
    down), `one_minus_lam0` False, `skip` False (no `D x`), and
    `mantissa_bits` (the model in the precision below the one it is served
    in: every matrix and every layer's normed inputs rounded to that many
    bits of mantissa, 3 for float8 e4m3)."""
    assert mb_per_layer == 2, "the order of the layers is written for 2"
    eps, heads, kv = layer_norm_eps, num_attention_heads, num_key_value_heads
    L = _depth(params)
    half = L // 2
    low = ((lambda a: a) if mantissa_bits is None else
           (lambda a: jax.lax.reduce_precision(a, 8, mantissa_bits)))
    stacks = params["layers"]
    wide = (sliding_window + window_shift) if window else None

    def block(kind, index):
        """Layer `index` of its kind, in float32."""
        return jax.tree.map(
            lambda a: (low(a[index].astype(jnp.float32)) if a.ndim > 2
                       else a[index].astype(jnp.float32)), stacks[kind])

    def with_ffn(x, layer, mixed):
        x = x + mixed
        return x + _ffn(layer["mlp"], low(_layer_norm(x, layer["ln2"], eps)))

    def mamba_layer(x, index):
        layer = block("mamba1", index)
        out, m = _mamba1(layer["mamba1"],
                         low(_layer_norm(x, layer["ln1"], eps)), skip,
                         memory_after_gate)
        return with_ffn(x, layer, out), m

    def attention_layer(x, kind, index, i, window=None):
        layer = block(kind, index)
        h = low(_layer_norm(x, layer["ln1"], eps))
        k, v = _keys_values(layer["attn"], h, kv)
        out = _diff_attention(layer["attn"], h, k, v, _lam0(i + lam0_shift),
                              heads, eps, window, one_minus_lam0)
        return with_ffn(x, layer, out), (k, v)

    x = low(params["embed"]["tokens"].astype(jnp.float32))[input_ids]

    def self_period(x, p):              # layers 2p and 2p + 1, p < L/4
        x, _ = mamba_layer(x, p)
        x, _ = attention_layer(x, "swa", p, 2 * p + 1, wide)
        return x, None

    x, _ = jax.lax.scan(self_period, x, jnp.arange(half // 2))
    x, m = mamba_layer(x, half // 2)                    # layer L/2
    x, (k, v) = attention_layer(x, "full", 0, half + 1)
    full = block("full", 0)["attn"]

    def cross_period(x, p):             # layers L/2 + 2 + 2p and + 3 + 2p
        layer = block("gmu", p)
        h = low(_layer_norm(x, layer["ln1"], eps))
        g = layer["gmu"]
        x = with_ffn(x, layer, (jax.nn.silu(h @ g["w_in"]) * m) @ g["w_out"])
        layer = block("cross", p)
        h = low(_layer_norm(x, layer["ln1"], eps))
        kk, vv = k, v
        if cross_own_kv:
            kk, vv = _keys_values(full, h, kv)
        out = _diff_attention(
            layer["attn"], h, kk, vv,
            _lam0(half + 3 + 2 * p + lam0_shift), heads, eps, None,
            one_minus_lam0)
        return with_ffn(x, layer, out), None

    x, _ = jax.lax.scan(cross_period, x, jnp.arange(half // 2 - 1))
    return _layer_norm(x, jax.tree.map(lambda a: a.astype(jnp.float32),
                                       params["final_norm"]), eps)


def logits(params, input_ids, **reference_args):
    """(B, S) int ids -> (B, S, V) float32 logits."""
    x = _hidden(params, input_ids, **reference_args)
    return x @ params["embed"]["tokens"].astype(jnp.float32).T


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
        logits = xb @ table.astype(jnp.float32).T
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
