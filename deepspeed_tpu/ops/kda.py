"""The gated delta rule with per-channel decay: the recurrence of a
linear-attention layer (``models/transformer._kda_mixer``).

Per head a float32 state ``S`` of ``d x d`` (key channel x value channel),
zero where a sequence starts. A token with query ``q``, key ``k``, value
``v`` (``d`` each), log-decay ``g`` (``d`` values, all <= 0) and write
strength ``beta`` (a scalar, up to 2: negative eigenvalues allowed) does

    S' = diag(exp(g)) S          u = beta (v - S'^T k)
    S  = S' + k u^T              o = S^T q

Three forms of it:

* ``kda_recurrence``: token by token under ``lax.scan``; the oracle.
* ``kda_chunk``: a chunk of tokens at once (the prefill-chunk, score and
  whole-sequence programs), in sub-chunks of ``SUB`` tokens. Within a
  sub-chunk ``u`` solves a unit lower-triangular system whose entries are
  ``sum_i k_t[i] k_s[i] exp(G_t[i] - G_s[i])`` with ``G`` the running sum of
  ``g``: the decay stays in log space and a DIFFERENCE is exponentiated, for
  ``s <= t`` only, so nothing over- or underflows however strong the decay,
  which the factored form ``(k_t exp(G_t)) . (k_s exp(-G_s))`` cannot
  promise. Plain ``jax.numpy``; the products against the state run at
  ``HIGHEST`` precision, because a TPU's default float32 matmul is one
  bfloat16 pass. A token with ``beta`` 0 and ``g`` 0 leaves the state as it
  was: that is how a ragged chunk's padding is written.
* ``kda_decode_step``: one token for each row of a decode step, a Pallas
  kernel. The states live in a pool ``(layers, slots, H, d, d)`` that stays
  in HBM: a grid step copies ``HEADS_PER_STEP`` heads of ONE row's state in
  (the pipeline's block copy, addressed through the scalar-prefetched
  ``layer`` and ``slots``), updates them on the VPU and copies them back to
  the same place (``input_output_aliases``), so a step moves each live
  state once in and once out and nothing else of the pool. The pool and the
  layer are operands for the reason the paged kernels and the expert kernel
  take theirs so: a custom call handed a layer's slice gets a copy of it.
  ``reference_kda_decode_step`` is its ``jax.numpy`` twin (CPU, and the
  oracle of the kernel's tests).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 16                    # tokens of a sub-chunk of kda_chunk
HEADS_PER_STEP = 16         # 1 MiB of float32 state a block at d = 128
_HI = lax.Precision.HIGHEST


def kda_recurrence(q, k, v, g, beta, state) -> Tuple[jax.Array, jax.Array]:
    """q, k, v, g (B, S, H, d); beta (B, S, H); state (B, H, d, d) float32
    -> (o (B, S, H, d) float32, state after the last token)."""
    f32 = jnp.float32

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x                   # (B, H, d) / (B, H)
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - (S * k_t[..., None]).sum(-2))
        S = S + k_t[..., None] * u[..., None, :]
        return S, (S * q_t[..., None]).sum(-2)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    state, o = lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(o, 0, 1), state


def kda_chunk(q, k, v, g, beta, state, sub: int = SUB
              ) -> Tuple[jax.Array, jax.Array]:
    """The same function of the same operands as ``kda_recurrence``, a
    sub-chunk of ``sub`` tokens a step. Any ``S``: the last sub-chunk is
    filled with tokens that write nothing."""
    f32 = jnp.float32
    B, S, H, d = q.shape
    n = -(-S // sub)
    pad = n * sub - S

    def split(a):       # (B, S, H, ...) -> (n, B, H, sub, ...)
        a = a.astype(f32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, n, sub) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    t_idx = jnp.arange(sub)
    upto = t_idx[:, None] >= t_idx[None, :]           # s <= t
    before = t_idx[:, None] > t_idx[None, :]          # s < t

    def step(S0, x):
        qc, kc, vc, gc, bc = x            # (B, H, c, d); bc (B, H, c)
        G = jnp.cumsum(gc, axis=2)
        # exp(G_t - G_s) a channel, for s <= t; the rest exactly zero
        E = jnp.exp(jnp.where(upto[..., None],
                              G[:, :, :, None] - G[:, :, None], -jnp.inf))
        kk = (kc[:, :, :, None] * kc[:, :, None] * E).sum(-1)
        qk = (qc[:, :, :, None] * kc[:, :, None] * E).sum(-1)
        A = jnp.where(before, kk, 0.0) * bc[..., None]        # (B, H, c, c)
        decay = jnp.exp(G)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bhtd,bhde->bhte", kc * decay, S0, precision=_HI))
        # (I + A) U = rhs, forward substitution, a row a step: exact in
        # float32 where a blocked triangular solve would round in bfloat16
        U = jnp.zeros_like(rhs)
        for t in range(sub):
            U = U.at[:, :, t].set(
                rhs[:, :, t] - (A[:, :, t, :, None] * U).sum(2))
        o = (jnp.einsum("bhtd,bhde->bhte", qc * decay, S0, precision=_HI)
             + jnp.einsum("bhts,bhse->bhte", qk, U, precision=_HI))
        last = G[:, :, -1:]
        S1 = (jnp.exp(last)[:, :, 0, :, None] * S0
              + jnp.einsum("bhsd,bhse->bhde", kc * jnp.exp(last - G), U,
                           precision=_HI))
        return S1, o

    state, o = lax.scan(step, state.astype(f32),
                        tuple(split(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(B, n * sub, H, d)
    return o[:, :S], state


# ---------------------------------------------------------------------------
# one token a row: the decode step
# ---------------------------------------------------------------------------

# the rows of a head's (8, d) operand tile
_ALPHA, _K, _Q, _BETA, _V = range(5)


def _decode_operands(q, k, v, g, beta):
    """(R, H, d) x 4 and (R, H) -> (R, H, 8, d) float32: one sublane tile a
    (row, head) with the decay, key, query, beta (across the lanes) and
    value in its first five rows."""
    f32 = jnp.float32
    rows = [jnp.exp(g.astype(f32)), k.astype(f32), q.astype(f32),
            jnp.broadcast_to(beta.astype(f32)[..., None], q.shape),
            v.astype(f32)]
    rows += [jnp.zeros(q.shape, f32)] * 3
    return jnp.stack(rows, axis=2)


def _kda_decode_kernel(layer_ref, slot_ref, vec_ref, s_ref, o_ref, s_out_ref,
                       *, heads: int, d: int):
    del layer_ref, slot_ref                 # read by the index maps
    fill = jnp.zeros((d - 8, d), jnp.float32)
    for h in range(heads):
        t = vec_ref[h]                                      # (8, d)
        # a vector along the lanes becomes one along the sublanes, as the
        # state's key channel runs: one (d, d) transpose serves all three
        cols = jnp.concatenate([t, fill], axis=0).T
        a_c = cols[:, _ALPHA:_ALPHA + 1]
        k_c = cols[:, _K:_K + 1]
        q_c = cols[:, _Q:_Q + 1]
        S = s_ref[h] * a_c
        u = (t[_V:_V + 1] - jnp.sum(S * k_c, axis=0, keepdims=True)) \
            * t[_BETA:_BETA + 1]
        S = S + k_c * u
        s_out_ref[h] = S
        o_ref[h:h + 1, :] = jnp.sum(S * q_c, axis=0, keepdims=True)


def kda_decode_step(q, k, v, g, beta, pool, layer, slots,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """q, k, v, g (R, H, d); beta (R, H); pool (L, SLOTS, H, d, d) float32;
    layer an int32 scalar (may be traced); slots (R,) int32, the pool slot of
    each row (rows that hold nothing share a scratch slot, whose content is
    never read for a live row). Returns (o (R, H, d) float32, pool) with
    ``pool[layer, slots[r]]`` advanced by row r's token, in place."""
    R, H, d = q.shape
    heads = next(h for h in range(min(H, HEADS_PER_STEP), 0, -1)
                 if H % h == 0)
    vec = _decode_operands(q, k, v, g, beta)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    slots = slots.astype(jnp.int32)

    def state_block(r, hg, layer, slots):
        return (layer[0], slots[r], hg, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, H // heads),
        in_specs=[
            pl.BlockSpec((None, heads, 8, d),
                         lambda r, hg, layer, slots: (r, hg, 0, 0)),
            pl.BlockSpec((None, None, heads, d, d), state_block),
        ],
        out_specs=[
            pl.BlockSpec((None, heads, d),
                         lambda r, hg, layer, slots: (r, hg, 0)),
            pl.BlockSpec((None, None, heads, d, d), state_block),
        ],
    )
    o, pool = pl.pallas_call(
        functools.partial(_kda_decode_kernel, heads=heads, d=d),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the two scalar-prefetch ones: the pool is the 4th
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="kda_decode_step",
        interpret=interpret,
    )(layer, slots, vec, pool)
    return o, pool


def reference_kda_decode_step(q, k, v, g, beta, pool, layer, slots
                              ) -> Tuple[jax.Array, jax.Array]:
    """The same step in plain ``jnp``: gather the rows' states, one token of
    ``kda_recurrence``, scatter them back. Rows that share a slot (the
    scratch one) leave some row's result there: it is never read."""
    f32 = jnp.float32
    o, state = kda_recurrence(q[:, None], k[:, None], v[:, None],
                              g[:, None], beta[:, None],
                              pool[layer, slots].astype(f32))
    return o[:, 0], pool.at[layer, slots].set(state.astype(pool.dtype))
