"""The Mamba-2 state-space recurrence: the mixer of a state-space layer
(``models/transformer._mamba2_mixer``).

Per head a float32 state ``S`` of ``P x N`` (head channel x state channel),
zero where a sequence starts. A token with input ``x`` (``P`` values a head),
step ``dt`` (a scalar a head, >= 0: the softplus is the caller's), the head's
rate ``A`` (a scalar, < 0) and the group's ``B`` and ``C`` (``N`` values each;
the heads of a group share them) does

    S = exp(dt A) S + dt x B^T          y = S C

(the skip ``D x`` is the mixer's). A token with ``dt`` 0 leaves the state as
it was: that is how a ragged chunk's padding is written. Three forms of it:

* ``mamba2_recurrence``: token by token under ``lax.scan``; the oracle.
* ``mamba2_chunk``: a chunk of tokens at once (the prefill-chunk, score and
  whole-sequence programs), the published chunked (SSD) form, ``CHUNK`` tokens
  a step. With ``G`` the running sum of ``dt A`` inside a chunk, ``y_t =
  exp(G_t) S0 C_t + sum_{s <= t} exp(G_t - G_s) (C_t . B_s) dt_s x_s``: the
  decay stays in log space and a DIFFERENCE is exponentiated, for ``s <= t``
  only, so nothing over- or underflows. Plain ``jax.numpy``; every product
  runs at ``HIGHEST`` precision, because a TPU's default float32 matmul is
  one bfloat16 pass.
* ``mamba2_decode_step``: one token for each row of a decode step, a Pallas
  kernel over a pool of states that stays in HBM, addressed through the
  scalar-prefetched ``layer`` and ``slots`` and updated in place
  (``input_output_aliases``) exactly as ``ops/kda.kda_decode_step`` does its
  own: a step moves each live state once in and once out and nothing else
  of the pool. ``reference_mamba2_decode_step`` is its ``jax.numpy`` twin
  (CPU, and the oracle of the kernel's tests).

**The pool's layout.** A head's state is ``P x N`` = 64 x 128 at the
published widths, and the ``K`` = 16 heads of a group share ``B`` and ``C``.
The pool keeps a GROUP's states transposed and side by side, ``(layers,
slots, G, N, K P)`` (``pack_states``): the state channel runs along the
sublanes and the group's ``K P`` = 1,024 head channels along the lanes. A
token's ``x`` and ``y`` are then rows as they lie in memory, ``S C`` is a sum
over sublanes (adds of whole registers), ``B`` and ``C`` become columns by ONE
transpose a grid step, and the chunked form's two products against the state
(``C S`` and ``B^T x``) are plain matmuls in this layout with no transpose of
the state on either side. That last point is the one that decides: a
transpose between the pool and the arithmetic is folded by XLA into the
POOL's layout, and the prefill-chunk program then copies the whole pool in
and out of that layout (two copies of 1.36 GB a chunk at the benchmark's
size; ``tests/kernels/test_tpu_compile.py`` holds the programs to none).
The bytes are the same either way.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128                 # tokens of a step of mamba2_chunk (the published
#                             chunk_size)
_HI = lax.Precision.HIGHEST


def mamba2_recurrence(x, dt, A, B, C, state) -> Tuple[jax.Array, jax.Array]:
    """x (Bt, S, H, P); dt (Bt, S, H); A (H,); B, C (Bt, S, G, N) with H a
    multiple of G; state (Bt, H, P, N) float32 -> (y (Bt, S, H, P) float32,
    the state after the last token)."""
    f32 = jnp.float32
    rep = x.shape[2] // B.shape[2]
    A = A.astype(f32)

    def step(S, tok):
        x_t, dt_t, B_t, C_t = tok          # (Bt, H, P) (Bt, H) (Bt, G, N)
        B_t, C_t = (jnp.repeat(a, rep, axis=1) for a in (B_t, C_t))
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, (S * C_t[:, :, None, :]).sum(-1)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (x, dt, B, C))
    state, y = lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(y, 0, 1), state


def mamba2_chunk(x, dt, A, B, C, state, chunk: int = CHUNK,
                 packed: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The same function of the same operands as ``mamba2_recurrence``,
    ``chunk`` tokens a step. Any ``S``: the last chunk is filled with tokens
    that write nothing (``dt`` 0). ``packed``: the state comes and goes in
    the pool's layout (Bt, G, N, K P), and is never transposed here."""
    f32 = jnp.float32
    Bt, S, H, P = x.shape
    G, N = B.shape[2:]
    K = H // G                                  # heads of a group
    c = min(chunk, S)
    n = -(-S // c)
    pad = n * c - S

    def split(a, tail):     # (Bt, S, ...) -> (n, Bt, c, ...)
        a = a.astype(f32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((Bt, n, c) + tail), 1, 0)

    t_idx = jnp.arange(c)
    upto = t_idx[:, None] >= t_idx[None, :]             # s <= t
    A = A.astype(f32).reshape(G, K)

    def lanes(a):           # (..., G, K) a head -> (..., G, K P) a channel
        return jnp.repeat(a, P, axis=-1)

    def step(S0, tok):
        xc, dtc, Bc, Cc = tok    # (Bt, c, G, K, P) (Bt, c, G, K) (Bt, c, G, N)
        Gs = jnp.cumsum(dtc * A, axis=1)                # (Bt, c, G, K), <= 0
        # exp(G_t - G_s) for s <= t; the rest exactly zero
        L = jnp.exp(jnp.where(upto[None, :, :, None, None],
                              Gs[:, :, None] - Gs[:, None], -jnp.inf))
        CB = jnp.einsum("btgn,bsgn->btsg", Cc, Bc, precision=_HI)
        xdt = xc * dtc[..., None]
        y = (jnp.einsum("btsgk,bsgkp->btgkp", L * CB[..., None], xdt,
                        precision=_HI).reshape(Bt, c, G, K * P)
             + lanes(jnp.exp(Gs)) * jnp.einsum(
                 "btgn,bgnq->btgq", Cc, S0, precision=_HI))
        last = Gs[:, -1]                                # (Bt, G, K)
        S1 = (lanes(jnp.exp(last))[:, :, None] * S0
              + jnp.einsum("bsgn,bsgq->bgnq", Bc, xdt.reshape(
                  Bt, c, G, K * P) * lanes(jnp.exp(last[:, None] - Gs)),
                  precision=_HI))
        return S1, y

    state = state.astype(f32)
    state, y = lax.scan(
        step, state if packed else pack_states(state, G),
        (split(x, (G, K, P)), split(dt, (G, K)), split(B, (G, N)),
         split(C, (G, N))))
    y = jnp.moveaxis(y, 0, 1).reshape(Bt, n * c, H, P)
    return y[:, :S], state if packed else unpack_states(state, P)


# ---------------------------------------------------------------------------
# one token a row: the decode step
# ---------------------------------------------------------------------------


def pack_states(state: jax.Array, groups: int) -> jax.Array:
    """(..., H, P, N) -> the pool's layout (..., G, N, K P): a group's K
    heads transposed and side by side along the last dim."""
    *lead, H, P, N = state.shape
    s = state.reshape(*lead, groups, H // groups, P, N)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, groups, N,
                                           H // groups * P)


def unpack_states(packed: jax.Array, head_dim: int) -> jax.Array:
    """The inverse of ``pack_states``."""
    *lead, G, N, KP = packed.shape
    s = packed.reshape(*lead, G, N, KP // head_dim, head_dim)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, G * KP // head_dim,
                                           head_dim, N)


def _mamba2_decode_kernel(layer_ref, slot_ref, vec_ref, bc_ref, s_ref, y_ref,
                          s_out_ref, *, n: int, width: int, lanes: int):
    del layer_ref, slot_ref                 # read by the index maps
    # the group's B and C lie along the lanes and run along the sublanes of
    # the state: one (n, n) transpose a grid step serves every head of it
    cols = jnp.concatenate(
        [bc_ref[...], jnp.zeros((n - 8, n), jnp.float32)], axis=0).T
    b_c, c_c = cols[:, 0:1], cols[:, 1:2]
    for j in range(0, width, lanes):        # a register's width of channels
        at = slice(j, j + lanes)
        S = s_ref[:, at] * vec_ref[1:2, at] + b_c * vec_ref[0:1, at]
        s_out_ref[:, at] = S
        y_ref[:, at] = jnp.sum(S * c_c, axis=0, keepdims=True)


def mamba2_decode_step(x, dt, A, B, C, pool, layer, slots,
                       interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array]:
    """x (R, H, P); dt (R, H); A (H,); B, C (R, G, N); pool (L, SLOTS, G, N,
    K P) float32 (``pack_states``); layer an int32 scalar (may be traced);
    slots (R,) int32, the pool slot of each row (rows that hold nothing share
    a scratch slot, whose content is never read for a live row). Returns
    (y (R, H, P) float32, pool) with ``pool[layer, slots[r]]`` advanced by
    row r's token, in place. A grid step is one (row, group): 512 KiB of
    float32 state at the published 128 x 1,024."""
    f32 = jnp.float32
    R, H, P = x.shape
    G, N = B.shape[1:]
    KP = H // G * P
    dt = dt.astype(f32)
    # a group's head channels a row of K P lanes: dt x, and the decay across
    # the lanes of its head; a sublane tile a (row, group)
    vec = jnp.stack(
        [(x.astype(f32) * dt[..., None]).reshape(R, G, KP),
         jnp.repeat(jnp.exp(dt * A.astype(f32)), P, axis=-1
                    ).reshape(R, G, KP)]
        + [jnp.zeros((R, G, KP), f32)] * 6, axis=2)
    bc = jnp.stack([B.astype(f32), C.astype(f32)]
                   + [jnp.zeros((R, G, N), f32)] * 6, axis=2)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    slots = slots.astype(jnp.int32)

    def state_block(r, g, layer, slots):
        return (layer[0], slots[r], g, 0, 0)

    def row_block(r, g, layer, slots):
        return (r, g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, G),
        in_specs=[
            pl.BlockSpec((None, None, 8, KP), row_block),
            pl.BlockSpec((None, None, 8, N), row_block),
            pl.BlockSpec((None, None, None, N, KP), state_block),
        ],
        out_specs=[
            pl.BlockSpec((None, None, 1, KP), row_block),
            pl.BlockSpec((None, None, None, N, KP), state_block),
        ],
    )
    y, pool = pl.pallas_call(
        functools.partial(_mamba2_decode_kernel, n=N, width=KP,
                          lanes=min(KP, 128)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, G, 1, KP), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the two scalar-prefetch ones: the pool is the 5th
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="mamba2_decode_step",
        interpret=interpret,
    )(layer, slots, vec, bc, pool)
    return y.reshape(R, H, P), pool


def reference_mamba2_decode_step(x, dt, A, B, C, pool, layer, slots
                                 ) -> Tuple[jax.Array, jax.Array]:
    """The same step in plain ``jnp``: gather the rows' states, one token of
    ``mamba2_recurrence``, scatter them back. Rows that share a slot (the
    scratch one) leave some row's result there: it is never read."""
    y, state = mamba2_recurrence(
        x[:, None], dt[:, None], A, B[:, None], C[:, None],
        unpack_states(pool[layer, slots].astype(jnp.float32), x.shape[-1]))
    return y[:, 0], pool.at[layer, slots].set(
        pack_states(state, B.shape[1]).astype(pool.dtype))
