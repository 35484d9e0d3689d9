"""The Mamba-1 selective scan: the mixer of a state-space layer whose decay
is a value a (channel, state) pair (``models/transformer._mamba1_mixer``).

A channel ``d`` of the inner width ``D`` keeps ``N`` float32 numbers, zero
where a sequence starts. A token with input ``x`` (``D`` values), step ``dt``
(``D`` values, >= 0: the softplus is the caller's), the rates ``A`` (``N x
D``, < 0: ``-exp(A_log)`` transposed) and its own ``B`` and ``C`` (``N``
values each, shared by every channel) does

    S = exp(dt A) * S + (dt x) B^T          y = S C

elementwise in ``(N, D)`` (the skip ``D x`` is the mixer's). A token with
``dt`` 0 leaves the state as it was: that is how a ragged chunk's padding is
written. Unlike Mamba-2 the decay differs a (channel, state) pair, so the
recurrence has no matmul form: every form below is the same elementwise
arithmetic, in float32.

* ``mamba1_recurrence``: token by token under ``lax.scan``; the oracle, and
  the form a CPU runs.
* ``mamba1_chunk_scan``: a chunk of tokens (the prefill-chunk and score
  programs), a Pallas kernel: a grid step is one (row, slab of channels) and
  walks the chunk's tokens with the slab's state in registers, so the state
  is read once and written once a chunk and a token costs its own ``x``,
  ``dt`` and ``y`` rows.
* ``mamba1_decode_step``: one token for each row of a decode step, a Pallas
  kernel over the pool of states where it lies in HBM, addressed through the
  scalar-prefetched ``layer`` and ``slots`` and updated in place
  (``input_output_aliases``), as ``ops/mamba2.mamba2_decode_step`` does its
  own. ``reference_mamba1_decode_step`` is its ``jax.numpy`` twin.

**The pool's layout** is ``(layers, slots, N, D)``: the state channel along
the sublanes (``N`` = 16 is two float32 tiles) and the inner width along the
lanes, so a token's ``x``, ``dt`` and ``y`` are rows as they lie in memory,
``B`` and ``C`` are columns that broadcast along the lanes, and ``S C`` is a
sum over sublanes.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SLAB = 512                 # lanes of channels a step of either kernel holds


def mamba1_recurrence(x, dt, A, B, C, state) -> Tuple[jax.Array, jax.Array]:
    """x, dt (Bt, S, D); A (N, D); B, C (Bt, S, N); state (Bt, N, D) float32
    -> (y (Bt, S, D) float32, the state after the last token)."""
    f32 = jnp.float32
    A = A.astype(f32)

    def step(S, tok):
        x_t, dt_t, B_t, C_t = tok               # (Bt, D) (Bt, D) (Bt, N)
        S = (jnp.exp(dt_t[:, None] * A) * S
             + (dt_t * x_t)[:, None] * B_t[:, :, None])
        return S, (S * C_t[:, :, None]).sum(1)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (x, dt, B, C))
    state, y = lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(y, 0, 1), state


def _slab(width: int) -> int:
    return _SLAB if width % _SLAB == 0 else width


# ---------------------------------------------------------------------------
# a chunk of tokens a row
# ---------------------------------------------------------------------------


def _chunk_kernel(x_ref, dt_ref, a_ref, bc_ref, s_ref, y_ref, s_out_ref, *,
                  tokens: int):
    a = a_ref[...]

    def token(t, S):
        dt = dt_ref[pl.ds(t, 1), :]                         # (1, W)
        cols = bc_ref[t]                                    # (N, 2)
        S = jnp.exp(dt * a) * S + cols[:, 0:1] * (dt * x_ref[pl.ds(t, 1), :])
        y_ref[pl.ds(t, 1), :] = jnp.sum(S * cols[:, 1:2], axis=0,
                                        keepdims=True)
        return S

    s_out_ref[...] = lax.fori_loop(0, tokens, token, s_ref[...])


def mamba1_chunk_scan(x, dt, A, B, C, state, interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """The same function of the same operands as ``mamba1_recurrence``, as a
    kernel: a grid step is one (row, slab of ``_SLAB`` channels)."""
    f32 = jnp.float32
    Bt, S, D = x.shape
    N = A.shape[0]
    W = _slab(D)
    bc = jnp.stack([B.astype(f32), C.astype(f32)], axis=-1)  # (Bt, S, N, 2)

    def rows(b, j):
        return (b, 0, j)

    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, tokens=S),
        grid=(Bt, D // W),
        in_specs=[
            pl.BlockSpec((None, S, W), rows),
            pl.BlockSpec((None, S, W), rows),
            pl.BlockSpec((N, W), lambda b, j: (0, j)),
            pl.BlockSpec((None, S, N, 2), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((None, N, W), rows),
        ],
        out_specs=[
            pl.BlockSpec((None, S, W), rows),
            pl.BlockSpec((None, N, W), rows),
        ],
        out_shape=[jax.ShapeDtypeStruct((Bt, S, D), f32),
                   jax.ShapeDtypeStruct((Bt, N, D), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="mamba1_chunk_scan",
        interpret=interpret,
    )(x.astype(f32), dt.astype(f32), A.astype(f32), bc, state.astype(f32))
    return y, state


# ---------------------------------------------------------------------------
# one token a row: the decode step
# ---------------------------------------------------------------------------


def _decode_kernel(layer_ref, slot_ref, vec_ref, bc_ref, a_ref, s_ref, y_ref,
                   s_out_ref, *, width: int, lanes: int):
    del layer_ref, slot_ref                 # read by the index maps
    b_c, c_c = bc_ref[:, 0:1], bc_ref[:, 1:2]
    for j in range(0, width, lanes):        # a slab of channels
        at = slice(j, j + lanes)
        S = (jnp.exp(vec_ref[1:2, at] * a_ref[:, at])
             * s_ref[:, at].astype(jnp.float32) + b_c * vec_ref[0:1, at])
        s_out_ref[:, at] = S.astype(s_out_ref.dtype)
        y_ref[:, at] = jnp.sum(S * c_c, axis=0, keepdims=True)


def mamba1_decode_step(x, dt, A, B, C, pool, layer, slots,
                       interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array]:
    """x, dt (R, D); A (N, D); B, C (R, N); pool (L, SLOTS, N, D) float32;
    layer an int32 scalar (may be traced); slots (R,) int32, the pool slot of
    each row (rows that hold nothing share a scratch slot, whose content is
    never read for a live row). Returns (y (R, D) float32, pool) with
    ``pool[layer, slots[r]]`` advanced by row r's token, in place. A grid
    step is one row: its state once in and once out (320 KiB of float32 at
    the published 16 x 5,120) and ``A``, which stays where it is between
    steps."""
    f32 = jnp.float32
    R, D = x.shape
    N = A.shape[0]
    dt = dt.astype(f32)
    # a row of D lanes each: dt x, and dt; a sublane tile a row
    vec = jnp.stack([x.astype(f32) * dt, dt]
                    + [jnp.zeros((R, D), f32)] * 6, axis=1)
    bc = jnp.stack([B.astype(f32), C.astype(f32)], axis=-1)  # (R, N, 2)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    slots = slots.astype(jnp.int32)

    def state_block(r, layer, slots):
        return (layer[0], slots[r], 0, 0)

    def row_block(r, layer, slots):
        return (r, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((None, 8, D), row_block),
            pl.BlockSpec((None, N, 2), row_block),
            pl.BlockSpec((N, D), lambda r, layer, slots: (0, 0)),
            pl.BlockSpec((None, None, N, D), state_block),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, D), row_block),
            pl.BlockSpec((None, None, N, D), state_block),
        ],
    )
    y, pool = pl.pallas_call(
        functools.partial(_decode_kernel, width=D, lanes=_slab(D)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, 1, D), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the two scalar-prefetch ones: the pool is the 6th
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mamba1_decode_step",
        interpret=interpret,
    )(layer, slots, vec, bc, A.astype(f32), pool)
    return y[:, 0], pool


def reference_mamba1_decode_step(x, dt, A, B, C, pool, layer, slots
                                 ) -> Tuple[jax.Array, jax.Array]:
    """The same step in plain ``jnp``: gather the rows' states, one token of
    ``mamba1_recurrence``, scatter them back. Rows that share a slot (the
    scratch one) leave some row's result there: it is never read."""
    y, state = mamba1_recurrence(
        x[:, None], dt[:, None], A, B[:, None], C[:, None],
        pool[layer, slots].astype(jnp.float32))
    return y[:, 0], pool.at[layer, slots].set(state.astype(pool.dtype))
