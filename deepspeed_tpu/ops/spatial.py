"""Spatial (diffusers) kernels — Stable-Diffusion-family inference ops.

Reference: ``csrc/spatial/csrc/opt_bias_add.cu`` (fused bias+residual adds),
``csrc/transformer/inference/csrc/transform.cu`` + the diffusers injection
path (``module_inject/containers/unet.py``, ``ops/transformer/inference/
diffusers_attention.py:23`` and ``diffusers_transformer_block.py``) whose hot
ops are: GroupNorm over spatial tokens, non-causal attention over H*W, and
bias+residual epilogues.

TPU mapping:
  * ``fused_group_norm`` — one Pallas kernel per batch row: a two-pass grid
    (accumulate per-group sum/sumsq over HW tiles, then normalise in place)
    reads the activation exactly twice, the bandwidth-optimal schedule for a
    cross-row norm. Group stats use a constant channel→group one-hot matmul
    so the reduction rides the MXU regardless of C/group alignment.
  * ``diffusers_attention`` — the spatial self/cross-attention: the flash
    kernel (ops/flash_attention.py) over flattened H*W tokens, causal=False.
    No separate CUDA kernel needed — same Pallas program, different mask.
  * bias+residual adds (opt_bias_add.cu) — dissolved: XLA fuses elementwise
    epilogues into the producing matmul on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _gn_kernel(x_ref, onehot_ref, scale_ref, bias_ref, o_ref,
               sum_scr, sq_scr, *, eps: float, n_elem: float, nt: int):
    p = pl.program_id(1)   # pass: 0 accumulate, 1 normalise
    t = pl.program_id(2)   # HW tile

    @pl.when((p == 0) & (t == 0))
    def _init():
        sum_scr[:] = jnp.zeros_like(sum_scr)
        sq_scr[:] = jnp.zeros_like(sq_scr)

    x = x_ref[0].astype(jnp.float32)                        # (bhw, C)
    onehot = onehot_ref[:]                                  # (C, G_pad)

    @pl.when(p == 0)
    def _accumulate():
        col = jnp.sum(x, axis=0, keepdims=True)             # (1, C)
        col_sq = jnp.sum(x * x, axis=0, keepdims=True)
        sum_scr[:] += jax.lax.dot_general(
            col, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (1, G_pad)
        sq_scr[:] += jax.lax.dot_general(
            col_sq, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0] = x_ref[0]                                 # keep block defined

    @pl.when(p == 1)
    def _normalise():
        mean_g = sum_scr[:] / n_elem                        # (1, G_pad)
        var_g = sq_scr[:] / n_elem - mean_g * mean_g
        rstd_g = jax.lax.rsqrt(var_g + eps)
        # broadcast group stats back to channels: (1,G) @ (G,C) via onehot^T
        mean_c = jax.lax.dot_general(mean_g, onehot,
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        rstd_c = jax.lax.dot_general(rstd_g, onehot,
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        y = (x - mean_c) * rstd_c
        y = y * scale_ref[:].astype(jnp.float32) + bias_ref[:].astype(jnp.float32)
        o_ref[0] = y.astype(o_ref.dtype)


def fused_group_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
                     num_groups: int, eps: float = 1e-5,
                     interpret: bool = False) -> jax.Array:
    """GroupNorm over spatial tokens: x (B, HW, C), per-channel affine.
    Stats are per (batch, group) across all HW positions and the group's
    channels — torch.nn.GroupNorm semantics in NHWC layout."""
    B, HW, C = x.shape
    if C % num_groups:
        raise ValueError(f"C={C} not divisible by num_groups={num_groups}")
    if num_groups > LANES:
        raise ValueError(f"num_groups must be <= {LANES}")
    cg = C // num_groups
    # constant channel -> group one-hot, lane-padded
    onehot = np.zeros((C, LANES), np.float32)
    onehot[np.arange(C), np.arange(C) // cg] = 1.0

    bhw = HW if HW <= 512 else 512
    while HW % bhw:
        bhw //= 2
    nt = HW // bhw
    kernel = functools.partial(_gn_kernel, eps=eps, n_elem=float(HW * cg),
                               nt=nt)
    out = pl.pallas_call(
        kernel,
        grid=(B, 2, nt),
        in_specs=[
            pl.BlockSpec((1, bhw, C), lambda b, p, t: (b, t, 0)),
            pl.BlockSpec((C, LANES), lambda b, p, t: (0, 0)),
            pl.BlockSpec((1, C), lambda b, p, t: (0, 0)),
            pl.BlockSpec((1, C), lambda b, p, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bhw, C), lambda b, p, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, HW, C), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.float32),
                        pltpu.VMEM((1, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, jnp.asarray(onehot), scale.reshape(1, C), bias.reshape(1, C))
    return out


def reference_group_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
                         num_groups: int, eps: float = 1e-5) -> jax.Array:
    """Pure-jnp oracle (torch GroupNorm semantics, NHWC tokens)."""
    B, HW, C = x.shape
    cg = C // num_groups
    xg = x.astype(jnp.float32).reshape(B, HW, num_groups, cg)
    mean = jnp.mean(xg, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 3), keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(B, HW, C)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def diffusers_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        interpret: bool = False) -> jax.Array:
    """Spatial self/cross attention (reference diffusers_attention.py:23):
    q (B, HWq, N, D), k/v (B, HWk, N, D) → (B, HWq, N, D). Non-causal flash
    kernel over the flattened spatial tokens."""
    from .flash_attention import flash_attention

    return flash_attention(q, k, v, causal=False, interpret=interpret)
