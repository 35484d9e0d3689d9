"""Pallas fused LAMB over a flat per-tensor buffer.

TPU-native analog of the reference's FusedLamb (``csrc/lamb/fused_lamb_cuda.cu``
+ ``ops/lamb/fused_lamb.py:19``). LAMB is Adam plus a per-tensor *trust ratio*
``||p|| / ||update||`` scaling the step, so the kernel is two-phase exactly like
the CUDA multi-tensor implementation:

  phase 1 (Pallas)  — one read of p/g/m/v per element: new moments, the
                      unscaled update vector, and per-block partial sums of
                      ``p**2`` and ``u**2`` (the CUDA kernel's per-CTA
                      reduction scratch).
  phase 2 (jnp/XLA) — finish the two norms (a (blocks,) sum), form the clamped
                      trust ratio, apply ``p - lr * ratio * u`` (fuses into a
                      single elementwise pass).

Used per tensor (LAMB's norm granularity in the reference); parity oracle below.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 1024 * 8


def _lamb_phase1_kernel(p_ref, g_ref, m_ref, v_ref, bc_ref,
                        u_out, m_out, v_out, norms_out, *,
                        beta1, beta2, eps, weight_decay, bias_correction):
    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:].astype(jnp.float32)
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    if bias_correction:
        u = (m / bc_ref[0]) / (jnp.sqrt(v / bc_ref[1]) + eps)
    else:
        u = m / (jnp.sqrt(v) + eps)
    if weight_decay != 0.0:
        u = u + weight_decay * p
    u_out[:] = u
    m_out[:] = m
    v_out[:] = v
    # one full (8, 128) tile per block — the smallest block the TPU lowering
    # takes; row 0 carries sum(p^2), row 1 sum(u^2)
    row = jax.lax.broadcasted_iota(jnp.int32, norms_out.shape[1:], 0)
    norms_out[0] = jnp.where(row == 0, jnp.sum(p * p),
                             jnp.where(row == 1, jnp.sum(u * u), 0.0))


def fused_lamb_flat(params: jax.Array, grads: jax.Array, exp_avg: jax.Array,
                    exp_avg_sq: jax.Array, step: int, lr: float = 1e-3,
                    beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-6,
                    weight_decay: float = 0.0, bias_correction: bool = True,
                    max_coeff: float = 10.0, min_coeff: float = 0.01,
                    interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One LAMB step on a flat fp32 tensor buffer (one tensor = one trust
    ratio, the reference granularity). Returns (params, exp_avg, exp_avg_sq).

    ``max_coeff``/``min_coeff`` clamp the trust ratio like the reference
    FusedLamb's lamb_coeff bounds (ops/lamb/fused_lamb.py:27-28).

    DONATION: on the no-padding path the caller's ``exp_avg``/``exp_avg_sq``
    device buffers are donated (``input_output_aliases``) and are INVALID
    after this call — rebind the moments from the returned tuple (the
    functional-update pattern every in-tree caller uses)."""
    n = params.shape[0]
    pad = (-n) % BLOCK
    if pad:
        params, grads, exp_avg, exp_avg_sq = (
            jnp.pad(x, (0, pad)) for x in (params, grads, exp_avg, exp_avg_sq))
    total = params.shape[0]
    stepf = jnp.asarray(step, jnp.float32)
    bc = jnp.stack([1.0 - beta1 ** stepf, 1.0 - beta2 ** stepf])
    kernel = functools.partial(
        _lamb_phase1_kernel, beta1=beta1, beta2=beta2, eps=eps,
        weight_decay=weight_decay, bias_correction=bias_correction)
    blocks = total // BLOCK
    bspec = pl.BlockSpec((BLOCK,), lambda i: (i,))
    u, m2, v2, partials = pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[bspec, bspec, bspec, bspec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[bspec, bspec, bspec,
                   pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((total,), jnp.float32),
                   jax.ShapeDtypeStruct((total,), jnp.float32),
                   jax.ShapeDtypeStruct((total,), jnp.float32),
                   jax.ShapeDtypeStruct((blocks, 8, 128), jnp.float32)],
        input_output_aliases={2: 1, 3: 2},
        interpret=interpret,
    )(params, grads, exp_avg, exp_avg_sq, bc)

    # padded tail contributes 0 to both partial sums (p and g pads are 0, so
    # u there is 0 + wd*0), so the norms are exact
    sums = jnp.sum(partials[:, :2, 0], axis=0)
    p_norm, u_norm = jnp.sqrt(sums[0]), jnp.sqrt(sums[1])
    ratio = jnp.where((p_norm > 0.0) & (u_norm > 0.0),
                      jnp.clip(p_norm / u_norm, min_coeff, max_coeff), 1.0)
    p2 = (params.astype(jnp.float32) - lr * ratio * u).astype(params.dtype)
    if pad:
        p2, m2, v2 = p2[:n], m2[:n], v2[:n]
    return p2, m2, v2


def reference_lamb_flat(params, grads, exp_avg, exp_avg_sq, step, lr=1e-3,
                        beta1=0.9, beta2=0.999, eps=1e-6, weight_decay=0.0,
                        bias_correction=True, max_coeff=10.0, min_coeff=0.01):
    """Pure-jnp oracle with identical semantics."""
    g = grads.astype(jnp.float32)
    p = params.astype(jnp.float32)
    m = beta1 * exp_avg + (1 - beta1) * g
    v = beta2 * exp_avg_sq + (1 - beta2) * g * g
    if bias_correction:
        u = (m / (1 - beta1 ** step)) / (jnp.sqrt(v / (1 - beta2 ** step)) + eps)
    else:
        u = m / (jnp.sqrt(v) + eps)
    if weight_decay != 0.0:
        u = u + weight_decay * p
    p_norm = jnp.linalg.norm(p)
    u_norm = jnp.linalg.norm(u)
    ratio = jnp.where((p_norm > 0.0) & (u_norm > 0.0),
                      jnp.clip(p_norm / u_norm, min_coeff, max_coeff), 1.0)
    return (p - lr * ratio * u).astype(params.dtype), m, v
