"""Fused LayerNorm / RMSNorm Pallas kernels.

TPU-native analog of the reference's normalize kernels
(``csrc/transformer/normalize_kernels.cu``, 2134 LoC, and inference
``layer_norm.cu``). Forward is a single VMEM pass; backward uses the saved
mean/rstd residuals (same scheme as the CUDA backward) expressed with
jax.custom_vjp — the backward math itself is jnp (XLA fuses it well; the fwd
kernel is the memory-bound hot path worth hand-scheduling).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_BLOCK = 128


def _ln_kernel(x_ref, scale_ref, bias_ref, o_ref, *, eps: float, rms: bool):
    x = x_ref[:].astype(jnp.float32)
    if rms:
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps)
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        y = xc * jax.lax.rsqrt(var + eps)
    y = y * scale_ref[:].astype(jnp.float32)
    if bias_ref is not None:
        y = y + bias_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _ln_forward(x: jax.Array, scale: jax.Array, bias: Optional[jax.Array],
                eps: float, rms: bool, interpret: bool) -> jax.Array:
    orig_shape = x.shape
    H = orig_shape[-1]
    x2 = x.reshape(-1, H)
    R = x2.shape[0]
    pad = (-R) % ROW_BLOCK
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    rows = x2.shape[0]
    kernel = functools.partial(_ln_kernel, eps=eps, rms=rms)
    in_specs = [pl.BlockSpec((ROW_BLOCK, H), lambda i: (i, 0)),
                pl.BlockSpec((H,), lambda i: (0,))]
    args = [x2, scale]
    if bias is not None:
        in_specs.append(pl.BlockSpec((H,), lambda i: (0,)))
        args.append(bias)
    else:
        kernel = functools.partial(_ln_kernel_nobias, eps=eps, rms=rms)
    out = pl.pallas_call(
        kernel,
        grid=(rows // ROW_BLOCK,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((ROW_BLOCK, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, H), x.dtype),
        name="fused_layer_norm",
        interpret=interpret,
    )(*args)
    if pad:
        out = out[:R]
    return out.reshape(orig_shape)


def _ln_kernel_nobias(x_ref, scale_ref, o_ref, *, eps: float, rms: bool):
    _ln_kernel(x_ref, scale_ref, None, o_ref, eps=eps, rms=rms)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_layer_norm(x, scale, bias, eps: float = 1e-5, rms: bool = False,
                     interpret: bool = False):
    """y = norm(x) * scale (+ bias). x (..., H); scale/bias (H,).
    rms=True → RMSNorm (no mean subtraction, no bias)."""
    return _ln_forward(x, scale, bias if not rms else None, eps, rms, interpret)


def _fln_fwd(x, scale, bias, eps, rms, interpret):
    y = _ln_forward(x, scale, bias if not rms else None, eps, rms, interpret)
    return y, (x, scale, bias)


def _fln_bwd(eps, rms, interpret, residuals, g):
    x, scale, bias = residuals
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    s32 = scale.astype(jnp.float32)
    H = x.shape[-1]
    if rms:
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = x32 * rstd
        gy = g32 * s32
        dx = rstd * (gy - xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
        dbias = None
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        xc = x32 - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = xc * rstd
        gy = g32 * s32
        dx = rstd * (gy - jnp.mean(gy, axis=-1, keepdims=True)
                     - xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
        dbias = g32.reshape(-1, H).sum(0).astype(bias.dtype) if bias is not None else None
    dscale = (g32 * xhat).reshape(-1, H).sum(0).astype(scale.dtype)
    return dx.astype(x.dtype), dscale, dbias


fused_layer_norm.defvjp(_fln_fwd, _fln_bwd)


def reference_layer_norm(x, scale, bias, eps=1e-5, rms=False):
    x32 = x.astype(jnp.float32)
    if rms:
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)
