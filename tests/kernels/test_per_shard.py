"""Pallas kernels under a mesh of several devices.

GSPMD cannot partition a Mosaic kernel, so ``models/transformer.py`` runs the
flash-attention and norm kernels per shard (``_per_shard``). Here the kernels
run through the Pallas interpreter on the 8-device CPU mesh, inputs laid out
as the training engine lays them out, against the jnp paths.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu.models.transformer as T
from deepspeed_tpu.config.config import ParallelConfig
from deepspeed_tpu.ops.flash_attention import flash_attention
from deepspeed_tpu.ops.normalization import fused_layer_norm
from deepspeed_tpu.parallel import mesh as mesh_mod

DATA = mesh_mod.DATA_SHARD


@pytest.fixture
def interpreted(monkeypatch):
    fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
    nrm = importlib.import_module("deepspeed_tpu.ops.normalization")
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda *a, **k: flash_attention(*a, **{**k, "interpret": True}))
    monkeypatch.setattr(
        nrm, "fused_layer_norm",
        lambda x, s, b, eps, rms: fused_layer_norm(x, s, b, eps, rms, True))


# (mesh degrees, batch, q heads, kv heads): batch and heads that split over
# the mesh, GQA groups kept whole, and sizes that do not divide (whole on
# every device instead)
LAYOUTS = {
    "dp8": (dict(data_parallel_size=8), 8, 4, 4),
    "dp2-sp2-tp2-gqa": (dict(data_parallel_size=2, sequence_parallel_size=2,
                             tensor_parallel_size=2), 4, 8, 4),
    "dp2-tp4-heads-indivisible": (dict(data_parallel_size=2,
                                       tensor_parallel_size=4), 2, 6, 6),
    "dp8-batch-indivisible": (dict(data_parallel_size=8), 4, 4, 2),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_flash_attention_per_shard_matches_jnp(interpreted, layout):
    par, b, n, kv = LAYOUTS[layout]
    mesh = mesh_mod.build_mesh(ParallelConfig(**par))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 128, n, 32))
    k = jax.random.normal(ks[1], (b, 128, kv, 32))
    v = jax.random.normal(ks[2], (b, 128, kv, 32))
    mask = jnp.ones((b, 128), jnp.int32).at[:, 100:].set(0)
    alibi = T.alibi_slopes(n)
    want = T.dot_product_attention(q, k, v, mask, causal=True, alibi=alibi)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, mask, causal=True, alibi=alibi) ** 2)

    with mesh_mod.ambient(mesh):
        dp = mesh_mod.get_data_parallel_world_size(mesh)
        batch = NamedSharding(mesh, P(DATA) if b % dp == 0 else P())
        qs, ks_, vs = (jax.device_put(x, batch) for x in (q, k, v))
        got = jax.jit(lambda q, k, v: T._flash_attention(
            q, k, v, mask, causal=True, alibi=alibi))(qs, ks_, vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        if layout != "dp2-sp2-tp2-gqa":
            return      # the backward pass once, on the richest layout
        got_g = jax.jit(jax.grad(lambda *a: loss(T._flash_attention, *a),
                                 (0, 1, 2)))(qs, ks_, vs)
    want_g = jax.grad(lambda *a: loss(T.dot_product_attention, *a),
                      (0, 1, 2))(q, k, v)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("kind", ["layernorm", "rmsnorm"])
def test_norm_per_shard_matches_jnp(interpreted, kind):
    mesh = mesh_mod.build_mesh(ParallelConfig(data_parallel_size=4,
                                              sequence_parallel_size=2))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (4, 64, 256))
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], (256,))
    bias = None if kind == "rmsnorm" else jax.random.normal(ks[2], (256,))
    want = T._norm(x, scale, bias, kind, 1e-5)           # CPU: the jnp path
    with mesh_mod.ambient(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P(DATA, "seq")))
        got = jax.jit(lambda x: T._fused_norm(x, scale, bias, kind, 1e-5))(xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_one_device_or_no_mesh_calls_the_kernel_directly(interpreted):
    x = jnp.ones((2, 8, 128))
    out = T._per_shard(lambda a, b: a * 2, (x, None), ((None,) * 3, ()))
    assert out.shape == x.shape and float(out[0, 0, 0]) == 2.0
