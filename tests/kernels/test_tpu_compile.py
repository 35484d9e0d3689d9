"""Compile the main-path Pallas kernels for a described (not attached) v5e.

The interpret-mode parity tests cannot see what the chip's compiler refuses:
a block whose last two dims are not (8, 128)-aligned, a kernel over the
scoped-VMEM limit. libtpu compiles for a topology that is only described, so
these cases run on the CPU sandbox at the widths ``chip_smoke.py`` reaches
(gpt2-125m training, opt-1.3b serving). Nothing executes — results are the
parity tests' job.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops import (decode_attention, flash_attention,
                               fused_layer_norm, paged_decode_attention,
                               paged_prefill_attention)

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device; the persistent compile cache is off while
    the module runs (such a compile is written to it but cannot be read back
    without a chip — the next run would warn and compile again)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # libtpu lets one process at a time load it unless told otherwise; with
    # no chip attached several pytest workers can describe one side by side
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"TPU topology cannot be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flash(b, t, h, d, grad):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(F32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    return fn, [((b, t, h, d), BF16)] * 3


def _layer_norm(rows, t, e, grad):
    def fwd(x, s, b):
        return fused_layer_norm(x, s, b, 1e-5, False)

    def loss(x, s, b):
        return fwd(x, s, b).astype(F32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    return fn, [((rows, t, e), BF16), ((e,), F32), ((e,), F32)]


def _paged_decode(rows, heads, d, block, maxb):
    pool = ((1024, block, heads * d), BF16)
    return (paged_decode_attention,
            [((rows, heads, d), BF16), pool, pool, ((rows, maxb), I32),
             ((rows,), I32)])


def _paged_prefill(chunk, heads, d, block, maxb):
    pool = ((1024, block, heads * d), BF16)
    return (paged_prefill_attention,
            [((1, chunk, heads, d), BF16), pool, pool, ((1, maxb), I32),
             ((1,), I32)])


def _dense_decode(b, t, heads, d):
    cache = ((b, t, heads, d), BF16)
    return (decode_attention,
            [((b, heads, d), BF16), cache, cache, ((b, t), I32)])


# gpt2-125m: 12 heads x 64, hidden 768, seq 1024, micro-batch 32.
# opt-1.3b: 32 heads x 64, hidden 2048, seq 2048; serving block 16,
# chunk 256, 16 decode rows, 128 blocks per sequence.
CASES = {
    "flash-fwd-gpt2-125m": lambda: _flash(32, 1024, 12, 64, grad=False),
    "flash-bwd-gpt2-125m": lambda: _flash(32, 1024, 12, 64, grad=True),
    "flash-fwd-opt-1.3b": lambda: _flash(4, 2048, 32, 64, grad=False),
    "flash-bwd-opt-1.3b": lambda: _flash(4, 2048, 32, 64, grad=True),
    "layernorm-fwd-gpt2-125m": lambda: _layer_norm(32, 1024, 768, False),
    "layernorm-bwd-gpt2-125m": lambda: _layer_norm(32, 1024, 768, True),
    "layernorm-fwd-opt-1.3b": lambda: _layer_norm(8, 1024, 2048, False),
    "layernorm-bwd-opt-1.3b": lambda: _layer_norm(8, 1024, 2048, True),
    "paged-decode-gpt2-125m": lambda: _paged_decode(16, 12, 64, 16, 64),
    "paged-decode-opt-1.3b": lambda: _paged_decode(16, 32, 64, 16, 128),
    "paged-prefill-gpt2-125m": lambda: _paged_prefill(256, 12, 64, 16, 64),
    "paged-prefill-opt-1.3b": lambda: _paged_prefill(256, 32, 64, 16, 128),
    "dense-decode-gpt2-125m": lambda: _dense_decode(8, 1024, 12, 64),
    "dense-decode-opt-1.3b": lambda: _dense_decode(8, 2048, 32, 64),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=v5e) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    if "layernorm-bwd" not in case:   # the norm's backward is plain jnp
        assert "tpu_custom_call" in compiled.as_text()
