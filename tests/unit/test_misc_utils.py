"""Misc parity-shim tests: OnDevice construction placement, MoE TP token
mappings (reference utils/init_on_device.py, moe/mappings.py)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import create_model
from deepspeed_tpu.utils.init_on_device import OnDevice, abstract_init


def test_ondevice_meta_is_abstract():
    model = create_model("tiny", dtype=jnp.float32)
    with OnDevice(device="meta") as ctx:
        shapes = ctx.init(model.init, jax.random.PRNGKey(0))
    leaf = jax.tree.leaves(shapes)[0]
    assert isinstance(leaf, jax.ShapeDtypeStruct)
    assert abstract_init(model.init, jax.random.PRNGKey(0))


def test_ondevice_real_with_dtype():
    model = create_model("tiny", dtype=jnp.float32)
    with OnDevice(dtype=jnp.bfloat16, device="device") as ctx:
        params = ctx.init(model.init, jax.random.PRNGKey(0))
    assert params["embed"]["tokens"].dtype == jnp.bfloat16


def test_moe_mappings_roundtrip():
    from deepspeed_tpu.config.config import ParallelConfig
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.moe_mappings import drop_tokens, gather_tokens

    mesh = mesh_mod.build_mesh(ParallelConfig(tensor_parallel_size=2,
                                              data_parallel_size=4))
    x = jnp.arange(2 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 4)

    @jax.jit
    def fn(x):
        g = gather_tokens(drop_tokens(x))
        return g * 2

    with mesh_mod.mesh_context(mesh):
        out = fn(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2)


@pytest.mark.parametrize("case", ["env-dir-is-jax's-own", "unset-uses-checkout",
                                  "switched-off"])
def test_compile_cache_is_placed_from_outside(case, monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: the code sets no directory. Unset:
    one fixed path in the checkout — never $HOME, a temp name, a pid or a
    time. ``jax_enable_compilation_cache`` off: nothing."""
    from deepspeed_tpu.utils import compile_cache as cc

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs")}
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1])
    try:
        if case == "env-dir-is-jax's-own":
            # jax reads the variable itself at import; stand in for that
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            real_update("jax_compilation_cache_dir", str(tmp_path))
            assert cc.enable_compile_cache() == str(tmp_path)
            assert "jax_compilation_cache_dir" not in updates
        elif case == "unset-uses-checkout":
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            real_update("jax_compilation_cache_dir", None)
            repo = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            assert cc.enable_compile_cache() == os.path.join(repo,
                                                             ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == cc.CHECKOUT_CACHE_DIR
            assert cc.enable_compile_cache() == cc.CHECKOUT_CACHE_DIR
            assert updates.count("jax_compilation_cache_dir") == 1
        else:
            real_update("jax_enable_compilation_cache", False)
            assert cc.enable_compile_cache() is None
            assert updates == []
    finally:
        for k, v in saved.items():
            real_update(k, v)
