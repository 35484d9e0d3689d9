"""Transformer model tests: shapes, causality, KV-cache consistency, loss
masking, and logical-axis spec resolution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import (create_model, cross_entropy_loss,
                                  resolve_param_specs, param_count)
from deepspeed_tpu.models.transformer import (TransformerConfig, build_model,
                                              forward, init_params)


@pytest.fixture(scope="module", params=["tiny", "tiny-llama"])
def model(request):
    return create_model(request.param)


def _batch(cfg, b=2, s=16, seed=0):
    rng = jax.random.PRNGKey(seed)
    return {"input_ids": jax.random.randint(rng, (b, s), 0, cfg.vocab_size)}


def test_forward_shapes(model):
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0))
    logits, cache = model.apply(params, _batch(cfg))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert cache is None
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_causality(model):
    """Changing a future token must not change past logits."""
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits1, _ = model.apply(params, batch)
    ids2 = batch["input_ids"].at[:, -1].set((batch["input_ids"][:, -1] + 1) % cfg.vocab_size)
    logits2, _ = model.apply(params, {"input_ids": ids2})
    np.testing.assert_allclose(np.asarray(logits1[:, :-1], np.float32),
                               np.asarray(logits2[:, :-1], np.float32), atol=1e-5)
    assert not np.allclose(np.asarray(logits1[:, -1], np.float32),
                           np.asarray(logits2[:, -1], np.float32))


@pytest.mark.slow
def test_kv_cache_matches_full_forward(model):
    """Prefill + token-by-token decode must reproduce the full forward — the
    correctness contract of the reference's KV-cache kernels
    (csrc/transformer/inference transform.cu KV append)."""
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, b=2, s=12)
    full_logits, _ = model.apply(params, batch)

    T_max = 16
    L, B = cfg.num_layers, 2
    cache = {
        "k": jnp.zeros((L, B, T_max, cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
        "v": jnp.zeros((L, B, T_max, cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
        "index": jnp.zeros((L,), jnp.int32),
    }
    # prefill on first 8 tokens
    prefill_logits, cache = model.apply(
        params, {"input_ids": batch["input_ids"][:, :8]}, cache=cache, start_pos=0)
    np.testing.assert_allclose(np.asarray(prefill_logits, np.float32),
                               np.asarray(full_logits[:, :8], np.float32),
                               atol=2e-4, rtol=1e-3)
    # decode tokens 8..11 one at a time
    for t in range(8, 12):
        step_logits, cache = model.apply(
            params, {"input_ids": batch["input_ids"][:, t:t + 1]}, cache=cache,
            start_pos=t)
        np.testing.assert_allclose(np.asarray(step_logits[:, 0], np.float32),
                                   np.asarray(full_logits[:, t], np.float32),
                                   atol=2e-4, rtol=1e-3)


def test_padding_mask(model):
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, b=1, s=8)
    mask = jnp.array([[1, 1, 1, 1, 0, 0, 0, 0]])
    logits_masked, _ = model.apply(params, {**batch, "attention_mask": mask})
    # perturb a masked-out position; unmasked logits must not move
    ids2 = batch["input_ids"].at[:, 5].set((batch["input_ids"][:, 5] + 7) % cfg.vocab_size)
    logits2, _ = model.apply(params, {"input_ids": ids2, "attention_mask": mask})
    np.testing.assert_allclose(np.asarray(logits_masked[:, :4], np.float32),
                               np.asarray(logits2[:, :4], np.float32), atol=1e-5)


@pytest.mark.slow
def test_loss_decreases_with_training():
    model = create_model("tiny")
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(model.config, b=4, s=32)

    loss_g = jax.jit(jax.value_and_grad(model.loss_fn))
    loss0, grads = loss_g(params, batch)
    # plain SGD steps on the same batch must reduce loss
    for _ in range(10):
        loss, grads = loss_g(params, batch)
        params = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
    loss1, _ = loss_g(params, batch)
    assert float(loss1) < float(loss0)


def test_cross_entropy_ignore_index():
    logits = jnp.zeros((1, 4, 10))
    labels = jnp.array([[1, 2, -100, -100]])
    loss = cross_entropy_loss(logits, labels)
    # uniform logits -> log(10) per counted token
    assert float(loss) == pytest.approx(np.log(10), rel=1e-5)


@pytest.mark.slow
def test_remat_matches(model):
    cfg_remat = TransformerConfig(**{**model.config.__dict__, "remat": True})
    m2 = build_model(cfg_remat)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(model.config)
    l1 = jax.jit(model.loss_fn)(params, batch)
    l2 = jax.jit(m2.loss_fn)(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    g1 = jax.jit(jax.grad(model.loss_fn))(params, batch)
    g2 = jax.jit(jax.grad(m2.loss_fn))(params, batch)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5), g1, g2)


def test_param_specs_tp_and_fsdp(model):
    params = model.init(jax.random.PRNGKey(0))
    specs = resolve_param_specs(params, model.axes, fsdp_axis="data", fsdp_min_size=1)
    flat = jax.tree_util.tree_leaves_with_path(specs)
    # attention qkv sharded over model axis on the heads dim
    d = dict((jax.tree_util.keystr(k), v) for k, v in flat)
    wq_key = [k for k in d if "wq" in k][0]
    assert d[wq_key] == P(None, "data", "model")
    tok_key = [k for k in d if "tokens" in k][0]
    assert d[tok_key] == P("model", "data")


@pytest.mark.slow
def test_param_count_presets():
    m = create_model("gpt2-125m")
    params = m.init(jax.random.PRNGKey(0))
    n = param_count(params)
    assert 115e6 < n < 135e6  # ~124M


@pytest.mark.slow
class TestDropout:
    """cfg.dropout applies at embed/attn-out/mlp-out when the train engine
    enables it; eval and decode stay deterministic (reference transformer
    kernel dropout semantics minus in-kernel attention-prob dropout — see
    TransformerConfig.dropout)."""

    def test_changes_training_forward_only_when_enabled(self):
        from deepspeed_tpu.models import create_model

        base = create_model("tiny")
        params = base.init(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 250)
        out0, _ = base.apply(params, {"input_ids": ids})

        off = create_model("tiny", dropout=0.5)           # rate set, not enabled
        out_off, _ = off.apply(params, {"input_ids": ids})
        np.testing.assert_array_equal(np.asarray(out_off), np.asarray(out0))

        on = create_model("tiny", dropout=0.5, dropout_enabled=True)
        out_on, _ = on.apply(params, {"input_ids": ids})
        assert not np.allclose(np.asarray(out_on), np.asarray(out0))
        assert np.isfinite(np.asarray(out_on)).all()

    def test_engine_enables_eval_disables(self):
        import deepspeed_tpu
        from deepspeed_tpu.models import create_model

        model = create_model("tiny", dropout=0.3)
        engine, *_ = deepspeed_tpu.initialize(
            model=model,
            config={"train_micro_batch_size_per_gpu": 2,
                    "steps_per_print": 1000,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
        assert engine.model.config.dropout_enabled
        ids = jax.random.randint(jax.random.PRNGKey(0),
                                 (1, engine.train_batch_size(), 16), 0, 250)
        l1 = float(engine.train_batch(batch={"input_ids": ids}))
        assert np.isfinite(l1)
        # eval is deterministic and dropout-free: matches a dropout-0 model
        ev_batch = jax.tree.map(lambda x: x[0], {"input_ids": ids})
        ev = float(engine.eval_loss(ev_batch))
        ref = create_model("tiny")
        ref_loss = float(jax.jit(ref.loss_fn)(engine.params, ev_batch))
        np.testing.assert_allclose(ev, ref_loss, rtol=1e-6)
        assert engine.model.config.dropout_enabled  # restored after eval


@pytest.mark.parametrize("kw", [
    dict(),                                             # gelu + layernorm
    dict(activation="swiglu", norm="rmsnorm", position="rope",
         tie_embeddings=False),
    dict(moe_num_experts=4, moe_use_residual=True),
])
def test_init_layer_block_matches_init_slice(kw):
    """Load-bearing contract for ZeRO-3 param offload: Model.init_layer_block
    (rng, lo, blen) must be BIT-IDENTICAL to the corresponding slice of
    init(rng)["layers"] — pinned-host runs init one block at a time and must
    train from exactly the weights the resident engine would."""
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  build_model)

    cfg = TransformerConfig(vocab_size=64, hidden_size=16, num_layers=5,
                            num_heads=2, max_seq_len=16, **kw)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(42)
    full = model.init(rng)["layers"]
    for lo, blen in ((0, 2), (2, 2), (4, 1), (0, 5)):
        # reuse is the contract under test: block init must be bit-identical
        # to full init under the SAME key. tpulint: disable=key-reuse
        blk = model.init_layer_block(rng, lo, blen)
        want = jax.tree.map(lambda l: l[lo:lo + blen], full)
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_flatten_with_path(blk)[0],
                jax.tree_util.tree_flatten_with_path(want)[0]):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{jax.tree_util.keystr(pa)} [{lo}:{lo + blen}]")


def test_remat_policy_knobs():
    """remat_policy surface incl. the cpu_checkpointing analog
    ('offload-dots' — saved dots live in pinned host memory; functional
    equivalence validated on real TPU, docs/offload_design.md). "dots" also
    keeps what the flash forward kernel tags with SAVED_RESIDUALS, and no
    other value that is not a dot."""
    from jax.ad_checkpoint import checkpoint_name

    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  resolve_remat_policy)
    from deepspeed_tpu.ops.flash_attention import SAVED_RESIDUALS

    assert resolve_remat_policy(TransformerConfig(remat_policy="full")) is None
    assert resolve_remat_policy(
        TransformerConfig(remat_policy="offload-dots")) is not None
    dots = resolve_remat_policy(TransformerConfig(remat_policy="dots"))

    def saved(fn, *args):
        eqn = jax.make_jaxpr(fn)(*args).eqns[-1]
        return bool(dots(eqn.primitive, *[v.aval for v in eqn.invars],
                         **eqn.params))

    x = jnp.ones((4, 4))
    for name in SAVED_RESIDUALS:
        assert saved(lambda x: checkpoint_name(x, name), x)
    assert saved(jnp.dot, x, x)
    assert not saved(lambda x: checkpoint_name(x, "some_other_name"), x)
    assert not saved(jnp.exp, x)


def _count_primitive(jaxpr, name):
    return sum((eqn.primitive.name == name)
               + sum(_count_primitive(sub, name)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("remat_policy", ["dots", "full"])
def test_remat_dots_keeps_the_flash_forward_results(monkeypatch, devices,
                                                    remat_policy):
    """Under "dots" the backward of a layer takes the flash forward kernel's
    o and lse from the forward pass: 3 Pallas calls a layer body (forward,
    dq, dkv), bit for bit the gradients of the bare dots policy, which runs
    the forward kernel again for them as "full" still does (4). On one
    device and through ``_per_shard`` on four (the four-chip cell's path)."""
    import importlib

    import deepspeed_tpu.models.transformer as T
    from deepspeed_tpu.config.config import ParallelConfig
    from deepspeed_tpu.ops.flash_attention import flash_attention
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from jax.sharding import NamedSharding

    fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda *a, **k: flash_attention(*a, **{**k, "interpret": True}))
    cfg = TransformerConfig(**{
        **create_model("tiny").config.__dict__, "remat": True,
        "remat_policy": remat_policy, "attention_impl": T._flash_attention})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, b=4, s=128)
    mesh = mesh_mod.build_mesh(ParallelConfig(data_parallel_size=devices),
                               devices=jax.devices()[:devices])

    def grads():
        with mesh_mod.ambient(mesh):
            sharded = jax.device_put(
                batch, NamedSharding(mesh, P(mesh_mod.DATA_SHARD)))
            grad = jax.grad(model.loss_fn)
            return (jax.make_jaxpr(grad)(params, sharded).jaxpr,
                    jax.jit(grad)(params, sharded))

    jaxpr, got = grads()
    # the layers are one scan, so a layer's body is in the program once
    assert _count_primitive(jaxpr, "pallas_call") == (
        3 if remat_policy == "dots" else 4)
    assert (_count_primitive(jaxpr, "shard_map") > 0) == (devices > 1)
    if remat_policy != "dots":
        return
    monkeypatch.setattr(
        T, "resolve_remat_policy",
        lambda cfg: jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    bare_jaxpr, want = grads()
    assert _count_primitive(bare_jaxpr, "pallas_call") == 4
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got, want)
