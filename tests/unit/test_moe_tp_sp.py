"""MoE / TP / SP tests — analogs of reference tests/unit/moe/test_moe.py,
moe/test_moe_tp.py, and the (post-reference) Ulysses sequence-parallel tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import create_model
from deepspeed_tpu.parallel.moe import top1gating, top2gating, _capacity

pytestmark = pytest.mark.slow  # heavy virtual-mesh trajectory tests



def _engine(preset="tiny", tp=1, sp=1, ep=1, zero=0, gas=1,
            sequence_parallel_impl="ulysses", **model_kw):
    model = create_model(preset, **model_kw)
    cfg = {"train_micro_batch_size_per_gpu": 4,
           "gradient_accumulation_steps": gas,
           "steps_per_print": 1000,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": zero},
           "parallel": {"tensor_parallel_size": tp,
                        "sequence_parallel_size": sp,
                        "expert_parallel_size": ep,
                        "sequence_parallel_impl": sequence_parallel_impl}}
    engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
    return engine


def _token_batch(engine, seq=16, seed=0, vocab=256):
    gas = engine.gradient_accumulation_steps()
    gb = engine.train_batch_size() // gas
    ids = jax.random.randint(jax.random.PRNGKey(seed), (gas, gb, seq), 0, vocab)
    return {"input_ids": ids}


class TestGating:
    def test_capacity(self):
        assert _capacity(64, 8, 1.0) == 8
        assert _capacity(64, 8, 1.25) == 10
        assert _capacity(4, 8, 1.0) == 4  # min_capacity floor

    def test_top1_dispatch_conservation(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
        out = top1gating(logits, capacity_factor=2.0)
        # each token dispatched to at most one (expert, slot)
        per_token = out.dispatch.sum(axis=(1, 2))
        assert (np.asarray(per_token) <= 1).all()
        # with generous capacity nothing is dropped
        assert float(per_token.sum()) == 64
        # each (expert, slot) holds at most one token
        per_slot = out.dispatch.sum(axis=0)
        assert (np.asarray(per_slot) <= 1).all()

    def test_top1_capacity_drops(self):
        # all tokens prefer expert 0 -> capacity limits dispatch
        logits = jnp.zeros((64, 8)).at[:, 0].set(10.0)
        out = top1gating(logits, capacity_factor=1.0)
        cap = _capacity(64, 8, 1.0)
        assert float(out.dispatch.sum()) == cap
        # aux loss is high when load is imbalanced
        balanced = top1gating(jax.random.normal(jax.random.PRNGKey(0), (64, 8)))
        assert float(out.aux_loss) > float(balanced.aux_loss)

    def test_top2_two_experts_per_token(self):
        logits = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
        out = top2gating(logits, capacity_factor=2.0)
        per_token = out.dispatch.sum(axis=(1, 2))
        assert (np.asarray(per_token) <= 2).all()
        assert float(per_token.sum()) > 64  # most tokens get 2 experts
        # combine weights normalized <= 1
        tot = out.combine.sum(axis=(1, 2))
        assert (np.asarray(tot) <= 1.0 + 1e-5).all()

    def test_gate_values_match_softmax(self):
        logits = jax.random.normal(jax.random.PRNGKey(2), (16, 4))
        out = top1gating(logits, capacity_factor=4.0)
        gates = jax.nn.softmax(logits, axis=-1)
        picked = np.asarray(out.combine.sum(axis=(1, 2)))
        expect = np.asarray(gates.max(axis=-1))
        np.testing.assert_allclose(picked, expect, rtol=1e-5)


class TestMoETraining:
    def test_moe_model_trains(self):
        engine = _engine("moe-tiny")
        batch = _token_batch(engine)
        losses = [float(engine.train_batch(batch=batch)) for _ in range(8)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_moe_expert_parallel(self):
        """EP over the 8-device data axis: experts sharded, training works."""
        engine = _engine("moe-tiny", ep=8)
        # expert weight leading dim sharded over the 'expert' mesh axis
        spec = engine.plan.param_specs["layers"]["mlp"]["w_up"]
        assert "expert" in str(spec)
        batch = _token_batch(engine)
        losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_moe_ep_matches_no_ep(self):
        """EP is a layout change only: same loss trajectory as replicated."""
        e1 = _engine("moe-tiny", ep=1)
        e2 = _engine("moe-tiny", ep=8)
        batch = _token_batch(e1)
        l1 = [float(e1.train_batch(batch=batch)) for _ in range(3)]
        l2 = [float(e2.train_batch(batch=batch)) for _ in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=1e-4)

    def test_moe_with_zero2(self):
        engine = _engine("moe-tiny", ep=8, zero=2)
        batch = _token_batch(engine)
        losses = [float(engine.train_batch(batch=batch)) for _ in range(3)]
        assert all(np.isfinite(losses))


class TestTensorParallel:
    def test_tp_matches_single(self):
        e1 = _engine("tiny", tp=1)
        e2 = _engine("tiny", tp=2)
        batch = _token_batch(e1)
        l1 = [float(e1.train_batch(batch=batch)) for _ in range(3)]
        l2 = [float(e2.train_batch(batch=batch)) for _ in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=1e-4)

    def test_tp_param_layout(self):
        e = _engine("tiny", tp=2)
        wq_spec = e.plan.param_specs["layers"]["attn"]["wq"]
        assert "model" in str(wq_spec)


class TestSequenceParallel:
    def test_sp_matches_single(self):
        e1 = _engine("tiny", sp=1)
        e2 = _engine("tiny", sp=2)
        batch = _token_batch(e1)
        l1 = [float(e1.train_batch(batch=batch)) for _ in range(3)]
        l2 = [float(e2.train_batch(batch=batch)) for _ in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=1e-4)

    def test_sp_tp_compose(self):
        e1 = _engine("tiny")
        e2 = _engine("tiny", sp=2, tp=2)
        batch = _token_batch(e1)
        l1 = [float(e1.train_batch(batch=batch)) for _ in range(3)]
        l2 = [float(e2.train_batch(batch=batch)) for _ in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=1e-4)

    def test_3d_zero1_pp_tp(self):
        """3D composition: ZeRO-1 x PP x TP on the 8-device mesh (the
        reference's supported combination — PP x ZeRO>=2 is asserted out
        there too, pipe/engine.py:56)."""
        model = create_model("tiny", num_layers=4)
        cfg = {"train_micro_batch_size_per_gpu": 4,
               "gradient_accumulation_steps": 2,
               "steps_per_print": 1000,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
               "zero_optimization": {"stage": 1},
               "parallel": {"pipeline_parallel_size": 2,
                            "tensor_parallel_size": 2,
                            "sequence_parallel_size": 1}}
        engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        batch = _token_batch(engine)
        losses = [float(engine.train_batch(batch=batch)) for _ in range(3)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_pp_zero2_rejected(self):
        model = create_model("tiny", num_layers=4)
        with pytest.raises(ValueError, match="ZeRO stage <= 1"):
            deepspeed_tpu.initialize(
                model=model,
                config={"train_micro_batch_size_per_gpu": 2,
                        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                        "zero_optimization": {"stage": 2},
                        "parallel": {"pipeline_parallel_size": 2}})


class TestMoEV2:
    def test_ep_smaller_than_dp_matches_dense(self):
        """ep=2 < total dp=8: experts shard over the 'expert' axis, each
        expert replicated across 4 'data' ranks — trajectory identical to
        no-EP (reference expert-data-parallel groups, groups.py:156)."""
        e1 = _engine("moe-tiny", ep=1)
        e2 = _engine("moe-tiny", ep=2)
        assert int(e2.mesh.shape["expert"]) == 2
        assert int(e2.mesh.shape["data"]) == 4
        batch = _token_batch(e1)
        l1 = [float(e1.train_batch(batch=batch)) for _ in range(3)]
        l2 = [float(e2.train_batch(batch=batch)) for _ in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=1e-4)

    def test_no_drop_keeps_every_token(self):
        from deepspeed_tpu.parallel.moe import top1gating

        logits = jax.random.normal(jax.random.PRNGKey(0), (64, 4))
        # heavily skewed: without capacity all tokens must still dispatch
        logits = logits.at[:, 0].add(5.0)
        out = top1gating(logits, capacity_factor=1.0, drop_tokens=False)
        assert float(out.dispatch.sum()) == 64.0
        dropped = top1gating(logits, capacity_factor=1.0, drop_tokens=True)
        assert float(dropped.dispatch.sum()) < 64.0

    def test_no_drop_top2(self):
        from deepspeed_tpu.parallel.moe import top2gating

        logits = jax.random.normal(jax.random.PRNGKey(2), (64, 4))
        logits = logits.at[:, 0].add(5.0)
        out = top2gating(logits, capacity_factor=0.5, drop_tokens=False)
        # every token keeps both its experts
        assert float(out.dispatch.sum()) == 128.0

    @pytest.mark.parametrize("k", [1, 2, 3, 8])
    def test_general_top_k_dispatch_conservation(self, k):
        """One plan for any k: with no drops every token holds k distinct
        (expert, slot) pairs, a slot holds one token, and the weights are
        the chosen probabilities (renormalised for k > 1 unless told not)."""
        from deepspeed_tpu.parallel.moe import topk_plan

        logits = jax.random.normal(jax.random.PRNGKey(k), (32, 8))
        plan = topk_plan(logits, k, drop_tokens=False)
        assert plan.expert_idx.shape == (32, k) and bool(plan.valid.all())
        slots = np.asarray(plan.expert_idx * plan.capacity + plan.slot_pos)
        assert len(set(slots.ravel().tolist())) == 32 * k
        gates = np.asarray(jax.nn.softmax(logits, axis=-1))
        picked = np.sort(gates, axis=-1)[:, ::-1][:, :k]
        want = picked / picked.sum(-1, keepdims=True) if k > 1 else picked
        np.testing.assert_allclose(np.asarray(plan.weight), want, rtol=1e-6)
        raw = topk_plan(logits, k, drop_tokens=False, normalize=False)
        np.testing.assert_allclose(np.asarray(raw.weight), picked, rtol=1e-6)

    def test_rts_top2_rejected(self):
        from deepspeed_tpu.parallel.moe import moe_mlp

        x = jnp.zeros((1, 8, 16))
        router = jnp.zeros((16, 4))
        experts = {"w_up": jnp.zeros((4, 16, 32)),
                   "w_down": jnp.zeros((4, 32, 16))}
        with pytest.raises(ValueError, match="top-1 only"):
            moe_mlp(x, router, experts, "gelu", top_k=2, use_rts=True,
                    rng=jax.random.PRNGKey(0))

    def test_rts_random_selection(self):
        from deepspeed_tpu.parallel.moe import top1gating

        logits = jnp.zeros((64, 2)).at[:, 0].add(1.0)  # all want expert 0
        seq = top1gating(logits, capacity_factor=1.0)
        rts = top1gating(logits, capacity_factor=1.0, use_rts=True,
                         rng=jax.random.PRNGKey(3))
        C = 32
        assert float(seq.dispatch.sum()) == C and float(rts.dispatch.sum()) == C
        # sequential keeps the FIRST C tokens; RTS keeps a random subset
        seq_tokens = np.asarray(seq.dispatch.sum(axis=(1, 2)))
        rts_tokens = np.asarray(rts.dispatch.sum(axis=(1, 2)))
        assert (seq_tokens[:C] == 1).all()
        assert not (rts_tokens[:C] == 1).all()

    def test_pr_moe_residual_trains(self):
        engine = _engine("moe-tiny", ep=1, moe_use_residual=True,
                         moe_top_k=1)
        assert "res_mlp" in engine.params["layers"]
        batch = _token_batch(engine)
        losses = [float(engine.train_batch(batch=batch)) for _ in range(6)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]


class TestSparseDispatch:
    """Sparse scatter/gather dispatch == dense einsum dispatch (the GShard
    formulation) — values AND gradients, across gating variants."""

    def _setup(self, T=64, H=16, F=32, E=4, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        x = jax.random.normal(ks[0], (2, T // 2, H), jnp.float32)
        router = jax.random.normal(ks[1], (H, E), jnp.float32)
        experts = {"w_up": jax.random.normal(ks[2], (E, H, F)) * 0.1,
                   "w_down": jax.random.normal(ks[3], (E, F, H)) * 0.1,
                   "w_gate": jax.random.normal(ks[4], (E, H, F)) * 0.1}
        return x, router, experts

    @pytest.mark.parametrize("top_k", [1, 2])
    @pytest.mark.parametrize("cap", [0.5, 1.25])
    def test_values_match(self, top_k, cap):
        from deepspeed_tpu.parallel.moe import moe_mlp

        x, router, experts = self._setup()
        outs = {}
        for impl in ("sparse", "einsum"):
            out, aux = moe_mlp(x, router, experts, "gelu", top_k=top_k,
                               capacity_factor=cap, dispatch_impl=impl)
            outs[impl] = (np.asarray(out), float(aux))
        np.testing.assert_allclose(outs["sparse"][0], outs["einsum"][0],
                                   rtol=1e-5, atol=1e-6)
        assert outs["sparse"][1] == outs["einsum"][1]

    @pytest.mark.parametrize("variant", ["rts", "nodrop", "swiglu"])
    def test_variants_match(self, variant):
        from deepspeed_tpu.parallel.moe import moe_mlp

        x, router, experts = self._setup(seed=3)
        kw = dict(top_k=1, capacity_factor=0.5)
        act = "gelu"
        if variant == "rts":
            kw.update(use_rts=True, rng=jax.random.PRNGKey(7))
        elif variant == "nodrop":
            kw.update(drop_tokens=False)
        else:
            act = "swiglu"
        a, aux_a = moe_mlp(x, router, experts, act,
                           dispatch_impl="sparse", **kw)
        b, aux_b = moe_mlp(x, router, experts, act,
                           dispatch_impl="einsum", **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("top_k", [1, 2, 3])
    def test_inference_grouped_path_matches_einsum_without_drops(self, top_k):
        """At inference the dropless grouped path (assigned rows only) gives
        what the (E, C, H) einsum path gives with C = T."""
        from deepspeed_tpu.parallel.moe import moe_mlp

        x, router, experts = self._setup(seed=9)
        dense, _ = moe_mlp(x, router, experts, "swiglu", top_k=top_k,
                           drop_tokens=False, dispatch_impl="einsum")
        grouped, _ = moe_mlp(x, router, experts, "swiglu", top_k=top_k,
                             infer=True)
        np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                                   rtol=1e-5, atol=1e-6)

    def test_grads_match(self):
        from deepspeed_tpu.parallel.moe import moe_mlp

        x, router, experts = self._setup(seed=5)

        def loss(impl, xx, rt, ex):
            out, aux = moe_mlp(xx, rt, ex, "gelu", top_k=2,
                               capacity_factor=1.0, dispatch_impl=impl)
            return (out ** 2).sum() + aux

        for arg in range(3):
            gs = jax.grad(lambda *a: loss("sparse", *a), argnums=arg)(
                x, router, experts)
            ge = jax.grad(lambda *a: loss("einsum", *a), argnums=arg)(
                x, router, experts)
            jax.tree.map(lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5), gs, ge)

    @pytest.mark.parametrize("ep", [1, 2])
    def test_engine_trajectory_sparse_vs_einsum(self, ep):
        """Full engine: an MoE model trains identically under either
        dispatch (same losses), sparse being the default — including under
        REAL expert parallelism, where the gather/scatter dispatch must
        produce the same cross-device exchange as the einsum's
        constraint-lowered all-to-all."""
        losses = {}
        for impl in ("sparse", "einsum"):
            eng = _engine(preset="moe-tiny", ep=ep, moe_dispatch=impl)
            losses[impl] = [float(eng.train_batch(batch=_token_batch(eng)))
                            for _ in range(3)]
        np.testing.assert_allclose(losses["sparse"], losses["einsum"],
                                   rtol=2e-5, atol=1e-6)


class TestRingAttention:
    def test_ring_matches_dense_attention(self):
        """ring_attention over the seq axis == plain causal attention."""
        from deepspeed_tpu.config.config import ParallelConfig
        from deepspeed_tpu.parallel import mesh as mesh_mod
        from deepspeed_tpu.parallel.ring import ring_attention
        from deepspeed_tpu.models.transformer import dot_product_attention

        mesh = mesh_mod.build_mesh(ParallelConfig(sequence_parallel_size=4,
                                                  data_parallel_size=2))
        mesh_mod.set_mesh(mesh)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (2, 64, 4, 16))
        k = jax.random.normal(ks[1], (2, 64, 4, 16))
        v = jax.random.normal(ks[2], (2, 64, 4, 16))
        with mesh:
            out = jax.jit(lambda q, k, v: ring_attention(q, k, v))(q, k, v)
        ref = dot_product_attention(q, k, v, None, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_ring_gradients_match(self):
        from deepspeed_tpu.config.config import ParallelConfig
        from deepspeed_tpu.parallel import mesh as mesh_mod
        from deepspeed_tpu.parallel.ring import ring_attention
        from deepspeed_tpu.models.transformer import dot_product_attention

        mesh = mesh_mod.build_mesh(ParallelConfig(sequence_parallel_size=4,
                                                  data_parallel_size=2))
        mesh_mod.set_mesh(mesh)
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 32, 2, 16))
        k = jax.random.normal(ks[1], (1, 32, 2, 16))
        v = jax.random.normal(ks[2], (1, 32, 2, 16))
        with mesh:
            g1 = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                ring_attention(q, k, v) ** 2), argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(lambda q, k, v: jnp.sum(
            dot_product_attention(q, k, v, None, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3,
                                       err_msg=f"d{name}")

    def test_ring_training_matches_dense(self):
        """End-to-end: sp=4 ring training trajectory == single-replica.
        Runs in a subprocess: compiling the ring step after other shard_map
        compiles in one process can abort inside the XLA CPU compiler
        (compile-order-dependent partitioner crash; standalone it is
        stable)."""
        import os
        import subprocess
        import sys
        import textwrap

        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        script = textwrap.dedent("""
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax
            jax.config.update("jax_platforms", "cpu")
            import sys; sys.path.insert(0, %r)
            import jax.numpy as jnp
            import numpy as np
            import deepspeed_tpu
            from deepspeed_tpu.models import create_model
            from deepspeed_tpu.parallel import mesh as mesh_mod

            def run(par):
                mesh_mod.reset_mesh()
                model = create_model("tiny", dtype=jnp.float32)
                cfg = {"train_micro_batch_size_per_gpu": 4,
                       "steps_per_print": 1000,
                       "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                       "zero_optimization": {"stage": 0},
                       "parallel": par}
                engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
                ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8, 16), 0, 250)
                return [float(engine.train_batch(batch={"input_ids": ids}))
                        for _ in range(3)]

            l1 = run({"sequence_parallel_size": 1})
            l2 = run({"sequence_parallel_size": 4, "data_parallel_size": 2,
                      "sequence_parallel_impl": "ring"})
            np.testing.assert_allclose(l1, l2, rtol=1e-4)
            print("RING-E2E-OK")
        """ % repo)
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=420)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "RING-E2E-OK" in out.stdout

    def test_ring_rejects_padding_mask(self):
        from deepspeed_tpu.config.config import ParallelConfig
        from deepspeed_tpu.parallel import mesh as mesh_mod
        from deepspeed_tpu.parallel.ring import ring_attention

        mesh = mesh_mod.build_mesh(ParallelConfig(sequence_parallel_size=2,
                                                  data_parallel_size=4))
        mesh_mod.set_mesh(mesh)
        q = jnp.zeros((1, 32, 2, 16))
        with pytest.raises(NotImplementedError, match="padding masks"):
            ring_attention(q, q, q, mask=jnp.ones((1, 32)))

    def test_ring_rejects_custom_attention_scale(self):
        """A model with cfg.attention_scale (GPT-Neo uses 1.0) must refuse
        ring SP instead of silently falling back to 1/sqrt(head_dim)."""
        from deepspeed_tpu.config.config import ParallelConfig
        from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                      forward, init_params)
        from deepspeed_tpu.parallel import mesh as mesh_mod
        from deepspeed_tpu.parallel.ring import set_ring_attention

        mesh = mesh_mod.build_mesh(ParallelConfig(sequence_parallel_size=2,
                                                  data_parallel_size=4))
        mesh_mod.set_mesh(mesh)
        set_ring_attention(True)
        try:
            cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                                    num_layers=2, num_heads=2, max_seq_len=32,
                                    attention_scale=1.0)
            params = init_params(jax.random.PRNGKey(0), cfg)
            ids = jnp.zeros((1, 32), jnp.int32)
            with pytest.raises(NotImplementedError,
                               match="custom attention_scale"):
                forward(params, ids, cfg)
        finally:
            set_ring_attention(False)
