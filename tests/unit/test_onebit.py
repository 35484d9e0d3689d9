"""1-bit / compressed-gradient tests — analog of reference
tests/unit/runtime/half_precision/onebit/test_onebit.py (warmup equivalence +
compressed-stage convergence) plus primitive-level checks of the
error-feedback collective."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm.compressed import (compressed_allreduce_flat,
                                           tree_flatten_pad,
                                           tree_unflatten_like)
from deepspeed_tpu.models import create_model
from deepspeed_tpu.parallel import mesh as mesh_mod

pytestmark = pytest.mark.slow  # heavy virtual-mesh trajectory tests



class TestCompressedAllreduce:
    def _run(self, per_rank, worker=None, server=None):
        devs = jax.devices()[:8]
        mesh = Mesh(np.array(devs), ("data",))
        W, n = per_rank.shape
        worker = worker if worker is not None else jnp.zeros((W, n))
        server = server if server is not None else jnp.zeros((n,))

        def body(v, w, s):
            out, w2, s2 = compressed_allreduce_flat(v[0], w[0], s, "data")
            return out[None], w2[None], s2

        fn = shard_map(body, mesh=mesh,
                           in_specs=(P("data"), P("data"), P("data")),
                           out_specs=(P("data", None), P("data"), P("data")),
                           check_vma=False)
        out, w2, s2 = fn(per_rank, worker, server)
        return np.asarray(out), np.asarray(w2.reshape(W, n)), np.asarray(s2)

    def test_approximates_mean(self):
        rng = np.random.RandomState(0)
        per_rank = jnp.asarray(rng.randn(8, 64).astype(np.float32))
        out, _, _ = self._run(per_rank)
        want = np.asarray(per_rank).mean(0)
        # every rank sees the same result
        assert np.allclose(out, out[0:1], atol=0)
        # int8 two-phase quantization error is bounded by ~2 * max|v|/127
        err = np.abs(out[0] - want).max()
        assert err < 2.5 * np.abs(per_rank).max() / 127, err

    def test_error_feedback_accumulates(self):
        # constant input: residual feedback must drive the LONG-Run average
        # toward the true mean (the whole point of error feedback)
        per_rank = jnp.asarray(
            np.random.RandomState(1).randn(8, 64).astype(np.float32))
        want = np.asarray(per_rank).mean(0)
        worker = jnp.zeros((8, 64))
        server = jnp.zeros((8,))
        outs = []
        for _ in range(30):
            out, w, s = self._run(per_rank, worker, server)
            worker, server = jnp.asarray(w), jnp.asarray(s.reshape(-1))
            outs.append(out[0])
        avg = np.stack(outs).mean(0)
        direct_err = np.abs(outs[0] - want).max()
        fb_err = np.abs(avg - want).max()
        assert fb_err < direct_err * 0.5, (fb_err, direct_err)

    def test_flatten_roundtrip(self):
        tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": {"c": jnp.ones((5,), jnp.bfloat16)}}
        flat, _, n = tree_flatten_pad(tree, 8)
        assert flat.shape[0] % 8 == 0 and n == 11
        back = tree_unflatten_like(flat, tree)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32))


def _train(opt_type, steps, freeze_step=2, seed=0):
    mesh_mod.reset_mesh()
    model = create_model("tiny", dtype=jnp.float32, max_seq_len=64)
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": opt_type,
                      "params": {"lr": 1e-3, "freeze_step": freeze_step}},
        "zero_optimization": {"stage": 0},
        "parallel": {"data_parallel_size": 8},
    }
    engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (1, 16, 32), 0, 250)
    return [float(engine.train_batch(batch={"input_ids": ids}))
            for _ in range(steps)]


class TestOnebitTraining:
    def test_warmup_matches_dense_exactly(self):
        dense = _train("adam", 3)
        onebit = _train("onebitadam", 3, freeze_step=100)  # all warmup
        np.testing.assert_allclose(dense, onebit, rtol=1e-6)

    def test_compressed_stage_converges(self):
        dense = _train("adam", 12)
        onebit = _train("onebitadam", 12, freeze_step=2)
        # loss still goes down and tracks dense within a few percent
        assert onebit[-1] < onebit[0]
        assert abs(onebit[-1] - dense[-1]) / dense[-1] < 0.05, (onebit, dense)

    def test_zero2_rejected(self):
        model = create_model("tiny", dtype=jnp.float32)
        with pytest.raises(ValueError, match="ZeRO stage <= 1"):
            deepspeed_tpu.initialize(
                model=model,
                config={"train_micro_batch_size_per_gpu": 2,
                        "optimizer": {"type": "onebitadam",
                                      "params": {"lr": 1e-3}},
                        "zero_optimization": {"stage": 2}})

    def test_tp_rejected(self):
        model = create_model("tiny", dtype=jnp.float32)
        with pytest.raises(ValueError, match="data-parallel only"):
            deepspeed_tpu.initialize(
                model=model,
                config={"train_micro_batch_size_per_gpu": 2,
                        "optimizer": {"type": "onebitadam",
                                      "params": {"lr": 1e-3}},
                        "parallel": {"tensor_parallel_size": 2}})

    def test_cpuadam_without_offload_rejected(self):
        model = create_model("tiny", dtype=jnp.float32)
        with pytest.raises(ValueError, match="cpuadam"):
            deepspeed_tpu.initialize(
                model=model,
                config={"train_micro_batch_size_per_gpu": 2,
                        "optimizer": {"type": "cpuadam",
                                      "params": {"lr": 1e-3}}})


class TestZeroOneAdam:
    """0/1 Adam (reference fp16/onebit/zoadam.py): exponential
    variance-update schedule + dense-on-variance-steps comm. The local-step
    policy is a documented deviation (SPMD keeps params replicated)."""

    def test_var_step_schedule_matches_reference_loop(self):
        from deepspeed_tpu.runtime.optimizer import zero_one_var_step

        for scaler in (3, 16):
            # reference zoadam.py:270 counter/interval state machine
            interval, counter = 1, 0
            hits = set()
            for s in range(1, 2001):
                if s % interval == 0:
                    hits.add(s)
                    counter += 1
                    if counter == scaler:
                        counter = 0
                        interval *= 2
            fn = jax.jit(jax.vmap(
                lambda c, _s=scaler: zero_one_var_step(c, _s, 10**6)))
            mask = np.asarray(fn(jnp.arange(2000)))
            got = {int(i) + 1 for i in np.nonzero(mask)[0]}
            assert got == hits, (scaler, sorted(got ^ hits)[:10])
        # frozen after var_freeze_step
        assert not bool(zero_one_var_step(jnp.int32(50), 16, 50))

    def test_variance_frozen_between_hits(self):
        from deepspeed_tpu.runtime.optimizer import zero_one_adam_transform

        tx = zero_one_adam_transform(b1=0.9, b2=0.999, eps=1e-8,
                                     weight_decay=0.0, var_freeze_step=10**6,
                                     var_update_scaler=2)
        p = {"w": jnp.ones((4,))}
        state = tx.init(p)
        g = {"w": jnp.full((4,), 0.5)}
        nus = []
        for _ in range(8):
            _, state = tx.update(g, state, p)
            nus.append(float(state["nu"]["w"][0]))
        # hits at steps 1,2 (interval 1), 4,6 (interval 2), 8 (interval 4):
        # nu changes exactly there and holds in between
        assert nus[0] != 0 and nus[1] != nus[0]
        assert nus[2] == nus[1]            # step 3: frozen
        assert nus[3] != nus[2]            # step 4: hit
        assert nus[4] == nus[3]
        assert nus[5] != nus[4]            # step 6: hit
        assert nus[6] == nus[5]
        assert nus[7] != nus[6]            # step 8: hit

    def test_zerooneadam_trains(self, devices8):
        import deepspeed_tpu
        from deepspeed_tpu.models import create_model

        model = create_model("tiny", dtype=jnp.float32)
        engine, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 2, "steps_per_print": 1000,
            "optimizer": {"type": "zerooneadam",
                          "params": {"lr": 5e-3, "freeze_step": 2,
                                     "var_update_scaler": 2}}})
        ids = np.random.RandomState(0).randint(0, 256, (1, 16, 16))
        losses = [float(engine.train_batch(batch={"input_ids": ids}))
                  for _ in range(8)]
        assert losses[-1] < losses[0]
        assert all(np.isfinite(losses))
