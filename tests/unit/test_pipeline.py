"""Pipeline tests — analog of reference tests/unit/runtime/pipe/
(test_pipe_schedule.py pure-python schedule checks, test_pipe.py convergence
vs non-pipeline baseline)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import create_model
from deepspeed_tpu.parallel.pipeline import (partition_balanced,
                                             partition_layers,
                                             partition_uniform,
                                             pipelinize_model)
from deepspeed_tpu.parallel.schedule import (BackwardPass, ForwardPass,
                                             InferenceSchedule, LoadMicroBatch,
                                             OptimizerStep, TrainSchedule)

pytestmark = pytest.mark.slow  # heavy virtual-mesh trajectory tests


class TestSchedules:
    def test_train_schedule_length(self):
        sched = TrainSchedule(micro_batches=4, stages=2, stage_id=0)
        assert len(sched) == 2 * (4 + 2 - 1)

    @pytest.mark.parametrize("stages,mb", [(2, 4), (4, 8), (3, 3)])
    def test_every_microbatch_forward_and_backward_once(self, stages, mb):
        for stage in range(stages):
            sched = TrainSchedule(micro_batches=mb, stages=stages, stage_id=stage)
            fwd, bwd = [], []
            for cmds in sched:
                for c in cmds:
                    if isinstance(c, ForwardPass):
                        fwd.append(c.kwargs["buffer_id"])
                    if isinstance(c, BackwardPass):
                        bwd.append(c.kwargs["buffer_id"])
            assert len(fwd) == mb, f"stage {stage}: {len(fwd)} forwards"
            assert len(bwd) == mb, f"stage {stage}: {len(bwd)} backwards"

    def test_backward_follows_forward(self):
        sched = TrainSchedule(micro_batches=4, stages=2, stage_id=1)
        seen_fwd = set()
        for cmds in sched:
            for c in cmds:
                if isinstance(c, ForwardPass):
                    seen_fwd.add(c.kwargs["buffer_id"])
                if isinstance(c, BackwardPass):
                    assert c.kwargs["buffer_id"] in seen_fwd

    def test_optimizer_step_last(self):
        sched = TrainSchedule(micro_batches=2, stages=2, stage_id=0)
        steps = list(sched)
        assert any(isinstance(c, OptimizerStep) for c in steps[-1])
        for cmds in steps[:-1]:
            assert not any(isinstance(c, OptimizerStep) for c in cmds)

    def test_first_stage_loads_microbatch(self):
        sched = TrainSchedule(micro_batches=2, stages=2, stage_id=0)
        loads = [c for cmds in sched for c in cmds if isinstance(c, LoadMicroBatch)]
        assert len(loads) == 2

    def test_inference_schedule(self):
        sched = InferenceSchedule(micro_batches=4, stages=2, stage_id=0)
        fwd = [c for cmds in sched for c in cmds if isinstance(c, ForwardPass)]
        assert len(fwd) == 4

    def test_num_pipe_buffers_1f1b_bound(self):
        # earlier stages hold more in-flight buffers
        s0 = TrainSchedule(micro_batches=8, stages=4, stage_id=0)
        s3 = TrainSchedule(micro_batches=8, stages=4, stage_id=3)
        assert s0.num_pipe_buffers() == 4
        assert s3.num_pipe_buffers() == 2


class TestPartitioning:
    def test_uniform(self):
        assert partition_uniform(8, 4) == [0, 2, 4, 6, 8]
        parts = partition_uniform(10, 4)
        assert parts[0] == 0 and parts[-1] == 10
        sizes = [parts[i + 1] - parts[i] for i in range(4)]
        assert max(sizes) - min(sizes) <= 1

    def test_balanced(self):
        parts = partition_balanced([1, 1, 1, 10, 1, 1], 2)
        assert parts[0] == 0 and parts[-1] == 6
        # the heavy item must sit alone-ish: first part carries items 0..3
        w = [1, 1, 1, 10, 1, 1]
        loads = [sum(w[parts[i]:parts[i + 1]]) for i in range(2)]
        assert max(loads) <= 13

    def test_partition_layers_type_regex(self):
        class TransformerLayer:
            pass

        class Embedding:
            pass

        layers = [Embedding()] + [TransformerLayer() for _ in range(4)] + [Embedding()]
        parts = partition_layers(layers, 2, method="type:transformerlayer")
        # each stage gets 2 transformer layers
        counts = []
        for i in range(2):
            counts.append(sum(1 for l in layers[parts[i]:parts[i + 1]]
                              if isinstance(l, TransformerLayer)))
        assert counts == [2, 2]


class TestPipelinedTraining:
    def _engine(self, pp, gas=4, zero=0, preset="tiny", **model_kw):
        model = create_model(preset, **model_kw)
        cfg = {"train_micro_batch_size_per_gpu": 2,
               "gradient_accumulation_steps": gas,
               "steps_per_print": 1000,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
               "zero_optimization": {"stage": zero},
               "parallel": {"pipeline_parallel_size": pp}}
        engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        return engine

    def _batch(self, engine, seed=0):
        gas = engine.gradient_accumulation_steps()
        gb = engine.train_batch_size() // gas
        ids = jax.random.randint(jax.random.PRNGKey(seed), (gas, gb, 16), 0, 256)
        return {"input_ids": ids}

    def test_pp_loss_matches_non_pp(self):
        """The pipelined program must compute the same loss and the same
        updated params as the plain engine (same data, same init)."""
        e1 = self._engine(pp=1, gas=4)
        e2 = self._engine(pp=2, gas=4)
        batch = self._batch(e1)
        l1 = float(e1.train_batch(batch=batch))
        l2 = float(e2.train_batch(batch=batch))
        assert l1 == pytest.approx(l2, rel=2e-3)

        # merge pp params back and compare trajectories
        from deepspeed_tpu.parallel.pipeline import _merge_stages

        p2 = dict(jax.device_get(e2.params))
        p2["layers"] = _merge_stages(p2["layers"])
        p1 = jax.device_get(e1.params)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=5e-3),
            p1, p2)

    def test_pp_with_zero1(self):
        e = self._engine(pp=2, gas=2, zero=1)
        batch = self._batch(e)
        losses = [float(e.train_batch(batch=batch)) for _ in range(4)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_pp4(self):
        e = self._engine(pp=4, gas=4, num_layers=4)
        batch = self._batch(e)
        losses = [float(e.train_batch(batch=batch)) for _ in range(3)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_pp_honors_labels_and_mask(self):
        """Custom labels (-100 masking, SFT-style) and attention_mask must give
        the same loss as the non-PP path."""
        e1 = self._engine(pp=1, gas=2)
        e2 = self._engine(pp=2, gas=2)
        gas, gb = 2, e1.train_batch_size() // 2
        rng = jax.random.PRNGKey(7)
        ids = jax.random.randint(rng, (gas, gb, 16), 0, 256)
        labels = ids.at[:, :, :8].set(-100)  # mask the "prompt" half
        mask = jnp.ones((gas, gb, 16), jnp.int32).at[:, :, 12:].set(0)
        batch = {"input_ids": ids, "labels": labels, "attention_mask": mask}
        l1 = float(e1.train_batch(batch=batch))
        l2 = float(e2.train_batch(batch=batch))
        assert l1 == pytest.approx(l2, rel=2e-3)

    def test_pp_forward_api_rejected(self):
        e = self._engine(pp=2, gas=2)
        with pytest.raises(RuntimeError, match="train_batch"):
            e.forward({"input_ids": jnp.zeros((2, 16), jnp.int32)})

    def test_pp_eval_loss(self):
        e = self._engine(pp=2, gas=2)
        gb = e.train_batch_size() // 2
        ids = jax.random.randint(jax.random.PRNGKey(0), (gb, 16), 0, 256)
        loss = float(e.eval_loss({"input_ids": ids}))
        assert np.isfinite(loss)

    def test_pp_rejects_indivisible_layers(self):
        model = create_model("tiny")  # 2 layers, pp=4 -> 2 % 4 != 0
        with pytest.raises(AssertionError):
            deepspeed_tpu.initialize(
                model=model,
                config={"train_micro_batch_size_per_gpu": 2,
                        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                        "parallel": {"pipeline_parallel_size": 4}})


class TestPipelineMemory:
    def test_activation_residency_is_o_p_not_o_m(self):
        """1F1B contract (reference schedule.py:212 num_pipe_buffers): live
        activation storage is bounded by the stage depth P, not the
        microbatch count M. Compiled temp memory for the grad step must grow
        sub-linearly when M quadruples at fixed P (the round-1 fill-drain
        executor stacked every tick: O(M) growth)."""
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.models import create_model
        from deepspeed_tpu.parallel import mesh as mesh_mod
        from deepspeed_tpu.parallel.pipeline import (pipelined_grad_fn,
                                                     pipelinize_model)
        from deepspeed_tpu.config.config import ParallelConfig

        mesh = mesh_mod.build_mesh(ParallelConfig(pipeline_parallel_size=4,
                                                  data_parallel_size=2))
        mesh_mod.set_mesh(mesh)
        model = create_model("tiny", dtype=jnp.float32, num_layers=4,
                             max_seq_len=64)
        pmodel = pipelinize_model(model, 4)
        params = pmodel.init(jax.random.PRNGKey(0))

        def temp_bytes(M):
            ids = jnp.zeros((M, 4, 64), jnp.int32)
            with mesh:
                lowered = jax.jit(pmodel.grad_fn).lower(
                    params, {"input_ids": ids}, jnp.float32(1.0))
                return lowered.compile().memory_analysis().temp_size_in_bytes

        with mesh:
            t2, t8 = temp_bytes(2), temp_bytes(8)
        # M x4 => temps must grow far less than proportionally
        assert t8 < t2 * 2.5, (
            f"temp memory grew {t8 / t2:.2f}x for 4x microbatches "
            f"({t2} -> {t8} bytes) — activation residency is not O(P)")


class TestPipelineMoE:
    def test_grad_fn_loss_matches_eval_loss_with_aux(self):
        """1F1B reported train loss and the eval loss_fn must agree for MoE
        models — both include CE + router aux (regression: the executor
        reported CE only while its grads included the aux term)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from deepspeed_tpu.models import create_model
        from deepspeed_tpu.parallel import mesh as mesh_mod
        from deepspeed_tpu.parallel.pipeline import pipelinize_model
        from deepspeed_tpu.config.config import ParallelConfig

        mesh = mesh_mod.build_mesh(ParallelConfig(pipeline_parallel_size=2,
                                                  data_parallel_size=4))
        mesh_mod.set_mesh(mesh)
        model = create_model("moe-tiny", dtype=jnp.float32, max_seq_len=64)
        pmodel = pipelinize_model(model, 2)
        params = pmodel.init(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 32), 0, 250)
        batch = {"input_ids": ids}
        with mesh:
            train_loss, grads = jax.jit(pmodel.grad_fn)(
                params, batch, jnp.float32(1.0))
            eval_loss = jax.jit(pmodel.loss_fn)(params, batch)
        np.testing.assert_allclose(float(train_loss), float(eval_loss),
                                   rtol=1e-5)
        # and aux really is in there: loss > plain-CE-only would require
        # recomputing without aux; instead check the router grads are nonzero
        g_router = np.abs(np.asarray(grads["layers"]["router"])).max()
        assert g_router > 0.0
