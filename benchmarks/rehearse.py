#!/usr/bin/env python3
"""Compile each cell's main programs for a DESCRIBED v5e, at the cell's real
shapes, before any chip call: what the chip's compiler would refuse, it
refuses here, at no chip time. Nothing runs and no time is measured.

    python benchmarks/rehearse.py [--workload <cell> ...]

For a training cell: `train/step` on one chip, or on a 2x2 mesh for a
four-chip cell. For a serving cell: `serving/decode` and
`serving/prefill_chunk`. Checks that the compiled text holds the kernels
(and, across chips, the collectives) the cell is there to measure, and
prints `memory_analysis()` in bytes per device.

A script, not a test: only one process at a time may load the TPU's library,
and the repo's topology-describing tests already live in
tests/kernels/test_tpu_compile.py. It reaches into the engines' private
builders (the program builds its mesh from `jax.devices()`, which here are
CPU devices); the benchmark itself (`run.py`) does not.
"""

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

SERVE_HOLDS = {"serving/decode": ("paged_decode_attention",),
               "serving/prefill_chunk": ("paged_prefill_attention",)}
V5E_NUM_BLOCKS = 2956     # 55% of a v5e's 15.75 GiB at 16 tokens a block


def report(name, compiled, needles):
    text = compiled.as_text()
    missing = [n for n in needles if n not in text]
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"[{name}] arguments {m.argument_size_in_bytes:,} B, outputs "
          f"{m.output_size_in_bytes:,} B (aliased {m.alias_size_in_bytes:,}),"
          f" temporaries {m.temp_size_in_bytes:,} B -> {live:,} B "
          f"({live / 2**30:.2f} GiB) live per device; holds "
          f"{', '.join(n for n in needles if n not in missing) or 'nothing'}",
          flush=True)
    if missing:
        raise SystemExit(f"[{name}] compiled program lacks {missing}")


def rehearse_train(cell, topo):
    import deepspeed_tpu
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.mesh import DATA_SHARD, build_mesh
    from deepspeed_tpu.parallel.zero import as_named

    from benchmarks.harness.program import build_model

    t = cell.traffic
    model = build_model(cell, **t.get("model_options", {}))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=dict(t["engine"], seed=0),
        mesh=build_mesh(devices=jax.devices()[:cell.chips]))
    tmesh = Mesh(np.asarray(topo.devices[:cell.chips]).reshape(
        engine.mesh.devices.shape), engine.mesh.axis_names)
    engine.mesh = tmesh
    engine.param_shardings = as_named(engine.plan.param_specs, tmesh)
    mesh_mod.set_mesh(tmesh)

    def sds(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    rows = t["engine"]["train_micro_batch_size_per_gpu"] * cell.chips
    params = sds(engine.params, engine.param_shardings)
    opt = sds(jax.eval_shape(engine.optimizer.init, engine.params),
              engine._opt_state_shardings())
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (1, rows, int(t["sequence"])), jnp.int32,
        sharding=NamedSharding(tmesh, P(None, DATA_SHARD, None)))}
    with mesh_mod.ambient(tmesh):
        compiled = engine._build_train_step().lower(
            params, opt, engine.scaler_state, None, batch).compile()
    report(f"{cell.name} train/step", compiled, t.get("must_hold", ()))


def rehearse_serve(cell, topo):
    import deepspeed_tpu
    from deepspeed_tpu.inference.engine import InferenceConfig
    from deepspeed_tpu.serving import ServingConfig
    from tools.tpuaudit.registry import get_entry_points

    from benchmarks.harness.program import build_model

    s = cell.config["serving"]
    model = build_model(cell)
    serving = deepspeed_tpu.init_serving(
        model=model,
        serving_config=ServingConfig(
            num_blocks=V5E_NUM_BLOCKS,
            **{k: int(s[k]) for k in ("block_size", "max_seqs",
                                      "prefill_chunk", "max_model_len")}),
        config=InferenceConfig(
            dtype=getattr(jnp, cell.config["model"]["dtype"]), seed=0))
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    for name, needles in SERVE_HOLDS.items():
        fn, args, kwargs = get_entry_points([name])[0].build()
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one),
            args)
        report(f"{cell.name} {name}", fn.lower(*args, **kwargs).compile(),
               needles)
    serving.close()


def main(argv=None):
    from jax.experimental import topologies

    from deepspeed_tpu.models import transformer
    from benchmarks.harness.spec import Spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    spec = Spec(REPO_ROOT)
    # the CPU backend is what jax.default_backend() answers here: steer the
    # model to its kernel branch, as it takes on the chip
    transformer._kernels_active = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    seen = set()
    for w in args.workload or [x["name"] for x in spec.doc["workloads"]]:
        cell = spec.cell(w)
        if cell.traffic["kind"] == "train":
            rehearse_train(cell, topo)
        elif cell.config_name not in seen:    # serving programs: per config
            seen.add(cell.config_name)
            rehearse_serve(cell, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
