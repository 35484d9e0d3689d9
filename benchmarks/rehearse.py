#!/usr/bin/env python3
"""Compile each cell's main programs for a DESCRIBED v5e, at the cell's real
shapes, before any chip call: what the chip's compiler would refuse, it
refuses here, at no chip time. Nothing runs and no time is measured.

    python benchmarks/rehearse.py [--workload <cell> ...]

For a training cell: `train/step` on one chip, or on a 2x2 mesh for a
four-chip cell. For a serving cell: `serving/decode` and
`serving/prefill_chunk`, with the arena the cell gets on the chip (the
harness's own arithmetic on the configuration's `arena_share_of_chip`).
Checks that the compiled programs call the kernels that the configuration's
own per-layer metrics name (and hold, across chips, the collectives the
traffic file lists), prints `memory_analysis()` in bytes per device, and for
each cell the live bytes of its fullest program as a share of the chip's
memory, marked where that is under the quarter below which a cell is too
small to stand for a deployment.

A script, not a test: only one process at a time may load the TPU's library,
and the repo's topology-describing tests already live in
tests/kernels/test_tpu_compile.py. It reaches into the engines' private
builders (the program builds its mesh from `jax.devices()`, which here are
CPU devices); the benchmark itself (`run.py`) does not.
"""

import argparse
import os
import re
import sys
from unittest import mock

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

SERVE_PROGRAMS = ("serving/decode", "serving/prefill_chunk")
# what `memory_stats()["bytes_limit"]` reads on one v5e chip (15.75 GiB; my
# chip runs, PR 24): the arena is the configuration's share of this
V5E_BYTES_LIMIT = 16_911_433_728
TOO_SMALL = 0.25    # of the chip's published memory (peaks.json)


def kernels_of(cells):
    """The kernels that the per-layer metrics of `cells` read by name."""
    found = set()
    for cell in cells:
        for m in cell.per_layer:
            args = m["reader"].get("args", {})
            found.update(args.get("kernels", ()))
            if "kernel" in args:
                found.add(args["kernel"])
    return sorted(found)


def calls_kernel(text, kernel):
    """A Mosaic custom call of that name: the instruction itself, not the
    module's name in some other operation's source location."""
    call = re.compile(rf"%?{re.escape(kernel)}(\.\d+)? = .*custom-call\(")
    return any(call.search(ln) and "tpu_custom_call" in ln
               for ln in text.splitlines())


def report(name, compiled, kernels=(), needles=()):
    """Prints the program's bytes a device; returns its live bytes and the
    kernels of `kernels` that it calls. Exits where a needle is missing."""
    text = compiled.as_text()
    held = [k for k in kernels if calls_kernel(text, k)]
    missing = [n for n in needles if n not in text]
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"[{name}] arguments {m.argument_size_in_bytes:,} B, outputs "
          f"{m.output_size_in_bytes:,} B (aliased {m.alias_size_in_bytes:,}),"
          f" temporaries {m.temp_size_in_bytes:,} B -> {live:,} B "
          f"({live / 2**30:.2f} GiB) live per device; calls "
          f"{', '.join(held) or 'no kernel'}"
          + (f"; holds {', '.join(needles)}" if needles and not missing
             else ""), flush=True)
    if missing:
        raise SystemExit(f"[{name}] compiled program lacks {missing}")
    return live, held


def share_of_chip(cell, live):
    """The line the driver's floor is reckoned from: a cell whose fullest
    device holds under a quarter of the chip's memory is too small."""
    from benchmarks.harness.device import peaks

    chip = peaks("TPU v5 lite")["hbm_bytes"]
    share = live / chip
    print(f"[{cell.name}] {live:,} B live on the fullest device: "
          f"{100 * share:.1f}% of the chip's {chip / 1e9:.0f} GB"
          + (f"  <-- UNDER {100 * TOO_SMALL:.0f}%: too small a cell"
             if share < TOO_SMALL else ""), flush=True)


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
    kernels = kernels_of([cell])
    live, held = report(f"{cell.name} train/step", compiled, kernels,
                        t.get("must_hold", ()))
    if set(kernels) - set(held):
        raise SystemExit(f"[{cell.name}] train/step calls no "
                         f"{sorted(set(kernels) - set(held))}")
    return live


class DescribedChip:
    """What `serve_cell.serving_config` asks of a device."""

    def memory_stats(self):
        return {"bytes_limit": V5E_BYTES_LIMIT}


def rehearse_serve(spec, cell, topo):
    """The two serving programs of the cell's configuration; the kernels
    are those that any serving cell of that configuration reads, each of
    which one of the two programs has to call."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.engine import InferenceConfig
    from tools.tpuaudit.registry import get_entry_points

    from benchmarks.harness.program import build_model
    from benchmarks.harness.serve_cell import serving_config

    cells = [spec.cell(w["name"]) for w in spec.doc["workloads"]
             if w["config"] == cell.config_name]
    kernels = kernels_of([c for c in cells if c.traffic["kind"] != "train"])
    model = build_model(cell)
    config = serving_config(cell, [DescribedChip()])(model.config)
    print(f"[{cell.config_name}] arena of {config.num_blocks:,} blocks of "
          f"{config.block_size} tokens: "
          f"{cell.config['serving']['arena_share_of_chip']} of "
          f"{V5E_BYTES_LIMIT:,} B", flush=True)
    serving = deepspeed_tpu.init_serving(
        model=model, serving_config=config,
        config=InferenceConfig(
            dtype=getattr(jnp, cell.config["model"]["dtype"]), seed=0))
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    live, called = 0, set()
    for name in SERVE_PROGRAMS:
        fn, args, kwargs = get_entry_points([name])[0].build()
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one),
            args)
        program_live, held = report(
            f"{cell.config_name} {name}",
            fn.lower(*args, **kwargs).compile(), kernels)
        live = max(live, program_live)
        called.update(held)
    serving.close()
    if set(kernels) - called:
        raise SystemExit(f"[{cell.config_name}] neither serving program "
                         f"calls {sorted(set(kernels) - called)}")
    return live


def main(argv=None):
    from jax.experimental import topologies

    from deepspeed_tpu.ops import registry
    from benchmarks.harness.spec import Spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    spec = Spec(REPO_ROOT)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    live = {}       # serving programs: compiled once a configuration
    # the CPU backend is what jax.default_backend() answers here: steer the
    # one probe that the model and the paged read ask, so that the programs
    # take their kernel branch as they do on the chip (patched, not
    # assigned: undone when this returns, for whoever imported the module)
    with mock.patch.object(registry, "kernels_active", lambda: True):
        for w in args.workload or [x["name"] for x in spec.doc["workloads"]]:
            cell = spec.cell(w)
            if cell.traffic["kind"] == "train":
                share_of_chip(cell, rehearse_train(cell, topo))
                continue
            if cell.config_name not in live:
                live[cell.config_name] = rehearse_serve(spec, cell, topo)
            share_of_chip(cell, live[cell.config_name])
    return 0


if __name__ == "__main__":
    sys.exit(main())
