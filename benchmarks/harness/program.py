"""What both kinds of cell take from the program under test: the model a
configuration file names, the seed in the form the program accepts, the
configuration's plain reference with the arguments it is called with and,
where the file states a balanced placement of the experts a chip holds, the
seed's parameters so placed."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict

from .spec import SpecError, expert_ways
from .traffic import calibration_ids, seed32


def program_seed(seed: int) -> int:
    """The program takes its seed as a 31-bit PRNG key."""
    return seed32(seed) % (2 ** 31 - 1)


def build_model(cell, **extra):
    import jax.numpy as jnp

    from deepspeed_tpu.models import create_model

    m = cell.config["model"]
    return create_model(m["preset"], dtype=getattr(jnp, m["dtype"]),
                        **m["overrides"], **extra)


def reference_module(cell):
    """`references/<family>.py` of the benchmark directory the cell was read
    from, loaded by its path: a family arrives as a file, whatever the root."""
    name = cell.config["reference"]
    path = os.path.join(cell.bench_dir, "references", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_args(cell) -> Dict[str, Any]:
    """The keyword arguments of the reference that the shapes of the
    parameters do not give: each read from the source's own key, none from
    the program under test."""
    published = cell.config["published"]
    return {arg: published[source["published"]]
            for arg, source in cell.config["reference_args"].items()}


def _with_leaves(tree, moved):
    """`tree` with the leaves that `moved` holds put in their places, each
    where the leaf it replaces lies; every other leaf is the object it was."""
    import jax

    if not isinstance(moved, dict):
        return jax.device_put(moved, tree.sharding)
    return {k: _with_leaves(v, moved[k]) if k in moved else v
            for k, v in tree.items()}


# the batch a placement is counted on, the same for every configuration:
# sequences and tokens a sequence (PERF.md section 6, PR 38: past 4,096
# tokens the held experts' scatter over seeds stops falling, and it reads the
# same in 16 sequences as in 64)
CALIBRATION_BATCH = (64, 64)


def balanced_order(load, held: int):
    """The policy of a balanced placement, one layer of it: `(E,)` the
    assignments to each of a router's outputs -> `(E,)` the outputs in the
    order that holds a balanced `held` of them first. By their load (ties by
    index) the outputs fall into `held` groups of `E / held` neighbours, and
    the middle one of each group is held (of an even number the two middle
    ones in turn), so that the held carry their part of the load at every
    level of it, whatever the draw; the rest follow as they were."""
    import jax.numpy as jnp

    E = load.shape[0]
    by_load = jnp.argsort(-load, stable=True)
    ways, group = E // held, jnp.arange(held)
    picked = by_load[group * ways + (ways - 1 + group % 2) // 2]
    is_held = jnp.zeros((E,), bool).at[picked].set(True)
    rest = jnp.argsort(is_held, stable=True)[:E - held]
    return jnp.concatenate([picked, rest])


def place_held_experts(cell, params, seed: int, vocab: int):
    """The seed's parameters with the experts this chip holds PLACED in
    balance, where the configuration's `share.placement` says so; else
    `None`, and nothing is computed. The weights stay the seed's draw. The
    family's reference (`place_held_experts` of `references/<family>.py`)
    walks its plain forward over a calibration batch from the seed and, at
    every layer's router, asks `balanced_order` in what order the outputs
    shall stand, reorders that layer's outputs so and goes on to the next
    layer with them; it returns the leaves it reordered and the assignments
    to each output in its new place. Those leaves replace the program's own,
    same shapes, dtypes and shardings, so no program compiles anew. Prints
    the held outputs' part of the calibration assignments, a layer."""
    if "placement" not in cell.config.get("share", {}):
        return None
    import jax
    import numpy as np

    reference, args = reference_module(cell), reference_args(cell)
    ids = calibration_ids(seed, *CALIBRATION_BATCH, vocab)
    with jax.default_matmul_precision("highest"):
        moved, load = jax.jit(lambda p, i: reference.place_held_experts(
            p, i, balanced_order, **args))(params, ids)
    load = np.asarray(load)
    held = load.shape[1] // expert_ways(cell.config)
    print(json.dumps({"placement": {
        "calibration_tokens": int(ids.size), "experts_held": held,
        "held_part_of_assignments": [
            round(float(row[:held].sum() / row.sum()), 4) for row in load]}}),
        flush=True)
    return _with_leaves(params, moved)
