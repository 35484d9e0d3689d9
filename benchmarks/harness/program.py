"""What both kinds of cell take from the program under test: the model a
configuration file names, the seed in the form the program accepts, and the
configuration's plain reference with the arguments it is called with."""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict

from .spec import SpecError
from .traffic import seed32


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
