"""What both kinds of cell take from the program under test: the model a
configuration file names, the seed in the form the program accepts, and the
configuration's plain reference."""

from __future__ import annotations

import importlib

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
    return importlib.import_module(
        f"benchmarks.references.{cell.config['reference']}")
