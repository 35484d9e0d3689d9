"""Persistent XLA compilation cache activation.

The TPU analog of the reference's JIT-extension build cache: the reference
compiles CUDA ops once and caches the .so (op_builder/builder.py
TORCH_EXTENSIONS_DIR); here the expensive artifact is the compiled XLA
executable, and jax's persistent compilation cache plays the same role.
Applied from both engines at construction so every step program — most
importantly the >10B param-offload segment programs, whose first compile
can take minutes — compiles once per (program, shape, flags) and loads from
disk afterwards.

Where the cache lives is decided outside the program or not at all:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this module sets
  no directory.
* unset — ``CHECKOUT_CACHE_DIR``, one fixed path beside the package (the
  path is part of the cache key, so a directory that moves never hits).

To switch the cache off use JAX's own ``jax_enable_compilation_cache``
(``JAX_ENABLE_COMPILATION_CACHE=0``).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from .logging import logger

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(min_compile_time_secs: float = 1.0) -> Optional[str]:
    """Make sure jax has a persistent compilation cache directory
    (idempotent). Returns the active dir, or None when the cache is
    switched off (``jax_enable_compilation_cache``)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = jax.config.jax_compilation_cache_dir
    else:
        path = CHECKOUT_CACHE_DIR
        if jax.config.jax_compilation_cache_dir != path:
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
            logger.info(f"persistent XLA compile cache: {path}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    return path
