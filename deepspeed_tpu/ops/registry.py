"""Op registry with compatibility probing.

TPU-native analog of ``op_builder/`` (reference ``builder.py:94`` OpBuilder ABC
with ``is_compatible()`` probes, ``all_ops.py`` enumeration, and the
``ds_report`` installed/compatible matrix env_report.py:29). CUDA JIT
compilation is replaced by: Pallas kernels (compiled by XLA on first trace)
with pure-jnp reference fallbacks selected per platform.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional

import jax

from ..utils.logging import logger


@dataclasses.dataclass
class OpSpec:
    name: str
    kernel: Callable          # pallas implementation
    reference: Callable       # pure-jnp fallback (also the parity oracle)
    platforms: tuple = ("tpu",)  # platforms where the kernel is used
    description: str = ""


_REGISTRY: Dict[str, OpSpec] = {}


def register_op(name: str, kernel: Callable, reference: Callable,
                platforms: tuple = ("tpu",), description: str = "") -> None:
    _REGISTRY[name] = OpSpec(name=name, kernel=kernel, reference=reference,
                             platforms=platforms, description=description)


def is_compatible(name: str) -> bool:
    spec = _REGISTRY.get(name)
    if spec is None:
        return False
    # a backend that fails to initialise raises here: answering "cpu" would
    # route a TPU job onto the jnp references without a word
    return jax.default_backend() in spec.platforms


@functools.lru_cache(maxsize=None)
def kernels_active() -> bool:
    """True when the Pallas kernels are compatible with the current backend.
    THE platform probe of the model code and of every op that chooses between
    a kernel and its jnp reference: evaluated once per process at trace time;
    CPU/test runs keep the pure-jnp paths. Callers read it through the module
    (``registry.kernels_active()``), so a test steers this one name."""
    return is_compatible("flash_attention")


def get_op(name: str, force_reference: bool = False) -> Callable:
    """Resolve an op: Pallas kernel when compatible, jnp fallback otherwise
    (the reference's OpBuilder.load() with compatibility check)."""
    spec = _REGISTRY.get(name)
    if spec is None:
        raise KeyError(f"unknown op '{name}' (registered: {sorted(_REGISTRY)})")
    if force_reference or not is_compatible(name):
        return spec.reference
    return spec.kernel


def available_ops() -> List[str]:
    return sorted(_REGISTRY)


def op_report() -> str:
    """``ds_report`` analog: name / kernel-compatible / description table."""
    lines = [f"{'op name':<28}{'kernel':<12}{'platforms':<16}description",
             "-" * 76]
    for name in sorted(_REGISTRY):
        spec = _REGISTRY[name]
        status = "ready" if is_compatible(name) else "fallback"
        lines.append(f"{name:<28}{status:<12}{','.join(spec.platforms):<16}"
                     f"{spec.description}")
    return "\n".join(lines)
