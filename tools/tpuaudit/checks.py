"""tpuaudit checks — program-semantic diagnostics over a traced ``Program``.

Each check inspects what XLA will actually execute (avals, jaxpr equations,
StableHLO/compiled HLO text), never source text. All of them are findings an
AST linter structurally cannot produce.
"""

from __future__ import annotations

import math
from collections import Counter
from typing import Any, Dict, Iterator, List

import numpy as np

from .core import Finding, Program, collect_collectives

__all__ = ["Check", "CHECKS", "register"]

DEFAULT_MIN_DONATION_BYTES = 1 << 20   # ignore sub-MiB donation misses
DEFAULT_MAX_CONST_BYTES = 1 << 20      # flag baked constants over 1 MiB


class Check:
    name: str = ""
    description: str = ""

    def run(self, program: Program, options: Dict[str, Any]) -> Iterator[Finding]:
        raise NotImplementedError

    def _f(self, program: Program, message: str) -> Finding:
        return Finding(self.name, program.entry.name, message)


CHECKS: List[Check] = []


def register(cls):
    CHECKS.append(cls())
    return cls


def _npdtype(dt):
    """np.dtype or None for extended dtypes (typed PRNG keys etc.)."""
    try:
        return np.dtype(dt)
    except TypeError:
        return None


def _aval_key(aval):
    return (tuple(aval.shape), str(aval.dtype))


def _aval_bytes(aval) -> int:
    dt = _npdtype(getattr(aval, "dtype", None))
    if dt is None:
        return 0
    try:
        return int(math.prod(aval.shape)) * dt.itemsize
    except (TypeError, ValueError):
        return 0


def _mib(n: int) -> str:
    return f"{n / 2**20:.1f}MiB"


@register
class UnexpectedCollective(Check):
    """GSPMD silently inserts resharding collectives when shardings don't
    line up; an all-gather you didn't plan for is HBM + ICI you pay every
    step. Entries declare the kinds they expect; everything else fails."""

    name = "unexpected-collective"
    description = ("collective ops in the lowered/compiled program that the "
                   "entry point did not declare in expected_collectives")

    def run(self, program, options):
        expected = program.entry.expected_collectives
        if expected is None:          # entry opted out of collective auditing
            return
        found = collect_collectives(program.stablehlo, program.compiled_hlo)
        for kind in sorted(found):
            if kind not in expected:
                yield self._f(
                    program,
                    f"program contains {found[kind]}x {kind} but the entry "
                    f"point declares expected_collectives="
                    f"{sorted(expected)} — an undeclared reshard/collective "
                    "(check shardings or declare the collective)")


@register
class MissedDonation(Check):
    """Inputs that shape/dtype-match an output but were not donated: XLA must
    keep both buffers live, doubling HBM for that tensor (the train-state
    round-trip is the canonical case)."""

    name = "missed-donation"
    description = ("non-donated inputs whose shape+dtype matches an output "
                   "that no donated buffer already aliases")

    def run(self, program, options):
        threshold = int(options.get("min_donation_bytes",
                                    DEFAULT_MIN_DONATION_BYTES))
        out_pool = Counter(_aval_key(a) for a in program.out_avals)
        # donated inputs claim their aliases first
        for aval, donated in zip(program.in_avals, program.donated):
            if donated and out_pool[_aval_key(aval)] > 0:
                out_pool[_aval_key(aval)] -= 1
        by_arg: Dict[int, int] = {}
        for i, (aval, donated) in enumerate(zip(program.in_avals,
                                                program.donated)):
            if donated:
                continue
            key = _aval_key(aval)
            if out_pool[key] > 0:
                out_pool[key] -= 1
                arg = program.arg_of_input[i]
                by_arg[arg] = by_arg.get(arg, 0) + _aval_bytes(aval)
        for arg, nbytes in sorted(by_arg.items()):
            if nbytes >= threshold:
                yield self._f(
                    program,
                    f"argument {arg} holds {_mib(nbytes)} of leaves that "
                    "shape/dtype-match outputs but are not in donate_argnums "
                    "— the old and new buffers coexist in HBM (donate, or "
                    "suppress with the reason at the registration site)")


@register
class DeadDonation(Check):
    """Donated args that cannot alias any output: the donation frees nothing,
    silently — XLA just invalidates the buffer. Usually a stale
    donate_argnums after an output was dropped or re-shaped."""

    name = "dead-donation"
    description = ("donated arguments with no shape+dtype-compatible output "
                   "to alias")

    def run(self, program, options):
        out_pool = Counter(_aval_key(a) for a in program.out_avals)
        dead: Dict[int, List[str]] = {}
        live: Dict[int, int] = {}
        for i, (aval, donated) in enumerate(zip(program.in_avals,
                                                program.donated)):
            if not donated:
                continue
            arg = program.arg_of_input[i]
            key = _aval_key(aval)
            if out_pool[key] > 0:
                out_pool[key] -= 1
                live[arg] = live.get(arg, 0) + 1
            else:
                dead.setdefault(arg, []).append(program.in_labels[i])
        for arg, leaves in sorted(dead.items()):
            if live.get(arg):
                continue   # partially aliasing args are doing their job
            shown = ", ".join(leaves[:3]) + ("..." if len(leaves) > 3 else "")
            yield self._f(
                program,
                f"argument {arg} is donated but none of its {len(leaves)} "
                f"leaves ({shown}) matches any output shape+dtype — the "
                "donation aliases nothing and only invalidates the input")


@register
class HostCallback(Check):
    """pure_callback/io_callback/debug prints that survived into the lowered
    program stall the TPU pipeline on a host round-trip every invocation."""

    name = "host-callback-in-program"
    description = ("pure_callback / io_callback / debug_callback equations "
                   "in the traced program")

    def run(self, program, options):
        counts: Counter = Counter()
        for eqn in program.iter_eqns():
            name = eqn.primitive.name
            # jax.debug.print is its own primitive (it was a debug_callback)
            if "callback" in name or name == "debug_print":
                counts[name] += 1
        for prim, n in sorted(counts.items()):
            yield self._f(
                program,
                f"{n}x {prim} in the lowered program — each invocation is a "
                "device->host->device round-trip on the hot path (remove, or "
                "suppress at the registration site for intentional debugging)")


@register
class WeakTypeCapture(Check):
    """Python scalars traced as weak-typed args: the jit cache keys on
    (shape, dtype, weak_type), so any call site that sometimes passes a
    python float and sometimes an array/np scalar retraces the program — the
    classic steady-state-recompile the observability watchdog flags at
    runtime, caught statically here."""

    name = "weak-type-capture"
    description = "inputs traced as weak-typed scalars (python int/float args)"

    def run(self, program, options):
        for aval, label in zip(program.in_avals, program.in_labels):
            if getattr(aval, "weak_type", False):
                yield self._f(
                    program,
                    f"input {label} traced weak ({aval.dtype})"
                    " — pass jnp.asarray(x, dtype) at the call site so the "
                    "jit cache key is stable across python/numpy scalar types")


@register
class ImplicitPromotion(Check):
    """Dtype widening inside the program: any f64 means the program silently
    runs double precision (x64 leaked into a TPU-bound function); f64 avals
    also appear when python floats mix with x64-enabled tracing."""

    name = "implicit-promotion"
    description = "float64 values appearing anywhere in the traced program"

    def run(self, program, options):
        sites: Counter = Counter()
        for eqn in program.iter_eqns():
            for v in eqn.outvars:
                dt = _npdtype(getattr(getattr(v, "aval", None), "dtype", None))
                if dt is not None and dt == np.float64:
                    sites[eqn.primitive.name] += 1
        for aval, label in zip(program.in_avals, program.in_labels):
            if _npdtype(aval.dtype) == np.float64:
                yield self._f(
                    program,
                    f"input {label} is float64 — double precision on the "
                    "program boundary (cast at the call site)")
        if sites:
            top = ", ".join(f"{k} x{n}" for k, n in sites.most_common(3))
            yield self._f(
                program,
                f"{sum(sites.values())} float64 value(s) produced inside the "
                f"program ({top}) — f32/bf16 math is being promoted to "
                "double precision")


@register
class BakedConstant(Check):
    """Large arrays captured by closure become jaxpr constants: they are
    re-hashed on every jit cache lookup, baked into the executable, and
    re-transferred per compilation instead of living in donated/sharded
    argument buffers."""

    name = "baked-constant"
    description = "multi-MiB constants folded into the jaxpr (closure capture)"

    def run(self, program, options):
        threshold = int(options.get("max_const_bytes",
                                    DEFAULT_MAX_CONST_BYTES))
        for const in program.closed_jaxpr.consts:
            shape = tuple(getattr(const, "shape", ()))
            dtype = getattr(const, "dtype", None)
            # closure constants arrive as jax's TypedNdArray, which has a
            # shape and a dtype but no nbytes
            nbytes = (int(np.prod(shape)) * np.dtype(dtype).itemsize
                      if dtype is not None else 0)
            if nbytes > threshold:
                yield self._f(
                    program,
                    f"constant {shape} {dtype} ({_mib(nbytes)}) baked into "
                    "the jaxpr — pass it as an argument (sharded, donatable) "
                    "instead of closing over the array")
