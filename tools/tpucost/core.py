"""tpucost core — per-entry cost vectors from host-side compilation.

For every entry in the tpuaudit registry the driver traces + lowers (+
compiles, host-only — the same ``trace_entry`` front half tpuaudit uses) and
extracts a **cost vector**: XLA's own cost analysis (flops, transcendentals,
bytes accessed), memory analysis (argument/output/temp/peak HBM), a
collective-bytes census per mesh axis, jaxpr/HLO op counts and program size
— then derives the analytic roofline bound (predicted step time, MFU
ceiling). No TPU, no device math: the whole vector exists at trace time,
which is what lets CI gate program-level perf without a chip.

Entries registered with ``compile=False`` (the 1F1B pipeline programs, whose
host compile hard-crashes CPU GSPMD) fall back to the PRE-partitioning
analyses: ``Lowered.cost_analysis`` and a StableHLO collective census.
Their vectors carry no memory metrics — the gate only judges the metrics a
vector actually has.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, List, Optional, Sequence

from ..tpuaudit.core import iter_eqns_of, resolve_mesh, trace_entry
from ..tpuaudit.registry import EntryPoint, StaleEntryError
from . import extract
from .roofline import roofline

__all__ = ["CostVector", "cost_entry", "run_cost", "registry_cost_vector",
           "measured_join", "publish_vectors"]


@dataclasses.dataclass
class CostVector:
    """Everything the gate, the report CLI and the autotuner read about one
    program. ``metrics`` holds only the scalars that exist for this entry
    (uncompiled entries have no memory metrics)."""

    entry: str
    metrics: Dict[str, float]
    hlo_ops: Dict[str, int]
    collectives: Dict[str, Any]      # {"total_bytes", "by_kind", "by_axis"}
    program_hash: str
    compiled: bool
    predicted_step_s: float
    mfu_ceiling: float
    bound: str
    predicted_tokens_per_sec: Optional[float] = None
    tags: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _jaxpr_eqn_count(closed_jaxpr) -> int:
    return sum(1 for _ in iter_eqns_of(closed_jaxpr))


@contextlib.contextmanager
def _fresh_compiles():
    """Disable jax's persistent compilation cache for the duration: an
    executable LOADED from the cache reports alias_size_in_bytes=0 (the
    deserialized artifact drops its donation-aliasing stats), which made
    peak_hbm_bytes flip run-to-run for programs near the cache's
    min-compile-time threshold. The gate needs the numbers of a real
    compile, and these programs compile in ~1 s host-side."""
    import jax

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def cost_entry(ep: EntryPoint, device_kind: Optional[str] = None,
               do_compile: Optional[bool] = None) -> CostVector:
    """Build one entry's cost vector. Honors ``ep.compile`` unless
    overridden; raises on trace failure (``run_cost`` maps that to a gate
    finding) and propagates ``StaleEntryError`` (caller skips)."""
    with _fresh_compiles():
        traced, lowered, compiled, _, _ = trace_entry(ep, do_compile)

    if compiled is not None:
        text = compiled.as_text()
        metrics = extract.cost_analysis_dict(compiled)
        metrics.update(extract.memory_analysis_dict(compiled))
        mesh = resolve_mesh(ep)
        axis_sizes = ({str(a): int(s) for a, s in mesh.shape.items()}
                      if mesh is not None else None)
        coll = extract.collective_census(text, axis_sizes)
    else:
        text = lowered.as_text()
        metrics = extract.cost_analysis_dict(lowered)
        coll = extract.stablehlo_collective_census(text)
    metrics.pop("generated_code_bytes", None)   # 0 on CPU; size is the text
    metrics["collective_bytes"] = coll["total_bytes"]
    metrics["jaxpr_eqns"] = float(_jaxpr_eqn_count(traced.jaxpr))
    hlo_ops = extract.hlo_op_census(text) if compiled is not None else {}
    metrics["hlo_op_count"] = float(sum(hlo_ops.values()))
    metrics["program_bytes"] = float(len(text))

    tokens = ep.tags.get("tokens_per_step")
    bound = roofline(metrics.get("flops", 0.0),
                     metrics.get("bytes_accessed", 0.0),
                     coll["total_bytes"], device_kind=device_kind,
                     tokens_per_step=tokens)
    return CostVector(
        entry=ep.name, metrics=metrics, hlo_ops=hlo_ops, collectives=coll,
        program_hash=extract.program_hash(text),
        compiled=compiled is not None,
        predicted_step_s=bound.predicted_step_s,
        mfu_ceiling=bound.mfu_ceiling, bound=bound.bound,
        predicted_tokens_per_sec=bound.predicted_tokens_per_sec,
        tags=dict(ep.tags))


def run_cost(entries: Sequence[EntryPoint],
             device_kind: Optional[str] = None,
             publish_metrics: bool = True
             ) -> tuple:
    """Cost every entry. Returns ``(vectors, errors)`` where ``errors`` maps
    entry name → exception string for entries that failed to trace/compile
    (the CLI gates on those — a program that stopped compiling host-side is
    a regression, not a skip). Stale entries (torn-down engines) are
    silently dropped, mirroring tpuaudit."""
    vectors: List[CostVector] = []
    errors: Dict[str, str] = {}
    for ep in entries:
        try:
            vectors.append(cost_entry(ep, device_kind=device_kind))
        except StaleEntryError:
            continue
        except Exception as e:                      # noqa: BLE001
            errors[ep.name] = f"{type(e).__name__}: {str(e)[:300]}"
    vectors.sort(key=lambda v: v.entry)
    if publish_metrics:
        publish_vectors(vectors)
    return vectors, errors


def registry_cost_vector(name: str, **kwargs) -> Optional[CostVector]:
    """Cost vector for ONE registered entry, or None when the entry is
    absent/stale/untraceable — the autotuner's discovery hook (it must
    degrade to its static tables, never raise)."""
    from ..tpuaudit.registry import get_entry_points

    try:
        ep = get_entry_points([name])[0]
    except KeyError:
        return None
    try:
        return cost_entry(ep, **kwargs)
    except Exception:                               # noqa: BLE001
        return None


def measured_join(entry: str, measured_step_s: float,
                  device_kind: Optional[str] = None) -> Optional[dict]:
    """Pair ONE measured per-invocation device time (seconds, from a
    profiler capture window) with this entry's roofline prediction — the
    join half of the measured-vs-predicted loop. Returns the comparison
    columns (``predicted_step_ms``, ``mfu_ceiling``, ``bound``,
    ``model_error`` = measured/predicted, and ``measured_mfu`` when the
    vector has flops) or None when the entry can't be costed — the
    profiler treats that as a missing column, never an error."""
    if measured_step_s <= 0:
        return None
    v = registry_cost_vector(entry, device_kind=device_kind)
    if v is None:
        return None
    out: Dict[str, Any] = {
        "predicted_step_ms": round(v.predicted_step_s * 1e3, 4),
        "mfu_ceiling": round(v.mfu_ceiling, 4),
        "bound": v.bound,
    }
    if v.predicted_step_s > 0:
        out["model_error"] = round(measured_step_s / v.predicted_step_s, 4)
    flops = v.metrics.get("flops", 0.0)
    if flops > 0:
        try:
            from deepspeed_tpu.autotuning.cost_model import peak_flops_for

            peak = peak_flops_for(device_kind)
        except Exception:                           # noqa: BLE001
            peak = 0.0
        if peak > 0:
            out["measured_mfu"] = round(
                flops / (measured_step_s * peak), 6)
    return out


# gauges published per entry (the report CLI's == cost == section reads
# exactly these back out of a metrics JSONL)
PUBLISHED_METRICS = ("flops", "bytes_accessed", "peak_hbm_bytes",
                     "collective_bytes", "program_bytes")


def publish_vectors(vectors: Sequence[CostVector]) -> None:
    """Publish ``tpucost/<entry>/<metric>`` gauges into the observability
    MetricsRegistry so cost vectors ride the same JSONL/report pipeline as
    goodput and serving metrics."""
    try:
        from deepspeed_tpu.observability import get_registry
    except ImportError:
        return
    reg = get_registry()
    for v in vectors:
        for metric in PUBLISHED_METRICS:
            if metric in v.metrics:
                reg.gauge(f"tpucost/{v.entry}/{metric}").set(v.metrics[metric])
        reg.gauge(f"tpucost/{v.entry}/predicted_step_ms").set(
            v.predicted_step_s * 1e3, bound=v.bound)
        reg.gauge(f"tpucost/{v.entry}/mfu_ceiling").set(v.mfu_ceiling)
        if v.predicted_tokens_per_sec is not None:
            reg.gauge(f"tpucost/{v.entry}/predicted_tokens_per_sec").set(
                v.predicted_tokens_per_sec)
