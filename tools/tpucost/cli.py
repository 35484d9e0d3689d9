"""tpucost CLI — the static perf gate.

Usage::

    # gate run (what CI does): selftest engines vs the committed baseline
    python -m tools.tpucost --config tools/tpuaudit/selftest_config.json

    python -m tools.tpucost --config cost.json --format json
    python -m tools.tpucost --config cost.json --baseline b.json --write-baseline
    python -m tools.tpucost --config cost.json --diff          # full delta table

Shares the tpuaudit registry + harness (one ``--config`` builds the engines
for both analyzers) and the tpulint/tpuaudit gate semantics: exit 0 clean,
1 regression findings or stale baseline entries, 2 usage error.
``--baseline`` defaults to the committed ``.tpucost-baseline.json`` when it
exists, so the bare gate command needs no flags. ``--devices`` defaults to
8 — the tier-1 virtual-mesh width — because the vectors (per-device shard
sizes, collective payloads) are a function of the mesh, and the committed
baseline is pinned to the CI mesh.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from ..tpulint.baseline import render_report
from . import baseline as baseline_mod

DEFAULT_BASELINE = ".tpucost-baseline.json"


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpucost",
        description="Static program-cost analyzer: AOT-compiles the "
                    "registered entry points host-side (no TPU) and gates "
                    "their XLA cost/memory/collective vectors against a "
                    "committed baseline with per-metric tolerance bands.")
    parser.add_argument("--config", metavar="FILE", default=None,
                        help="JSON harness config (same file tpuaudit uses); "
                             "builds the engines so they register their "
                             "entry points")
    parser.add_argument("--entries", metavar="NAMES", default=None,
                        help="comma-separated entry-point names "
                             "(default: every registered entry)")
    parser.add_argument("--format", choices=("text", "json"), default="text")
    parser.add_argument("--baseline", metavar="FILE", default=None,
                        help=f"baseline of committed cost vectors (default: "
                             f"{DEFAULT_BASELINE} when it exists)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="write the current vectors to --baseline and "
                             "exit 0")
    parser.add_argument("--prune-baseline", action="store_true",
                        help="drop vanished entries/metrics and ratchet "
                             "surviving values down to current, then exit 0")
    parser.add_argument("--diff", action="store_true",
                        help="print the full per-entry metric delta table "
                             "vs the baseline (not just over-band metrics)")
    parser.add_argument("--device-kind", metavar="KIND", default=None,
                        help="chip generation for the roofline denominators "
                             "(e.g. 'v5e', 'v5p'; default: v5e-class)")
    parser.add_argument("--metrics-jsonl", metavar="FILE", default=None,
                        help="also dump the tpucost/* gauges to a metrics "
                             "JSONL (readable by 'observability report')")
    parser.add_argument("--devices", type=int, default=8,
                        help="virtual CPU device count (default 8, the "
                             "tier-1 mesh; must run before jax imports)")
    parser.add_argument("--list-entries", action="store_true",
                        help="print the registered entry points and exit")
    return parser


def _table(vectors) -> str:
    headers = ["entry", "flops", "bytes", "peak_hbm", "coll_B", "ops",
               "pred_ms", "mfu_ceil", "bound"]
    rows = []
    for v in vectors:
        m = v.metrics
        rows.append([
            v.entry + ("" if v.compiled else " *"),
            f"{m.get('flops', 0):,.0f}",
            f"{m.get('bytes_accessed', 0):,.0f}",
            f"{m.get('peak_hbm_bytes', 0):,.0f}" if "peak_hbm_bytes" in m
            else "-",
            f"{m.get('collective_bytes', 0):,.0f}",
            f"{int(m.get('hlo_op_count', 0))}",
            f"{v.predicted_step_s * 1e3:.4f}",
            f"{v.mfu_ceiling:.3f}",
            v.bound,
        ])
    widths = [max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
              for i, h in enumerate(headers)]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    lines = [fmt.format(*headers), fmt.format(*("-" * w for w in widths))]
    lines += [fmt.format(*r) for r in rows]
    if any(not v.compiled for v in vectors):
        lines.append("* pre-partitioning analysis (entry registered "
                     "compile=False); no memory metrics")
    return "\n".join(lines)


def _diff_table(vectors, known) -> str:
    lines = ["== diff vs baseline =="]
    for v in vectors:
        base = known.get(v.entry)
        if base is None:
            lines.append(f"{v.entry}: NEW (not in baseline)")
            continue
        base_metrics = base.get("metrics", {})
        changed = []
        for metric in sorted(set(base_metrics) | set(
                m for m in v.metrics if m in baseline_mod.TOLERANCES)):
            b, c = base_metrics.get(metric), v.metrics.get(metric)
            if b is None or c is None or b != c:
                b_s = baseline_mod._fmt(float(b)) if b is not None else "-"
                c_s = baseline_mod._fmt(float(c)) if c is not None else "-"
                pct = (baseline_mod._delta_pct(float(b), float(c))
                       if b is not None and c is not None else "")
                changed.append(f"  {metric}: {b_s} -> {c_s} {pct}".rstrip())
        if changed:
            lines.append(f"{v.entry}:")
            lines.extend(changed)
            grown = baseline_mod.grown_op_classes(
                base.get("hlo_ops", {}), v.hlo_ops, top=6)
            if grown:
                lines.append("  grown HLO op classes: " + ", ".join(
                    f"{op} +{d}" for op, d in grown))
        else:
            lines.append(f"{v.entry}: unchanged")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    # the persistent XLA compile cache must stay OFF for the whole process:
    # executables deserialized from it drop their donation-aliasing stats
    # (alias_size_in_bytes=0), which flips peak_hbm_bytes run-to-run for
    # programs near the cache's min-compile-time threshold. Host compiles of
    # the selftest programs are ~1 s each — determinism is worth more here.
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"   # read at jax import

    from ..tpuaudit.cli import _setup_platform

    _setup_platform(args.devices)

    from ..tpuaudit.registry import get_entry_points

    if args.config:
        from ..tpuaudit import harness

        try:
            harness.build_from_config(harness.load_config(args.config))
        except (OSError, json.JSONDecodeError, ValueError, KeyError) as e:
            print(f"tpucost: bad --config {args.config}: {e}",
                  file=sys.stderr)
            return 2

    try:
        names = ([n.strip() for n in args.entries.split(",") if n.strip()]
                 if args.entries else None)
        entries = get_entry_points(names)
    except KeyError as e:
        print(f"tpucost: {e.args[0]}", file=sys.stderr)
        return 2

    if args.list_entries:
        for ep in entries:
            print(f"{ep.name}: compile={ep.compile} tags={ep.tags}")
        return 0
    if not entries:
        print("tpucost: no entry points registered (pass --config, or "
              "construct the engines in-process first)", file=sys.stderr)
        return 2

    from .core import run_cost

    vectors, errors = run_cost(entries, device_kind=args.device_kind)

    if args.metrics_jsonl:
        from deepspeed_tpu.observability import get_registry

        get_registry().dump_jsonl(args.metrics_jsonl, extra={"tool": "tpucost"})

    baseline_path = args.baseline
    if baseline_path is None and not (args.write_baseline
                                      or args.prune_baseline):
        if os.path.exists(DEFAULT_BASELINE):
            baseline_path = DEFAULT_BASELINE

    if (args.write_baseline or args.prune_baseline) and not baseline_path:
        print("tpucost: --write-baseline/--prune-baseline require "
              "--baseline FILE", file=sys.stderr)
        return 2

    if args.write_baseline:
        if errors:
            for name, msg in sorted(errors.items()):
                print(f"tpucost: {name}: {msg}", file=sys.stderr)
            print("tpucost: refusing to write a baseline while entries fail "
                  "to build", file=sys.stderr)
            return 2
        records = baseline_mod.records_of(vectors)
        if names is not None and os.path.exists(baseline_path):
            # a partial --entries write must not destroy the other entries'
            # committed budgets: merge into the existing baseline
            try:
                records = {**baseline_mod.load(baseline_path), **records}
            except (ValueError, json.JSONDecodeError) as e:
                print(f"tpucost: bad baseline {baseline_path}: {e}",
                      file=sys.stderr)
                return 2
        baseline_mod.write(baseline_path, records)
        print(f"tpucost: wrote {len(vectors)} cost vector(s) to "
              f"{baseline_path} ({len(records)} total)")
        return 0

    # partial runs (--entries) must not condemn keys they never measured
    def in_scope(key: str) -> bool:
        entry, _, _ = key.rpartition("::")
        return names is None or entry in names

    known = {}
    stale: List[str] = []
    findings: List[baseline_mod.CostFinding] = []
    if baseline_path and not os.path.exists(baseline_path):
        if args.prune_baseline:
            print(f"tpucost: cannot prune: baseline {baseline_path} not "
                  "found", file=sys.stderr)
            return 2
        print(f"tpucost: warning: baseline {baseline_path} not found; "
              "reporting without gating", file=sys.stderr)
        baseline_path = None
    if baseline_path:
        try:
            known = baseline_mod.load(baseline_path)
        except (ValueError, json.JSONDecodeError) as e:
            print(f"tpucost: bad baseline {baseline_path}: {e}",
                  file=sys.stderr)
            return 2
        if args.prune_baseline:
            if errors:
                # same contract as --write-baseline: a prune that silently
                # skips a broken entry looks like a successful ratchet
                for name, msg in sorted(errors.items()):
                    print(f"tpucost: {name}: {msg}", file=sys.stderr)
                print("tpucost: refusing to prune while entries fail to "
                      "build", file=sys.stderr)
                return 2
            out = baseline_mod.pruned(vectors, known, in_scope=in_scope)
            baseline_mod.write(baseline_path, out)
            print(f"tpucost: pruned baseline {baseline_path}: "
                  f"{len(known)} -> {len(out)} entries")
            return 0
        findings, stale = baseline_mod.compare(vectors, known, errors=errors,
                                               in_scope=in_scope)
    else:
        findings = [baseline_mod.CostFinding(
            name, "trace-error", f"entry failed to trace/compile "
            f"host-side: {msg}") for name, msg in sorted(errors.items())]

    if args.format == "json":
        return render_report(
            findings, stale, tool="tpucost", fmt="json",
            baseline_path=baseline_path, total=len(vectors),
            stale_note=("is outside the tolerance band on the improving "
                        "side — run --prune-baseline"),
            extra_json={"entries": {v.entry: v.to_json() for v in vectors}})

    print("== cost ==")
    print(_table(vectors))
    if args.diff and known:
        print()
        print(_diff_table(vectors, known))
    print()
    return render_report(
        findings, stale, tool="tpucost", fmt="text",
        baseline_path=baseline_path, total=len(vectors),
        stale_note=("is outside the tolerance band on the improving side "
                    "— run --prune-baseline"))


if __name__ == "__main__":
    sys.exit(main())
