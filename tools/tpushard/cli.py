"""tpushard CLI — the static sharding gate.

Usage::

    # gate run (what CI does): selftest engines vs the committed baseline
    python -m tools.tpushard --config tools/tpuaudit/selftest_config.json

    python -m tools.tpushard --config c.json --format json
    python -m tools.tpushard --config c.json --baseline b.json --write-baseline
    python -m tools.tpushard --config c.json --override-rule vocab=data

Shares the tpuaudit registry + harness (one ``--config`` builds the engines
for all analyzers) and the tpulint/tpuaudit/tpucost gate semantics: exit 0
clean, 1 new findings or stale baseline entries, 2 usage error.
``--baseline`` defaults to the committed ``.tpushard-baseline.json`` when it
exists, so the bare gate command needs no flags. ``--override-rule`` remaps a
logical axis on the EXPECTATION side only — the fault-injection seam: a
deliberately wrong rule must surface as named rule-violations and exit 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

from ..tpulint.baseline import gate_and_report
from .core import EntryReport, run_shard

DEFAULT_BASELINE = ".tpushard-baseline.json"


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpushard",
        description="Whole-program sharding analyzer: lowers the registered "
                    "entry points host-side (no TPU) and checks every "
                    "parameter/output placement against the logical-axis "
                    "rule registry (deepspeed_tpu/parallel/rules.py).")
    parser.add_argument("--config", metavar="FILE", default=None,
                        help="JSON harness config (same file tpuaudit uses); "
                             "builds the engines so they register their "
                             "entry points")
    parser.add_argument("--entries", metavar="NAMES", default=None,
                        help="comma-separated entry-point names "
                             "(default: every registered entry)")
    parser.add_argument("--format", choices=("text", "json"), default="text")
    parser.add_argument("--baseline", metavar="FILE", default=None,
                        help=f"baseline of accepted findings (default: "
                             f"{DEFAULT_BASELINE} when it exists)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="write the current findings to --baseline and "
                             "exit 0")
    parser.add_argument("--prune-baseline", action="store_true",
                        help="drop stale baseline keys and ratchet budgets "
                             "down to current counts, then exit 0")
    parser.add_argument("--override-rule", metavar="AXIS=MESH_AXIS",
                        action="append", default=[],
                        help="remap one logical axis in the EXPECTED rules "
                             "(fault injection; repeatable; MESH_AXIS of "
                             "'none' clears the mapping)")
    parser.add_argument("--devices", type=int, default=8,
                        help="virtual CPU device count (default 8, the "
                             "tier-1 mesh; must run before jax imports)")
    parser.add_argument("--metrics-jsonl", metavar="FILE", default=None,
                        help="also dump the tpushard/* metrics to a JSONL "
                             "(readable by 'observability report')")
    parser.add_argument("--list-entries", action="store_true",
                        help="print the registered entry points and exit")
    return parser


def _parse_overrides(items: List[str]) -> Dict[str, Optional[str]]:
    out: Dict[str, Optional[str]] = {}
    for item in items:
        axis, sep, mesh_axis = item.partition("=")
        if not sep or not axis:
            raise ValueError(f"--override-rule wants AXIS=MESH_AXIS, "
                             f"got {item!r}")
        out[axis.strip()] = (None if mesh_axis.strip().lower() == "none"
                             else mesh_axis.strip())
    return out


def _table(reports: List[EntryReport]) -> str:
    headers = ["entry", "policy", "group", "checked", "viol", "reshards",
               "repl_bytes", "hash"]
    rows = []
    for r in reports:
        rows.append([
            r.entry,
            r.policy or "-",
            r.group or "-",
            f"{r.params_checked}/{r.params_total}",
            str(r.rule_violations),
            str(r.reshard_collectives),
            f"{r.replicated_bytes:,}",
            (r.program_hash or "-"),
        ])
    widths = [max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
              for i, h in enumerate(headers)]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    lines = [fmt.format(*headers), fmt.format(*("-" * w for w in widths))]
    lines += [fmt.format(*r) for r in rows]
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    try:
        overrides = _parse_overrides(args.override_rule)
    except ValueError as e:
        print(f"tpushard: {e}", file=sys.stderr)
        return 2

    # determinism (same contract as tpucost): executables deserialized from
    # the persistent compile cache lose analysis-relevant attributes
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"   # read at jax import

    from ..tpuaudit.cli import _setup_platform

    _setup_platform(args.devices)

    from ..tpuaudit.registry import get_entry_points

    if args.config:
        from ..tpuaudit import harness

        try:
            harness.build_from_config(harness.load_config(args.config))
        except (OSError, json.JSONDecodeError, ValueError, KeyError) as e:
            print(f"tpushard: bad --config {args.config}: {e}",
                  file=sys.stderr)
            return 2

    try:
        names = ([n.strip() for n in args.entries.split(",") if n.strip()]
                 if args.entries else None)
        entries = get_entry_points(names)
    except KeyError as e:
        print(f"tpushard: {e.args[0]}", file=sys.stderr)
        return 2

    if args.list_entries:
        for ep in entries:
            tag = ep.tags.get("shard")
            handoff = ep.tags.get("handoff")
            contract = (f"policy={tag['policy']}" if tag
                        else f"handoff={handoff['role']}" if handoff
                        else "untagged")
            print(f"{ep.name}: {contract}")
        return 0
    if not entries:
        print("tpushard: no entry points registered (pass --config, or "
              "construct the engines in-process first)", file=sys.stderr)
        return 2

    findings, reports = run_shard(entries, rule_overrides=overrides or None)

    if args.metrics_jsonl:
        from deepspeed_tpu.observability import get_registry

        get_registry().dump_jsonl(args.metrics_jsonl,
                                  extra={"tool": "tpushard"})

    baseline_path = args.baseline
    if baseline_path is None and not (args.write_baseline
                                      or args.prune_baseline):
        if os.path.exists(DEFAULT_BASELINE):
            baseline_path = DEFAULT_BASELINE

    if (args.write_baseline or args.prune_baseline) and any(
            f.check == "trace-error" for f in findings):
        # same contract as tpucost: accepting debt while entries fail to
        # build looks like a successful ratchet
        for f in findings:
            if f.check == "trace-error":
                print(f"tpushard: {f.render()}", file=sys.stderr)
        print("tpushard: refusing to touch the baseline while entries fail "
              "to trace", file=sys.stderr)
        return 2

    # partial runs (--entries) must not condemn keys they never analyzed;
    # cross-program keys need BOTH sides, so they are in scope only for
    # full runs
    def in_scope(key: str) -> bool:
        entry, _, _ = key.rpartition("::")
        return names is None or entry in names

    if args.format == "text":
        tagged = sum(1 for r in reports)
        print("== sharding ==")
        if reports:
            print(_table(reports))
        untagged = [ep.name for ep in entries
                    if "shard" not in ep.tags and "handoff" not in ep.tags]
        if untagged:
            print(f"no layout contract (untagged): {', '.join(untagged)}")
        print(f"{tagged}/{len(entries)} entries carry a layout contract")
        print()

    rc = gate_and_report(
        findings, tool="tpushard", fmt=args.format,
        baseline_path=baseline_path, write_baseline=args.write_baseline,
        prune_baseline=args.prune_baseline, in_scope=in_scope)
    return rc


if __name__ == "__main__":
    sys.exit(main())
