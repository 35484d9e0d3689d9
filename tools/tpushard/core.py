"""tpushard core — actual vs registry-derived layout, per entry point.

For every tpuaudit entry point carrying a ``tags["shard"]`` contract (see
``deepspeed_tpu.parallel.rules.shard_tag``) the analyzer

1. traces/lowers (and, where the entry allows, compiles) the program
   host-side via tpuaudit's ``trace_entry`` — no device math;
2. reads the ACTUAL sharding of every parameter leaf (the compiled
   executable's ``input_shardings`` when available — what XLA will really
   run — else the registration-site ``ShapeDtypeStruct`` shardings);
3. recomputes the EXPECTED placement from the rule registry: the tag's
   policy resolved over the model's logical-axis tree;
4. reports four finding classes:

   * ``rule-violation``       — a leaf's actual sharding is not equivalent
     to what the registry derives for it;
   * ``implicit-reshard``     — GSPMD inserted collective kinds outside the
     entry's declared set WHILE rule violations exist: the cost of the
     mismatch, attributed to the mismatched operands (without violations
     this stays tpuaudit's ``unexpected-collective`` — no double report);
   * ``cross-program-mismatch`` — the same logical param is sharded
     differently in two entries of one ``group`` (entries exchanging live
     buffers: train↔eval, prefill↔decode↔verify, the RLHF flip's target vs
     the serving programs), or the KV-handoff export's output buffers do
     not land exactly like the import's staging args;
   * ``replication-waste``    — a >1 MiB buffer is fully replicated where
     the rules map an axis; priced as actual bytes minus the expected
     per-device shard size.

Findings reuse tpuaudit's shape (``key`` = ``entry::check``) so the gate,
baseline and CLI semantics are shared via ``tools.tpulint.baseline``.

Equivalence uses ``Sharding.is_equivalent_to(other, ndim)``: it compares
across distinct mesh objects and normalizes size-1 mesh axes (``P('model')``
over a 1-wide model axis IS replication), so a 1-device debug mesh never
false-positives.
"""

from __future__ import annotations

import dataclasses
import hashlib
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..tpuaudit.core import Finding, collect_collectives, resolve_mesh, \
    trace_entry
from ..tpuaudit.registry import EntryPoint, StaleEntryError

__all__ = ["EntryReport", "analyze_entry", "canonical_hash", "run_shard"]

REPLICATION_WASTE_MIN_BYTES = 1 << 20   # 1 MiB: below this, replication is
                                        # a latency win, not a memory bug

# compiled-HLO canonicalization: the raw text embeds source-location
# metadata (file/line of every op), so ANY refactor that shifts lines
# changes the raw hash. Stripping `metadata={...}` and collapsing
# whitespace leaves exactly the computation + layout — the thing the
# rule-registry migration must preserve bit-for-bit.
_METADATA_RE = re.compile(r",?\s*metadata=\{[^}]*\}")
# the module header's source tables (FileNames / FunctionNames /
# FileLocations / StackFrames: numbered lines up to the first blank one)
# carry the same positions the per-op metadata points into
_SOURCE_TABLES_RE = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(\d+ .*\n)*",
    re.MULTILINE)
_WS_RE = re.compile(r"\s+")


def canonical_hash(hlo_text: str) -> str:
    """Position-independent hash of a compiled-HLO text (16 hex chars)."""
    text = _SOURCE_TABLES_RE.sub("", hlo_text)
    text = _METADATA_RE.sub("", text)
    text = _WS_RE.sub(" ", text).strip()
    return hashlib.sha256(text.encode("utf-8")).hexdigest()[:16]


@dataclasses.dataclass
class EntryReport:
    """Per-entry coverage/cost stats for the CLI table and the metrics."""

    entry: str
    policy: Optional[str] = None        # None: handoff-only or untagged
    group: Optional[str] = None
    params_total: int = 0               # leaves the contract covers
    params_checked: int = 0             # leaves with a known actual sharding
    rule_violations: int = 0
    reshard_collectives: int = 0        # occurrences of undeclared kinds
    replicated_bytes: int = 0           # waste priced by replication-waste
    program_hash: Optional[str] = None  # canonical compiled-HLO hash

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


# -- per-leaf comparison helpers ---------------------------------------------


def _flat_with_labels(tree: Any) -> List[Tuple[str, Any]]:
    import jax

    return [(jax.tree_util.keystr(path), leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)]


def _spec_leaves(specs: Any) -> List[Any]:
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))


def _sharding_of(leaf: Any) -> Optional[Any]:
    if hasattr(leaf, "is_equivalent_to"):
        return leaf                      # already a Sharding leaf
    s = getattr(leaf, "sharding", None)
    return s if s is not None and hasattr(s, "is_equivalent_to") else None


def _mesh_of_tree(tree: Any) -> Optional[Any]:
    """The mesh implied by a tree of actual shardings — the first
    NamedSharding leaf's. Output-contract entries (the RLHF flip) land on a
    mesh that is NOT the trace mesh, and the tag cannot carry the Mesh
    object itself: everything in ``ep.tags`` must stay JSON-serializable
    (crash-bundle fingerprints, the analyzers' ``--format json``)."""
    import jax

    for leaf in jax.tree.leaves(tree):
        mesh = getattr(_sharding_of(leaf), "mesh", None)
        if mesh is not None:
            return mesh
    return None


def _describe(sharding: Any) -> str:
    spec = getattr(sharding, "spec", None)
    if spec is not None:
        return str(spec)
    if getattr(sharding, "is_fully_replicated", False):
        return "replicated"
    return str(sharding)


def _nbytes(leaf: Any) -> int:
    size = 1
    for s in getattr(leaf, "shape", ()):
        size *= int(s)
    dtype = getattr(leaf, "dtype", None)
    return size * (dtype.itemsize if dtype is not None else 1)


def _check_tree(entry: str, side: str, policy_name: str, mesh: Any,
                sds_tree: Any, actual_tree: Any, expected_specs: Any,
                findings: List[Finding], report: EntryReport,
                group_params: Optional[Dict[str, List]] = None,
                group: Optional[str] = None) -> None:
    """Compare one (params-or-outputs) tree leaf-by-leaf against the
    registry-derived specs; append rule-violation / replication-waste
    findings and record shardings for the cross-program pass."""
    from jax.sharding import NamedSharding

    labelled = _flat_with_labels(sds_tree)
    actuals = [_sharding_of(x) for _, x in _flat_with_labels(actual_tree)] \
        if actual_tree is not None else [None] * len(labelled)
    specs = _spec_leaves(expected_specs)
    if not (len(labelled) == len(actuals) == len(specs)):
        findings.append(Finding(
            "trace-error", entry,
            f"{side} tree/spec arity mismatch: {len(labelled)} leaves, "
            f"{len(actuals)} shardings, {len(specs)} specs"))
        return

    for (label, sds), actual, spec in zip(labelled, actuals, specs):
        report.params_total += 1
        shape = tuple(getattr(sds, "shape", ()))
        expected = NamedSharding(mesh, spec)
        if actual is None:
            continue    # registration site carried no placement: uncheckable
        report.params_checked += 1
        if group_params is not None and group is not None:
            group_params.setdefault((group, label), []).append(
                (entry, actual, sds))
        try:
            ok = actual.is_equivalent_to(expected, len(shape))
        except (TypeError, ValueError) as e:
            findings.append(Finding(
                "rule-violation", entry,
                f"{side} {label}: cannot compare actual {_describe(actual)} "
                f"with expected {spec} (policy {policy_name!r}): {e}"))
            report.rule_violations += 1
            continue
        if not ok:
            report.rule_violations += 1
            findings.append(Finding(
                "rule-violation", entry,
                f"{side} {label}: expected {spec} (policy {policy_name!r}), "
                f"actual {_describe(actual)}"))
        nbytes = _nbytes(sds)
        if (nbytes >= REPLICATION_WASTE_MIN_BYTES
                and getattr(actual, "is_fully_replicated", False)
                and not expected.is_fully_replicated):
            shard_elems = 1
            for s in expected.shard_shape(shape):
                shard_elems *= int(s)
            dtype = getattr(sds, "dtype", None)
            shard_bytes = shard_elems * (dtype.itemsize if dtype is not None
                                         else 1)
            waste = nbytes - shard_bytes
            report.replicated_bytes += waste
            findings.append(Finding(
                "replication-waste", entry,
                f"{side} {label}: {nbytes:,} B fully replicated where the "
                f"rules map {spec} ({waste:,} B/device recoverable)"))


# -- single-entry analysis ---------------------------------------------------


def analyze_entry(ep: EntryPoint,
                  rule_overrides: Optional[Dict[str, Any]] = None,
                  group_params: Optional[Dict[str, List]] = None,
                  handoff_sides: Optional[Dict[str, Dict]] = None,
                  ) -> Tuple[List[Finding], Optional[EntryReport]]:
    """Analyze one entry point. Returns ``(findings, report)``; report is
    None for entries with neither a ``shard`` nor a ``handoff`` tag (no
    contract to audit — e.g. programs that take no parameters).

    ``rule_overrides`` remaps logical axes on the EXPECTATION side only —
    the fault-injection seam the selftest drives (a wrong rule must produce
    a named rule-violation and fail the gate).
    """
    from deepspeed_tpu.parallel.rules import get_policy

    shard = ep.tags.get("shard")
    handoff = ep.tags.get("handoff")
    if shard is None and handoff is None:
        return [], None

    findings: List[Finding] = []
    report = EntryReport(entry=ep.name,
                         policy=shard.get("policy") if shard else None,
                         group=shard.get("group") if shard else None)
    try:
        traced, lowered, compiled, args, kwargs = trace_entry(ep)
    except StaleEntryError:
        return [], None
    except Exception as e:                 # noqa: BLE001 — reportable outcome
        msg = f"{type(e).__name__}: {e}"
        findings.append(Finding(
            "trace-error", ep.name,
            f"could not trace/lower entry point: {msg[:500]}"))
        return findings, report

    if compiled is not None:
        report.program_hash = canonical_hash(compiled.as_text())

    mesh = resolve_mesh(ep)

    if shard is not None and mesh is not None:
        parg = shard.get("params_arg", 0)
        params_sds = args[parg]
        policy = get_policy(shard["policy"])
        in_shardings = None
        if compiled is not None:
            try:
                in_shardings = compiled.input_shardings[0][parg]
            except Exception:       # noqa: BLE001 — fall back to the SDS tree
                in_shardings = None
        actual_in = in_shardings if in_shardings is not None else params_sds

        if shard.get("check_output"):
            # output-contract entry (the RLHF flip): the policy binds the
            # OUTPUT tree, resolved on the target mesh (read off the actual
            # output shardings — the tag stays JSON-serializable); the input
            # side is checked against the nested ``source`` policy
            out_specs = policy.param_specs(
                params_sds, shard["axes"],
                expert_parallel=shard.get("expert_parallel", False),
                fsdp_min_size=shard.get("fsdp_min_size"),
                rule_overrides=rule_overrides)
            actual_out = (compiled.output_shardings if compiled is not None
                          else None)
            out_mesh = _mesh_of_tree(actual_out) or mesh
            _check_tree(ep.name, "output", shard["policy"], out_mesh,
                        params_sds, actual_out, out_specs, findings, report,
                        group_params=group_params, group=shard.get("group"))
            source = shard.get("source")
            if source is not None:
                src_policy = get_policy(source["policy"])
                src_specs = src_policy.param_specs(
                    params_sds, shard["axes"],
                    expert_parallel=shard.get("expert_parallel", False),
                    fsdp_min_size=source.get("fsdp_min_size"),
                    rule_overrides=rule_overrides)
                _check_tree(ep.name, "param", source["policy"], mesh,
                            params_sds, actual_in, src_specs, findings,
                            report)
        else:
            specs = policy.param_specs(
                params_sds, shard["axes"],
                expert_parallel=shard.get("expert_parallel", False),
                fsdp_min_size=shard.get("fsdp_min_size"),
                rule_overrides=rule_overrides)
            _check_tree(ep.name, "param", shard["policy"], mesh, params_sds,
                        actual_in, specs, findings, report,
                        group_params=group_params, group=shard.get("group"))

        # implicit-reshard: undeclared collective kinds coexisting with rule
        # violations — the GSPMD cost of the mismatch. Without violations
        # this is tpuaudit's unexpected-collective; we do not double-report.
        if report.rule_violations and ep.expected_collectives is not None:
            counts = collect_collectives(
                lowered.as_text(),
                compiled.as_text() if compiled is not None else None)
            extra = {k: n for k, n in counts.items()
                     if k not in ep.expected_collectives}
            if extra:
                report.reshard_collectives = sum(extra.values())
                kinds = ", ".join(f"{k}×{n}" for k, n in sorted(extra.items()))
                findings.append(Finding(
                    "implicit-reshard", ep.name,
                    f"GSPMD inserted undeclared collectives ({kinds}) while "
                    f"{report.rule_violations} param(s) violate the "
                    f"{shard['policy']!r} rules — the reshard is the price "
                    f"of the mismatched operands"))

    if handoff is not None and handoff_sides is not None:
        side: Dict[str, Any] = {"entry": ep.name, "mesh": mesh}
        if handoff.get("role") == "export":
            side["shardings"] = (list(compiled.output_shardings)
                                 if compiled is not None else None)
            side["avals"] = list(traced.jaxpr.out_avals)
        else:
            buf_args = tuple(handoff.get("buffer_args", ()))
            shardings, avals = [], []
            for i in buf_args:
                avals.append(args[i])
                s = None
                if compiled is not None:
                    try:
                        s = compiled.input_shardings[0][i]
                    except Exception:   # noqa: BLE001
                        s = _sharding_of(args[i])
                shardings.append(s)
            side["shardings"] = shardings
            side["avals"] = avals
        handoff_sides[handoff.get("role", "?")] = side

    return findings, report


def _check_handoff(handoff_sides: Dict[str, Dict],
                   findings: List[Finding]) -> None:
    """KV-handoff geometry: the export program's output buffers must be
    laid out exactly like the import program's staging-buffer args — a
    mismatch means every migrated request's KV reshards mid-flight (the
    runtime twin is ``HandoffGeometryError``)."""
    exp, imp = handoff_sides.get("export"), handoff_sides.get("import")
    if not exp or not imp:
        return
    e_sh, i_sh = exp.get("shardings"), imp.get("shardings")
    e_av, i_av = exp.get("avals", []), imp.get("avals", [])
    if e_sh is None or i_sh is None:
        return
    if len(e_sh) != len(i_sh) or len(e_av) != len(i_av):
        findings.append(Finding(
            "cross-program-mismatch", exp["entry"],
            f"handoff arity mismatch: export produces {len(e_sh)} "
            f"buffer(s), import stages {len(i_sh)}"))
        return
    for k, (ea, ia, es, isx) in enumerate(zip(e_av, i_av, e_sh, i_sh)):
        e_shape = tuple(getattr(ea, "shape", ()))
        i_shape = tuple(getattr(ia, "shape", ()))
        if e_shape != i_shape or getattr(ea, "dtype", None) != getattr(
                ia, "dtype", None):
            findings.append(Finding(
                "cross-program-mismatch", exp["entry"],
                f"handoff buffer {k}: export emits "
                f"{e_shape}/{getattr(ea, 'dtype', '?')}, import expects "
                f"{i_shape}/{getattr(ia, 'dtype', '?')}"))
            continue
        if es is None or isx is None:
            continue
        try:
            ok = es.is_equivalent_to(isx, len(e_shape))
        except (TypeError, ValueError):
            ok = False
        if not ok:
            findings.append(Finding(
                "cross-program-mismatch", exp["entry"],
                f"handoff buffer {k}: export lands {_describe(es)} but "
                f"{imp['entry']} stages {_describe(isx)} — the fleet would "
                f"reshard every migrated request's KV"))


def _check_groups(group_params: Dict[Tuple[str, str], List],
                  findings: List[Finding]) -> None:
    """Same logical param, different sharding, inside one buffer-exchange
    group. Entries only compare when their leaf shapes/dtypes AND mesh
    geometry (axis names + sizes) agree — the precondition for actually
    exchanging live buffers; disjoint harness engines that merely share a
    group name never cross-fire."""
    def mesh_sig(sh):
        m = getattr(sh, "mesh", None)
        if m is None:
            return None
        return (tuple(m.axis_names), tuple(m.devices.shape))

    for (group, label), uses in sorted(group_params.items()):
        if len(uses) < 2:
            continue
        ref_entry, ref_sh, ref_sds = uses[0]
        for entry, sh, sds in uses[1:]:
            if (tuple(getattr(sds, "shape", ())) !=
                    tuple(getattr(ref_sds, "shape", ()))
                    or getattr(sds, "dtype", None) !=
                    getattr(ref_sds, "dtype", None)):
                continue
            sig_a, sig_b = mesh_sig(ref_sh), mesh_sig(sh)
            if sig_a is not None and sig_b is not None and sig_a != sig_b:
                continue
            ndim = len(getattr(sds, "shape", ()))
            try:
                ok = sh.is_equivalent_to(ref_sh, ndim)
            except (TypeError, ValueError):
                ok = False
            if not ok:
                findings.append(Finding(
                    "cross-program-mismatch", entry,
                    f"param {label}: sharded {_describe(sh)} here but "
                    f"{_describe(ref_sh)} in {ref_entry} (group "
                    f"{group!r}) — exchanging this buffer reshards it"))


# -- driver ------------------------------------------------------------------


def run_shard(entries: Sequence[EntryPoint],
              rule_overrides: Optional[Dict[str, Any]] = None,
              publish_metrics: bool = True,
              ) -> Tuple[List[Finding], List[EntryReport]]:
    """Analyze every entry; returns sorted findings + per-entry reports
    (reports only for entries carrying a layout contract)."""
    findings: List[Finding] = []
    reports: List[EntryReport] = []
    group_params: Dict[Tuple[str, str], List] = {}
    handoff_sides: Dict[str, Dict] = {}
    for ep in entries:
        fs, report = analyze_entry(ep, rule_overrides=rule_overrides,
                                   group_params=group_params,
                                   handoff_sides=handoff_sides)
        findings.extend(fs)
        if report is not None:
            reports.append(report)
    _check_handoff(handoff_sides, findings)
    _check_groups(group_params, findings)
    findings.sort(key=lambda f: (f.entry, f.check, f.message))
    if publish_metrics:
        _publish(reports, findings)
    return findings, reports


def _publish(reports: Sequence[EntryReport],
             findings: Sequence[Finding]) -> None:
    try:
        from deepspeed_tpu.observability import get_registry
    except ImportError:
        return
    reg = get_registry()
    reg.counter("tpushard/entries_analyzed",
                help="entry points with a layout contract analyzed by "
                     "tpushard").inc(len(reports))
    counter = reg.counter("tpushard/findings",
                          help="tpushard findings per entry point and check")
    for f in findings:
        counter.inc(entry=f.entry, check=f.check)
    for r in reports:
        for metric in ("params_total", "params_checked", "rule_violations",
                       "reshard_collectives", "replicated_bytes"):
            reg.gauge(f"tpushard/{r.entry}/{metric}").set(
                getattr(r, metric))
