"""The eight tpulint rules.

Each rule is small and heuristic by design: the goal is catching the silent
TPU performance/correctness failure modes (host syncs, trace-time side
effects, missed donation, phantom mesh axes, removed APIs, PRNG reuse) at
review time, with inline suppressions as the escape hatch for intentional
cases.
"""

from __future__ import annotations

import ast
from typing import Iterator, Optional, Set

from .core import Finding, ModuleInfo, Rule, RunContext, own_nodes, register
from .jitgraph import JitGraph

# ---------------------------------------------------------------------------
# shared helpers


def collect_declared_axes(module: ModuleInfo) -> Set[str]:
    """Mesh axis names this module declares.

    Sources: ``FOO_AXIS = "foo"`` constants and ``*AXES`` string tuples
    (parallel/mesh.py idiom), plus literal axis tuples / ``axis_names=``
    passed to a ``Mesh(...)`` constructor (test-fixture idiom).
    """
    axes: Set[str] = set()

    def strings_of(node: ast.AST) -> Iterator[str]:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            yield node.value
        elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            for elt in node.elts:
                yield from strings_of(elt)

    for node in ast.walk(module.tree):
        if isinstance(node, ast.Assign):
            for target in node.targets:
                if isinstance(target, ast.Name) and (
                        target.id.endswith("_AXIS") or target.id.endswith("AXES")):
                    axes.update(strings_of(node.value))
        elif isinstance(node, ast.Call):
            dotted = module.dotted(node.func) or ""
            if dotted.rpartition(".")[2] == "Mesh":
                if len(node.args) >= 2:
                    axes.update(strings_of(node.args[1]))
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        axes.update(strings_of(kw.value))
    return axes


def _call_args(node: ast.Call) -> Iterator[ast.AST]:
    yield from node.args
    for kw in node.keywords:
        yield kw.value


def _finding(rule: Rule, module: ModuleInfo, node: ast.AST, message: str) -> Finding:
    return Finding(rule.name, module.path, getattr(node, "lineno", 0),
                   getattr(node, "col_offset", 0), message)


# ---------------------------------------------------------------------------
# 1. host-sync-in-jit


@register
class HostSyncInJit(Rule):
    name = "host-sync-in-jit"
    description = ("device->host transfer or blocking sync reachable from a "
                   "jit-compiled function (forces a round-trip / trace error)")

    _SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
    _SYNC_DOTTED = {
        "numpy.asarray", "numpy.array", "numpy.copy",
        "jax.device_get", "jax.block_until_ready",
    }
    _CAST_BUILTINS = {"float", "int", "bool"}

    def check(self, module: ModuleInfo, jit: JitGraph,
              context: RunContext) -> Iterator[Finding]:
        for fn in jit.reachable:
            for node in own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if isinstance(func, ast.Attribute) and func.attr in self._SYNC_ATTRS:
                    yield _finding(self, module, node,
                                   f".{func.attr}() blocks on device inside a "
                                   "jit-reachable function")
                    continue
                dotted = module.dotted(func)
                if dotted in self._SYNC_DOTTED:
                    yield _finding(self, module, node,
                                   f"{dotted}() pulls values to host inside a "
                                   "jit-reachable function")
                elif (isinstance(func, ast.Name)
                      and func.id in self._CAST_BUILTINS
                      and len(node.args) == 1
                      and not isinstance(node.args[0], ast.Constant)):
                    yield _finding(self, module, node,
                                   f"{func.id}() on a traced value concretizes "
                                   "(host sync or trace-time error) inside a "
                                   "jit-reachable function")


# ---------------------------------------------------------------------------
# 2. impure-jit


@register
class ImpureJit(Rule):
    name = "impure-jit"
    description = ("Python side effect inside a jit-compiled function — runs "
                   "once at trace time, not per step")

    _IMPURE_PREFIXES = ("time.", "random.", "numpy.random.")

    def check(self, module: ModuleInfo, jit: JitGraph,
              context: RunContext) -> Iterator[Finding]:
        for fn in jit.reachable:
            for node in own_nodes(fn):
                if isinstance(node, ast.Global):
                    yield _finding(self, module, node,
                                   "global statement inside a jit-reachable "
                                   "function (trace-time mutation)")
                elif isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        if isinstance(t, ast.Attribute):
                            yield _finding(
                                self, module, node,
                                f"attribute mutation '{ast.unparse(t)} = ...' "
                                "inside a jit-reachable function happens at "
                                "trace time only")
                elif isinstance(node, ast.Call):
                    dotted = module.dotted(node.func)
                    if dotted == "print":
                        yield _finding(self, module, node,
                                       "print() inside a jit-reachable function "
                                       "fires at trace time only — use "
                                       "jax.debug.print")
                    elif dotted and dotted.startswith(self._IMPURE_PREFIXES):
                        yield _finding(self, module, node,
                                       f"{dotted}() is host-side nondeterminism/"
                                       "clock inside a jit-reachable function "
                                       "(baked in at trace time)")


# ---------------------------------------------------------------------------
# 3. missing-donation


@register
class MissingDonation(Rule):
    name = "missing-donation"
    description = ("jitted step/update takes and returns a params/opt-state "
                   "pytree without donate_argnums — doubles peak HBM")

    _DONATABLE = {"params", "param", "opt_state", "opt_states", "state",
                  "optimizer_state", "scaler_state", "master_params"}

    def _donatable_roundtrip(self, fn: ast.AST) -> Optional[str]:
        """Name of a donatable parameter that the function also returns."""
        args = getattr(fn, "args", None)
        if args is None:
            return None
        names = {a.arg for a in
                 list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)}
        candidates = names & self._DONATABLE
        if not candidates:
            return None
        returned: Set[str] = set()
        for node in own_nodes(fn):
            if isinstance(node, ast.Return) and node.value is not None:
                vals = node.value.elts if isinstance(node.value, ast.Tuple) \
                    else [node.value]
                for v in vals:
                    if isinstance(v, ast.Name):
                        returned.add(v.id)
        for cand in sorted(candidates):
            if cand in returned or f"new_{cand}" in returned:
                return cand
        return None

    def check(self, module: ModuleInfo, jit: JitGraph,
              context: RunContext) -> Iterator[Finding]:
        seen: Set[int] = set()
        # decorator form: @jax.jit def step(params, ...) -> ... return params'
        for fn in jit.roots:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            decs = [d for d in fn.decorator_list if jit._is_jit_expr(d)]
            if not decs or any(jit.binding_donates(d) for d in decs):
                continue
            cand = self._donatable_roundtrip(fn)
            if cand and id(fn) not in seen:
                seen.add(id(fn))
                yield _finding(self, module, fn,
                               f"jitted '{fn.name}' takes and returns "
                               f"'{cand}' without donate_argnums — old "
                               "buffers stay live (2x HBM)")
        # call-wrapping form: jax.jit(step) / jax.jit(lambda ...)
        for binding in jit.jit_bindings:
            if not isinstance(binding, ast.Call) or jit.binding_donates(binding):
                continue
            target = jit.binding_target(binding)
            if target is None or id(target) in seen:
                continue
            cand = self._donatable_roundtrip(target)
            if cand:
                seen.add(id(target))
                label = getattr(target, "name", "<lambda>")
                yield _finding(self, module, binding,
                               f"jax.jit('{label}') takes and returns "
                               f"'{cand}' without donate_argnums — old "
                               "buffers stay live (2x HBM)")


# ---------------------------------------------------------------------------
# 4. unknown-mesh-axis


@register
class UnknownMeshAxis(Rule):
    name = "unknown-mesh-axis"
    description = ("PartitionSpec/shard_map/collective references a mesh axis "
                   "name no mesh declares — shards nothing, silently")

    _COLLECTIVES = {
        "psum", "pmean", "pmax", "pmin", "ppermute", "pshuffle", "psum_scatter",
        "all_gather", "all_reduce", "reduce_scatter", "all_to_all", "broadcast",
        "send_next", "send_prev", "axis_index", "axis_size", "axis_rank",
    }

    def _strings_of(self, node: ast.AST) -> Iterator[ast.AST]:
        """Constant-string nodes, through one level of tuple/list/set nesting."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            yield node
        elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            for elt in node.elts:
                yield from self._strings_of(elt)

    def check(self, module: ModuleInfo, jit: JitGraph,
              context: RunContext) -> Iterator[Finding]:
        declared = context.declared_axes
        if not declared:
            return  # nothing to validate against in this run
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = module.dotted(node.func) or ""
            leaf = dotted.rpartition(".")[2]
            if leaf == "PartitionSpec":
                for s in node.args:
                    for c in self._strings_of(s):
                        if c.value not in declared:
                            yield _finding(
                                self, module, c,
                                f"PartitionSpec axis '{c.value}' is not "
                                f"declared by any mesh (known: "
                                f"{', '.join(sorted(declared))})")
            elif leaf == "shard_map":
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        for c in self._strings_of(kw.value):
                            if c.value not in declared:
                                yield _finding(
                                    self, module, c,
                                    f"shard_map axis '{c.value}' is not "
                                    "declared by any mesh")
            if leaf in self._COLLECTIVES:
                for kw in node.keywords:
                    if kw.arg in {"axis", "axis_name"}:
                        for c in self._strings_of(kw.value):
                            if c.value not in declared:
                                yield _finding(
                                    self, module, c,
                                    f"collective {leaf}() names axis "
                                    f"'{c.value}' that no mesh declares")


# ---------------------------------------------------------------------------
# 5. deprecated-jax-api


@register
class DeprecatedJaxApi(Rule):
    name = "deprecated-jax-api"
    description = "JAX API that is deprecated/removed in current releases"

    _PREFIXES = ("jax.experimental.pjit", "jax.experimental.maps",
                 "jax.experimental.shard_map")
    _EXACT = {
        "jax.tree_map": "use jax.tree.map (or jax.tree_util.tree_map)",
        "jax.tree_multimap": "use jax.tree.map",
        "jax.experimental.pjit": "jit handles shardings; use jax.jit",
        "jax.experimental.maps": "removed; use jax.shard_map / jax.jit",
        "jax.experimental.shard_map":
            "use jax.shard_map (check_vma / axis_names)",
        "jax.experimental.pallas.tpu.TPUCompilerParams":
            "removed; use pltpu.CompilerParams",
    }

    def _advice(self, dotted: str) -> str:
        for prefix in sorted(self._EXACT, key=len, reverse=True):
            if dotted == prefix or dotted.startswith(prefix + "."):
                return self._EXACT[prefix]
        return "migrate to the current API"

    def check(self, module: ModuleInfo, jit: JitGraph,
              context: RunContext) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.startswith(self._PREFIXES):
                        yield _finding(self, module, node,
                                       f"import of deprecated '{a.name}' — "
                                       f"{self._advice(a.name)}")
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.module.startswith(self._PREFIXES):
                    yield _finding(self, module, node,
                                   f"import from deprecated '{node.module}' — "
                                   f"{self._advice(node.module)}")
            elif isinstance(node, ast.Attribute):
                # only the outermost attribute of a chain, once
                if isinstance(module.parents.get(node), ast.Attribute):
                    continue
                dotted = module.dotted(node)
                if dotted and (dotted in self._EXACT
                               or dotted.startswith(self._PREFIXES)):
                    yield _finding(self, module, node,
                                   f"deprecated '{dotted}' — "
                                   f"{self._advice(dotted)}")


# ---------------------------------------------------------------------------
# 6. wallclock-timing-without-sync


@register
class WallclockTimingWithoutSync(Rule):
    name = "wallclock-timing-without-sync"
    description = ("time.time()/time.perf_counter() delta measured around "
                   "dispatched work with no blocking fence between — async "
                   "dispatch means the delta times the enqueue, not the work")

    _CLOCKS = {"time.time", "time.perf_counter", "time.monotonic"}
    _SYNC_ATTRS = {"block_until_ready", "item", "tolist"}
    _SYNC_DOTTED = {"jax.block_until_ready", "jax.device_get",
                    "jax.effects_barrier", "numpy.asarray", "numpy.array"}
    _SYNC_BUILTINS = {"float", "int", "bool"}
    # calls that cannot enqueue device work — ignored when deciding whether
    # the timed interval contains anything worth fencing
    _BENIGN_DOTTED_PREFIXES = (
        "time.", "os.", "sys.", "json.", "math.", "logging.", "collections.",
        "itertools.", "functools.", "re.", "subprocess.", "argparse.",
    )
    _BENIGN_NAMES = {
        "print", "len", "range", "sorted", "min", "max", "sum", "abs",
        "round", "str", "repr", "open", "isinstance", "getattr", "hasattr",
        "setattr", "enumerate", "zip", "list", "dict", "set", "tuple",
        "next", "iter", "log_dist", "super", "type", "id", "format", "vars",
    }
    _BENIGN_ATTRS = {
        "append", "extend", "add", "update", "join", "format", "split",
        "strip", "items", "keys", "values", "get", "pop", "setdefault",
        "write", "flush", "read", "close", "info", "debug", "warning",
        "error", "exception", "mean", "startswith", "endswith", "copy",
        # AOT lowering/compilation runs synchronously on the host — timing
        # it needs no device fence
        "lower", "compile",
        # mesh context-manager factory (parallel/mesh.py ambient idiom)
        # dispatches nothing
        "ambient",
    }

    def _is_clock_call(self, module: ModuleInfo, node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and module.dotted(node.func) in self._CLOCKS)

    def _classify(self, module: ModuleInfo, call: ast.Call,
                  syncing_defs: Set[str]) -> str:
        """'sync' | 'benign' | 'work' for one call in the timed interval."""
        func = call.func
        if isinstance(func, ast.Name) and func.id in syncing_defs:
            # locally-defined helper whose body fences — calling it syncs
            return "sync"
        if isinstance(func, ast.Attribute):
            if func.attr in self._SYNC_ATTRS:
                return "sync"
            if func.attr in self._BENIGN_ATTRS:
                return "benign"
        dotted = module.dotted(func)
        if dotted in self._SYNC_DOTTED:
            return "sync"
        if dotted in self._CLOCKS:
            return "benign"
        if isinstance(func, ast.Name):
            if (func.id in self._SYNC_BUILTINS and len(call.args) == 1
                    and not isinstance(call.args[0], ast.Constant)):
                return "sync"          # float(loss) materialises the array
            if func.id in self._BENIGN_NAMES:
                return "benign"
        if dotted and (dotted in self._BENIGN_NAMES
                       or dotted.startswith(self._BENIGN_DOTTED_PREFIXES)):
            return "benign"
        return "work"

    def _syncing_defs(self, module: ModuleInfo, scope: ast.AST) -> Set[str]:
        """Names of functions defined in this scope whose own body contains a
        blocking fence — calling them from a timed interval counts as sync."""
        out: Set[str] = set()
        for node in own_nodes(scope):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for inner in own_nodes(node):
                if not isinstance(inner, ast.Call):
                    continue
                func = inner.func
                if ((isinstance(func, ast.Attribute)
                     and func.attr in self._SYNC_ATTRS)
                        or module.dotted(func) in self._SYNC_DOTTED):
                    out.add(node.name)
                    break
        return out

    def _scan_scope(self, module: ModuleInfo, scope: ast.AST) -> Iterator[Finding]:
        nodes = list(own_nodes(scope))
        # clock-start assignments: name -> sorted start linenos
        starts: dict = {}
        for node in nodes:
            if (isinstance(node, ast.Assign)
                    and self._is_clock_call(module, node.value)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                starts.setdefault(node.targets[0].id, []).append(node.lineno)
        if not starts:
            return
        syncing_defs = self._syncing_defs(module, scope)
        calls = [n for n in nodes if isinstance(n, ast.Call)]
        for node in nodes:
            # delta = clock() - t0   (possibly nested, e.g. xs.append(...))
            if not (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)
                    and self._is_clock_call(module, node.left)
                    and isinstance(node.right, ast.Name)
                    and node.right.id in starts):
                continue
            begin = max((ln for ln in starts[node.right.id]
                         if ln < node.lineno), default=None)
            if begin is None:
                continue
            between = [c for c in calls if begin < c.lineno <= node.lineno
                       and c is not node.left]
            kinds = [(self._classify(module, c, syncing_defs), c.lineno)
                     for c in between]
            work_lines = [ln for k, ln in kinds if k == "work"]
            sync_lines = [ln for k, ln in kinds if k == "sync"]
            # work dispatched AFTER the last fence is still unfenced at the
            # closing clock read — one early fence does not bless the rest
            if work_lines and (not sync_lines
                               or max(work_lines) > max(sync_lines)):
                yield _finding(
                    self, module, node,
                    f"wall-clock delta over '{node.right.id}' spans "
                    "dispatched calls with no fence (block_until_ready / "
                    "device_get / float()) before reading the clock — "
                    "under async dispatch this times the enqueue only")

    def check(self, module: ModuleInfo, jit: JitGraph,
              context: RunContext) -> Iterator[Finding]:
        # a module that never imports jax cannot dispatch async device work
        if not any(v == "jax" or v.startswith("jax.")
                   for v in module.aliases.values()):
            return
        scopes = [module.tree] + [f for f in jit.all_defs
                                  if isinstance(f, (ast.FunctionDef,
                                                    ast.AsyncFunctionDef))]
        for scope in scopes:
            yield from self._scan_scope(module, scope)


# ---------------------------------------------------------------------------
# 7. hardcoded-partition-spec


@register
class HardcodedPartitionSpec(Rule):
    name = "hardcoded-partition-spec"
    description = ("PartitionSpec built from literal mesh-axis strings "
                   "outside the rule registry (parallel/rules.py) — layout "
                   "decisions the tpushard analyzer cannot see or audit")

    _EXEMPT_SUFFIXES = (
        # THE place mesh-axis placement is allowed to be spelled out: the
        # logical-axis rule registry itself, and the mesh module that
        # defines the axis vocabulary the registry maps onto
        "parallel/rules.py",
        "parallel/mesh.py",
    )

    def _is_test_path(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        base = norm.rpartition("/")[2]
        return ("/tests/" in norm or norm.startswith("tests/")
                or base.startswith("test_") or base.endswith("_test.py"))

    def _strings_of(self, node: ast.AST) -> Iterator[ast.AST]:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            yield node
        elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            for elt in node.elts:
                yield from self._strings_of(elt)

    def check(self, module: ModuleInfo, jit: JitGraph,
              context: RunContext) -> Iterator[Finding]:
        norm = module.path.replace("\\", "/")
        if norm.endswith(self._EXEMPT_SUFFIXES) or self._is_test_path(norm):
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = module.dotted(node.func) or ""
            if dotted.rpartition(".")[2] != "PartitionSpec":
                continue
            literals = [c.value for arg in node.args
                        for c in self._strings_of(arg)]
            if literals:
                yield _finding(
                    self, module, node,
                    f"PartitionSpec({', '.join(repr(s) for s in literals)}) "
                    "hardcodes mesh axes outside parallel/rules.py — derive "
                    "the placement from the rule registry (or suppress if "
                    "this spec is genuinely not a parameter/output layout)")


# ---------------------------------------------------------------------------
# 8. key-reuse


@register
class KeyReuse(Rule):
    name = "key-reuse"
    description = ("a PRNGKey consumed by more than one call without split — "
                   "correlated randomness")

    _KEY_MAKERS = {"jax.random.PRNGKey", "jax.random.key"}

    def _scan_scope(self, module: ModuleInfo, scope: ast.AST) -> Iterator[Finding]:
        events = sorted(
            (n for n in own_nodes(scope) if isinstance(n, (ast.Assign, ast.Call))),
            key=lambda n: (n.lineno, n.col_offset))
        uses = {}  # var name -> consumption count
        for node in events:
            if isinstance(node, ast.Assign):
                if (isinstance(node.value, ast.Call)
                        and module.dotted(node.value.func) in self._KEY_MAKERS
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    uses[node.targets[0].id] = 0
                else:
                    # any rebinding kills tracking, including tuple unpacks
                    # like `key, sub = jax.random.split(key)`
                    for t in node.targets:
                        for name in ast.walk(t):
                            if isinstance(name, ast.Name):
                                uses.pop(name.id, None)
            else:  # Call: every argument position consumes
                for arg in _call_args(node):
                    if isinstance(arg, ast.Name) and arg.id in uses:
                        uses[arg.id] += 1
                        if uses[arg.id] == 2:
                            yield _finding(
                                self, module, node,
                                f"PRNGKey '{arg.id}' is consumed by a second "
                                "call without jax.random.split — both sites "
                                "draw identical randomness")

    def check(self, module: ModuleInfo, jit: JitGraph,
              context: RunContext) -> Iterator[Finding]:
        scopes = [module.tree] + [f for f in jit.all_defs
                                  if isinstance(f, (ast.FunctionDef,
                                                    ast.AsyncFunctionDef))]
        for scope in scopes:
            yield from self._scan_scope(module, scope)
