"""metricsdoc — the metric-name ↔ documentation drift gate.

Every ``registry.counter/gauge/histogram("name", ...)`` metric published in
the tree must appear in the ``docs/observability.md`` metric table. The
table has grown by hand for 13+ PRs; without a gate, a new metric (or a
renamed one) silently drifts out of the documentation and dashboards built
from the table go stale.

Mechanics:

* **Publish side** — a stdlib-AST walk over the source tree collects the
  FIRST argument of every ``.counter(`` / ``.gauge(`` / ``.histogram(``
  call when it is a string literal. f-strings and variables are skipped
  (unverifiable statically); literal names are the contract.
* **Doc side** — backtick code spans on markdown-table lines (``|``-rows)
  of the doc. Spans expand the table's established shorthands:
  ``a/{x,y}_z``-style brace alternation, ``{label=,...}`` annotations
  (stripped — labels are not part of the name), ``<stat>`` wildcard
  segments, and trailing ``*`` wildcards (``Train/Samples/*``).
* A published name missing from the table is a finding; the gate exits 1.
  ``scripts/lint.sh`` runs this after tpulint.

Usage::

    python -m tools.tpulint.metricsdoc [--doc docs/observability.md]
                                       [paths...]
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, List, Tuple

DEFAULT_PATHS = ("deepspeed_tpu", "tools", "__graft_entry__.py")
DEFAULT_DOC = os.path.join("docs", "observability.md")
_METRIC_METHODS = ("counter", "gauge", "histogram")
_BACKTICK = re.compile(r"`([^`]+)`")


def collect_published(paths: List[str]) -> Dict[str, List[str]]:
    """name -> [file:line, ...] for every literal metric registration."""
    from .core import iter_python_files

    out: Dict[str, List[str]] = {}
    for path in iter_python_files(paths):
        try:
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METRIC_METHODS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                name = node.args[0].value
                out.setdefault(name, []).append(f"{path}:{node.lineno}")
    return out


def _expand(token: str) -> List[str]:
    """Expand one doc token into its concrete alternatives: label braces
    (``{k=,...}``) are stripped, alternation braces (``{a,b}`` / ``{a|b}``)
    multiply out."""
    m = re.search(r"\{([^{}]*)\}", token)
    if m is None:
        return [token]
    inner = m.group(1)
    head, tail = token[:m.start()], token[m.end():]
    if "=" in inner:
        return _expand(head + tail)       # label annotation, not the name
    alts = [a for part in inner.split(",") for a in part.split("|")]
    out: List[str] = []
    for alt in alts:
        out.extend(_expand(head + alt.strip() + tail))
    return out


def doc_patterns(doc_path: str) -> List[Tuple[str, re.Pattern]]:
    """(doc token, compiled pattern) for every backtick span on a table
    row. ``<seg>`` matches one path segment; a trailing ``*`` matches the
    rest of the name."""
    patterns: List[Tuple[str, re.Pattern]] = []
    with open(doc_path, encoding="utf-8") as fh:
        for line in fh:
            if not line.lstrip().startswith("|"):
                continue
            for span in _BACKTICK.findall(line):
                span = span.strip()
                if "/" not in span or " " in span:
                    continue              # prose / file references
                for tok in _expand(span):
                    rx = "".join(
                        "[^/]+" if part.startswith("<") else
                        ".*" if part == "*" else re.escape(part)
                        for part in re.split(r"(<[^<>]*>|\*)", tok) if part)
                    patterns.append((span, re.compile(rx + r"\Z")))
    return patterns


def find_undocumented(paths: List[str], doc_path: str
                      ) -> List[Tuple[str, List[str]]]:
    published = collect_published(paths)
    patterns = doc_patterns(doc_path)
    missing = []
    for name in sorted(published):
        if not any(rx.fullmatch(name) for _, rx in patterns):
            missing.append((name, published[name]))
    return missing


def main(argv: List[str]) -> int:
    doc = DEFAULT_DOC
    paths: List[str] = []
    it = iter(argv)
    for arg in it:
        if arg == "--doc":
            doc = next(it, doc)
        elif arg in ("-h", "--help"):
            print("usage: python -m tools.tpulint.metricsdoc "
                  "[--doc docs/observability.md] [paths...]")
            return 0
        else:
            paths.append(arg)
    if not paths:
        paths = [p for p in DEFAULT_PATHS if os.path.exists(p)]
    if not os.path.exists(doc):
        print(f"metricsdoc: doc not found: {doc}", file=sys.stderr)
        return 2
    missing = find_undocumented(paths, doc)
    if not missing:
        print(f"metricsdoc: OK — every literal metric name is documented "
              f"in {doc}")
        return 0
    print(f"metricsdoc: {len(missing)} metric name(s) published but "
          f"missing from {doc}'s metric table:", file=sys.stderr)
    for name, sites in missing:
        print(f"  {name}  ({sites[0]}"
              + (f" +{len(sites) - 1}" if len(sites) > 1 else "") + ")",
              file=sys.stderr)
    print("add a table row (see docs/observability.md 'What gets recorded "
          "where') or rename the metric", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
