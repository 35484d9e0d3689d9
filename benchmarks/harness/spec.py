"""BENCHMARK.json and the data files its names point to.

A cell names a configuration and a traffic mix; `configs/<config>.json`,
`traffic/<traffic>.json` and `layer_metrics/<metric>.json` are found by those
names and nowhere else, so a later PR adds a cell by adding files and
entries. A configuration's file also says what the harness needs to know of
its family: which of the program's sizes is which of the source's (`widths`),
what its plain reference is called with (`reference_args`), the small
model of the same family that the CPU rehearsal runs (`tiny`) and, where the
cell is one chip's share of a deployment that divides each layer over
several, that deployment (`share`). `validate` holds the files to the parts
of the contract that a run on the CPU can check.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_KINDS = ("train", "closed_loop", "open_loop")
# widths a configuration may never cut (the contract's rule for `reduced`)
WIDTH_WORDS = ("hidden_size", "intermediate", "ffn", "latent", "state_size",
               "head_dim", "head_size", "expansion", "experts_per_tok",
               "proj")
# counts that a deployment may divide among the chips that share a layer: a
# configuration's file accounts for them as for its widths, and holds them
# whole unless its `share` block names them
COUNT_WORDS = ("heads", "n_head", "vocab_size", "experts")
# the one kind of size a configuration may cut with no deployment stated
DEPTH_WORDS = ("layer",)
TINY_KEYS = {"preset", "dtype", "overrides", "reference_args"}
SHARE_KEYS = {"chips", "divided", "how"}
# optional in a share that divides an expert count, `"placement":
# "balanced"`: the held experts PLACED in balance before the first request
# (`program.place_held_experts`); what a family's reference then supplies
PLACEMENT = "balanced"
PLACES = "place_held_experts"
# the floors of the `model-configs` guide, section 4, for a chip's share
MIN_EXPERTS_HELD = 8
MAX_WAYS_OVER_A_VOCABULARY = 8
MIN_LAYERS_OF_A_SHARE = 4


def names_a_width(key: str) -> bool:
    return (any(w in key for w in WIDTH_WORDS)
            or key.endswith(("_dim", "_rank")))


def names_a_size(key: str, value: Any) -> bool:
    """A number of the source that its file has to map to the program's."""
    return (isinstance(value, (int, float)) and not isinstance(value, bool)
            and (names_a_width(key) or any(w in key for w in COUNT_WORDS)))


def names_depth(key: str) -> bool:
    return any(w in key for w in DEPTH_WORDS)


def names_a_count(key: str, value: Any) -> bool:
    """A size that chips sharing a layer may divide among them: how many
    experts, heads or rows of the vocabulary, never how wide."""
    return names_a_size(key, value) and not names_a_width(key)


def share_ways(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Source key -> the number of ways the chips that share a layer divide
    it; `{}` for a file that states no `share`. The one place that tells the
    two forms of `share.divided` apart: a list divides every key it names
    over all of `share.chips`, an object gives each key its own ways."""
    share = cfg.get("share")
    if not share:
        return {}
    divided = share["divided"]
    if isinstance(divided, dict):
        return dict(divided)
    return {key: share["chips"] for key in divided}


class SpecError(ValueError):
    """BENCHMARK.json or a file it names breaks the contract."""


def expert_ways(cfg: Dict[str, Any]) -> int:
    """The ways the file's `share` divides its experts (the one divided key
    that counts experts): what a placement of the held experts reads."""
    ways = [w for key, w in share_ways(cfg).items() if "experts" in key]
    if len(ways) != 1:
        raise SpecError(f"share.divided names {len(ways)} counts of "
                        "experts; a placement of the held experts needs "
                        "one")
    return ways[0]


def _load(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: not JSON ({e})") from None


@dataclasses.dataclass
class Cell:
    """One entry of `workloads` with everything its names resolve to."""

    name: str
    chips: int
    bench_dir: str                  # where references/<family>.py is found
    config_name: str
    traffic_name: str
    config: Dict[str, Any]          # configs/<config>.json
    traffic: Dict[str, Any]         # traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]    # BENCHMARK.json entries reported here
    per_layer: List[Dict[str, Any]]     # each with its layer_metrics file
    #   merged in under "reader": {"reducer": ..., "args": {...}}


class Spec:
    """BENCHMARK.json under `root`, with the benchmark's files under the
    first of its `paths`."""

    def __init__(self, root: str = REPO_ROOT):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.doc["paths"][0])

    def path(self, *parts: str) -> str:
        return os.path.join(self.bench_dir, *parts)

    @staticmethod
    def _in_cell(metric: Dict[str, Any], cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def reader(self, metric: str) -> Dict[str, Any]:
        return _load(self.path("layer_metrics", f"{metric}.json"))

    def cell(self, name: str) -> Cell:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                break
        else:
            known = ", ".join(x["name"] for x in self.doc["workloads"])
            raise SpecError(f"no workload '{name}' (known: {known})")
        cfg_entry = next((c for c in self.doc["configs"]
                          if c["name"] == w["config"]), None)
        if cfg_entry is None:
            raise SpecError(f"workload {name}: no config '{w['config']}'")
        per_layer = []
        for m in self.doc["per_layer"]:
            if self._in_cell(m, name):
                per_layer.append(dict(m, reader=self.reader(m["name"])))
        return Cell(
            name=name, chips=int(w["chips"]), bench_dir=self.bench_dir,
            config_name=w["config"],
            traffic_name=w["traffic"],
            config=_load(os.path.join(self.root, cfg_entry["file"])),
            traffic=_load(self.path("traffic", f"{w['traffic']}.json")),
            end_to_end=[m for m in self.doc["end_to_end"]
                        if self._in_cell(m, name)],
            per_layer=per_layer)

    @staticmethod
    def _validate_share(cfg: Dict[str, Any], reduced, bad) -> Dict[str, int]:
        """The file's optional `share` block: the cell is what ONE of `chips`
        chips that share each layer would hold. Returns, for each source key
        it divides, the count held here (exactly `published / ways`, the
        key's own ways: `share_ways`); `{}` for a file that states no
        deployment."""
        if "share" not in cfg:
            return {}
        share, published = cfg["share"], cfg["published"]
        if (not isinstance(share, dict)
                or set(share) - {"placement"} != SHARE_KEYS):
            has = sorted(share) if isinstance(share, dict) else share
            raise bad(f"share has keys {has!r}, not {sorted(SHARE_KEYS)} "
                      "(and, where it divides experts, 'placement')")
        chips, divided = share["chips"], share["divided"]
        if isinstance(chips, bool) or not isinstance(chips, int) or chips < 2:
            raise bad(f"share.chips is {chips!r}: a share is of 2 chips or "
                      "more that divide each layer among them")
        if (not isinstance(divided, (list, dict)) or not divided
                or len(set(divided)) != len(divided)):
            raise bad("share.divided lists, once each, the keys of "
                      "'published' that the chips divide (or gives each its "
                      "own number of ways)")
        if not isinstance(share["how"], str) or not share["how"].strip():
            raise bad("share.how says what is divided over the chips that "
                      "share a layer and what every chip holds whole")
        ways = share_ways(cfg)
        if isinstance(divided, dict):
            for key, w in ways.items():
                if isinstance(w, bool) or not isinstance(w, int) or w < 2:
                    raise bad(f"share.divided.{key} is {w!r}: the ways the "
                              f"chips divide {key}, a whole number of 2 or "
                              "more (a count held whole is not listed)")
                if chips % w:
                    raise bad(f"share.divided.{key} is {w} ways, which do "
                              f"not divide share.chips of {chips}: a count "
                              "divided w ways over c chips lives in c / w "
                              "copies")
            key, most = max(ways.items(), key=lambda kw: kw[1])
            if most != chips:
                raise bad(f"share.chips is {chips} and nothing is divided "
                          f"{chips} ways (the most: {key} {most} ways): "
                          "chips counts the chips that share each layer, so "
                          "this states the wrong deployment")
        held = {}
        for key, w in ways.items():
            if key not in published:
                raise bad(f"share.divided: '{key}' is no key of 'published'")
            if names_a_width(key):
                raise bad(f"share.divided: '{key}' is a width, and no width "
                          "is ever cut: a share divides a count (experts, "
                          "heads, rows of the vocabulary)")
            if not names_a_count(key, published[key]):
                raise bad(f"share.divided: '{key}' names no count that "
                          "chips can divide (experts, heads, vocabulary)")
            if key not in reduced:
                raise bad(f"share.divided names '{key}', which 'reduced' "
                          "does not list: the count held here is a change "
                          "from the source")
            if published[key] % w:
                raise bad(f"share: {chips} chips do not divide the source's "
                          f"{key} of {published[key]} {w} ways (remainder "
                          f"{published[key] % w})")
            held[key] = published[key] // w
            if "experts" in key and held[key] < MIN_EXPERTS_HELD:
                raise bad(f"share: {key} {w} ways: {held[key]} of "
                          f"{published[key]} {key} held; the floor is "
                          f"{MIN_EXPERTS_HELD} experts in each layer that "
                          "has them")
            if "vocab" in key and w > MAX_WAYS_OVER_A_VOCABULARY:
                raise bad(f"share: {key} over {w} chips; the floor is an "
                          "eighth of the vocabulary (at most "
                          f"{MAX_WAYS_OVER_A_VOCABULARY} ways, however many "
                          "chips share the layer)")
        return held

    def _validate_placement(self, cfg: Dict[str, Any], bad) -> None:
        """`share.placement`: the held experts placed in balance on the
        harness's calibration batch from the seed. Only a share that divides
        ONE count of experts can state it, only as `"balanced"`, and only
        where the family's reference has the function that places."""
        try:
            expert_ways(cfg)
        except SpecError as e:
            raise bad(f"share.placement: {e}") from None
        placement = cfg["share"]["placement"]
        if placement != PLACEMENT:
            raise bad(f"share.placement is {placement!r}: the one placement "
                      f"is {PLACEMENT!r} (without the key the held experts "
                      "are the router's first)")
        with open(self.path("references", f"{cfg['reference']}.py")) as f:
            defined = {node.name for node in ast.parse(f.read()).body
                       if isinstance(node, ast.FunctionDef)}
        if PLACES not in defined:
            raise bad(f"share.placement, and references/{cfg['reference']}"
                      f".py has no {PLACES}")

    def _validate_config(self, entry: Dict[str, Any]) -> None:
        """What a configuration's file says of its family holds together:
        no size is written that the source does not have, and the program
        runs every size as published but the depth, where `reduced` says,
        and the counts that a stated deployment (`share`) divides among the
        chips that share a layer, each at exactly this chip's part."""
        cfg = _load(os.path.join(self.root, entry["file"]))

        def bad(what):
            return SpecError(f"config {entry['name']} ({entry['file']}): "
                             f"{what}")

        for block in ("published", "model", "reference", "widths",
                      "reference_args", "tiny"):
            if block not in cfg:
                raise bad(f"no '{block}'")
        published, widths = cfg["published"], cfg["widths"]
        equal = cfg.get("equal_widths", {})
        overrides = cfg["model"]["overrides"]
        if not os.path.exists(self.path("references",
                                        f"{cfg['reference']}.py")):
            raise bad(f"no references/{cfg['reference']}.py")
        if set(cfg.get("reduced", {})) != set(entry["reduced"]):
            raise bad("'reduced' differs from BENCHMARK.json's")
        held = self._validate_share(cfg, entry["reduced"], bad)
        if "placement" in cfg.get("share", {}):
            self._validate_placement(cfg, bad)
        ways = share_ways(cfg)
        for key in entry["reduced"]:
            if ((key in widths.values() or key in equal)
                    and not names_depth(key) and key not in held):
                raise bad(f"'{key}' is in 'reduced' and does not name the "
                          "depth: of the sizes in 'widths', only the number "
                          "of layers may be cut, or a count that a 'share' "
                          "block lists under 'divided'")
        for key, source in widths.items():
            if source not in published:
                raise bad(f"widths: '{source}' is no key of 'published'")
            if key not in overrides:
                raise bad(f"model.overrides leaves '{key}' to the preset")
            if source in held:
                # held whole (a router keeps its published width) or exactly
                # this chip's part: no other number is a share
                if overrides[key] not in (published[source], held[source]):
                    raise bad(f"model.overrides.{key} is {overrides[key]}: "
                              f"of the source's {source} a chip of "
                              f"{cfg['share']['chips']}, which divide "
                              f"{source} {ways[source]} ways, holds all "
                              f"{published[source]} or its share of "
                              f"{held[source]}, nothing else")
            elif (overrides[key] != published[source]
                    and source not in entry["reduced"]):
                raise bad(f"model.overrides.{key} is {overrides[key]}, the "
                          f"source's {source} is {published[source]}, and "
                          f"'{source}' is not in 'reduced'")
        for source, part in held.items():
            if part not in [overrides[k] for k, s in widths.items()
                            if s == source]:
                raise bad(f"share.divided names '{source}' "
                          f"{ways[source]} ways, and no key that "
                          f"'widths' maps to it holds the share of {part}")
        if held:
            depth = [overrides[k] for k, s in widths.items()
                     if names_depth(s)]
            if not depth or min(depth) < MIN_LAYERS_OF_A_SHARE:
                raise bad(f"a share runs {depth} layers; the floor is "
                          f"{MIN_LAYERS_OF_A_SHARE} (whole periods of the "
                          "layer pattern: the reviewer's to check)")
        for key, other in equal.items():
            if (key not in published or other not in widths.values()
                    or published[key] != published[other]):
                raise bad(f"equal_widths: '{key}' must be a key of "
                          f"'published' with the number of '{other}', and "
                          "'widths' must map that one")
        for key, value in published.items():
            if (names_a_size(key, value) and key not in widths.values()
                    and key not in equal):
                raise bad(f"'{key}' names a size: map it in 'widths', or "
                          "name under 'equal_widths' the mapped key whose "
                          "number it has")
        for arg, source in cfg["reference_args"].items():
            if set(source) != {"published"} \
                    or source["published"] not in published:
                raise bad(f"reference_args.{arg} must be "
                          '{"published": <a key of \'published\'>}')
        tiny = cfg["tiny"]
        if set(tiny) != TINY_KEYS:
            raise bad(f"tiny has keys {sorted(tiny)}, not {sorted(TINY_KEYS)}")
        if set(tiny["reference_args"]) != set(cfg["reference_args"]):
            raise bad("tiny.reference_args names other arguments than "
                      "reference_args")
        left = sorted(set(widths) - set(tiny["overrides"]))
        if left:
            raise bad(f"tiny.overrides leaves {left} to the preset")

    # -- the contract, as far as it can be checked without a chip ---------
    def validate(self) -> None:
        d = self.doc
        want = {"command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"}
        if set(d) != want:
            raise SpecError(f"BENCHMARK.json keys {sorted(d)} != {sorted(want)}")
        if not 1 <= int(d["run_seconds"]) <= 51:
            raise SpecError("run_seconds outside 1..51")

        def names(entries, what):
            seen = set()
            for e in entries:
                if not NAME_RE.match(e["name"]):
                    raise SpecError(f"{what} name '{e['name']}' not allowed")
                if e["name"] in seen:
                    raise SpecError(f"{what} name '{e['name']}' twice")
                seen.add(e["name"])
            return seen

        configs = names(d["configs"], "config")
        cells = names(d["workloads"], "workload")
        names(d["end_to_end"] + d["per_layer"], "metric")
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if "setup_s" not in e2e:
            raise SpecError("no setup_s among end_to_end")
        if "workloads" in e2e["setup_s"]:
            raise SpecError("setup_s must be reported by every cell")
        for c in d["configs"]:
            for key in c["reduced"]:
                if names_a_width(key):
                    raise SpecError(f"config {c['name']}: reduces a width "
                                    f"('{key}')")
            if not c["file"].startswith(tuple(p + "/" for p in d["paths"])):
                raise SpecError(f"config file {c['file']} outside paths")
            self._validate_config(c)
        pairs = set()
        for w in d["workloads"]:
            if w["config"] not in configs:
                raise SpecError(f"workload {w['name']}: unknown config")
            if w["chips"] not in (1, 4):
                raise SpecError(f"workload {w['name']}: chips must be 1 or 4")
            for key in ("config", "traffic"):
                if not NAME_RE.match(w[key]):
                    raise SpecError(f"workload {w['name']}: bad {key} name")
            if (w["config"], w["traffic"]) in pairs:
                raise SpecError(f"pair of {w['name']} appears twice")
            pairs.add((w["config"], w["traffic"]))
            if not 1 <= len(w["why"]) <= 200:
                raise SpecError(f"workload {w['name']}: why is 1..200 chars")
        four = sum(w["chips"] == 4 for w in d["workloads"])
        if four > max(1, len(d["workloads"]) // 4):
            raise SpecError(f"{four} four-chip cells of {len(cells)}")
        if {c["name"] for c in d["configs"]} - {w["config"]
                                               for w in d["workloads"]}:
            raise SpecError("a config is used by no cell")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                raise SpecError(f"metric {m['name']}: unit '{m['unit']}'")
            if m["better"] not in ("lower", "higher"):
                raise SpecError(f"metric {m['name']}: better")
            if m["source"] not in SOURCES:
                raise SpecError(f"metric {m['name']}: source")
            for cell in m.get("workloads", ()):
                if cell not in cells:
                    raise SpecError(f"metric {m['name']}: unknown cell {cell}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise SpecError(f"end-to-end {m['name']}: source")
            if not 0 < m["bound"] <= 0.1:
                raise SpecError(f"end-to-end {m['name']}: bound")
        for m in d["per_layer"]:
            if m["moves"] not in e2e:
                raise SpecError(f"per-layer {m['name']}: moves "
                                f"'{m['moves']}' is no end-to-end metric")
            for cell in cells:
                if (self._in_cell(m, cell)
                        and not self._in_cell(e2e[m["moves"]], cell)):
                    raise SpecError(
                        f"per-layer {m['name']} is reported in {cell}, "
                        f"where {m['moves']} is not")
            if m["name"].endswith("_roofline") and m["unit"] != "%":
                raise SpecError(f"{m['name']}: a roofline share is in %")
            r = self.reader(m["name"])
            for key in ("layer", "unit", "moves", "source"):
                if r.get(key) != m[key]:
                    raise SpecError(f"layer_metrics/{m['name']}.json: {key} "
                                    f"differs from BENCHMARK.json")
            if not os.path.exists(self.path("reducers",
                                            f"{r['reducer']}.py")):
                raise SpecError(f"{m['name']}: no reducer '{r['reducer']}'")
        for cell in cells:
            c = self.cell(cell)
            if c.traffic.get("kind") not in TRAFFIC_KINDS:
                raise SpecError(f"traffic {c.traffic_name}: kind")
            if len(c.end_to_end) < 2 or not c.per_layer:
                raise SpecError(f"cell {cell}: needs setup_s, another "
                                "end-to-end metric and a per-layer metric")
