"""The benchmark's harness, held to the contract on the CPU: the data files
are found by name and validate, the arithmetic of the metrics is right on
known inputs, the traffic is the same work under every seed, the trace
reduction gives known numbers on a small recorded trace, and every cell runs
end to end at tiny size through a test-only steer — where it reports counts
and refuses to report a time, a rate or a share."""

import copy
import inspect
import json
import math
import os
import shutil
import statistics
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
import live_document  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (device, layers, program,  # noqa: E402
                                spec as spec_mod, stats, traffic,
                                trace as trace_mod)

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
DOC = SPEC.doc
CELLS = [w["name"] for w in DOC["workloads"]]
# every serving mix in the directory, also one that no cell uses yet
SERVE_TRAFFIC = sorted(
    f[:-5] for f in os.listdir(SPEC.path("traffic"))
    if json.load(open(SPEC.path("traffic", f)))["kind"] != "train")


# -- every cell's files are found by name and validate ---------------------

def test_benchmark_json_meets_the_contract():
    SPEC.validate()
    assert DOC["command"][-1] == "benchmarks/run.py"
    assert len(json.dumps(DOC)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c = SPEC.cell(cell)
    assert c.traffic["kind"] in spec_mod.TRAFFIC_KINDS
    assert c.traffic["why"] and c.traffic["who"]
    assert c.config["source"].startswith("https://")
    assert os.path.exists(SPEC.path("references",
                                    f"{c.config['reference']}.py"))
    published, model = c.config["published"], c.config["model"]["overrides"]
    entry = next(x for x in DOC["configs"] if x["name"] == c.config_name)
    # of the sizes the file maps to the source's, the program changes those
    # that `reduced` names and no other, and each of them is the depth or a
    # count that the file's `share` divides, at exactly this chip's part:
    # widths are the source's, and so are heads, experts and vocabulary
    # where no deployment is stated
    widths = c.config["widths"]
    cut = {source for key, source in widths.items()
           if model[key] != published[source]}
    assert cut == set(entry["reduced"]) & set(widths.values())
    assert set(entry["reduced"]) == set(c.config["reduced"])
    ways = spec_mod.share_ways(c.config)
    for source in cut:
        if spec_mod.names_depth(source):
            continue
        assert source in ways
        assert spec_mod.names_a_count(source, published[source])
        assert published[source] % ways[source] == 0
        assert {model[k] for k, s in widths.items() if s == source} <= {
            published[source], published[source] // ways[source]}
    accounted = set(widths.values()) | set(c.config.get("equal_widths", {}))
    assert all(key in accounted for key, value in published.items()
               if spec_mod.names_a_size(key, value))
    # what the file says the reference is called with, the reference takes
    reference, args = program.reference_module(c), program.reference_args(c)
    for fn in (reference.loss, reference.next_token_logprobs):
        inspect.signature(fn).bind("params", "ids", **args)
    assert c.config["tiny"]["dtype"] == "float32"
    if c.traffic["kind"] == "train":
        assert c.chips == 4 or c.traffic["engine"][
            "zero_optimization"]["stage"] == 0
    else:
        s = c.config["serving"]
        longest = (c.traffic["prompt_tokens"]["max"]
                   + c.traffic["output_tokens"]["max"])
        assert longest <= s["max_model_len"]    # no request can fail to fit
        assert c.traffic["requests"] >= 64


ALL_NAMES = sorted(
    {x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
     for x in DOC[k]}
    | {w["traffic"] for w in DOC["workloads"]}
    | {k for c in DOC["configs"] for k in c["reduced"]})


@pytest.mark.parametrize("name", ALL_NAMES)
def test_names_use_only_allowed_characters(name):
    assert spec_mod.NAME_RE.match(name)
    assert all(ch.isascii() for ch in name)


@pytest.mark.parametrize("metric", DOC["end_to_end"] + DOC["per_layer"],
                         ids=lambda m: m["name"])
def test_units_and_sources(metric):
    assert spec_mod.UNIT_RE.match(metric["unit"])
    assert metric["source"] in spec_mod.SOURCES
    assert metric["better"] in ("lower", "higher")
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if metric in DOC["end_to_end"] else {"layer", "moves"})
    assert set(metric) <= allowed


@pytest.mark.parametrize("metric", DOC["per_layer"], ids=lambda m: m["name"])
def test_moves_is_reported_wherever_the_metric_is(metric):
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    target = e2e[metric["moves"]]
    for cell in metric.get("workloads", CELLS):
        assert "workloads" not in target or cell in target["workloads"]
    reader = SPEC.reader(metric["name"])
    assert os.path.exists(SPEC.path("reducers", f"{reader['reducer']}.py"))
    assert hasattr(layers.reducer(reader["reducer"]), "reduce")


def _break(doc, how):
    """`doc` broken in one way. What is broken is found by NAME: a later PR
    appends to every list of the document."""
    doc, named = copy.deepcopy(doc), live_document.named
    if how == "bad-name":
        named(doc["workloads"], "opt-1.3b-d8.train-x1")["name"] = "has space"
    elif how == "unknown-moves":
        named(doc["per_layer"], "train_step_dev_ms")["moves"] = "nothing"
    elif how == "moves-not-reported":
        # a training cell's metric said to move what only serve-decode reports
        named(doc["per_layer"], "train_step_dev_ms")["moves"] = "ttft_p25_ms"
    elif how == "width-reduced":
        named(doc["configs"], "opt-1.3b")["reduced"] = ["hidden_size"]
    elif how == "too-many-four-chip-cells":
        # a quarter of the cells may take four chips, however many there are
        for w in doc["workloads"]:
            w["chips"] = 4
    elif how == "no-setup":
        doc["end_to_end"] = [m for m in doc["end_to_end"]
                             if m["name"] != "setup_s"]
    elif how == "loose-bound":
        named(doc["end_to_end"], "train_tok_s")["bound"] = 0.5
    elif how == "extra-key":
        doc["notes"] = "x"
    return doc


def _break_config(cfg, entry, how):
    """A configuration's file (and, where the break needs it, its entry in
    BENCHMARK.json), broken in one of the ways `validate` refuses; returns
    what the refusal has to say."""
    key, source = next((k, s) for k, s in cfg["widths"].items()
                       if s not in cfg["reduced"])
    if how == "config-without-tiny":
        del cfg["tiny"]
        return "no 'tiny'"
    if how == "widths-source-unknown":
        cfg["widths"][key] = "d_model"
        return "'d_model' is no key of 'published'"
    if how == "size-neither-mapped-nor-equal":
        cfg["published"]["bottleneck_dim"] = 512
        return "'bottleneck_dim' names a size"
    if how == "override-differs-unreduced":
        cfg["model"]["overrides"][key] += 64
        return f"'{source}' is not in 'reduced'"
    if how == "reference-arg-source-unknown":
        arg = next(iter(cfg["reference_args"]))
        cfg["reference_args"][arg] = {"published": "n_head"}
        return f"reference_args.{arg} must be"
    if how == "tiny-leaves-a-size-to-the-preset":
        del cfg["tiny"]["overrides"][key]
        return "tiny.overrides leaves"
    if how in ("heads-reduced", "vocab-reduced"):
        # a count that is no width by its name: listed in `reduced` on both
        # sides and halved in the program, it is still not the depth
        key, source = next((k, s) for k, s in cfg["widths"].items()
                           if how[:5] in s)
        assert not spec_mod.names_a_width(source)
        cfg["model"]["overrides"][key] //= 2
        cfg["reduced"][source] = "halved"
        entry["reduced"] = entry["reduced"] + [source]
        return f"'{source}' is in 'reduced' and does not name the depth"
    if how == "heads-left-unmapped":
        key, source = next((k, s) for k, s in cfg["widths"].items()
                           if "heads" in s)
        del cfg["widths"][key]
        cfg["model"]["overrides"][key] //= 2
        return f"'{source}' names a size"
    if how == "equal-width-differs":
        name = next(iter(cfg["equal_widths"]))
        cfg["published"][name] //= 2
        return f"equal_widths: '{name}' must be"
    raise KeyError(how)


SHARED = "olmoe-1b-7b-d12"      # the configuration the share cases start from


def _share(cfg, entry):
    """OLMoE's own file as one of 8 chips that share each layer would run it:
    the router keeps its 64 outputs, a second program key holds the 8 experts
    that live here, an eighth of the vocabulary, attention whole. `validate`
    builds no model, so the second key needs no program behind it. The small
    model of the rehearsal follows: 64 experts of which 8 are held."""
    cfg["widths"]["moe_experts_held"] = "num_experts"
    cfg["model"]["overrides"].update(moe_experts_held=8, vocab_size=6288)
    cfg["tiny"]["overrides"].update(moe_num_experts=64, moe_experts_held=8,
                                    num_layers=4)
    cfg["reduced"].update(num_experts="64 -> 8 held, the router whole",
                          vocab_size="50304 -> 6288, an eighth")
    cfg["share"] = {"chips": 8, "divided": ["num_experts", "vocab_size"],
                    "how": "expert parallel over 8 chips: each holds 8 of a "
                           "layer's 64 experts and an eighth of the "
                           "vocabulary; router and attention whole"}
    entry["reduced"] = entry["reduced"] + ["num_experts", "vocab_size"]


def _undivide(cfg, entry, source):
    """Take one count back out of the share: held whole again."""
    cfg["share"]["divided"].remove(source)
    del cfg["reduced"][source]
    entry["reduced"].remove(source)
    if source == "num_experts":
        del cfg["widths"]["moe_experts_held"]
        del cfg["model"]["overrides"]["moe_experts_held"]
    else:
        cfg["model"]["overrides"][source] = cfg["published"][source]


def _break_share(cfg, entry, how):
    """A sound share (`_share`), broken in one of the ways `validate`
    refuses; returns what the refusal has to say."""
    overrides, share = cfg["model"]["overrides"], cfg["share"]
    if how == "share-remainder":
        share["chips"] = 7
        return "7 chips do not divide the source's num_experts of 64"
    if how == "share-vocabulary-over-16-chips":
        _undivide(cfg, entry, "num_experts")
        share["chips"], overrides["vocab_size"] = 16, 50304 // 16
        return "the floor is an eighth of the vocabulary"
    if how == "share-four-experts-held":
        _undivide(cfg, entry, "vocab_size")
        share["chips"], overrides["moe_experts_held"] = 16, 4
        return "4 of 64 num_experts held; the floor is 8 experts"
    if how == "share-divides-a-width":
        share["divided"].append("num_experts_per_tok")
        return "'num_experts_per_tok' is a width, and no width is ever cut"
    if how == "share-divides-no-count":
        share["divided"].append("rope_theta")
        return "'rope_theta' names no count"
    if how == "share-divided-not-reduced":
        del cfg["reduced"]["vocab_size"]
        entry["reduced"].remove("vocab_size")
        return "share.divided names 'vocab_size', which 'reduced' does not"
    if how == "share-override-neither-whole-nor-share":
        overrides["moe_experts_held"] = 9
        return "holds all 64 or its share of 8, nothing else"
    if how == "share-held-by-no-key":
        overrides["moe_experts_held"] = 64
        return "no key that 'widths' maps to it holds the share of 8"
    if how == "share-three-layers":
        overrides["num_layers"] = 3
        return "the floor is 4"
    if how == "share-of-one-chip":
        share["chips"] = 1
        return "a share is of 2 chips or more"
    if how == "share-with-another-key":
        share["layers_elsewhere"] = 4
        return "share has keys"
    if how.startswith("placement-"):
        # OLMoE's reference has no `place_held_experts`: each case is refused
        # at the first rule it breaks (the experts' ways, the word, the
        # reference's function, in that order)
        share["placement"] = "balanced"
        if how == "placement-where-no-experts-are-divided":
            _undivide(cfg, entry, "num_experts")
            return "share.placement: share.divided names 0 counts of experts"
        if how == "placement-not-balanced":
            share["placement"] = "popular"
            return "share.placement is 'popular': the one placement is " \
                   "'balanced'"
        if how == "placement-an-object":
            share["placement"] = {"experts": "balanced",
                                  "calibration_tokens": 4096}
            return "the one placement is 'balanced'"
        if how == "placement-the-reference-cannot-make":
            return "share.placement, and references/olmoe.py has no " \
                   "place_held_experts"
    # the object form: the experts over all 8 chips, the vocabulary 4 ways
    # (each quarter on two of the eight), sound before it is broken
    share["divided"] = {"num_experts": 8, "vocab_size": 4}
    overrides["vocab_size"] = 50304 // 4
    if how == "share-ways-do-not-divide-the-chips":
        share["divided"]["vocab_size"] = 3
        return "share.divided.vocab_size is 3 ways, which do not divide " \
               "share.chips of 8"
    if how == "share-widest-ways-under-the-chips":
        share["divided"]["num_experts"] = 4
        return "nothing is divided 8 ways \\(the most: num_experts 4 ways\\)"
    if how == "share-override-the-chips-part-not-the-keys":
        overrides["vocab_size"] = 50304 // 8
        return "a chip of 8, which divide vocab_size 4 ways, holds all " \
               "50304 or its share of 12576, nothing else"
    if how == "share-ways-held-by-no-key":
        overrides["vocab_size"] = 50304
        return "names 'vocab_size' 4 ways, and no key that 'widths' maps " \
               "to it holds the share of 12576"
    raise KeyError(how)


BROKEN_SHARES = ["share-remainder", "share-vocabulary-over-16-chips",
                 "share-four-experts-held", "share-divides-a-width",
                 "share-divides-no-count", "share-divided-not-reduced",
                 "share-override-neither-whole-nor-share",
                 "share-held-by-no-key", "share-three-layers",
                 "share-of-one-chip", "share-with-another-key",
                 "share-ways-do-not-divide-the-chips",
                 "share-widest-ways-under-the-chips",
                 "share-override-the-chips-part-not-the-keys",
                 "share-ways-held-by-no-key",
                 "placement-where-no-experts-are-divided",
                 "placement-not-balanced", "placement-an-object",
                 "placement-the-reference-cannot-make"]
BROKEN_DOCS = ["bad-name", "unknown-moves", "moves-not-reported",
               "width-reduced", "too-many-four-chip-cells", "no-setup",
               "loose-bound", "extra-key"]
BROKEN_CONFIGS = ["config-without-tiny", "widths-source-unknown",
                  "size-neither-mapped-nor-equal",
                  "override-differs-unreduced",
                  "reference-arg-source-unknown",
                  "tiny-leaves-a-size-to-the-preset",
                  "heads-reduced", "vocab-reduced", "heads-left-unmapped",
                  "equal-width-differs"]


def _add_config_and_cell(root, name, cfg, like, why):
    """Entries in the root's BENCHMARK.json for a configuration whose file
    is `cfg`, and for a cell of it under the traffic and the metrics of the
    cell `like`; returns the new cell's name."""
    doc = copy.deepcopy(DOC)
    like = next(w for w in doc["workloads"] if w["name"] == like)
    cell = f"{name}.{like['traffic']}"
    doc["configs"].append({
        "name": name, "source": cfg["source"],
        "reduced": sorted(cfg["reduced"]),
        "file": f"benchmarks/configs/{name}.json", "why": why})
    doc["workloads"].append(dict(like, name=cell, config=name))
    for m in doc["end_to_end"] + doc["per_layer"]:
        if like["name"] in m.get("workloads", ()):
            m["workloads"].append(cell)
    json.dump(doc, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return cell


def _shared_root(tmp):
    """A tiny root in which OLMoE's REAL file states a share (`_share`);
    returns the root, the document, the file's path and its entry."""
    root = bench_tiny.make_root(str(tmp))
    doc = copy.deepcopy(DOC)
    entry = next(c for c in doc["configs"] if c["name"] == SHARED)
    cfg = json.load(open(os.path.join(spec_mod.REPO_ROOT, entry["file"])))
    _share(cfg, entry)
    path = os.path.join(root, entry["file"])
    json.dump(cfg, open(path, "w"))
    json.dump(doc, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root, doc, path, entry


@pytest.mark.parametrize("how", BROKEN_DOCS + BROKEN_CONFIGS + BROKEN_SHARES)
def test_validate_refuses(how, tmp_path):
    if how in BROKEN_SHARES:
        root, doc, path, entry = _shared_root(tmp_path)
    else:
        root, doc = bench_tiny.make_root(str(tmp_path)), copy.deepcopy(DOC)
        entry = live_document.named(doc["configs"], "opt-1.3b")
        path = os.path.join(root, entry["file"])
    spec_mod.Spec(root).validate()      # sound before it is broken
    if how in BROKEN_DOCS:
        doc, says = _break(DOC, how), None
    else:
        cfg = json.load(open(path))
        says = (_break_share if how in BROKEN_SHARES
                else _break_config)(cfg, entry, how)
        json.dump(cfg, open(path, "w"))
    json.dump(doc, open(os.path.join(root, "BENCHMARK.json"), "w"))
    with pytest.raises(spec_mod.SpecError, match=says):
        spec_mod.Spec(root).validate()


def test_a_share_of_a_stated_deployment_validates(tmp_path):
    """The guide's third cut at rule level, on a copy of OLMoE's file: the
    router's key at the source's 64, a second program key mapped to the same
    source at 64 / 8, an eighth of the vocabulary. The file's own small
    model follows the same rule: `published` there holds the WHOLE counts
    (what is held times the chips) and the `share` block stays."""
    root, doc, path, entry = _shared_root(tmp_path)
    spec = spec_mod.Spec(root)
    spec.validate()
    real = json.load(open(path))
    assert [real["model"]["overrides"][k] for k in (
        "moe_num_experts", "moe_experts_held", "vocab_size")] == [64, 8, 6288]
    tiny = bench_tiny.tiny_config(real)
    assert tiny["share"] == real["share"]
    assert tiny["published"]["num_experts"] == 64       # 8 held x 8 chips
    assert tiny["published"]["vocab_size"] == 256 * 8   # the slice x 8 chips
    assert tiny["model"]["overrides"]["vocab_size"] == 256
    json.dump(tiny, open(path, "w"))
    spec.validate()
    # OLMoE's small model as the repo has it (8 experts, 2 layers) is too
    # small to be a share: the floors hold in the rehearsal too
    real["tiny"] = json.load(open(os.path.join(
        spec_mod.REPO_ROOT, entry["file"])))["tiny"]
    real["tiny"]["overrides"]["moe_experts_held"] = 1
    json.dump(bench_tiny.tiny_config(real), open(path, "w"))
    with pytest.raises(spec_mod.SpecError, match="the floor is 8 experts"):
        spec.validate()


# a file shaped as a model whose experts outnumber a chip would arrive: the
# names are made up, `validate` builds no model and needs no family
A_SHARE = {
    "source": "https://example.org/made-up/sparse-320e/config.json",
    "published": {
        "hidden_size": 4096, "moe_intermediate_size": 1280,
        "num_attention_heads": 64, "num_key_value_heads": 8, "head_dim": 128,
        "n_routed_experts": 320, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "vocab_size": 196608,
        "num_hidden_layers": 48, "rope_theta": 10000, "rms_norm_eps": 1e-05,
        "norm_topk_prob": True},
    "reduced": {
        "num_hidden_layers": "48 -> 8: two periods of four",
        "n_routed_experts": "320 -> 40 held; the router keeps 320 outputs",
        "vocab_size": "196608 -> 24576, an eighth"},
    "share": {"chips": 8, "divided": ["n_routed_experts", "vocab_size"],
              "how": "8 chips share each layer: 40 of its 320 routed "
                     "experts and 24,576 rows of the vocabulary on each; "
                     "attention, the shared expert and the router whole"},
    "model": {"preset": "made-up", "dtype": "bfloat16", "overrides": {
        "hidden_size": 4096, "ffn_hidden_size": 1280, "num_heads": 64,
        "num_kv_heads": 8, "head_dim": 128, "moe_num_experts": 320,
        "moe_experts_held": 40, "moe_top_k": 8, "moe_shared_experts": 1,
        "vocab_size": 24576, "num_layers": 8}},
    "widths": {
        "hidden_size": "hidden_size", "ffn_hidden_size":
        "moe_intermediate_size", "num_heads": "num_attention_heads",
        "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
        "moe_num_experts": "n_routed_experts", "moe_experts_held":
        "n_routed_experts", "moe_top_k": "num_experts_per_tok",
        "moe_shared_experts": "n_shared_experts",
        "vocab_size": "vocab_size", "num_layers": "num_hidden_layers"},
    "reference": "olmoe",
    "reference_args": {
        "num_heads": {"published": "num_attention_heads"},
        "num_experts_per_tok": {"published": "num_experts_per_tok"}},
    "tiny": {"preset": "made-up-tiny", "dtype": "float32", "overrides": {
        "hidden_size": 64, "ffn_hidden_size": 32, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "moe_num_experts": 64,
        "moe_experts_held": 8, "moe_top_k": 3, "moe_shared_experts": 1,
        "vocab_size": 256, "num_layers": 4},
        "reference_args": {"num_heads": 4, "num_experts_per_tok": 3}},
}


def _two_divisors():
    """The same made-up file with counts that 8 chips cannot hold at four
    layers: 384 experts over 32 chips (12 held, the router whole), 163,840
    rows 8 ways (each slice on 4 of the 32), 5 of 61 layers. The small model
    follows: a router of 8 x 32 outputs over 8 experts held."""
    cfg = copy.deepcopy(A_SHARE)
    cfg["published"].update(n_routed_experts=384, vocab_size=163840,
                            num_hidden_layers=61)
    cfg["reduced"] = {
        "num_hidden_layers": "61 -> 5: the leading dense layer and four",
        "n_routed_experts": "384 -> 12 held; the router keeps 384 outputs",
        "vocab_size": "163840 -> 20480, an eighth"}
    cfg["share"] = {
        "chips": 32, "divided": {"n_routed_experts": 32, "vocab_size": 8},
        "how": "32 chips share each layer: the 384 routed experts expert-"
               "parallel 32 ways (12 here); the vocabulary 8 ways, each "
               "slice on 4 of the 32; attention, router and shared expert "
               "whole on every chip"}
    cfg["model"]["overrides"].update(moe_num_experts=384, moe_experts_held=12,
                                     vocab_size=20480, num_layers=5)
    cfg["tiny"]["overrides"].update(moe_num_experts=256)
    return cfg


def _break_two_divisors(cfg, how):
    """`_two_divisors`, as stated (returns None: it validates) or broken in
    one of the ways `validate` refuses (returns what the refusal says)."""
    share, overrides = cfg["share"], cfg["model"]["overrides"]
    ways, published = share["divided"], cfg["published"]
    if how == "as-stated":
        return None
    if how == "a-vocabulary-that-8-divide-and-32-do-not":
        published["vocab_size"], overrides["vocab_size"] = 163848, 20481
        return None
    if how == "the-list-form-at-32-chips":      # why the object form exists
        share["divided"] = sorted(ways)
        overrides["vocab_size"] = 163840 // 32
        return "vocab_size over 32 chips; the floor is an eighth"
    if how == "ways-that-do-not-divide-the-chips":
        ways["vocab_size"] = 12
        return "share.divided.vocab_size is 12 ways, which do not divide " \
               "share.chips of 32"
    if how == "widest-ways-under-the-chips":
        ways["n_routed_experts"], overrides["moe_experts_held"] = 16, 24
        return "share.chips is 32 and nothing is divided 32 ways"
    if how == "vocabulary-16-ways":
        ways["vocab_size"], overrides["vocab_size"] = 16, 163840 // 16
        return "vocab_size over 16 chips; the floor is an eighth of the " \
               "vocabulary \\(at most 8 ways"
    if how == "384-experts-64-ways":
        share["chips"], ways["n_routed_experts"] = 64, 64
        overrides["moe_experts_held"] = 6
        return "n_routed_experts 64 ways: 6 of 384 n_routed_experts held; " \
               "the floor is 8 experts"
    if how == "remainder-against-the-keys-own-ways":
        published["vocab_size"] = 163844        # 32 x 12 divides the experts
        return "32 chips do not divide the source's vocab_size of 163844 " \
               "8 ways \\(remainder 4\\)"
    if how in ("ways-of-one", "ways-a-bool", "ways-a-string"):
        ways["vocab_size"] = {"ways-of-one": 1, "ways-a-bool": True,
                              "ways-a-string": "8"}[how]
        return f"share.divided.vocab_size is {ways['vocab_size']!r}: the " \
               "ways the chips divide vocab_size, a whole number of 2 or more"
    if how == "override-a-32nd-of-the-vocabulary":
        overrides["vocab_size"] = 5120
        return "model.overrides.vocab_size is 5120: of the source's " \
               "vocab_size a chip of 32, which divide vocab_size 8 ways, " \
               "holds all 163840 or its share of 20480, nothing else"
    if how == "experts-per-token-8-ways":
        ways["num_experts_per_tok"] = 8
        return "'num_experts_per_tok' is a width"
    raise KeyError(how)


TWO_DIVISORS = [
    "as-stated", "a-vocabulary-that-8-divide-and-32-do-not",
    "the-list-form-at-32-chips", "ways-that-do-not-divide-the-chips",
    "widest-ways-under-the-chips", "vocabulary-16-ways",
    "384-experts-64-ways", "remainder-against-the-keys-own-ways",
    "ways-of-one", "ways-a-bool", "ways-a-string",
    "override-a-32nd-of-the-vocabulary", "experts-per-token-8-ways"]


@pytest.mark.parametrize("how", [
    "as-stated", "as-stated-at-its-tiny-size", "41-experts-held",
    "16-chips", "experts-per-token-divided", "no-share-block"]
    + [f"two-divisors:{how}" for how in TWO_DIVISORS]
    + ["two-divisors:as-stated-at-its-tiny-size"])
def test_a_model_whose_experts_outnumber_a_chip_arrives_as_a_share(
        how, tmp_path):
    """320 routed experts, 8 a token, a vocabulary of 196,608 and 48 layers
    as published; as run 40 experts beside a router of 320, 24,576 rows and
    8 layers, one of 8 chips that share each layer. That validates; another
    number of experts, a vocabulary in sixteenths, a divided top-k or the
    same cuts with no deployment stated do not. `two-divisors`: 384 experts
    that 32 chips share and a vocabulary that 8 of them divide, in one file
    (`_two_divisors`); each break is refused by the key and ITS ways."""
    root = bench_tiny.make_root(str(tmp_path))
    two, how = how.startswith("two-divisors:"), how.split(":")[-1]
    cfg, says = _two_divisors() if two else copy.deepcopy(A_SHARE), None
    if how == "as-stated-at-its-tiny-size":
        cfg = bench_tiny.tiny_config(cfg)
        # what the small model holds, times each key's OWN ways
        assert [cfg["published"][k] for k in (
            "n_routed_experts", "vocab_size")] == [8 * (32 if two else 8),
                                                   256 * 8]
    elif two:
        says = _break_two_divisors(cfg, how)
    elif how == "41-experts-held":
        cfg["model"]["overrides"]["moe_experts_held"] = 41
        says = "holds all 320 or its share of 40, nothing else"
    elif how == "16-chips":
        cfg["share"]["chips"] = 16
        says = "vocab_size over 16 chips; the floor is an eighth"
    elif how == "experts-per-token-divided":
        cfg["share"]["divided"].append("num_experts_per_tok")
        says = "'num_experts_per_tok' is a width"
    elif how == "no-share-block":
        del cfg["share"]
        says = "is in 'reduced' and does not name the depth"
    _add_config_and_cell(
        root, "sparse-320e-s8", cfg, f"{SHARED}.serve-decode",
        "one of 8 chips of an expert-parallel deployment; attention sees "
        "more than its share of the batch")
    spec = spec_mod.Spec(root)
    json.dump(cfg, open(spec.path("configs", "sparse-320e-s8.json"), "w"))
    if says is None:
        spec.validate()
    else:
        with pytest.raises(spec_mod.SpecError, match=says):
            spec.validate()


@pytest.mark.parametrize("form", ["no-share", "a-list", "an-object"])
def test_share_ways_tells_the_two_forms_apart(form):
    """The one reader of `share.divided`: a list is every key over all of
    `chips` (Solar's file, byte for byte what PR 33 committed), an object
    gives each key its own ways, no block divides nothing."""
    if form == "no-share":
        cfg = SPEC.cell("opt-1.3b.serve-decode").config
        assert "share" not in cfg and spec_mod.share_ways(cfg) == {}
    elif form == "a-list":
        cfg = SPEC.cell("solar-open2-250b-ep8-d4.serve-decode-r64").config
        assert cfg["share"]["divided"] == ["n_routed_experts", "vocab_size"]
        assert spec_mod.share_ways(cfg) == {"n_routed_experts": 8,
                                            "vocab_size": 8}
    else:
        cfg = _two_divisors()
        assert spec_mod.share_ways(cfg) == {"n_routed_experts": 32,
                                            "vocab_size": 8}
        assert spec_mod.share_ways(cfg) is not cfg["share"]["divided"]


# -- the arithmetic --------------------------------------------------------

@pytest.mark.parametrize("stalled", [0, 7, 24])
def test_window_rate_carries_a_stalled_group_and_the_median_does_not(stalled):
    groups = [1.6] * 25
    steady = stats.group_rates(49152, groups)
    assert steady["window_tok_s"] == pytest.approx(49152 / 1.6)
    groups[stalled] = 2.4          # one group lost 0.8 s
    got = stats.group_rates(49152, groups)
    assert got["median_tok_s"] == steady["median_tok_s"] == 49152 / 1.6
    assert got["window_tok_s"] == pytest.approx(49152 * 25 / (40 + 0.8))
    # time between groups is the window's too
    assert stats.group_rates(49152, groups, window_s=41.0)[
        "window_tok_s"] == pytest.approx(49152 * 25 / 41.0)


@pytest.mark.parametrize("kind", ["train", "closed_loop"])
def test_end_to_end_rates_are_all_the_work_over_all_the_time(kind):
    """Where the cells' loops take their rates from: read in the source, so
    that a median cannot come back as the end-to-end figure unseen."""
    import inspect

    from benchmarks.harness import serve_cell, train_cell

    src = inspect.getsource(train_cell if kind == "train" else serve_cell)
    if kind == "train":
        assert '"train_tok_s": rates["window_tok_s"]' in src
    else:
        assert '"serve_tok_s": in_window / seconds' in src


@pytest.mark.parametrize("case", ["inside", "straddles-open",
                                  "straddles-close", "outside", "no-token"])
def test_prompt_tokens_inside_the_window(case):
    import numpy as np

    from benchmarks.harness.serve_cell import Record, prompt_tokens_inside

    submit, first = {"inside": (11.0, 13.0), "straddles-open": (9.0, 11.0),
                     "straddles-close": (19.0, 23.0), "outside": (2.0, 9.5),
                     "no-token": (12.0, None)}[case]
    rec = Record(0, 1000, 4, np.zeros(1000, np.int32), submit_time=submit,
                 token_times=[] if first is None else [first, first + 0.1])
    want = {"inside": 1000.0, "straddles-open": 500.0,
            "straddles-close": 250.0, "outside": 0.0, "no-token": 0.0}[case]
    assert prompt_tokens_inside([rec], 10.0, 20.0) == pytest.approx(want)


def test_percentile_and_spread_match_the_contracts_definitions():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0, 6.0]
    assert stats.percentile(xs, 50) == 3.5
    assert stats.percentile(xs, 95) == pytest.approx(5.75)
    q = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q[2] - q[0]) / 3.5)
    assert stats.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert stats.gaps([(0, 2), (1, 3), (5, 6)], 0, 7) == [[3, 5], [6, 7]]


@pytest.mark.parametrize("case", ["one-far-run", "two-far-runs",
                                  "no-far-run", "two-runs"])
def test_trimmed_spread_on_known_inputs(case):
    """What decides admission: the set's inter-quartile distance, or that
    of the set without the run farthest from the median where that is
    narrower, over the set's median. One far-off run in a set does no harm,
    two do."""
    xs = {"one-far-run": [1.0, 2.0, 3.0, 4.0, 5.0, 60.0],
          "two-far-runs": [10.0, 10.1, 9.9, 10.0, 12.0, 12.2],
          "no-far-run": [16.90, 16.95, 17.00, 17.05, 17.10, 17.15],
          "two-runs": [3.0, 5.0]}[case]
    got = stats.trimmed_spread(xs)
    assert got <= stats.spread(xs)
    if case == "one-far-run":       # [1..5]: quartiles 1.5 and 4.5
        assert got == pytest.approx(3.0 / 3.5)
        assert stats.spread(xs) == pytest.approx(17.0 / 3.5)
    elif case == "two-far-runs":    # 12.2 goes, 12.0 stays in the quartile
        assert got == pytest.approx((11.05 - 9.95) / 10.05)
    elif case == "no-far-run":      # 17.15 goes: 0.15 for 0.175
        assert got == pytest.approx(0.15 / 17.025)
        assert stats.spread(xs) == pytest.approx(0.175 / 17.025)
    else:                           # nothing to leave out of two
        assert got == stats.spread(xs)


@pytest.mark.parametrize("case", ["pr-35-as-the-ledger-has-it",
                                  "on-the-line", "solar-at-pr-34"])
def test_admission_is_the_mean_of_the_sets_against_half_the_bound(case):
    """PR 35 was refused on these numbers (ledger): two sets spreading by
    0.168873 and 0.14035 ms, a bound of 2% of 14.7885 ms, so 0.1546 against
    0.1479: too noisy, by 4.5%."""
    from benchmarks import measure

    units, bound, median, verdict, over = {
        "pr-35-as-the-ledger-has-it":
            ([0.168873, 0.14035], 0.02, 14.7885, "too_noisy", 0.0455),
        "on-the-line": ([0.125, 0.375], 0.03125, 16.0, "ok", 0.0),
        # the check's note at PR 34: 0.196869 ms of a bound of 0.338064
        "solar-at-pr-34":
            ([0.196869, 0.196869], 0.02, 16.9032, "too_noisy", 0.1647),
    }[case]
    got = measure.admission(units, bound, median)
    assert got["verdict"] == verdict
    assert got["mean"] == pytest.approx(sum(units) / 2)
    assert got["half_bound"] == pytest.approx(0.5 * bound * median)
    assert got["over_by"] == pytest.approx(over, abs=1e-3)


@pytest.mark.parametrize("verdict", ["ok", "too_noisy"])
def test_measure_prints_the_admission_line(verdict, tmp_path, monkeypatch,
                                           capsys):
    """Two sets of six through `measure.main`, the runs canned: a line a
    cell and bounded metric, the same fields in `summary.json`, and none
    for `setup_s`, which the check judges by its medians."""
    from benchmarks import measure

    cell = "solar-open2-250b-ep8-d4.serve-decode-r64"
    bound = next(m["bound"] for m in DOC["end_to_end"]
                 if m["name"] == "itl_p50_ms")      # whatever the file has
    wide = bound * 17.0 * (1.5 if verdict == "too_noisy" else 0.15)
    runs = iter([17.0 + wide * d for d in (-.5, -.3, -.1, .1, .3, 3.0)] * 2)

    def one_run(workload, seed, seconds, trace, log):
        value = next(runs)
        return {"rc": 0, "wall_s": 1.0, "result": {
            "correct": True, "metrics": {
                "itl_p50_ms": {"value": value, "unit": "ms"},
                "setup_s": {"value": 40.0 + value, "unit": "s"}}}}

    monkeypatch.setattr(measure, "one_run", one_run)
    assert measure.main(["--workload", cell, "--sets", "2", "--runs", "6",
                         "--out", str(tmp_path)]) == 0
    lines = [ln.split() for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("ADMISSION")]
    # the far run (3.0) is left out of each set: quartiles -0.4 and 0.2
    mean, half = 0.6 * wide, 0.5 * bound * 17.0
    assert lines == [["ADMISSION", cell, "itl_p50_ms", f"mean={mean:.6g}",
                      f"half_bound={half:.6g}", verdict]]
    doc = json.load(open(tmp_path / "summary.json"))[cell]
    assert set(doc["admission"]) == {"itl_p50_ms"}
    assert doc["admission"]["itl_p50_ms"]["verdict"] == verdict
    for one in doc["sets"]:
        got = one["itl_p50_ms"]
        assert got["trimmed_unit"] == pytest.approx(mean)
        assert got["trimmed"] == pytest.approx(mean / got["median"])
        assert got["spread"] > got["trimmed"] and got["n"] == 6


# -- seed-invariant traffic ------------------------------------------------

@pytest.mark.parametrize("name", SERVE_TRAFFIC)
def test_two_seeds_offer_the_same_work(name):
    """The seed permutes the order of a fixed set of lengths and draws the
    token ids (and the weights): the same work, in another order."""
    t = json.load(open(SPEC.path("traffic", f"{name}.json")))
    seeds = (1, 2 ** 31 + 12345)
    sets = [traffic.request_set(t, s) for s in seeds]
    assert sets[0] != sets[1]                           # another order
    assert sorted(sets[0]) == sorted(sets[1])           # of the same set
    assert sets[0] == traffic.request_set(t, seeds[0])  # the seed's own
    seqs = traffic.client_sequences(t, seeds[0])
    assert len(seqs) == t["clients"]
    flat = [r for seq in seqs for r in seq]
    assert sorted(flat) == sorted(sets[0]) and len(flat) == t["requests"]
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
    assert all(lo <= r.prompt_len <= hi for r in flat)
    assert len({r.new_tokens for r in flat}) > len(flat) // 2   # a spread
    # stratified: the set's mean is the distribution's, to a percent or two
    want = ((hi - lo) / math.log(hi / lo)
            if t["prompt_tokens"]["dist"] == "log_uniform" else (lo + hi) / 2)
    assert sum(r.prompt_len for r in flat) / len(flat) == pytest.approx(
        want, rel=0.02)

    class Serving:      # what Load needs of an engine before it starts
        pass

    from benchmarks.harness.serve_cell import Load
    turns = t["requests"] // t["clients"]       # once through the whole set
    totals = []
    for seed in seeds:
        load = Load(Serving(), t, seed, 50272, horizon_s=10.0)
        assert load.requests == traffic.client_sequences(t, seed)
        recs = [load._take(c, turn) for turn in range(turns)
                for c in range(t["clients"])]
        totals.append((sum(r.prompt_len for r in recs),
                       sum(r.new_tokens for r in recs),
                       int(sum(int(r.prompt.sum()) for r in recs))))
    assert totals[0][:2] == totals[1][:2]       # the same token totals
    assert totals[0][2] != totals[1][2]         # of other token ids
    ids = traffic.prompt_ids(2 ** 31 + 5, 3, 40, 50272)
    assert ids.shape == (40,) and ids.min() >= 0 and ids.max() < 50272
    assert (ids != traffic.prompt_ids(2 ** 31 + 5, 4, 40, 50272)).any()


@pytest.mark.parametrize("process", ["poisson", "bursts"])
def test_open_loop_arrivals(process):
    arr = {"process": process, "rate": 10.0, "burst": 5, "seed": 3}
    times = traffic.arrival_times(arr, 200.0)
    assert times == sorted(times) and times[-1] < 200.0
    assert times == traffic.arrival_times(arr, 200.0)    # the mix's own
    assert len(times) == pytest.approx(2000, rel=0.15)    # the mean rate
    if process == "bursts":
        assert len(set(times)) * 5 == len(times)


# -- the trace reduction, on a small recorded trace -------------------------

@pytest.fixture(scope="module")
def small_trace():
    doc = json.load(open(os.path.join(HERE, "fixtures", "small_trace.json")))
    return doc, trace_mod.Trace.from_json(doc["trace"])


def _ctx(small_trace):
    doc, tr = small_trace
    cell = SPEC.cell("opt-1.3b.train-zero3-x4")
    cfg = type("Cfg", (), dict(doc["model_config"]))()
    ctx = layers.Context(cell=cell, chips=1, peaks=device.peaks("TPU v5 lite"),
                         counters=dict(doc["counters"]), model_config=cfg,
                         trace=tr)
    return doc, ctx


def test_small_trace_known_numbers_exist(small_trace):
    doc, _ = small_trace
    assert set(doc["expect"]) >= {"train_idle_pct", "train_step_dev_ms"}


@pytest.mark.parametrize("metric", [
    "train_step_dev_ms", "train_idle_pct", "train_attn_time_pct",
    "train_collective_exposed_pct", "flash_attention_fwd_roofline",
    "flash_attention_bwd_dq_roofline", "flash_attention_bwd_dkv_roofline",
    "train_mfu_pct", "train_group_median_tok_s", "train_compiles_in_window",
    "serve_host_iter_pct"])
def test_reduction_gives_known_numbers(metric, small_trace):
    doc, ctx = _ctx(small_trace)
    r = SPEC.reader(metric)
    got = layers.reducer(r["reducer"]).reduce(ctx, **r.get("args", {}))
    assert got == pytest.approx(doc["expect"][metric], rel=1e-6)


@pytest.mark.parametrize("metric,loud", [
    ("serve_decode_iter_ms", True), ("serve_prefill_iter_ms", True),
    ("serve_prefill_iter_ms.decode", False)])
def test_a_program_the_trace_does_not_hold(metric, loud, small_trace):
    """The fixture is a training trace: a metric of a serving program finds
    none of its executions there. That is an error, except where the metric
    says a short trace may miss the program."""
    _, ctx = _ctx(small_trace)
    r = SPEC.reader(metric)
    read = lambda: layers.reducer(r["reducer"]).reduce(ctx, **r["args"])
    if loud:
        with pytest.raises(layers.MissingProgram, match="jit_"):
            read()
    else:
        assert read() is None


def test_a_program_the_benchmark_has_no_file_for_is_an_error(small_trace):
    _, ctx = _ctx(small_trace)
    assert ctx.module_of("train/step") == "jit_train_step"
    with pytest.raises(spec_mod.SpecError, match="programs/train/renamed.json"):
        ctx.module_of("train/renamed")


def test_breakdown_names_operations_and_gaps(small_trace):
    doc, tr = small_trace
    top = tr.top_ops(10)
    assert [t[0] for t in top][:2] == doc["expect"]["top_ops"]
    assert all(not n.startswith("while") for n, _ in top)
    gaps = dict(tr.idle_gaps(10))
    assert gaps == pytest.approx(doc["expect"]["idle_gaps"])
    assert tr.busy_s(0) == pytest.approx(doc["expect"]["busy_s"])


@pytest.mark.parametrize("metric", [m["name"] for m in DOC["per_layer"]
                                    if m["source"] == "device_trace"])
def test_reader_with_nothing_to_read_returns_nothing(metric):
    ctx = layers.Context(cell=SPEC.cell("opt-1.3b-d8.train-x1"), chips=1,
                         peaks={}, counters={}, model_config=None, trace=None)
    r = SPEC.reader(metric)
    assert layers.reducer(r["reducer"]).reduce(ctx, **r.get("args", {})) \
        is None


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_attention_cost_counts_resident_pages_only(kernel):
    from benchmarks.harness.serve_cell import Record
    import numpy as np

    cell = SPEC.cell("opt-1.3b.serve-decode")
    cfg = type("Cfg", (), dict(num_heads=32, num_kv_heads=32, head_dim=64,
                               num_layers=24))()
    rec = Record(0, 100, 4, np.zeros(100, np.int32), submit_time=10.0,
                 token_times=[11.0, 11.1, 11.2, 50.0])
    ctx = layers.Context(cell=cell, chips=1, peaks={}, counters={},
                         model_config=cfg, records=[rec], traced=(10.5, 12.0))
    ops, nbytes = layers.reducer(
        f"paged_{kernel}_attention_cost").total(ctx, calls=1)
    page = lambda tokens: 2 * (-(-tokens // 16) * 16) * 32 * 64 * 2
    if kernel == "decode":      # tokens 1 and 2 fell inside; contexts 101, 102
        assert ops == 24 * 4 * 2048 * (101 + 102)
        assert nbytes == 24 * (page(101) + page(102) + 2 * 2 * 2048 * 2)
    else:       # half of submit -> first token lies inside: half the chunk
        assert ops == 24 * 0.5 * 4 * 2048 * (100 * 101 // 2)
        assert nbytes == 24 * 0.5 * (page(100) + 2 * 100 * 2048 * 2)


# -- every cell end to end at tiny size, steered to the CPU ----------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("tinybench")))


@pytest.fixture
def steered(monkeypatch, tiny_root):
    monkeypatch.setitem(device.TARGET, "platform", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(tiny_root, ".jax_cache"))
    return spec_mod.Spec(tiny_root)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_at_tiny_size(cell, trace, steered, capsys):
    # a serving window long enough for a few requests even when six test
    # workers share the machine
    seconds = 0.6 if steered.cell(cell).traffic["kind"] == "train" else 3.0
    result = bench_run.run_cell(steered, cell, 2 ** 31 + 17, seconds,
                                bool(trace), time.perf_counter())
    out = capsys.readouterr().out
    assert result["correct"], out
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["device"]["platform"] == "cpu" and result["rehearsal"]
    assert result["device"]["count"] == steered.cell(cell).chips
    # counts only: never a time, a rate or a share from a CPU run
    sources = {m["name"]: m["source"]
               for m in DOC["end_to_end"] + DOC["per_layer"]}
    assert all(sources[k] == "program_counter" for k in result["metrics"])
    assert "busy_s" not in result["device"] and "breakdown" not in result
    if trace:
        assert [v["value"] for k, v in result["metrics"].items()
                if "compiles_in_window" in k] == [0.0]
    else:
        assert result["metrics"] == {}
    kind = steered.cell(cell).traffic["kind"]
    notes = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    if kind == "train":
        groups = next(n for n in notes if "group_seconds" in n)
        assert len(groups["group_seconds"]) >= 2      # printed, every run
    else:
        counts = next(n for n in notes if "ttft_samples" in n)
        assert counts["ttft_samples"] >= 2 and counts["gap_samples"] >= 20


def test_open_loop_cell_arrives_as_data_only(steered, tiny_root, capsys):
    """A cell under Open questions is data: a traffic file of kind
    `open_loop` and an entry in BENCHMARK.json, and it runs."""
    root = steered.root
    t = json.load(open(os.path.join(root, "benchmarks", "traffic",
                                    "serve-decode.json")))
    t.update(kind="open_loop", clients=6,
             arrivals={"process": "bursts", "rate": 30.0, "burst": 3})
    json.dump(t, open(os.path.join(root, "benchmarks", "traffic",
                                   "serve-chat-burst.json"), "w"))
    doc = copy.deepcopy(DOC)
    doc["workloads"].append(dict(live_document.named(doc["workloads"],
                                        "opt-1.3b.serve-decode"),
                                 name="opt-1.3b.serve-chat-burst",
                                 traffic="serve-chat-burst"))
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "opt-1.3b.serve-decode" in m.get("workloads", ()):
            m["workloads"].append("opt-1.3b.serve-chat-burst")
    json.dump(doc, open(os.path.join(root, "BENCHMARK.json"), "w"))
    try:
        s = spec_mod.Spec(root)
        s.validate()
        result = bench_run.run_cell(s, "opt-1.3b.serve-chat-burst", 5, 3.0,
                                    False, time.perf_counter())
    finally:
        json.dump(DOC, open(os.path.join(root, "BENCHMARK.json"), "w"))
    assert result["correct"] and result["attempted"] >= 2
    notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert next(n for n in notes if "ttft_samples" in n)[
        "generator_late_p95_ms"] is not None     # lateness is reported


def _files(top):
    return {os.path.relpath(os.path.join(d, f), top): os.path.join(d, f)
            for d, _, fs in os.walk(top) if "__pycache__" not in d
            for f in fs}


def _arrives(name, cfg, like, why, tmp_path, monkeypatch, capsys,
             reference=None):
    """Adds a configuration (as `cfg` has it) to a tiny root as files and
    entries, with its plain reference where the benchmark has none for its
    family, validates it as it is and cut to its own small model, and serves
    its cell (under the traffic and metrics of the cell `like`) at tiny size
    on the CPU. Returns the result, the served log-probabilities' distance
    from the reference, the run's output, the root and its files as they
    were before."""
    root = bench_tiny.make_root(str(tmp_path))
    before = {rel: open(path, "rb").read()
              for rel, path in _files(root).items()}
    if reference:
        shutil.copy(os.path.join(HERE, "fixtures", "references", reference),
                    os.path.join(root, "benchmarks", "references"))
    cell = _add_config_and_cell(root, name, cfg, like, why)
    # the file holds together as it is, and again cut to its own small model
    spec = spec_mod.Spec(root)
    for as_run in (cfg, bench_tiny.tiny_config(cfg)):
        json.dump(as_run, open(spec.path("configs", f"{name}.json"), "w"))
        spec.validate()
    monkeypatch.setitem(device.TARGET, "platform", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(root, ".jax_cache"))
    assert spec.cell(cell).traffic["reference"]["logprob_atol"] == 1e-3
    result = bench_run.run_cell(spec, cell, 2 ** 31 + 29, 3.0, False,
                                time.perf_counter())
    out = capsys.readouterr().out
    assert result["failed"] == 0 and result["attempted"] >= 2
    notes = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    diff = next(n for n in notes if "ttft_samples" in n)[
        "reference_logprob_maxdiff"]
    return result, diff, out, root, before


def _neox_arrives(cfg, why, tmp_path, monkeypatch, capsys):
    return _arrives("gptneox-20b", cfg, "opt-1.3b.serve-decode", why,
                    tmp_path, monkeypatch, capsys, reference="gptneox.py")


NEOX_FILES = {"benchmarks/configs/gptneox-20b.json",
              "benchmarks/references/gptneox.py"}


def _only_files_were_added(root, before, files=NEOX_FILES):
    """The harness that ran is the repo's own, and what was copied beside it
    is byte for byte what it was."""
    after = _files(root)
    assert all(open(after[rel], "rb").read() == data
               for rel, data in before.items() if rel != "BENCHMARK.json")
    added = {rel for rel in set(after) - set(before)
             if not rel.startswith(".jax_cache")}
    assert added == files
    repo = _files(SPEC.bench_dir)
    for sub in ("references", "reducers", "layer_metrics"):
        for rel, path in _files(os.path.join(root, "benchmarks", sub)).items():
            if os.path.join(sub, rel) in repo:
                assert open(path, "rb").read() == open(
                    repo[os.path.join(sub, rel)], "rb").read()
    assert bench_run.__file__.startswith(spec_mod.REPO_ROOT)


def _neox_fixture():
    return json.load(open(os.path.join(HERE, "fixtures", "configs",
                                       "gptneox-20b.json")))


@pytest.mark.parametrize("rope_base", ["the-small-models-own",
                                       "the-sources"])
def test_new_family_arrives_as_files(rope_base, tmp_path, monkeypatch,
                                     capsys):
    """A configuration of a family the benchmark has never run (GPT-NeoX:
    rotary on a quarter of each head, parallel residual, a head of its own)
    is a configuration file, a reference file and entries in BENCHMARK.json:
    it validates and serves `correct` against its own float32 reference
    through the paged path, and no file that was there has changed.

    float32 on both sides: the served log-probabilities read 9.5e-7 from the
    reference. Called with the source's rope base (10000) where the small
    model turns at 500 the reference reads 4.4e-4 away, with half of each
    head rotated 1.5e-3, with 8 heads 2.7e-3: the limit here is 1e-4, so
    that an argument which did not come from the file's `tiny` block shows
    (the second case)."""
    cfg = _neox_fixture()
    if rope_base == "the-sources":
        cfg["tiny"]["reference_args"]["rotary_emb_base"] = cfg["published"][
            "rotary_emb_base"]
    result, diff, out, root, before = _neox_arrives(
        cfg, "rotary on part of each head, parallel residual, untied head",
        tmp_path, monkeypatch, capsys)
    if rope_base == "the-sources":
        assert diff > 1e-4
        return
    assert result["correct"], out
    assert diff <= 1e-4
    _only_files_were_added(root, before)


def _solar_over_16(cfg):
    """Solar's own file as one of SIXTEEN chips that share each layer would
    run it: 20 of the 320 experts here, and the vocabulary still in eighths
    (each slice on two of the sixteen), which only the object form can say.
    The small model: a router of 8 x 16 outputs over 8 experts held."""
    cfg["share"] = {
        "chips": 16, "divided": {"n_routed_experts": 16, "vocab_size": 8},
        "how": "16 chips share each layer: its 320 routed experts 16 ways "
               "(20 here), the vocabulary 8 ways, each slice on 2 of the "
               "16; everything else whole on every chip"}
    cfg["n_routed_experts"] = cfg["model"]["overrides"][
        "moe_experts_held"] = 20
    cfg["reduced"]["n_routed_experts"] = "320 -> 20 held, the router whole"
    cfg["tiny"]["overrides"].update(moe_num_experts=128, moe_experts_held=8)
    return cfg


@pytest.mark.parametrize("what", ["gptneox-vocabulary-over-8",
                                  "solar-experts-16-ways-vocabulary-8"])
def test_a_sliced_vocabulary_arrives_as_files(what, tmp_path, monkeypatch,
                                              capsys):
    """The same family as one of 8 chips that divide the vocabulary among
    them (the guide's third cut, where it needs nothing of the program): the
    file states the deployment under `share`, runs 50432 / 8 rows as a
    smaller vocabulary, and lists the key under `reduced`. The traffic draws
    its ids from the slice, the served tokens are checked against it and the
    reference, which reads the slice from the parameters' shapes, agrees:
    files and entries only, as for any family.

    The second case is a share with TWO divisors rehearsed end to end: the
    Solar program's small model with its experts divided 16 ways (8 of 128
    held, the router whole) and its vocabulary 8 ways, one configuration
    file and entries."""
    if what.startswith("solar"):
        like = "solar-open2-250b-ep8-d4.serve-decode-r64"
        name, reference = "solar-open2-250b-ep16-d4", None
        cfg = _solar_over_16(copy.deepcopy(SPEC.cell(like).config))
        why = ("one of 16 chips that share each layer, the vocabulary in "
               "eighths: attention and the recurrent layers see 16 times "
               "their share of the batch")
        ways, held = {"n_routed_experts": 16, "vocab_size": 8}, 8
        files = {f"benchmarks/configs/{name}.json"}
    else:
        like, name, reference = ("opt-1.3b.serve-decode", "gptneox-20b",
                                 "gptneox.py")
        cfg = _neox_fixture()
        cfg["model"]["overrides"]["vocab_size"] = 50432 // 8
        cfg["reduced"]["vocab_size"] = "50432 -> 6304: an eighth"
        cfg["share"] = {"chips": 8, "divided": ["vocab_size"],
                        "how": "8 chips divide the embedding's and the "
                               "head's rows; every layer whole on each"}
        cfg["tiny"]["overrides"]["num_layers"] = 4      # a share's floor
        why = ("an eighth of the vocabulary, every layer whole: attention "
               "sees more than its share of the batch")
        ways, held, files = {"vocab_size": 8}, None, NEOX_FILES
    result, diff, out, root, before = _arrives(
        name, cfg, like, why, tmp_path, monkeypatch, capsys,
        reference=reference)
    assert result["correct"], out
    assert diff <= 1e-4
    traffic_name = next(w["traffic"] for w in DOC["workloads"]
                        if w["name"] == like)
    as_run = spec_mod.Spec(root).cell(f"{name}.{traffic_name}").config
    assert spec_mod.share_ways(as_run) == ways
    # `published` holds the WHOLE counts: what is run times each key's ways
    assert as_run["model"]["overrides"]["vocab_size"] == 256
    assert as_run["published"]["vocab_size"] == 256 * 8
    if held:
        over = as_run["model"]["overrides"]
        assert over["moe_experts_held"] == held
        assert over["moe_num_experts"] == as_run["published"][
            "n_routed_experts"] == held * ways["n_routed_experts"]
    _only_files_were_added(root, before, files)


# -- no chip, no result ----------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_without_a_tpu_the_command_fails_and_prints_no_result(cell, capsys,
                                                              monkeypatch,
                                                              tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = bench_run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                         "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == 1
    assert "need a tpu device" in captured.err
    assert not [ln for ln in captured.out.splitlines() if ln.startswith("{")]


def test_unknown_device_kind_is_an_error_not_a_default():
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(device.NoChip):
        device.peaks("TPU v99")
