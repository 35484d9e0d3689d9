"""No test of the harness holds the live document to a count or a place.

A later PR may only ADD: a cell at the end of `workloads`, its name at the end
of the lists that hold cells like it, a per-layer entry at the END of
`per_layer` (the driver reads one anywhere else as a change to what stood
there). A test that counts the live document or reads it by index then fails
in THAT PR, for a line under `tests/benchmark_harness/` that it may not edit
(PR 57 wrote `len(SERVING) == 6`; `per_layer[-3:]` stood from PR 47 to PR 59
and kept twenty readers out of the document). So the fault has to show in the
PR that writes it: this file builds the later document from the live one, lays
it over a scratch copy of the benchmark's own directories, and runs there every
test that reads the document's entries. The scratch copy is a tree of its own
(`benchmarks/` is imported from it), so nothing is steered or patched: it is
the recipe a builder runs by hand before a PR that touches these tests is
offered (`benchmarks/README.md`, "Adding things")."""

import copy
import glob
import json
import os
import shutil
import subprocess
import sys

import live_document
from benchmarks.harness import spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
# the later cell: an accepted configuration under another accepted traffic
# file (the first pair the document does not have yet: run from a scratch
# tree that IS a later document, this file adds the next one); and the reader
# whose file the scratch entry copies (one of the device's trace: a reader of
# the program's spans would also owe a known number under `fixtures/spans/`,
# as `test_program_span_metrics.py` asks)
CONFIG, TRAFFICS = "phi-4-mini-flash-reasoning", (
    "serve-decode-r64", "serve-decode-r64-ssm", "serve-decode")
COPIED_READER, SCRATCH_METRIC = "serve_decode_iter_ms", "scratch_later_entry"
# the tests that read the document's entries; those that run a tiny model
# end to end (minutes) are the whole directory's, by hand
FILES = ("test_*_cell.py", "test_*_reading.py", "test_program_span_metrics.py",
         "test_gap_phase_metrics.py", "test_moe_metrics.py",
         "test_benchmark_harness.py")
NOT_END_TO_END = "not tiny_size and not arrives"
PROBE = """from benchmarks.harness import spec as spec_mod


def test_the_scratch_tree_is_what_is_read():
    assert spec_mod.REPO_ROOT == {root!r}
    doc = spec_mod.Spec().doc
    assert doc["workloads"][-1]["name"] == {cell!r}
    assert doc["per_layer"][-1]["name"] == {metric!r}
"""


def later_document(spec):
    """The live document as a later `model_config` PR would leave it: one
    more serving cell, named in every end-to-end and per-layer list that
    holds ALL serving cells, and one per-layer entry appended at the end,
    which the new cell alone reports: the last of `workloads` and the last
    of `per_layer`."""
    doc = copy.deepcopy(spec.doc)
    serving = live_document.serving_cells(spec)
    have = {w["name"] for w in doc["workloads"]}
    cell, traffic = next((f"{CONFIG}.{t}", t) for t in TRAFFICS
                         if f"{CONFIG}.{t}" not in have)
    doc["workloads"].append({
        "name": cell, "config": CONFIG, "traffic": traffic, "chips": 1,
        "why": "a later PR's cell: closed loop on the whole model"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if set(serving) <= set(m.get("workloads", ())):
            m["workloads"].append(cell)
    r = spec.reader(COPIED_READER)
    doc["per_layer"].append(dict(
        name=f"{SCRATCH_METRIC}_{len(doc['per_layer'])}", workloads=[cell],
        **{k: r[k] for k in ("unit", "better", "source", "layer", "moves")}))
    return doc


def scratch_tree(tmp, spec, doc):
    """`BENCHMARK.json` as the later document `doc` beside copies of the
    benchmark's own directories (and `tests/conftest.py`), with the scratch
    entry's reader as one more file: what `git archive` of that later PR
    would hold of them."""
    metric = doc["per_layer"][-1]["name"]
    ignore = shutil.ignore_patterns("__pycache__", ".jax_cache", "*.pyc")
    for path in spec.doc["paths"]:
        shutil.copytree(os.path.join(spec.root, path),
                        os.path.join(tmp, path), ignore=ignore)
    shutil.copy(os.path.join(spec.root, "tests", "conftest.py"),
                os.path.join(tmp, "tests"))
    metrics = os.path.join(tmp, spec.doc["paths"][0], "layer_metrics")
    shutil.copy(os.path.join(metrics, f"{COPIED_READER}.json"),
                os.path.join(metrics, f"{metric}.json"))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f, indent=1)
    return tmp


def test_the_later_document_is_the_live_one_and_what_a_later_pr_adds(
        tmp_path):
    doc, live = later_document(SPEC), SPEC.doc
    new_cell = doc["workloads"][-1]["name"]
    new_metric = doc["per_layer"][-1]["name"]
    assert new_cell not in {w["name"] for w in live["workloads"]}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        # nothing that stood is moved or reworded, but for a list that grew
        for was, now in zip(live[key], doc[key]):
            grown = dict(now)
            if "workloads" in now and now["workloads"] != was["workloads"]:
                assert now["workloads"] == was["workloads"] + [new_cell]
                grown["workloads"] = was["workloads"]
            assert grown == was
    assert len(doc["workloads"]) == len(live["workloads"]) + 1
    assert len(doc["per_layer"]) == len(live["per_layer"]) + 1
    later = spec_mod.Spec(scratch_tree(str(tmp_path), SPEC, doc))
    later.validate()
    assert live_document.serving_cells(later) \
        == live_document.serving_cells(SPEC) + [new_cell]
    cell = later.cell(new_cell)
    assert sorted(m["name"] for m in cell.end_to_end) == ["itl_p50_ms",
                                                          "setup_s"]
    assert new_metric in {m["name"] for m in cell.per_layer}


def test_a_later_document_breaks_no_test(tmp_path):
    """Every document-reading test of this directory, run from a scratch
    tree whose document is the later one. On the tree before PR 59 this
    failed at `test_nemotron_h_cell.py`'s `per_layer[-3:]` and at
    `test_host_causes_reading.py`'s `len(SERVING) == 6`."""
    doc = later_document(SPEC)
    root = scratch_tree(str(tmp_path), SPEC, doc)
    tests = os.path.join(root, os.path.relpath(HERE, SPEC.root))
    found = [sorted(glob.glob(os.path.join(tests, p))) for p in FILES]
    assert all(found), FILES
    files = sorted({f for fs in found for f in fs})
    # and one test more, so that a pass cannot be the LIVE document's: the
    # run imports the scratch tree's `benchmarks` and reads its document
    probe = os.path.join(tests, "test_the_scratch_tree_is_what_is_read.py")
    with open(probe, "w") as f:
        f.write(PROBE.format(root=root, cell=doc["workloads"][-1]["name"],
                             metric=doc["per_layer"][-1]["name"]))
    files.append(probe)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [SPEC.root] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "--rootdir", root,
         "-k", NOT_END_TO_END] + files,
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stdout[-6000:] + run.stderr[-2000:]
