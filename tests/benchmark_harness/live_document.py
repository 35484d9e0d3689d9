"""What a cell's or a reader's test asks of the LIVE document, by name.

A later PR appends cells to `workloads` and entries to `per_layer`; a test
that counts the live document, or reads it by place, then fails in that PR
for a line it may not edit (`test_later_document.py` runs every
document-reading test against such a document). So a test finds its own
entries here: a metric by its name, the serving cells by their traffic's
kind."""

import json


def named(entries, name, default=None):
    """The entry of a list of the document that is called `name`."""
    return next((e for e in entries if e["name"] == name), default)


def serving_cells(spec):
    """The document's cells whose traffic is not of kind `train`, whatever
    their number, in the document's order."""
    def kind(traffic):
        with open(spec.path("traffic", f"{traffic}.json")) as f:
            return json.load(f)["kind"]
    return [w["name"] for w in spec.doc["workloads"]
            if kind(w["traffic"]) != "train"]


def entry(spec, name):
    """The `per_layer` entry called `name`, or None where the metric is a
    file under `layer_metrics/` and no entry."""
    return named(spec.doc["per_layer"], name)


def is_what_its_file_gives(spec, name, cells=None):
    """The declared entry `name` says what `layer_metrics/<name>.json` says
    and nothing else, and lists serving cells only: exactly `cells` where
    given, else some of them (a reader is listed for the cells whose every
    traced run on the chip reported it, which may be fewer than all)."""
    m, r = entry(spec, name), spec.reader(name)
    assert m is not None, f"{name} is a file and no entry"
    keys = ("unit", "better", "source", "layer", "moves")
    assert {k: m[k] for k in keys} == {k: r[k] for k in keys}
    assert set(m) == {"name", "workloads", *keys}
    serving = serving_cells(spec)
    assert m["workloads"] and set(m["workloads"]) <= set(serving)
    if cells is not None:
        assert m["workloads"] == list(cells)
    return m
