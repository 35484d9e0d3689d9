"""`layer_metrics/serve_steps_ahead_pct.json` (PR 54) reads the share of decode
steps the driver thread enqueued ahead off the `serving/decode` spans' `ahead`,
through the accepted `span_count` reducer: nothing where the program carries no
`ahead` (the parent commit). Since PR 59 the reader is an entry of
`BENCHMARK.json`, at the end of `per_layer`, and its fixture lies in
`fixtures/spans/steps_ahead.json`, where `test_program_span_metrics.py` finds
it by its place and makes the cases that stood here: the known number, nothing
recorded, no span record, what a fixture holds, the entry equal to the file."""

import json
import os

import pytest

import live_document
from benchmarks.harness import layers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
METRIC = "serve_steps_ahead_pct"
FIXTURE = json.load(open(os.path.join(HERE, "fixtures", "spans",
                                      "steps_ahead.json")))


def _read(traced=None):
    ctx = layers.Context(cell=SPEC.cell("opt-1.3b.serve-decode"), chips=1,
                         peaks={}, counters={}, model_config=None,
                         traced=traced)
    r = SPEC.reader(METRIC)
    return layers.reducer(r["reducer"]).reduce(ctx, **r["args"])


@pytest.fixture
def program(monkeypatch):
    from deepspeed_tpu import observability

    def set_spans(spans):
        monkeypatch.setattr(observability, "recorded_spans",
                            lambda: list(spans))
    return set_spans


def test_the_reader_is_what_the_issue_names():
    r = SPEC.reader(METRIC)
    assert r["reducer"] == "span_count"
    assert r["args"] == {"span": "serving/decode", "count": "ahead",
                         "stat": "mean", "scale": 100.0, "has": "rows"}
    assert {k: r[k] for k in ("layer", "unit", "better", "source",
                              "moves")} == {
        "layer": "serving engine", "unit": "%", "better": "higher",
        "source": "program_span", "moves": "itl_p50_ms"}
    assert spec_mod.NAME_RE.match(METRIC) and spec_mod.UNIT_RE.match(r["unit"])


def test_the_metric_is_declared_and_equal_to_its_file():
    """For serving cells only, each by the evidence of three traced runs on
    the chip (`PERF.md` section 3); the fixture declares this metric alone."""
    live_document.is_what_its_file_gives(SPEC, METRIC)
    assert list(FIXTURE["expect"]) == [METRIC]


def test_a_program_whose_spans_carry_no_ahead_leaves_the_metric_out(program):
    """The parent's `serving/decode` spans: `rows`, and no `ahead`."""
    spans = [dict(s, attrs={k: v for k, v in s.get("attrs", {}).items()
                            if k != "ahead"}) for s in FIXTURE["spans"]]
    program(spans)
    assert _read(tuple(FIXTURE["traced"])) is None
