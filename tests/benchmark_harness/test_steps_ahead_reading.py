"""`layer_metrics/serve_steps_ahead_pct.json` (PR 54) reads the share of decode
steps the driver thread enqueued ahead off the `serving/decode` spans' `ahead`,
through the accepted `span_count` reducer: a known number on recorded spans,
nothing where the program carries no `ahead` (the parent commit) or no span
record at all. The reader is NOT yet an entry of `BENCHMARK.json`: an entry
behind the last breaks `test_nemotron_h_cell.py`'s `per_layer[-3:]`, a file
this PR may not edit (`PERF.md` §7). Its fixture therefore lies beside
`fixtures/spans/`, not in it, where every fixture's metric must be declared;
the PR that declares the metric moves it there and drops the known-number
case here."""

import json
import os

import pytest

from benchmarks.harness import layers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
METRIC = "serve_steps_ahead_pct"
FIXTURE = json.load(open(os.path.join(HERE, "fixtures",
                                      "steps_ahead_spans.json")))


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


def test_the_fixture_holds_what_a_known_number_needs():
    assert {"spans", "traced", "expect"} <= set(FIXTURE)
    lo, hi = FIXTURE["traced"]
    assert any(lo <= s["start_s"] and s["end_s"] <= hi
               for s in FIXTURE["spans"])
    assert len({s["id"] for s in FIXTURE["spans"]}) == len(FIXTURE["spans"])


def test_known_number_on_the_recorded_spans(program, capfd):
    """Three of the four steps with rows inside the traced second went
    ahead; the span that holds a fetch alone and the step after the second
    are no samples."""
    program(FIXTURE["spans"])
    assert _read(tuple(FIXTURE["traced"])) == pytest.approx(
        FIXTURE["expect"][METRIC], rel=1e-9)
    assert " samples" in capfd.readouterr().err


def test_a_program_whose_spans_carry_no_ahead_leaves_the_metric_out(program):
    """The parent's `serving/decode` spans: `rows`, and no `ahead`."""
    spans = [dict(s, attrs={k: v for k, v in s.get("attrs", {}).items()
                            if k != "ahead"}) for s in FIXTURE["spans"]]
    program(spans)
    assert _read(tuple(FIXTURE["traced"])) is None


@pytest.mark.parametrize("spans", [[], None], ids=["nothing", "no_record"])
def test_nothing_recorded_leaves_the_metric_out(spans, program, monkeypatch):
    if spans is None:
        from deepspeed_tpu import observability
        monkeypatch.delattr(observability, "recorded_spans")
    else:
        program(spans)
    assert _read((300.0, 301.0)) is None
