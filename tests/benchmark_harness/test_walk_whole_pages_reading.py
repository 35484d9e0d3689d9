"""`layer_metrics/serve_walk_whole_pages_pct.json` (PR 66) reads, off the
`serving/decode` spans, the share of a step's resident pages that lie in
tiles whose every page is resident (`walk_pages_whole` over `walk_pages`,
through the accepted `span_count` reducer): the pages the decode walk starts
unrolled and awaits with one wait a side. Nothing where the program carries
neither count (the parent commit). The entry stands at the end of
`per_layer`; the known number is `fixtures/spans/walk_whole_pages.json`'s,
which `test_program_span_metrics.py` finds by its place."""

import json
import os

import pytest

import live_document
from benchmarks.harness import layers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
METRIC = "serve_walk_whole_pages_pct"
FIXTURE = json.load(open(os.path.join(HERE, "fixtures", "spans",
                                      "walk_whole_pages.json")))


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
    assert r["args"] == {"span": "serving/decode",
                         "count": "walk_pages_whole", "over": "walk_pages",
                         "stat": "mean", "scale": 100.0}
    assert {k: r[k] for k in ("layer", "unit", "better", "source",
                              "moves")} == {
        "layer": "kernels", "unit": "%", "better": "higher",
        "source": "program_span", "moves": "itl_p50_ms"}
    assert spec_mod.NAME_RE.match(METRIC) and spec_mod.UNIT_RE.match(r["unit"])


def test_the_metric_is_declared_and_equal_to_its_file():
    """For the serving cells that run the two-pool walk most, each by the
    evidence of three traced runs on the chip (`PERF.md` section 3); the
    fixture declares this metric alone."""
    live_document.is_what_its_file_gives(SPEC, METRIC)
    assert list(FIXTURE["expect"]) == [METRIC]


def test_the_known_number_is_the_mean_of_the_steps_shares(program):
    program(FIXTURE["spans"])
    assert _read(tuple(FIXTURE["traced"])) == pytest.approx(
        (0 + 256 / 288 + 80 / 85) / 3 * 100)


def test_a_program_whose_spans_carry_neither_count_leaves_the_metric_out(
        program):
    """The parent's `serving/decode` spans: `rows`, and no page counts."""
    spans = [dict(s, attrs={k: v for k, v in s.get("attrs", {}).items()
                            if not k.startswith("walk_")})
             for s in FIXTURE["spans"]]
    program(spans)
    assert _read(tuple(FIXTURE["traced"])) is None


def test_a_step_whose_rows_hold_no_page_is_no_sample(program):
    """`walk_pages` 0 (no such step is enqueued; a reader divides by it)."""
    spans = [dict(s, attrs=dict(s["attrs"], walk_pages=0, walk_pages_whole=0))
             for s in FIXTURE["spans"][:1]] + FIXTURE["spans"][1:]
    program(spans)
    assert _read(tuple(FIXTURE["traced"])) == pytest.approx(
        (256 / 288 + 80 / 85) / 2 * 100)
