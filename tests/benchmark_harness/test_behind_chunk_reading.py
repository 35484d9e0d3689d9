"""`layer_metrics/serve_decode_behind_chunk_pct.json` (PR 56) reads the share
of decode steps the driver thread enqueued behind their iteration's chunk, with
the chunk not yet fetched, off the `serving/decode` spans' `behind_chunk`,
through the accepted `span_count` reducer: nothing where the program carries no
`behind_chunk` (the parent commit). Since PR 59 the reader is an entry of
`BENCHMARK.json`, at the end of `per_layer`, and its fixture lies in
`fixtures/spans/behind_chunk.json`, where `test_program_span_metrics.py` finds
it by its place and makes the cases that stood here: the known number, nothing
recorded, no span record, what a fixture holds, the entry equal to the file.
The fixture's spans have the shapes `docs/serving.md`'s table gives an
iteration whose step went behind its chunk, so the accepted readers of the
same spans are held to their meaning on it too."""

import json
import os

import pytest

import live_document
from benchmarks.harness import layers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
METRIC = "serve_decode_behind_chunk_pct"
FIXTURE = json.load(open(os.path.join(HERE, "fixtures", "spans",
                                      "behind_chunk.json")))


def _read(traced=None, metric=METRIC):
    ctx = layers.Context(cell=SPEC.cell("opt-1.3b.serve-prefill"), chips=1,
                         peaks={}, counters={}, model_config=None,
                         traced=traced)
    r = SPEC.reader(metric)
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
    assert r["args"] == {"span": "serving/decode", "count": "behind_chunk",
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


def test_a_program_whose_spans_carry_no_count_leaves_the_metric_out(program):
    """The parent's `serving/decode` spans: `rows`, `ahead`, and no
    `behind_chunk`."""
    spans = [dict(s, attrs={k: v for k, v in s.get("attrs", {}).items()
                            if k != "behind_chunk"})
             for s in FIXTURE["spans"]]
    program(spans)
    assert _read(tuple(FIXTURE["traced"])) is None


@pytest.mark.parametrize("metric,want", [
    # three chunks carry `tokens`: two late halves (fetch 5.3 + apply 1.3 in
    # 6.8 ms: 1.5 less the fetch) and the whole last chunk (9.2 less 7.1)
    ("serve_host_prefill_ms", 1.5),
    # 256 + 256 + 128 tokens in the traced second, each chunk counted once
    ("serve_prefill_tok_s.program", 640.0),
    # four steps with rows: two hold prepare, dispatch and the delivery
    # (0.9 ms, no fetch), one stays in flight (0.6), one is ahead (6.5 - 5.6)
    ("serve_host_decode_ms", 0.9),
    ("serve_steps_ahead_pct", 25.0),
    ("serve_host_prepare_ms", 0.2),
    ("serve_dispatch_host_operands", 1.0),
])
def test_the_accepted_readers_keep_their_meaning_on_these_spans(
        metric, want, program):
    program(FIXTURE["spans"])
    assert _read(tuple(FIXTURE["traced"]), metric) == pytest.approx(
        want, rel=1e-6)
