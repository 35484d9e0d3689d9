"""`layer_metrics/serve_chunk_ahead_pct.json` (PR 58) reads the share of
prefill chunks the driver thread enqueued AHEAD, behind their prompt's last
chunk and the decode step behind that, with the step's tokens not yet fetched,
off the `serving/prefill_chunk` spans' `ahead`, through the accepted
`span_count` reducer, over the spans that carry `tokens` (the one that holds a
chunk's fetch: each chunk once): nothing where the program carries no `ahead`
(the parent commit). Since PR 59 the reader is an entry of `BENCHMARK.json`,
at the end of `per_layer`, and its fixture lies in
`fixtures/spans/chunk_ahead.json`, where `test_program_span_metrics.py` finds
it by its place and makes the cases that stood here: the known number, nothing
recorded, no span record, what a fixture holds, the entry equal to the file.
The fixture's spans have the shapes `docs/serving.md`'s table gives an
iteration whose next chunk went ahead, so the accepted readers of the same
spans are held to their meaning on it too."""

import json
import os

import pytest

import live_document
from benchmarks.harness import layers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
METRIC = "serve_chunk_ahead_pct"
FIXTURE = json.load(open(os.path.join(HERE, "fixtures", "spans",
                                      "chunk_ahead.json")))


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
    assert r["args"] == {"span": "serving/prefill_chunk", "count": "ahead",
                         "stat": "mean", "scale": 100.0, "has": "tokens"}
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


def test_both_halves_of_a_chunk_say_whether_it_went_ahead_and_agree():
    halves = {}
    for s in FIXTURE["spans"]:
        if s["name"] == "serving/prefill_chunk":
            a = s["attrs"]
            halves.setdefault((a["rid"], a["chunk_start"]), set()).add(
                (a["ahead"], a.get("late")))
    assert halves and all(len(v) == 1 for v in halves.values())


def test_a_program_whose_spans_carry_no_count_leaves_the_metric_out(program):
    """The parent's `serving/prefill_chunk` spans: `rid`, `chunk_start`,
    `sampled_rows`, `tokens`, and no `ahead`."""
    spans = [dict(s, attrs={k: v for k, v in s.get("attrs", {}).items()
                            if k not in ("ahead", "late")})
             for s in FIXTURE["spans"]]
    program(spans)
    assert _read(tuple(FIXTURE["traced"])) is None


@pytest.mark.parametrize("metric,want", [
    # four chunks carry `tokens`: two late halves behind their step (fetch
    # 5.3 / 5.8 + apply 1.3 in 6.75 / 7.25 ms: 1.45 less the fetch), the last
    # chunk that went ahead (the delivery and the account before its fetch:
    # 6.15 less 4.0) and the whole one-chunk prompt (9.2 less 7.1)
    ("serve_host_prefill_ms", 1.775),
    # 256 + 256 + 128 + 100 tokens in the traced second, each chunk once
    ("serve_prefill_tok_s.program", 740.0),
    # five steps with rows: prepare, dispatch and the delivery (0.9 ms), two
    # that start their iteration or follow a last chunk (0.6), one ahead
    # (6.5 - 5.6) and one fetched where it was enqueued (6.8 - 5.8)
    ("serve_host_decode_ms", 0.9),
    ("serve_steps_ahead_pct", 20.0),
    ("serve_decode_behind_chunk_pct", 40.0),
    ("serve_host_prepare_ms", 0.2),
    ("serve_dispatch_host_operands", 1.0),
    # `late` on a decode span is the STEP's; a chunk's lies on its own spans
    ("serve_ahead_late_pct", 0.0),
    ("serve_chunk_first_pct.last_chunk", 50.0),
])
def test_the_accepted_readers_keep_their_meaning_on_these_spans(
        metric, want, program):
    program(FIXTURE["spans"])
    assert _read(tuple(FIXTURE["traced"]), metric) == pytest.approx(
        want, rel=1e-6)
