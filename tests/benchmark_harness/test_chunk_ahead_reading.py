"""`layer_metrics/serve_chunk_ahead_pct.json` (PR 58) reads the share of
prefill chunks the driver thread enqueued AHEAD, behind their prompt's last
chunk and the decode step behind that, with the step's tokens not yet fetched,
off the `serving/prefill_chunk` spans' `ahead`, through the accepted
`span_count` reducer, over the spans that carry `tokens` (the one that holds a
chunk's fetch: each chunk once): a known number on recorded spans, nothing
where the program carries no `ahead` (the parent commit) or no span record at
all. Like PR 56's `serve_decode_behind_chunk_pct` beside it, the reader is a
FILE, not yet an entry of `BENCHMARK.json` (ROADMAP B0 xiii: an entry behind
the last breaks `test_nemotron_h_cell.py`'s `per_layer[-3:]`, a file no PR but
a `benchmark` PR may edit). Its fixture therefore lies beside `fixtures/spans/`,
not in it, where every fixture's metric must be declared; the PR that declares
the metric moves it there and drops the known-number case here. The fixture's
spans have the shapes `docs/serving.md`'s table gives an iteration whose next
chunk went ahead, so the accepted readers of the same spans are held to their
meaning on it too."""

import json
import os

import pytest

from benchmarks.harness import layers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
METRIC = "serve_chunk_ahead_pct"
FIXTURE = json.load(open(os.path.join(HERE, "fixtures",
                                      "chunk_ahead_spans.json")))


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


def test_the_fixture_holds_what_a_known_number_needs():
    assert {"spans", "traced", "expect"} <= set(FIXTURE)
    lo, hi = FIXTURE["traced"]
    assert any(lo <= s["start_s"] and s["end_s"] <= hi
               for s in FIXTURE["spans"])
    assert len({s["id"] for s in FIXTURE["spans"]}) == len(FIXTURE["spans"])
    # both halves of a chunk say whether it went ahead, and agree
    halves = {}
    for s in FIXTURE["spans"]:
        if s["name"] == "serving/prefill_chunk":
            a = s["attrs"]
            halves.setdefault((a["rid"], a["chunk_start"]), set()).add(
                (a["ahead"], a.get("late")))
    assert all(len(v) == 1 for v in halves.values())


def test_known_number_on_the_recorded_spans(program, capfd):
    """Two of the four chunks with `tokens` inside the traced second went
    ahead; the spans that hold a prepare and a dispatch alone, the chunk the
    pool could not place and the chunk after the second are no samples."""
    program(FIXTURE["spans"])
    assert _read(tuple(FIXTURE["traced"])) == pytest.approx(
        FIXTURE["expect"][METRIC], rel=1e-9)
    assert " samples" in capfd.readouterr().err


def test_a_program_whose_spans_carry_no_count_leaves_the_metric_out(program):
    """The parent's `serving/prefill_chunk` spans: `rid`, `chunk_start`,
    `sampled_rows`, `tokens`, and no `ahead`."""
    spans = [dict(s, attrs={k: v for k, v in s.get("attrs", {}).items()
                            if k not in ("ahead", "late")})
             for s in FIXTURE["spans"]]
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
