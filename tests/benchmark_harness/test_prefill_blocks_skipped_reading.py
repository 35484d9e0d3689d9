"""`layer_metrics/serve_prefill_blocks_skipped_pct.json` (PR 69) reads, off
the `serving/prefill_chunk` spans, the share of the (query block, key
sub-block) pairs a chunk's tiles span that the paged prefill kernel's tile
step does not compute (`prefill_blocks_skipped` over `prefill_blocks`,
through the accepted `span_count` reducer): the sub-blocks past the row's
keys, above the chunk's diagonal or of pad queries alone. Nothing where the
program carries neither count (the parent commit). The entry stands at the
end of `per_layer`; the known number is
`fixtures/spans/prefill_blocks_skipped.json`'s, which
`test_program_span_metrics.py` finds by its place."""

import json
import os

import pytest

import live_document
from benchmarks.harness import layers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
METRIC = "serve_prefill_blocks_skipped_pct"
CELLS = ["opt-1.3b.serve-prefill", "lfm2-8b-a1b-d12.serve-docs-r16"]
FIXTURE = json.load(open(os.path.join(HERE, "fixtures", "spans",
                                      "prefill_blocks_skipped.json")))


def _read(traced=None):
    ctx = layers.Context(cell=SPEC.cell("opt-1.3b.serve-prefill"), chips=1,
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
    assert r["args"] == {"span": "serving/prefill_chunk",
                         "count": "prefill_blocks_skipped",
                         "over": "prefill_blocks", "stat": "mean",
                         "scale": 100.0}
    assert {k: r[k] for k in ("layer", "unit", "better", "source",
                              "moves")} == {
        "layer": "kernels", "unit": "%", "better": "higher",
        "source": "program_span", "moves": "itl_p50_ms"}
    assert spec_mod.NAME_RE.match(METRIC) and spec_mod.UNIT_RE.match(r["unit"])


def test_the_metric_is_declared_for_the_two_cells_the_issue_names():
    """The two cells whose median iteration carries a chunk; the fixture
    declares this metric alone."""
    live_document.is_what_its_file_gives(SPEC, METRIC, cells=CELLS)
    assert list(FIXTURE["expect"]) == [METRIC]


def test_the_known_number_is_the_mean_of_the_chunks_shares(program):
    program(FIXTURE["spans"])
    assert _read(tuple(FIXTURE["traced"])) == pytest.approx(
        (1 / 2 + 0 / 2 + 1 / 4) / 3 * 100)


def test_a_program_whose_spans_carry_neither_count_leaves_the_metric_out(
        program):
    """The parent's `serving/prefill_chunk` spans: no block counts."""
    spans = [dict(s, attrs={k: v for k, v in s.get("attrs", {}).items()
                            if not k.startswith("prefill_blocks")})
             for s in FIXTURE["spans"]]
    program(spans)
    assert _read(tuple(FIXTURE["traced"])) is None


def test_a_chunk_whose_tiles_span_no_block_is_no_sample(program):
    """`prefill_blocks` 0 (no such chunk is enqueued; a reader divides by
    it)."""
    spans = [dict(s, attrs=dict(s["attrs"], prefill_blocks=0,
                                prefill_blocks_skipped=0))
             for s in FIXTURE["spans"][:1]] + FIXTURE["spans"][1:]
    program(spans)
    assert _read(tuple(FIXTURE["traced"])) == pytest.approx(
        (0 / 2 + 1 / 4) / 2 * 100)
