"""The per-layer metrics that read the PROGRAM's spans (`program_span`, and
`serve_preemptions`, a count): each gives a known number on a small recorded
set of spans, leaves itself out where there is nothing to read, and reads
nothing from a program that has no span record (the parent commit, which the
driver runs these files against)."""

import json
import os

import pytest

from benchmarks.harness import layers, spec as spec_mod
from benchmarks.reducers import program_spans, span_count, span_ms

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
FIXTURE = json.load(open(os.path.join(HERE, "fixtures", "small_spans.json")))


def span_fixtures(directory):
    """`small_spans.json` and every `spans/<anything>.json` beside it, by
    name: a later PR brings the known number of a span metric of its own as
    one more file there, each with its `spans`, its `traced` seconds and the
    numbers to `expect`."""
    found = {"small_spans": json.load(
        open(os.path.join(directory, "small_spans.json")))}
    more = os.path.join(directory, "spans")
    for f in sorted(os.listdir(more)) if os.path.isdir(more) else ():
        if f.endswith(".json"):
            found[f"spans/{f[:-5]}"] = json.load(open(os.path.join(more, f)))
    return found


FIXTURES = span_fixtures(os.path.join(HERE, "fixtures"))
KNOWN = [(name, metric) for name, doc in FIXTURES.items()
         for metric in sorted(doc["expect"])]
METRICS = sorted({metric for _, metric in KNOWN})


def _ctx(traced=None):
    return layers.Context(cell=SPEC.cell("opt-1.3b.serve-decode"), chips=1,
                          peaks={}, counters={}, model_config=None,
                          traced=traced)


def _read(metric, ctx):
    r = SPEC.reader(metric)
    return layers.reducer(r["reducer"]).reduce(ctx, **r.get("args", {}))


@pytest.fixture
def program(monkeypatch):
    """Stands in for the program's accessor: what it would hand back."""
    from deepspeed_tpu import observability

    def set_spans(spans):
        monkeypatch.setattr(observability, "recorded_spans",
                            lambda: list(spans))
    return set_spans


def test_every_span_metric_of_the_benchmark_has_a_known_number():
    """In some fixture: the ten of `small_spans.json` stay as they are, and
    a metric that a later PR declares needs a file under `fixtures/spans/`,
    not an edit here. Every reducer over the recorded spans counts (`span_ms`,
    `span_count` and, since PR 57, `span_wait_ms`, `span_max`, `span_share`:
    whatever is called `span_*`), a later PR's too."""
    declared = {m["name"] for m in SPEC.doc["per_layer"]
                if SPEC.reader(m["name"])["reducer"].startswith("span_")}
    assert declared <= set(METRICS), sorted(declared - set(METRICS))
    assert len(FIXTURE["expect"]) == 10 and set(FIXTURE["expect"]) <= declared
    # and a known number is of a metric that has its file
    for metric in METRICS:
        assert os.path.exists(SPEC.path("layer_metrics", f"{metric}.json"))


@pytest.mark.parametrize("fixture", sorted(FIXTURES))
def test_a_span_fixture_holds_what_a_known_number_needs(fixture):
    doc = FIXTURES[fixture]
    assert {"spans", "traced", "expect"} <= set(doc) and doc["expect"]
    lo, hi = doc["traced"]
    assert lo < hi
    assert any(lo <= s["start_s"] and s["end_s"] <= hi for s in doc["spans"])
    assert len({s["id"] for s in doc["spans"]}) == len(doc["spans"])


def test_a_later_fixture_is_found_by_its_place(tmp_path):
    """What a PR that may only add files does: one more file, no edit."""
    (tmp_path / "spans").mkdir()
    for name, doc in (("small_spans.json", FIXTURE),
                      ("spans/a_new_metric.json",
                       {"spans": [], "traced": [0.0, 1.0],
                        "expect": {"a_new_span_metric_ms": 1.0}}),
                      ("spans/notes.txt", "not a fixture")):
        (tmp_path / name).write_text(json.dumps(doc))
    found = span_fixtures(str(tmp_path))
    assert sorted(found) == ["small_spans", "spans/a_new_metric"]
    assert found["spans/a_new_metric"]["expect"] == {
        "a_new_span_metric_ms": 1.0}


@pytest.mark.parametrize("fixture,metric", KNOWN,
                         ids=[f"{f}-{m}" for f, m in KNOWN])
def test_known_number_on_the_recorded_spans(fixture, metric, program, capfd):
    doc = FIXTURES[fixture]
    program(doc["spans"])
    got = _read(metric, _ctx(traced=tuple(doc["traced"])))
    assert got == pytest.approx(doc["expect"][metric], rel=1e-9)
    assert " samples" in capfd.readouterr().err     # how many it rests on


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_recorded_leaves_the_metric_out(metric, program):
    program([])
    assert _read(metric, _ctx(traced=(100.0, 103.0))) is None
    assert _read(metric, _ctx()) is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_a_span_record_leaves_the_metric_out(
        metric, monkeypatch):
    """The parent commit has no `recorded_spans`: the reader returns nothing
    and does not raise."""
    from deepspeed_tpu import observability

    monkeypatch.delattr(observability, "recorded_spans")
    assert _read(metric, _ctx(traced=(100.0, 103.0))) is None


@pytest.mark.parametrize("metric", sorted(FIXTURE["expect_all_recorded"]))
def test_without_traced_seconds_every_recorded_span_counts(metric, program):
    """The CPU rehearsal sets no `ctx.traced`."""
    program(FIXTURE["spans"])
    assert _read(metric, _ctx()) == pytest.approx(
        FIXTURE["expect_all_recorded"][metric])


def test_a_rate_needs_the_traced_seconds(program):
    program(FIXTURE["spans"])
    assert _read("serve_prefill_tok_s.program", _ctx()) is None


def test_spans_outside_the_traced_seconds_do_not_count(program):
    program(FIXTURE["spans"])
    _, inside = program_spans.recorded(_ctx(traced=(100.0, 103.0)),
                                       "serving/iteration")
    assert [s["attrs"]["it"] for s in inside] == [7, 8, 9]
    _, every = program_spans.recorded(_ctx(), "serving/iteration")
    assert [s["attrs"]["it"] for s in every] == [6, 7, 8, 9, 30]


def test_self_time_is_duration_less_what_the_children_cover(program):
    program(FIXTURE["spans"])
    ctx = _ctx(traced=(100.0, 103.0))
    # iteration 7: 80 ms, its children cover 1 + 1 + 76 + 1; 8: 200 ms,
    # 1 + 108 + 86; 9: 90 ms, 86 + 1
    assert span_ms.reduce(ctx, "serving/iteration", stat="mean",
                          less="children") == pytest.approx(
                              (1.0 + 5.0 + 3.0) / 3)
    assert span_ms.reduce(ctx, "serving/iteration", stat="mean") == \
        pytest.approx((80.0 + 200.0 + 90.0) / 3)
    with pytest.raises(ValueError, match="unknown statistic"):
        span_ms.reduce(ctx, "serving/iteration", stat="p99")


def test_an_event_without_the_count_is_no_sample(program):
    spans = [dict(s) for s in FIXTURE["spans"]]
    for s in spans:
        if s["name"] == "serving/prefill_chunk" and s["id"] == 12:
            s["attrs"] = {"rid": 5, "chunk_start": 0}   # pool dry: no chunk
    program(spans)
    assert span_count.reduce(_ctx(traced=(100.0, 103.0)),
                             "serving/prefill_chunk", "tokens",
                             stat="rate") is None


@pytest.mark.parametrize("metric,unfiltered", [
    ("serve_host_decode_ms", 5.0),          # median of 1, 4, 6, 8
    ("serve_host_prefill_ms", 4.5),         # median of 1, 8
    ("serve_batch_occupancy_pct", 56.25),   # mean of 0, 50, 100, 75
])
def test_a_span_whose_work_did_not_run_is_no_sample(metric, unfiltered,
                                                    program):
    """A decode step with no row ready (`rows` 0) and a chunk the pool could
    not place (no `tokens`) are recorded, and kept out by `has`: the cell's
    metrics then read one population."""
    program(FIXTURE["spans"])
    ctx = _ctx(traced=tuple(FIXTURE["traced"]))
    r = SPEC.reader(metric)
    args = dict(r["args"])
    assert args.pop("has") in ("rows", "tokens")
    reduce = layers.reducer(r["reducer"]).reduce
    assert reduce(ctx, **args) == pytest.approx(unfiltered)
    assert reduce(ctx, **r["args"]) == pytest.approx(
        FIXTURE["expect"][metric])


def test_new_metrics_agree_with_their_files():
    by_name = {m["name"]: m for m in SPEC.doc["per_layer"]}
    for name in METRICS:
        r, m = SPEC.reader(name), by_name[name]
        assert {k: r[k] for k in ("layer", "unit", "moves", "source",
                                  "better")} == \
            {k: m[k] for k in ("layer", "unit", "moves", "source", "better")}
        if name in FIXTURE["expect"]:
            assert m["source"] == ("program_counter"
                                   if name == "serve_preemptions"
                                   else "program_span")
        else:       # a later fixture's: a span's time, or a count it carries
            assert m["source"] in ("program_span", "program_counter")
