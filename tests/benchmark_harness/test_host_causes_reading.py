"""The readers of the host's causes (PR 57): ten `layer_metrics/` files over
the spans the serving engine records while a capture is open, and the three
reducers they bring (`span_wait_ms`, `span_max`, `span_share`; the rest read
through the accepted `span_count`). Each reads nothing on the records of a
commit before the counts were added, and the accepted readers of the same
spans keep their meaning on the fixture, whose iterations are laid out from
designed numbers (its `about` says which).

Since PR 59 the ten are entries of `BENCHMARK.json`, at the end of
`per_layer`, and the fixture lies in `fixtures/spans/host_causes.json`, where
`test_program_span_metrics.py` finds it by its place and makes the cases that
stood here: each reader's known number, nothing recorded, no span record.
What is asked of the LIVE document is asked by name (`live_document.py`): the
serving cells are its cells whose traffic is not of kind `train`, whatever
their number."""

import json
import os

import pytest

import live_document
from benchmarks.harness import layers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
FIXTURE = json.load(open(os.path.join(HERE, "fixtures", "spans",
                                      "host_causes.json")))
SERVING = live_document.serving_cells(SPEC)

# name -> (unit, reducer, args): what the issue's table names
NEW = {
    "serve_ahead_late_pct": ("%", "span_count", {
        "span": "serving/decode", "count": "late", "stat": "mean",
        "scale": 100.0, "has": "rows"}),
    # the two that read `cpu_us` take the MEAN where the issue's table says
    # median: on the chip's host the thread's clock advances in steps of 10
    # ms, so one span's `cpu_us` is 0 or 10,000 and a median reads 0, while
    # the mean over a window's spans is what the thread ran (PERF.md, s. 3)
    "serve_host_sched_wait_ms": ("ms", "span_wait_ms", {
        "span": "serving/iteration", "stat": "mean",
        "less": ["serving/prefill_chunk", "serving/decode", "serving/verify",
                 "serving/iteration/lock_wait"]}),
    "serve_fetch_cpu_ms": ("ms", "span_count", {
        "span": "serving/decode/fetch", "count": "cpu_us", "stat": "mean",
        "scale": 0.001}),
    "serve_gc_pause_pct": ("%", "span_count", {
        "span": "runtime/gc", "count": "pause_us", "stat": "rate",
        "scale": 1e-4}),
    "serve_gc_pause_max_ms": ("ms", "span_max", {
        "span": "runtime/gc", "count": "pause_us", "scale": 0.001}),
    **{f"serve_not_ahead_pct.{rule}": ("%", "span_share", {
        "span": "serving/decode", "count": "held_by", "equals": rule,
        "among": ["rows"], "scale": 100.0})
       for rule in ("queued", "prefill", "row_freed")},
    **{f"serve_chunk_first_pct.{rule}": ("%", "span_share", {
        "span": "serving/decode", "count": "chunk_first_by", "equals": rule,
        "among": ["chunk_first_by", "behind_chunk"], "scale": 100.0})
       for rule in ("last_chunk", "pages")},
}
# the count each reader needs on the records: without it (the parent's
# records) the reader finds nothing
NEEDS = {name: ("cpu_us" if "cpu" in name or "wait" in name
                else args.get("count"))
         for name, (_, _, args) in NEW.items()}


def _read(metric, traced=tuple(FIXTURE["traced"])):
    ctx = layers.Context(cell=SPEC.cell("opt-1.3b.serve-decode"), chips=1,
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


def _without(spans, count, only=None):
    """The records of a commit before `count` was added."""
    return [dict(s, attrs={k: v for k, v in s.get("attrs", {}).items()
                           if k != count})
            if only is None or s["name"] == only else s for s in spans]


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_reader_is_what_the_issue_names(name):
    unit, reducer, args = NEW[name]
    r = SPEC.reader(name)
    assert (r["reducer"], r["args"]) == (reducer, args)
    assert {k: r[k] for k in ("layer", "unit", "better", "source",
                              "moves")} == {
        "layer": "serving engine", "unit": unit, "better": "lower",
        "source": "program_span", "moves": "itl_p50_ms"}
    assert spec_mod.NAME_RE.match(name) and spec_mod.UNIT_RE.match(unit)
    assert os.path.exists(SPEC.path("reducers", reducer + ".py"))
    assert hasattr(layers.reducer(reducer), "reduce")


def test_the_fixture_holds_what_a_known_number_needs():
    assert {"spans", "traced", "expect", "accepted", "about"} <= set(FIXTURE)
    assert set(FIXTURE["expect"]) == set(NEW)
    lo, hi = FIXTURE["traced"]
    inside = [s for s in FIXTURE["spans"]
              if lo <= s["start_s"] and s["end_s"] <= hi]
    assert inside and len(inside) < len(FIXTURE["spans"])
    assert len({s["id"] for s in FIXTURE["spans"]}) == len(FIXTURE["spans"])
    # a span that says how long its thread ran: never longer than it lasted
    asked = [s for s in FIXTURE["spans"] if "cpu_us" in s.get("attrs", {})]
    assert {s["name"] for s in asked} == {
        "serving/iteration", "serving/iteration/lock_wait", "serving/decode",
        "serving/prefill_chunk", "serving/decode/fetch", "runtime/gc"}
    assert all(0 <= s["attrs"]["cpu_us"] <= s["dur_us"] for s in asked)
    # a child lies inside its parent
    by_id = {s["id"]: s for s in FIXTURE["spans"]}
    for s in FIXTURE["spans"]:
        if "parent_id" in s and s["thread"] == by_id[s["parent_id"]]["thread"]:
            p = by_id[s["parent_id"]]
            assert p["start_s"] <= s["start_s"] and s["end_s"] <= p["end_s"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_whose_spans_carry_no_such_count_leaves_it_out(
        name, program):
    """The parent's records: `rows`, `ahead`, `behind_chunk`, and neither
    `cpu_us` nor `late`, `held_by`, `chunk_first_by`; no `runtime/gc` span."""
    spans = [s for s in _without(FIXTURE["spans"], NEEDS[name])
             if s["name"] != "runtime/gc"]
    program(spans)
    assert _read(name) is None


def _wait(spans, **args):
    return layers.reducer("span_wait_ms").reduce(
        layers.Context(cell=SPEC.cell("opt-1.3b.serve-decode"), chips=1,
                       peaks={}, counters={}, model_config=None), **args)


def _span(id_, name, start, dur_us, cpu_us=None, parent=None, thread="t"):
    s = {"name": name, "id": id_, "start_s": start,
         "end_s": start + dur_us * 1e-6, "thread": thread, "attrs": {}}
    if cpu_us is not None:
        s["attrs"]["cpu_us"] = cpu_us
    if parent is not None:
        s["parent_id"] = parent
    return s


class TestOffCpuSelfTime:
    """`span_wait_ms`: (duration less the children's) less (`cpu_us` less
    the children's), children of the same thread only."""

    def test_a_parent_and_its_children(self, program):
        program([_span(1, "p", 10.0, 1000, 300),
                 _span(2, "c", 10.0001, 400, 250, parent=1),
                 _span(3, "d", 10.0006, 100, 20, parent=1)])
        # own wall 500 us, own CPU 30 us
        assert _wait(None, span="p", less="children") == pytest.approx(0.47)
        # less `c` alone: own wall 600, own CPU 50
        assert _wait(None, span="p", less=["c"]) == pytest.approx(0.55)
        # nothing taken out: what the whole span waited
        assert _wait(None, span="p") == pytest.approx(0.7)

    def test_a_child_of_another_thread_is_ignored(self, program):
        program([_span(1, "p", 10.0, 1000, 300),
                 _span(2, "c", 10.0001, 400, 250, parent=1),
                 _span(3, "c", 10.0002, 700, 700, parent=1, thread="other")])
        assert _wait(None, span="p", less=["c"]) == pytest.approx(0.55)

    @pytest.mark.parametrize("bare", ["the_span", "a_child_taken_out"])
    def test_no_cpu_us_is_nothing_to_read(self, program, bare):
        program([_span(1, "p", 10.0, 1000,
                       None if bare == "the_span" else 300),
                 _span(2, "c", 10.0001, 400,
                       250 if bare == "the_span" else None, parent=1)])
        assert _wait(None, span="p", less=["c"]) is None

    def test_a_child_that_stays_in_needs_none(self, program):
        program([_span(1, "p", 10.0, 1000, 300),
                 _span(2, "c", 10.0001, 400, None, parent=1)])
        assert _wait(None, span="p", less=["other"]) == pytest.approx(0.7)

    def test_median_over_the_events_that_have_the_count(self, program):
        spans = [_span(i, "p", 10.0 + i, 1000, cpu)
                 for i, cpu in ((1, 100), (2, 500), (3, 900))]
        spans[0]["attrs"]["rows"] = 0
        for s in spans[1:]:
            s["attrs"]["rows"] = 4
        program(spans)
        assert _wait(None, span="p", stat="median") == pytest.approx(0.5)
        assert _wait(None, span="p", stat="median",
                     has="rows") == pytest.approx(0.3)


def test_span_share_counts_the_two_sets_each_for_itself(program):
    """The span that says why is not the span that carries the step's
    `rows`; a name is `among` as a number above 0 is."""
    spans = [dict(_span(i, "serving/decode", 10.0 + i, 100, 1), attrs=a)
             for i, a in enumerate([
                 {"rows": 8}, {"rows": 8, "held_by": "queued"},
                 {"held_by": "queued"}, {"held_by": "prefill"},
                 {"rows": 0, "held_by": "ends"}, {"rows": 8},
                 {"rows": 8, "behind_chunk": 1},
                 {"rows": 8, "behind_chunk": 0, "chunk_first_by": "pages"}])]
    program(spans)
    share = layers.reducer("span_share").reduce
    ctx = layers.Context(cell=SPEC.cell("opt-1.3b.serve-decode"), chips=1,
                         peaks={}, counters={}, model_config=None)
    held = dict(span="serving/decode", count="held_by", among=["rows"],
                scale=100.0)
    assert share(ctx, equals="queued", **held) == pytest.approx(40.0)
    assert share(ctx, equals="prefill", **held) == pytest.approx(20.0)
    assert share(ctx, equals="cow", **held) == 0.0      # asked, held none
    first = dict(span="serving/decode", count="chunk_first_by",
                 among=["chunk_first_by", "behind_chunk"], scale=100.0)
    assert share(ctx, equals="pages", **first) == pytest.approx(50.0)
    assert share(ctx, equals="last_chunk", **first) == 0.0


@pytest.mark.parametrize("metric", sorted(FIXTURE["accepted"]))
def test_the_accepted_readers_keep_their_meaning_on_these_spans(metric,
                                                                program):
    program(FIXTURE["spans"])
    assert _read(metric) == pytest.approx(FIXTURE["accepted"][metric],
                                          rel=1e-6)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_is_declared_and_equal_to_its_file(name):
    """Each for serving cells only, and only for those whose every traced
    run on the chip reported it (`PERF.md` section 3)."""
    m = live_document.is_what_its_file_gives(SPEC, name)
    assert m["layer"] == "serving engine" and m["moves"] == "itl_p50_ms"


def test_no_training_cell_reports_the_ten():
    """A training program records none of these spans."""
    assert set(FIXTURE["expect"]) == set(NEW)
    training = [w["name"] for w in SPEC.doc["workloads"]
                if w["name"] not in SERVING]
    assert SERVING and training
    for cell in training:
        assert not set(NEW) & {m["name"] for m in SPEC.cell(cell).per_layer}
