"""`gap_phase_ms`: the gap between two serving programs, split by
intersection with the program's own spans on the capture's clock. A small
hand-made capture (`fixtures/serving_gaps_trace.json`) holds the reducer, and
the metrics of the benchmark that read it, to round numbers: a gap with all
four parts, a program that started before its call returned, a fetch that
was over when the program ended, gaps with no dispatch of their own, the
gaps before one program, and the longest. A capture whose device clock is
early (programs that begin before their operands are on the device) is
moved back before it is split."""

import json
import os

import pytest

import live_document
from benchmarks.harness import layers, spec as spec_mod, trace as trace_mod
from benchmarks.reducers import gap_phase_ms, program_gap_pct

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
DOC = json.load(open(os.path.join(HERE, "fixtures",
                                  "serving_gaps_trace.json")))
SPLIT = ("wake", "host", "enqueue", "launch")
METRICS = sorted(m for m in DOC["expect"] if m != "serve_host_iter_pct")


def _ctx(trace=None):
    return layers.Context(cell=SPEC.cell("opt-1.3b.serve-prefill"), chips=1,
                          peaks={}, counters={}, model_config=None,
                          trace=trace)


@pytest.fixture(scope="module")
def ctx():
    return _ctx(trace_mod.Trace.from_json(DOC["trace"]))


def _read(metric, ctx):
    r = SPEC.reader(metric)
    return layers.reducer(r["reducer"]).reduce(ctx, **r.get("args", {}))


def test_every_gap_metric_of_the_benchmark_has_a_known_number():
    declared = {m["name"] for m in SPEC.doc["per_layer"]
                if SPEC.reader(m["name"])["reducer"] == "gap_phase_ms"}
    assert declared == set(METRICS)
    by_name = {m["name"]: m for m in SPEC.doc["per_layer"]}
    serving = live_document.serving_cells(SPEC)     # by the traffic's kind
    for name in METRICS:
        m = by_name[name]
        assert (m["layer"], m["unit"], m["better"], m["source"],
                m["moves"]) == ("serving engine", "ms", "lower",
                                "program_span", "itl_p50_ms")
        assert m["workloads"] == (["opt-1.3b.serve-prefill"]
                                  if name.endswith(".chunk") else serving)


@pytest.mark.parametrize("metric", METRICS)
def test_known_number_on_the_small_capture(metric, ctx, capfd):
    assert _read(metric, ctx) == pytest.approx(DOC["expect"][metric],
                                               rel=1e-9)
    said = capfd.readouterr().err
    assert "gap_phase_ms" in said and (
        "2 samples" if metric.endswith(".chunk") else "6 samples") in said


@pytest.mark.parametrize("gap", DOC["gaps"],
                         ids=[g["what"].split(":")[0][:48].replace(" ", "_")
                              for g in DOC["gaps"]])
def test_each_gap_of_the_capture_splits_as_written(gap, ctx):
    named = [(n, s, s + d) for evs in ctx.trace.host.values()
             for n, s, d in evs]
    got = gap_phase_ms.split(
        *(2.0 + t * 1e-3 for t in gap["ms"]),
        [(s, e) for n, s, e in named if n.endswith("/dispatch")],
        [(s, e) for n, s, e in named if n.endswith("/fetch")])
    for part in ("gap",) + SPLIT:
        assert 1e3 * got[part] == pytest.approx(gap[part], abs=1e-9), part
    assert sum(got[p] for p in SPLIT) == pytest.approx(got["gap"])


def test_the_four_parts_add_up_to_the_gap(ctx):
    parts = [gap_phase_ms.reduce(ctx, part=p) for p in SPLIT]
    assert sum(parts) == pytest.approx(gap_phase_ms.reduce(ctx, part="gap"),
                                       rel=1e-12)
    chunk = [gap_phase_ms.reduce(ctx, part=p, before="serving/prefill_chunk")
             for p in SPLIT]
    assert chunk == pytest.approx([0.25, 0.9, 0.85, 0.25])
    assert sum(chunk) == pytest.approx(gap_phase_ms.reduce(
        ctx, part="gap", before="serving/prefill_chunk"))


def test_mean_gap_times_gaps_is_the_share_program_gap_pct_reads(ctx):
    """Both take the union of the first chip's modules: the one can be held
    against the other in every traced run."""
    share = program_gap_pct.reduce(ctx)
    assert share == pytest.approx(DOC["expect"]["serve_host_iter_pct"])
    assert 100 * gap_phase_ms.reduce(ctx, part="gap") * len(DOC["gaps"]) \
        / DOC["window_ms"] == pytest.approx(share)


def test_the_longest_of_each_part(ctx):
    assert [gap_phase_ms.reduce(ctx, part=p, stat="max")
            for p in ("gap",) + SPLIT] == pytest.approx(
                [5.0, 1.0, 5.0, 1.0, 0.5])


def test_gaps_before_a_program_that_did_not_run_leave_the_metric_out(
        ctx, monkeypatch):
    monkeypatch.setattr(ctx, "module_of", lambda program: "jit_verify")
    assert gap_phase_ms.reduce(ctx, part="host",
                               before="serving/verify") is None


def test_only_the_serving_engines_spans_are_read(ctx):
    """A caller's `np.asarray` and the harness's `serve/...` spans cover
    gaps too and claim nothing; with no span of the program in the capture
    (a program that records none) every gap is all host."""
    tr = ctx.trace
    bare = trace_mod.Trace(ops=tr.ops, modules=tr.modules,
                           host={"python#0": tr.host["python#0"]})
    got = {p: gap_phase_ms.reduce(_ctx(bare), part=p)
           for p in ("gap",) + SPLIT}
    assert got == pytest.approx(dict(gap=2.75, wake=0.0, host=2.75,
                                     enqueue=0.0, launch=0.0))


def _steady(early_ms=0.0, puts=True):
    """Five decode programs of 6 ms, 3 ms apart, as a sound capture shows
    them: the fetch returns 1.2 ms after a program's end, 0.5 ms of host,
    a call of 1.6 ms whose two `DevicePut`s end 1.3 ms into it, which is
    when the next program begins. `early_ms` moves the device's times
    earlier, as a machine's first capture does."""
    mods, driver = [], []
    for k in range(5):
        t = 9.0 * k
        mods.append(["jit_decode(5)", 1.0 + (t - early_ms) * 1e-3, 6e-3])
        driver.append(["serving/decode/fetch", 1.0 + (t + 0.4) * 1e-3,
                       6.8e-3])
        d = t + 6.0 + 1.2 + 0.5
        driver.append(["serving/decode/dispatch", 1.0 + d * 1e-3, 1.6e-3])
        if puts:
            driver += [["DevicePut", 1.0 + (d + 0.1) * 1e-3, 0.6e-3],
                       ["DevicePut", 1.0 + (d + 0.7) * 1e-3, 0.6e-3]]
    return _ctx(trace_mod.Trace.from_json(
        {"ops": {"0": []}, "modules": {"0": mods},
         "host": {"dstpu-serving#3": driver}}))


@pytest.mark.parametrize("early_ms,moved", [(0.0, "+0.000"), (1.0, "+1.000"),
                                            (2.5, "+2.500")])
def test_a_capture_whose_device_clock_is_early_is_moved_back(early_ms, moved,
                                                             capfd):
    """A program cannot begin before its operands are on the device: where
    the capture says it did, by 1 ms (less than a call) or by 2.5 (more:
    the gap's end then lies before its own dispatch begins), the device's
    times move later by that much and the split is the sound capture's."""
    got = {p: gap_phase_ms.reduce(_steady(early_ms), part=p)
           for p in ("gap",) + SPLIT}
    assert got == pytest.approx(dict(gap=3.0, wake=1.2, host=0.5,
                                     enqueue=1.3, launch=0.0))
    assert f"device moved {moved} ms over 4 gaps" in capfd.readouterr().err


def test_a_late_start_is_launch_latency_and_moves_nothing(capfd):
    """Programs that begin AFTER their operands are there contradict
    nothing; and with no `DevicePut` in the call (operands that live on the
    device) the bound is the dispatch's own begin."""
    late = gap_phase_ms.reduce(_steady(-0.5), part="launch")
    assert late == pytest.approx(0.2)       # the call ended 0.3 ms after
    assert "device moved +0.000 ms" in capfd.readouterr().err
    got = {p: gap_phase_ms.reduce(_steady(1.0, puts=False), part=p)
           for p in SPLIT}
    assert got == pytest.approx(dict(wake=2.2, host=0.5, enqueue=0.3,
                                     launch=0.0))
    assert "device moved +0.000 ms" in capfd.readouterr().err


def test_the_fixtures_programs_begin_after_their_dispatch_began(ctx, capfd):
    gap_phase_ms.reduce(ctx, part="gap")
    assert "device moved +0.000 ms over 6 gaps" in capfd.readouterr().err


@pytest.mark.parametrize("metric", METRICS)
def test_no_trace_or_no_program_leaves_the_metric_out(metric):
    assert _read(metric, _ctx()) is None
    empty = trace_mod.Trace(ops={0: []}, modules={0: []}, host={})
    assert _read(metric, _ctx(empty)) is None


def test_an_unknown_part_or_statistic_is_an_error(ctx):
    with pytest.raises(ValueError, match="unknown part"):
        gap_phase_ms.reduce(ctx, part="sleep")
    with pytest.raises(ValueError, match="unknown statistic"):
        gap_phase_ms.reduce(ctx, part="gap", stat="median")
