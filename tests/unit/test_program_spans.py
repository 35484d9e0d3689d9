"""The one span API on the profiler's clock (``observability/spans.py``) and
its call sites in the serving and training engines: a span lies in the
``jax.profiler`` capture with its counts, and in the in-memory record with an
id and its parent's id; nothing records without a session or a capture; the
serving iteration is covered from the inside; the program's TTFT counts from
entry to ``submit()``; one clock reading serves each dispatch boundary."""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.config.config import ObservabilityConfig, ServingConfig
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.observability import (configure_observability,
                                         get_registry, get_session,
                                         recorded_spans, reset_session)
from deepspeed_tpu.observability.memory import hbm_counts
from deepspeed_tpu.observability.spans import NOOP_SPAN, Span, SpanTracer
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.serving import ServingEngine


@pytest.fixture(autouse=True)
def _obs_isolation():
    reset_session()
    get_registry().reset()
    yield
    reset_session()
    get_registry().reset()


class Capture:
    """A ``jax.profiler`` capture as the benchmark's ``--trace 1`` opens it;
    ``events()`` reads the host events back from the ``.xplane.pb``."""

    def __init__(self, path):
        self.dir = str(path)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def events(self, prefix):
        """{line index: [(name, start_ns, end_ns, stats)]} of the host
        events whose name starts with `prefix`."""
        (path,) = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        out = {}
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for i, line in enumerate(plane.lines):
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith(prefix)]
                if evs:
                    out[i] = evs
        return out


# ---------------------------------------------------------------------------
# the span call


class TestSpanCall:
    def test_no_session_no_capture_is_the_shared_noop(self, monkeypatch):
        """No ``Span`` is built and no clock is read."""
        obs = get_session()
        assert not obs.enabled
        monkeypatch.setattr(Span, "__init__", lambda *a, **k: pytest.fail(
            "a Span was built with nothing recording"))
        reads = []
        monkeypatch.setattr(time, "perf_counter_ns",
                            lambda: reads.append(1) or 0)
        with obs.span("serving/x", rows=3) as s:
            s.annotate(rows=4)
        assert s is NOOP_SPAN and not s.recording and s.duration_s == 0.0
        assert obs.span("serving/y").begin().end() is NOOP_SPAN
        assert not reads and recorded_spans() == []

    def test_span_lies_in_the_capture_with_its_stats_nested(self, tmp_path):
        obs = get_session()

        def work():
            with obs.span("serving/outer", it=3, queued=2) as outer:
                time.sleep(0.002)
                with obs.span("serving/inner", rows=5):
                    time.sleep(0.001)
                outer.annotate(blocks_in_use=7)

        with Capture(tmp_path) as cap:
            th = threading.Thread(target=work, name="worker")
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()
        (line,) = cap.events("serving/").values()      # one thread, one line
        by = {name: (a, b, stats) for name, a, b, stats in line}
        assert by["serving/outer"][2] == {"it": 3, "queued": 2,
                                          "blocks_in_use": 7}
        assert by["serving/inner"][2] == {"rows": 5}
        assert by["serving/outer"][0] <= by["serving/inner"][0]
        assert by["serving/inner"][1] <= by["serving/outer"][1]

    def test_in_memory_record_has_id_parent_id_thread_and_counts(
            self, tmp_path):
        obs = get_session()
        with Capture(tmp_path):
            t0 = time.perf_counter()
            with obs.span("serving/outer", it=1):
                with obs.span("serving/inner", rows=5):
                    pass
                with obs.span("serving/inner", rows=6):
                    pass
            t1 = time.perf_counter()
        inner1, inner2, outer = recorded_spans()        # closing order
        assert outer["name"] == "serving/outer" and "parent_id" not in outer
        assert inner1["parent_id"] == inner2["parent_id"] == outer["id"]
        assert len({inner1["id"], inner2["id"], outer["id"]}) == 3
        assert [inner1["attrs"], inner2["attrs"]] == [{"rows": 5},
                                                      {"rows": 6}]
        assert outer["thread"] == threading.current_thread().name
        # perf_counter seconds: the clock the harness brackets a capture on
        assert t0 <= outer["start_s"] <= inner1["start_s"]
        assert inner2["end_s"] <= outer["end_s"] <= t1

    def test_span_begun_before_the_capture_is_not_recorded(self, tmp_path):
        obs = get_session()
        early = obs.span("serving/early")
        early.begin()
        with Capture(tmp_path):
            early.end()
            with obs.span("serving/late"):
                pass
        assert early is NOOP_SPAN
        assert [s["name"] for s in recorded_spans()] == ["serving/late"]

    def test_next_capture_starts_with_an_empty_record(self, tmp_path):
        obs = get_session()
        with Capture(tmp_path / "a"):
            with obs.span("serving/first"):
                pass
        assert obs.span("serving/between") is NOOP_SPAN
        assert [s["name"] for s in recorded_spans()] == ["serving/first"]
        with Capture(tmp_path / "b"):
            with obs.span("serving/second"):
                pass
        assert [s["name"] for s in recorded_spans()] == ["serving/second"]

    def test_enabled_session_records_without_a_capture_and_keeps_all(
            self, tmp_path):
        obs = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "obs"),
            recompile_watchdog=False, flight_recorder=False,
            hang_watchdog=False))
        with obs.span("train_batch", step=1):
            pass
        with Capture(tmp_path / "cap") as cap:
            with obs.span("train_batch", step=2):
                pass
        with obs.span("train_batch", step=3):
            pass
        assert [s["attrs"]["step"] for s in recorded_spans()] == [1, 2, 3]
        ((only,),) = cap.events("train_batch").values()
        assert only[3] == {"step": 2}       # the capture holds what it saw

    def test_a_span_stamps_itself_on_perf_counter(self):
        """Whatever clock its call site keeps for itself: parents, children
        and the harness's traced seconds are then on one clock."""
        tr = SpanTracer(process_index=0)
        p0 = time.perf_counter()
        tr.span("serving/decode/dispatch").begin().end()
        p1 = time.perf_counter()
        (rec,) = tr.snapshot()
        assert p0 <= rec["start_s"] <= rec["end_s"] <= p1
        assert rec["dur_us"] == pytest.approx(
            (rec["end_s"] - rec["start_s"]) * 1e6, abs=1e-3)

    def test_phase_spans_leave_compile_attribution_to_their_parent(self):
        tr = SpanTracer(process_index=0)
        with tr.span("serving/decode"):
            with tr.span("serving/decode/dispatch", category="phase"):
                assert tr.current_name() == "serving/decode"
            with tr.span("serving/cow_copy"):
                assert tr.current_name() == "serving/cow_copy"
        assert tr.current_name() is None

    def test_parent_end_closes_a_child_an_exception_left_open(self,
                                                              tmp_path):
        obs = get_session()
        with Capture(tmp_path) as cap:
            with obs.span("serving/decode"):
                obs.span("serving/decode/dispatch").begin()    # never ended
            with obs.span("serving/after"):
                pass
        names = [s["name"] for s in recorded_spans()]
        assert names == ["serving/decode", "serving/after"]
        assert "parent_id" not in recorded_spans()[1]   # the stack healed
        (line,) = cap.events("serving/").values()
        assert {e[0] for e in line} == {"serving/decode", "serving/after",
                                        "serving/decode/dispatch"}

    def test_a_capture_only_span_never_syncs(self, tmp_path, monkeypatch):
        from deepspeed_tpu.observability import spans as spans_mod

        monkeypatch.setattr(spans_mod, "_drain_dispatch_queue",
                            lambda: pytest.fail("synced under a capture"))
        with Capture(tmp_path):
            with get_session().span("checkpoint/save", sync=True) as s:
                pass
        assert s.recording and not s.sync

    def test_hbm_counts_are_empty_on_a_statless_backend(self):
        assert hbm_counts() == {}       # the CPU reports no memory stats


# ---------------------------------------------------------------------------
# the serving engine from the inside


@pytest.fixture(scope="module")
def tiny_engine():
    return init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)


def serving(tiny_engine, **kw):
    cfg = dict(block_size=16, num_blocks=32, max_seqs=4, max_model_len=128,
               prefill_chunk=16, max_queue=64, prefix_cache=False)
    return ServingEngine(tiny_engine, ServingConfig(**cfg), **kw)


@pytest.fixture(scope="module")
def served(tiny_engine, tmp_path_factory):
    """Six requests through a tiny engine on its driver thread, under a
    capture: (recorded spans, rise of prefill_tokens_run, handles)."""
    reset_session()
    srv = serving(tiny_engine)
    srv.submit(np.arange(1, 40), max_new_tokens=3)
    srv.run()                                   # both programs compiled
    srv.start()
    with Capture(tmp_path_factory.mktemp("served")):
        before = srv.prefill_tokens_run
        handles = [srv.submit(np.arange(1, 20 + 7 * i),
                              max_new_tokens=4 + i) for i in range(6)]
        for h in handles:
            h.result(timeout_s=120)
        time.sleep(0.01)                        # a few idle polls
        srv.stop()
        rise = srv.prefill_tokens_run - before
    spans = recorded_spans()
    srv.close()
    return spans, rise, handles


class TestServingFromTheInside:
    def test_driver_thread_is_covered_by_iteration_and_idle(self, served):
        spans, _, _ = served
        driver = [s for s in spans if s["thread"] == "dstpu-serving"]
        top = sorted((s for s in driver if "parent_id" not in s),
                     key=lambda s: s["start_s"])
        assert {s["name"] for s in top} == {"serving/iteration",
                                            "serving/idle"}
        assert len(top) >= 8
        # between two top-level spans the driver only re-reads its queue:
        # the median stretch outside a span is tens of microseconds
        between = sorted(b["start_s"] - a["end_s"]
                         for a, b in zip(top, top[1:]))
        assert between[0] >= 0
        assert between[len(between) // 2] < 500e-6
        inside = sum(s["end_s"] - s["start_s"] for s in top)
        assert inside > 0.9 * (top[-1]["end_s"] - top[0]["start_s"])

    def test_iteration_children_are_the_documented_boundaries(self, served):
        spans, _, _ = served
        by_id = {s["id"]: s for s in spans}
        kids = {}
        for s in spans:
            if "parent_id" in s:
                kids.setdefault(by_id[s["parent_id"]]["name"],
                                set()).add(s["name"])
        assert kids["serving/iteration"] >= {
            "serving/iteration/lock_wait", "serving/admit",
            "serving/prefill_chunk", "serving/decode"}
        # the driver thread delivers and publishes behind the iteration's
        # first enqueue: inside that program's span, before its fetch
        assert kids["serving/decode"] == {
            "serving/decode/prepare", "serving/decode/dispatch",
            "serving/decode/fetch", "serving/emit", "serving/publish"}
        shadowed = 0
        for s in spans:
            parent = by_id.get(s.get("parent_id"))
            if (s["name"] == "serving/emit" and s["attrs"]["deferred"]) or (
                    s["name"] == "serving/publish"
                    and parent["name"] != "serving/iteration"):
                shadowed += 1
                disp, fetch = (next(
                    c for c in spans if c.get("parent_id") == parent["id"]
                    and c["name"] == parent["name"] + part)
                    for part in ("/dispatch", "/fetch"))
                assert disp["end_s"] <= s["start_s"] <= s["end_s"] \
                    <= fetch["start_s"]
        assert shadowed >= 8
        assert kids["serving/prefill_chunk"] >= {
            "serving/prefill_chunk/prepare",
            "serving/prefill_chunk/dispatch", "serving/prefill_chunk/fetch"}
        assert kids["serving/submit"] == {"serving/submit/lock_wait"}

    def test_iteration_and_decode_carry_their_counts(self, served):
        spans, _, _ = served
        its = [s["attrs"] for s in spans if s["name"] == "serving/iteration"]
        assert all(set(a) >= {"it", "queued", "running", "blocks_in_use",
                              "blocks_running", "blocks_total",
                              "preemptions"} for a in its)
        assert [a["it"] for a in its] == sorted(a["it"] for a in its)
        assert {a["blocks_total"] for a in its} == {32}
        assert max(a["blocks_in_use"] for a in its) > 0
        # no prefix cache here: every block handed out is in a running row
        assert all(a["blocks_running"] == a["blocks_in_use"] for a in its)
        dec = [s["attrs"] for s in spans if s["name"] == "serving/decode"]
        assert all(a["max_rows"] == 4 and 1 <= a["rows"] <= 4 for a in dec)
        admits = [s["attrs"] for s in spans if s["name"] == "serving/admit"]
        assert sum(a["admitted"] for a in admits) == 6

    def test_prefill_chunk_tokens_add_up_to_the_engines_count(self, served):
        spans, rise, _ = served
        chunks = [s["attrs"] for s in spans
                  if s["name"] == "serving/prefill_chunk"]
        assert sum(a["tokens"] for a in chunks) == rise > 0
        assert all(a["tokens"] <= 16 and a["chunk_start"] % 16 == 0
                   for a in chunks)

    def test_a_requests_life_shares_one_rid(self, served):
        spans, _, handles = served
        life = {}
        for s in spans:
            if s["name"].startswith(("serving/request/", "serving/submit")) \
                    and s["name"] != "serving/submit/lock_wait":
                life.setdefault(s["attrs"]["rid"], []).append(s)
        assert len(life) == len(handles)
        for rid, evs in life.items():
            names = [s["name"] for s in sorted(evs,
                                               key=lambda s: s["start_s"])]
            assert names == ["serving/submit", "serving/request/admitted",
                             "serving/request/first_token",
                             "serving/request/finished"], rid
            done = next(s["attrs"] for s in evs
                        if s["name"].endswith("finished"))
            assert done["state"] == "finished" and done["tokens"] >= 4
            first = next(s["attrs"] for s in evs
                         if s["name"].endswith("first_token"))
            admitted = next(s["attrs"] for s in evs
                            if s["name"].endswith("admitted"))
            assert first["ttft_us"] >= admitted["queue_wait_us"] >= 0
        chunk_rids = {s["attrs"]["rid"] for s in spans
                      if s["name"] == "serving/prefill_chunk"}
        assert chunk_rids == set(life)


class TestTtftFromEntryToSubmit:
    def test_ttft_includes_a_lock_another_thread_held(self, tiny_engine,
                                                      tmp_path):
        """A caller blocked on the engine's lock is waiting for its first
        token: `ttft_s`, `serving/ttft_ms` and the request trace count it."""
        obs = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "obs"),
            recompile_watchdog=False, flight_recorder=False,
            hang_watchdog=False, request_tracing=True,
            trace_sample_rate=1.0, serve_goodput=True))
        srv = serving(tiny_engine)
        held, release = threading.Event(), threading.Event()

        def holder():
            with srv._lock:
                held.set()
                release.wait(30)

        th = threading.Thread(target=holder)
        th.start()
        assert held.wait(30)
        threading.Timer(0.25, release.set).start()
        h = srv.submit(np.arange(1, 20), max_new_tokens=2)   # blocks 0.25 s
        th.join(timeout=30)
        assert not th.is_alive()
        req = h._req
        assert req.arrival_s - req.submit_s >= 0.2     # stamped on entry
        srv.run()
        assert req.ttft_s >= 0.2
        assert req.ttft_s == pytest.approx(req.first_token_s - req.submit_s)
        hist = obs.registry.histogram("serving/ttft_ms")
        assert hist.stats(tenant="default")["min"] >= 200.0
        (trace,) = obs.reqtrace.snapshot()
        assert trace["ttft_ms"] >= 200.0
        assert trace["phases"]["queue_wait"] >= 0.2
        waits = [s for s in recorded_spans()
                 if s["name"] == "serving/submit/lock_wait"]
        assert waits[-1]["dur_us"] >= 200_000
        first = next(s for s in recorded_spans()
                     if s["name"] == "serving/request/first_token")
        assert first["attrs"]["ttft_us"] >= 200_000
        assert first["attrs"]["trace_id"] == trace["trace_id"]
        srv.close()

    def test_admission_order_still_follows_arrival(self, tiny_engine):
        srv = serving(tiny_engine)
        a = srv.submit(np.arange(1, 9), max_new_tokens=1)._req
        b = srv.submit(np.arange(1, 9), max_new_tokens=1)._req
        assert a.submit_s <= a.arrival_s <= b.submit_s <= b.arrival_s
        assert srv.sched._pick_next() is a
        srv.run()
        srv.close()


class FakeClock:
    """An injected engine clock, as tests and the RLHF trainer pass one:
    whole seconds from 1000, nowhere near ``time.perf_counter``."""

    def __init__(self):
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return 1000.0 + self.reads


@pytest.mark.parametrize("telemetry", ["off", "goodput_and_reqtrace"])
def test_one_pair_of_engine_clock_readings_per_dispatch(tiny_engine, tmp_path,
                                                        telemetry):
    """Dispatch begins, the tokens are on the host: one pair of readings of
    the engine's clock a program, whoever consumes them (ServeGoodput,
    ReqTrace). The spans never take the engine's clock: they stay on
    ``perf_counter``, the clock of their parents and of the capture."""
    if telemetry != "off":
        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "obs"),
            recompile_watchdog=False, flight_recorder=False,
            hang_watchdog=False, request_tracing=True,
            trace_sample_rate=1.0, serve_goodput=True))
    clock = FakeClock()
    srv = serving(tiny_engine, clock=clock)
    obs = get_session()
    args = srv._decode_operands([])
    before = clock.reads
    p0 = time.perf_counter()
    with mesh_mod.ambient(srv.engine.mesh):
        with obs.span("serving/decode") as parent:
            tok, t0, t1 = srv._run_program(obs, "serving/decode",
                                           srv._decode, *args, srv._base_rng)
    p1 = time.perf_counter()
    assert clock.reads - before == 2 and t1 - t0 == 1.0
    assert tok.shape == (4,)
    if telemetry == "off":
        assert parent is NOOP_SPAN and recorded_spans() == []
    else:
        disp, fetch, dec = recorded_spans()[-3:]
        assert (disp["name"], fetch["name"]) == ("serving/decode/dispatch",
                                                 "serving/decode/fetch")
        assert disp["parent_id"] == fetch["parent_id"] == dec["id"]
        assert p0 <= dec["start_s"] <= disp["start_s"] <= disp["end_s"] \
            <= fetch["start_s"] <= fetch["end_s"] <= dec["end_s"] <= p1
    srv.close()


# ---------------------------------------------------------------------------
# the training step and generate()


def test_train_batch_spans_reach_the_capture_with_the_step(tmp_path):
    import deepspeed_tpu
    from deepspeed_tpu.models import simple_model
    from deepspeed_tpu.models.simple import random_batches

    engine, *_ = deepspeed_tpu.initialize(
        model=simple_model(hidden_dim=10),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}}})
    assert not engine._obs.enabled
    batches = random_batches(jax.random.PRNGKey(0), 3,
                             engine.train_batch_size())
    it = iter(batches)
    engine.train_batch(data_iter=it)            # compiles, no capture
    assert recorded_spans() == []
    with Capture(tmp_path) as cap:
        engine.train_batch(data_iter=it)
        engine.train_batch(data_iter=it)
    steps = [s for s in recorded_spans() if s["name"] == "train_batch"]
    assert [s["attrs"]["step"] for s in steps] == [1, 2]
    kids = {s["name"] for s in recorded_spans()
            if s.get("parent_id") == steps[0]["id"]}
    assert kids == {"train_batch/h2d", "train_batch/dispatch"}
    (line,) = cap.events("train_batch").values()
    assert [e[3] for e in line if e[0] == "train_batch"] == [{"step": 1},
                                                              {"step": 2}]


def test_generate_ttft_needs_no_telemetry(tiny_engine):
    assert not get_session().enabled
    out, ttft = tiny_engine.generate(np.arange(1, 9)[None], max_new_tokens=2,
                                     return_ttft=True)
    assert out.shape == (1, 2) and ttft > 0
