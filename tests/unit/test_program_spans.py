"""The one span API on the profiler's clock (``observability/spans.py``) and
its call sites in the serving and training engines: a span lies in the
``jax.profiler`` capture with its counts, and in the in-memory record with an
id and its parent's id; nothing records without a session or a capture; the
serving iteration is covered from the inside, in named leaves from one
enqueue to the next; the program's TTFT counts from entry to ``submit()``;
one clock reading serves each dispatch boundary, and a program that held the
engine is counted with nothing recording."""

import gc
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


def counts(attrs, cpu=False):
    """A span's counts less ``cpu_us``, which a span opened with ``cpu``
    carries and no test can give a number for."""
    assert ("cpu_us" in attrs) == cpu
    assert not cpu or (isinstance(attrs["cpu_us"], int)
                       and attrs["cpu_us"] >= 0)
    return {k: v for k, v in attrs.items() if k != "cpu_us"}


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
        monkeypatch.setattr(time, "thread_time_ns", lambda: pytest.fail(
            "the thread's clock was read with nothing recording"))
        with obs.span("serving/x", rows=3) as s:
            s.annotate(rows=4)
        assert s is NOOP_SPAN and not s.recording and s.duration_s == 0.0
        assert obs.span("serving/y").begin().end() is NOOP_SPAN
        assert not reads and recorded_spans() == []

    def test_span_lies_in_the_capture_with_its_stats_nested(self, tmp_path):
        obs = get_session()

        def work():
            with obs.span("serving/outer", cpu=True, it=3,
                          queued=2) as outer:
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
        assert counts(by["serving/outer"][2], cpu=True) == {
            "it": 3, "queued": 2, "blocks_in_use": 7}
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

    def test_snapshot_gives_the_record_it_gave_with_cpu_us_added(self):
        """One golden record, key for key: the dictionary is built when the
        record is read, not as the span closes. ``cpu_us`` where the span
        asked for it and nowhere else."""
        tr = SpanTracer(process_index=3, all_ranks=True)
        with tr.span("serving/decode", rows=2):
            with tr.span("serving/decode/fetch", category="phase",
                         cpu=True) as child:
                pass
            with tr.span("serving/decode/apply", category="phase"):
                pass
        fetch, apply_, decode = tr.snapshot()
        assert "attrs" not in apply_ and decode["attrs"] == {"rows": 2}
        assert list(fetch) == ["type", "name", "cat", "id", "start_s",
                               "end_s", "dur_us", "depth", "synced",
                               "parent_id", "attrs", "pid", "tid", "thread"]
        assert fetch == {
            "type": "span", "name": "serving/decode/fetch", "cat": "phase",
            "id": 2, "start_s": child.start_ns / 1e9,
            "end_s": child.end_ns / 1e9,
            "dur_us": (child.end_ns - child.start_ns) / 1e3, "depth": 1,
            "synced": False, "parent_id": 1,
            "attrs": {"cpu_us": fetch["attrs"]["cpu_us"]}, "pid": 3,
            "tid": threading.get_ident() & 0xFFFF,
            "thread": threading.current_thread().name}
        assert "parent_id" not in decode and decode["depth"] == 0
        assert tr.snapshot() == [fetch, apply_, decode]
        tr.close()

    def test_cpu_us_is_what_the_thread_ran(self):
        """A small part of the duration for a span that sleeps; for one
        that spins until its thread has run 30 ms, those 30 ms and at most
        the duration (however many other threads the machine runs)."""
        tr = SpanTracer(process_index=0)
        with tr.span("sleeps", cpu=True):
            time.sleep(0.05)
        with tr.span("spins", cpu=True):
            until = time.thread_time() + 0.03
            while time.thread_time() < until:
                pass
        sleeps, spins = tr.snapshot()
        assert sleeps["attrs"]["cpu_us"] < 0.2 * sleeps["dur_us"]
        assert 29_000 <= spins["attrs"]["cpu_us"] <= spins["dur_us"]
        tr.close()

    def test_off_cpu_self_time_is_what_a_span_waited(self):
        """(duration less the children's) less (``cpu_us`` less the
        children's): a parent that sleeps 30 ms around a child that spins."""
        tr = SpanTracer(process_index=0)
        with tr.span("parent", cpu=True):
            time.sleep(0.03)
            with tr.span("child", cpu=True):
                until = time.perf_counter() + 0.02
                while time.perf_counter() < until:
                    pass
        child, parent = tr.snapshot()
        waited = (parent["dur_us"] - child["dur_us"]) - (
            parent["attrs"]["cpu_us"] - child["attrs"]["cpu_us"])
        assert 25e3 < waited <= parent["dur_us"] - child["dur_us"]
        tr.close()


# ---------------------------------------------------------------------------
# the collector's pauses


class TestCollectorSpans:
    watches_collector = True        # ``tests/conftest.py``: elsewhere no
    #   tracer hooks the collector, so no test's record holds a stray span

    @pytest.fixture(autouse=True)
    def _nobody_watches_yet(self):
        """Tracers that other tests of this class built enabled and never
        closed would still watch: each test starts from a process that hooks
        nothing."""
        from deepspeed_tpu.observability import spans as spans_mod

        for tracer in list(spans_mod._gc_tracers):
            spans_mod._gc_watch(tracer, False)
        assert spans_mod._gc_callback not in gc.callbacks

    def test_a_forced_collection_is_one_span_of_its_threads_open_span(
            self, tmp_path):
        obs = get_session()
        before = list(gc.callbacks)
        seen = {}

        def collect():
            with obs.span("serving/submit") as mine:
                seen["mine"] = mine.id
                gc.collect()

        with Capture(tmp_path) as cap:
            with obs.span("serving/iteration") as other:
                assert len(gc.callbacks) == len(before) + 1
                th = threading.Thread(target=collect, name="caller")
                th.start()
                th.join(timeout=30)
            full = [s for s in recorded_spans() if s["name"] == "runtime/gc"
                    and s["attrs"]["generation"] == 2]
        (span,) = full
        assert span["parent_id"] == seen["mine"] != other.id
        assert span["thread"] == "caller" and span["cat"] == "runtime"
        assert set(span["attrs"]) == {"generation", "collected", "pause_us",
                                      "cpu_us"}
        assert span["attrs"]["pause_us"] == pytest.approx(span["dur_us"])
        # an event of the capture too, on the thread it ran on
        events = [e for line in cap.events("runtime/gc").values()
                  for e in line if e[3].get("generation") == 2]
        assert len(events) == 1 and events[0][3]["pause_us"] > 0
        # the capture has closed: reading the record takes the hook out
        assert len(recorded_spans()) >= 3 and gc.callbacks == before

    def test_a_root_on_a_thread_with_no_open_span(self, tmp_path):
        obs = get_session()
        with Capture(tmp_path):
            with obs.span("serving/iteration"):
                pass
            th = threading.Thread(target=gc.collect, name="bare")
            th.start()
            th.join(timeout=30)
            (span,) = [s for s in recorded_spans()
                       if s["name"] == "runtime/gc" and s["thread"] == "bare"]
        assert "parent_id" not in span and span["depth"] == 0

    def test_short_collections_are_counted_not_recorded(self, monkeypatch):
        from deepspeed_tpu.observability import spans as spans_mod

        tr = SpanTracer(process_index=0)
        assert tr.gc_counts() == {"gc_collections": 0, "gc_pause_us": 0}
        monkeypatch.setattr(spans_mod, "GC_RECORD_US", 60e6)
        gc.collect(0)
        gc.collect(1)
        got = tr.gc_counts()
        assert got["gc_collections"] == 2 and got["gc_pause_us"] >= 0
        assert tr.gc_counts() == {"gc_collections": 0, "gc_pause_us": 0}
        assert tr.snapshot() == []
        gc.collect()                    # generation 2: whatever it lasted
        assert [s["name"] for s in tr.snapshot()] == ["runtime/gc"]
        tr.close()

    def test_the_hook_is_there_exactly_while_something_records(
            self, tmp_path):
        before = list(gc.callbacks)
        tr = SpanTracer(process_index=0)            # enabled: it records
        assert len(gc.callbacks) == len(before) + 1
        other = SpanTracer(process_index=0)
        assert len(gc.callbacks) == len(before) + 1     # ONE hook a process
        tr.close()
        assert len(gc.callbacks) == len(before) + 1
        other.close()
        assert gc.callbacks == before
        obs = get_session()
        with Capture(tmp_path):
            assert gc.callbacks == before       # no span() call has seen it
            obs.span("serving/x").begin().end()
            assert len(gc.callbacks) == len(before) + 1
        obs.span("serving/y")                   # the edge, seen at a call
        assert gc.callbacks == before

    def test_nothing_recording_never_touches_the_callbacks(self,
                                                           monkeypatch):
        class Untouchable(list):
            def append(self, item):
                pytest.fail("gc.callbacks touched with nothing recording")
            remove = append

        monkeypatch.setattr(gc, "callbacks", Untouchable(gc.callbacks))
        obs = get_session()
        assert not obs.enabled
        with obs.span("serving/x"):
            gc.collect()
        SpanTracer(enabled=False, process_index=0).span("y").begin().end()
        assert recorded_spans() == []


# ---------------------------------------------------------------------------
# the serving engine from the inside


@pytest.fixture(scope="module")
def tiny_engine():
    return init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)


def serving(tiny_engine, **kw):
    cfg = dict(block_size=16, num_blocks=32, max_seqs=4, max_model_len=128,
               prefill_chunk=16, max_queue=64, prefix_cache=False)
    srv = ServingEngine(tiny_engine, ServingConfig(**cfg), **kw)
    # held to the chunk and decode programs, whose spans these tests fix
    # (the mixed step's are ``test_serving.py::TestMixedStep``'s)
    srv._mixed = None
    return srv


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
            "serving/decode/fetch", "serving/decode/apply", "serving/emit",
            "serving/publish"}
        shadowed = 0
        for s in spans:
            parent = by_id.get(s.get("parent_id"))
            if (s["name"] == "serving/emit" and s["attrs"]["deferred"]) or (
                    s["name"] == "serving/publish"
                    and parent["name"] != "serving/iteration"):
                shadowed += 1
                disp, fetch, applied = (next(
                    (c for c in spans if c.get("parent_id") == parent["id"]
                     and c["name"] == parent["name"] + part), None)
                    for part in ("/dispatch", "/fetch", "/apply"))
                if disp is None:
                    # the span that lands a step behind its chunk, with the
                    # prompt's next chunk enqueued ahead of that fetch: the
                    # device is busy, the tokens are delivered at once
                    # (and the late half of a LAST chunk that went ahead,
                    # fetched before its iteration's step is prepared: the
                    # delivery and the account lie before that fetch)
                    if parent["name"] == "serving/decode":
                        assert parent["attrs"]["held_by"] == "prefill"
                        assert "chunk_held_by" not in parent["attrs"]
                        assert applied["end_s"] <= s["start_s"]
                    else:
                        assert parent["attrs"]["ahead"] == 1
                        assert s["end_s"] <= fetch["start_s"]
                    continue
                assert disp["end_s"] <= s["start_s"]
                if parent["attrs"].get("ahead") \
                        and s["name"] == "serving/emit" \
                        and s["start_s"] >= fetch["start_s"]:
                    # a step ahead is running: its predecessor's tokens
                    # are delivered as soon as they are applied
                    assert applied["end_s"] <= s["start_s"]
                elif fetch is not None:     # else the step stays in flight
                    assert s["end_s"] <= fetch["start_s"]
        assert shadowed >= 8
        assert kids["serving/prefill_chunk"] >= {
            "serving/prefill_chunk/prepare",
            "serving/prefill_chunk/dispatch", "serving/prefill_chunk/fetch",
            "serving/prefill_chunk/apply"}
        # a prompt's first token is never kept: its delivery runs where it
        # is applied
        assert kids["serving/prefill_chunk/apply"] == {"serving/emit"}
        assert kids["serving/submit"] == {"serving/submit/lock_wait"}

    @pytest.mark.parametrize("program", ["serving/decode",
                                         "serving/prefill_chunk"])
    def test_the_driver_thread_is_named_from_enqueue_to_enqueue(
            self, served, program):
        """Under a program's span the driver thread's time lies in leaves,
        in this order: ``prepare``, ``dispatch``, (what is delivered and
        published in the device's shadow,) ``fetch``, ``apply``. The
        ``dispatch`` span says how many of the call's operands were host
        arrays: one, the step's packed operands. A chunk that waited for the
        decode step's enqueue has its leaves in two spans: ``prepare`` and
        ``dispatch`` in the first, ``fetch`` and ``apply`` in the one that
        carries its ``tokens``."""
        from deepspeed_tpu.serving import paged_kv

        spans, _, _ = served
        # ``serving()``'s 4 rows, 128 / 16 blocks a sequence, chunks of 16
        packed_shape = {
            "serving/decode": paged_kv.decode_rows_shape(4, 8),
            "serving/prefill_chunk": paged_kv.chunk_shape(8, 16, False)}
        ran = [s for s in spans if s["name"] == program
               and s["attrs"].get("rows", s["attrs"].get("tokens"))]
        assert len(ran) >= 4
        for parent in ran:
            kids = sorted((c for c in spans
                           if c.get("parent_id") == parent["id"]),
                          key=lambda c: c["start_s"])
            names = [c["name"].replace(program, "...") for c in kids]
            if ".../prepare" not in names:
                # the late half: of a chunk whose step went behind it, or of
                # one that the last iteration enqueued ahead (a last chunk's
                # holds what is delivered before its fetch too)
                assert program == "serving/prefill_chunk"
                early = max((s for s in spans if s["name"] == program
                             and s["end_s"] <= parent["start_s"]),
                            key=lambda s: s["end_s"])
                assert "tokens" not in early["attrs"]
                assert [early["attrs"][k] for k in ("rid", "chunk_start")] \
                    == [parent["attrs"][k] for k in ("rid", "chunk_start")]
                kids = sorted((c for c in spans
                               if c.get("parent_id") == early["id"]),
                              key=lambda c: c["start_s"]) + kids
                names = [c["name"].replace(program, "...") for c in kids]
            shadow = [n for n in names[2:] if n.startswith("serving/")]
            # a decode step that stays in flight at its iteration's end has
            # no fetch of its own; one enqueued AHEAD holds its
            # predecessor's, and delivers that step's tokens at once
            landed = names.count(".../fetch")
            ahead = (parent["attrs"].get("ahead", 0)
                     if program == "serving/decode" else 0)
            assert landed or program == "serving/decode"
            assert names == [".../prepare", ".../dispatch",
                             *shadow[:len(shadow) - ahead],
                             *[".../fetch", ".../apply"][:2 * landed],
                             *shadow[len(shadow) - ahead:]]
            assert landed >= ahead
            assert set(shadow) <= {"serving/emit", "serving/publish"}
            for a, b in zip(kids, kids[1:]):
                assert a["end_s"] <= b["start_s"]
            assert all(c["cat"] == "phase" for c in kids
                       if c["name"].startswith(program))
            # the step's operands packed into one numpy array; the key, and
            # the decode program's last tokens, are on the device already
            assert kids[1]["attrs"]["host_operands"] == 1
            assert kids[1]["attrs"]["host_operand_bytes"] == 4 * int(
                np.prod(packed_shape[program]))

    def test_a_step_ahead_is_dispatched_before_its_predecessor_is_fetched(
            self, served):
        """The decode program's calls and fetches, each in the order of its
        begin on the driver thread: the k-th fetch is of the k-th call. A
        span with ``ahead`` holds call k+1 and THEN fetch k; every other
        span with a fetch holds its own call's, or no call at all."""
        spans, _, _ = served
        steps = sorted((s for s in spans if s["name"] == "serving/decode"),
                       key=lambda s: s["start_s"])

        def leaves(part):
            return sorted((s for s in spans
                           if s["name"] == f"serving/decode/{part}"),
                          key=lambda s: s["start_s"])

        calls, fetches = leaves("dispatch"), leaves("fetch")
        assert len(calls) == len(fetches) >= 8
        ahead = 0
        for step in steps:
            mine = [calls.index(c) for c in calls
                    if c["parent_id"] == step["id"]]
            got = [fetches.index(f) for f in fetches
                   if f["parent_id"] == step["id"]]
            assert len(mine) <= 1 and len(got) <= 1
            if step["attrs"].get("ahead"):
                ahead += 1
                assert got == [mine[0] - 1]
                assert calls[mine[0]]["end_s"] <= fetches[got[0]]["start_s"]
            elif mine and got:
                assert got == mine
        # the six requests overlap, so rows end and chunks come all the
        # time; still some steps found nobody waiting
        assert 0 < ahead < len(calls)

    def test_a_decode_step_is_dispatched_before_a_chunk_that_is_not_the_last_is_fetched(
            self, tiny_engine, tmp_path):
        """A prompt of three chunks beside two rows that decode, on the
        driver thread: in the iterations of chunks one and two the decode
        step's call has returned before the chunk's fetch begins
        (``behind_chunk`` 1, and the chunk's fetch and apply lie in a span of
        their own that carries its ``tokens``); the third chunk brings a
        first token, and its fetch and apply come before the step is
        prepared. Neither program's span lies inside the other's. Chunks two
        and three are enqueued AHEAD (PR 58): ``.../prepare`` and
        ``.../dispatch`` in a span of the iteration before, between the fetch
        of that iteration's chunk and the fetch of its step, ``.../fetch`` and
        ``.../apply`` in a span of their own in the next; ``ahead`` and
        ``late`` agree on the two."""
        reset_session()
        srv = serving(tiny_engine)
        srv.submit(np.arange(1, 40), max_new_tokens=3)
        srv.run()                                   # both programs compiled
        srv.start()
        with Capture(tmp_path):
            rows = [srv.submit(np.arange(1, 8 + i), max_new_tokens=90)
                    for i in range(2)]
            deadline = time.monotonic() + 60
            while not all(h.tokens for h in rows):
                assert time.monotonic() < deadline
                time.sleep(0.001)
            late = srv.submit(np.arange(50, 90), max_new_tokens=2)
            assert len(late.result(timeout_s=120)) == 2
            srv.stop()
        spans = recorded_spans()
        srv.close()
        by_id = {s["id"]: s for s in spans}

        def leaf(parent, part):
            return next((c for c in spans if c.get("parent_id") == parent["id"]
                         and c["name"] == f"{parent['name']}/{part}"), None)

        behind = []
        for start in (0, 16, 32):
            chunk = next(s for s in spans
                         if s["name"] == "serving/prefill_chunk"
                         and s["attrs"]["rid"] == late.request_id
                         and s["attrs"]["chunk_start"] == start
                         and "tokens" in s["attrs"])
            iteration = by_id[chunk["parent_id"]]
            assert iteration["name"] == "serving/iteration"
            step = next(s for s in spans if s["name"] == "serving/decode"
                        and s.get("parent_id") == iteration["id"]
                        and s["attrs"].get("rows"))
            assert step["attrs"]["rows"] == 2 + (start == 32)
            call, fetch = leaf(step, "dispatch"), leaf(chunk, "fetch")
            behind.append(step["attrs"]["behind_chunk"])
            if start < 32:
                assert call["end_s"] <= fetch["start_s"]
                assert leaf(chunk, "prepare") is None
                assert leaf(step, "fetch") is None
                assert step["end_s"] <= chunk["start_s"]
                landed = next(s for s in spans if s["name"] == "serving/decode"
                              and s.get("parent_id") == iteration["id"]
                              and "rows" not in s["attrs"])
                assert chunk["end_s"] <= landed["start_s"]
                assert leaf(chunk, "apply")["end_s"] \
                    <= leaf(landed, "fetch")["start_s"]
            else:
                assert leaf(chunk, "apply")["end_s"] \
                    <= leaf(step, "prepare")["start_s"]
                assert step["attrs"]["chunk_first_by"] == "last_chunk"
            assert chunk["attrs"]["ahead"] == (start > 0)
            early = [s for s in spans if s["name"] == "serving/prefill_chunk"
                     and s["attrs"]["rid"] == late.request_id
                     and s["attrs"]["chunk_start"] == start
                     and "tokens" not in s["attrs"]]
            assert len(early) == 1 and leaf(early[0], "fetch") is None
            assert leaf(early[0], "dispatch")["end_s"] <= fetch["start_s"]
            assert early[0]["attrs"]["ahead"] == (start > 0)
            if start == 0:
                assert early[0]["parent_id"] == iteration["id"]
                assert "late" not in chunk["attrs"]
                continue
            # enqueued in the iteration before, behind that iteration's
            # chunk's fetch and ahead of its step's
            before = by_id[early[0]["parent_id"]]
            assert before["name"] == "serving/iteration"
            assert before["end_s"] <= iteration["start_s"]
            tops = sorted((s for s in spans if s.get("parent_id")
                           == before["id"] and s["name"] in (
                               "serving/prefill_chunk", "serving/decode")),
                          key=lambda s: s["start_s"])
            assert tops[-2] is early[0]
            assert tops[-3]["attrs"]["chunk_start"] == start - 16
            assert leaf(tops[-3], "fetch") is not None
            assert tops[-3]["end_s"] <= early[0]["start_s"]
            assert early[0]["end_s"] <= tops[-1]["start_s"]
            assert tops[-1]["attrs"]["held_by"] == "prefill"
            assert "chunk_held_by" not in tops[-1]["attrs"]
            assert leaf(early[0], "dispatch")["end_s"] \
                <= leaf(tops[-1], "fetch")["start_s"]
            assert chunk["attrs"]["late"] == early[0]["attrs"]["late"]
        assert behind == [1, 1, 0]
        went = [s["attrs"] for s in spans if s["name"] == "serving/prefill_chunk"
                and s["attrs"]["ahead"] and "tokens" in s["attrs"]]
        assert len(went) == 2       # (the registry's counter, which counts
        #   under an enabled session: ``test_serving.py::TestChunkAhead``)
        assert sum(s["attrs"].get("tokens", 0) for s in spans
                   if s["name"] == "serving/prefill_chunk"
                   and s["attrs"]["rid"] == late.request_id) == 40

    def test_host_operands_reach_the_capture_as_stats(self, tiny_engine,
                                                      tmp_path):
        srv = serving(tiny_engine)
        srv.submit(np.arange(1, 20), max_new_tokens=2)
        with Capture(tmp_path) as cap:
            srv.run()
        stats = [st for evs in cap.events("serving/decode/dispatch").values()
                 for _, _, _, st in evs]
        assert stats and all(
            (int(st["host_operands"]), int(st["host_operand_bytes"]))
            == (1, srv._decode_operands([]).nbytes) for st in stats)
        srv.close()

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
        # a span with no ``rows`` holds the fetch of a step that nothing
        # could go ahead of, and is no step of its own
        assert all(a["max_rows"] == 4 for a in dec)
        assert all(1 <= a["rows"] <= 4 and a["ahead"] in (0, 1)
                   for a in dec if "rows" in a)
        assert sum("rows" in a for a in dec) > len(dec) / 2
        admits = [s["attrs"] for s in spans if s["name"] == "serving/admit"]
        assert sum(a["admitted"] for a in admits) == 6

    def test_a_decode_step_counts_the_pages_its_walks_meet(self, served):
        """``walk_pages``: the resident pages of the step's rows; of them
        ``walk_pages_whole`` lie in tiles whose every page is resident: none
        here, where a row holds 8 pages at most and a tile 16."""
        spans, _, _ = served
        dec = [s["attrs"] for s in spans if s["name"] == "serving/decode"
               and s["attrs"].get("rows")]
        assert dec and all(a["rows"] <= a["walk_pages"] <= 8 * a["rows"]
                           and a["walk_pages_whole"] == 0 for a in dec)
        assert any(a["walk_pages"] > a["rows"] for a in dec)

    def test_a_chunk_counts_the_blocks_its_tile_steps_span(self, served):
        """``prefill_blocks``: the (query block, key sub-block) pairs a
        chunk's tiles span under the prefill kernel, on every span of a
        chunk; ``prefill_blocks_skipped``: those its tile step leaves out.
        A tiny prompt is one tile, which the arena of this engine parts
        into sub-blocks of whole pages."""
        spans, _, _ = served
        chunks = [s["attrs"] for s in spans
                  if s["name"] == "serving/prefill_chunk"]
        assert chunks and all(
            0 <= a["prefill_blocks_skipped"] < a["prefill_blocks"]
            for a in chunks)

    def test_prefill_chunk_tokens_add_up_to_the_engines_count(self, served):
        spans, rise, _ = served
        chunks = [s["attrs"] for s in spans
                  if s["name"] == "serving/prefill_chunk"]
        # a chunk that waited for the decode step's enqueue lies in two
        # spans, and the one with its fetch carries the count
        assert sum(a.get("tokens", 0) for a in chunks) == rise > 0
        assert all(a.get("tokens", 0) <= 16 and a["chunk_start"] % 16 == 0
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


@pytest.mark.parametrize("lengths, pages, whole", [
    ((140,), 9, 0), ((280,), 18, 16), ((1300,), 82, 80),
    ((140, 280, 1300, 0), 109, 96)])
def test_the_walks_page_counts_match_a_hand_count(lengths, pages, whole):
    """Rows of 140, 280 and 1,300 tokens over pages of 16 keys and tiles of
    256: 9 pages and no whole tile; 18 pages, one tile of 16 whole; 82
    pages, five tiles whole; a row that holds nothing adds nothing. Over
    pages of 64 KiB a side the two-pool walk keeps its loops, so none of
    its pages takes the whole-tile form; the latent walk's tile is 512 keys
    of one pool."""
    from deepspeed_tpu.ops.paged_decode_attention import walk_page_counts

    narrow = jax.ShapeDtypeStruct((3, 64, 16, 1280), jnp.bfloat16)
    assert walk_page_counts(np.asarray(lengths), narrow) == {
        "walk_pages": pages, "walk_pages_whole": whole}
    wide = jax.ShapeDtypeStruct((3, 64, 16, 2048), jnp.bfloat16)
    assert walk_page_counts(np.asarray(lengths), wide) == {
        "walk_pages": pages, "walk_pages_whole": 0}
    latent = jax.ShapeDtypeStruct((8, 64, 16, 640), jnp.bfloat16)
    assert walk_page_counts(np.asarray(lengths), latent, latent=True) == {
        "walk_pages": pages, "walk_pages_whole": 64 * (max(lengths) > 1024)}


@pytest.mark.parametrize("start, length, blocks, skipped", [
    (0, 256, 2, 1), (256, 512, 2, 1), (512, 700, 2, 0), (1536, 1792, 4, 0),
    (1024, 1100, 4, 1)])
def test_the_chunks_block_counts_match_a_hand_count(start, length, blocks,
                                                    skipped):
    """opt-1.3b's shape: a chunk of 256 queries is one query block, a tile
    of 1,024 keys two key sub-blocks of 512. Keys 0-255 and 0-511: the
    tile's second half is above the diagonal; 188 real tokens at 512 reach
    into the second half; a chunk at 1,536 spans two tiles and needs all of
    both; 76 real tokens at 1,024 need the first half of their second tile
    alone. Two rows add up, and a row that holds nothing adds nothing."""
    from deepspeed_tpu.ops.paged_decode_attention import prefill_block_counts

    arena = jax.ShapeDtypeStruct((24, 2956, 16, 2048), jnp.bfloat16)
    assert prefill_block_counts([start], [length], 256, 32, 64, arena) == {
        "prefill_blocks": blocks, "prefill_blocks_skipped": skipped}
    assert prefill_block_counts([start, 0, 0], [length, 0, 256], 256, 32, 64,
                                arena) == {
        "prefill_blocks": blocks + 2, "prefill_blocks_skipped": skipped + 1}


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
def test_three_engine_clock_readings_per_dispatch(tiny_engine, tmp_path,
                                                  telemetry):
    """Dispatch begins, the call has returned, the tokens are on the host:
    the first and the last reading of the engine's clock are the one pair
    that every consumer shares (ServeGoodput, ReqTrace), and with the one
    between them a program that held the engine is told, whatever records.
    The spans never take the engine's clock: they stay on ``perf_counter``,
    the clock of their parents and of the capture."""
    if telemetry != "off":
        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "obs"),
            recompile_watchdog=False, flight_recorder=False,
            hang_watchdog=False, request_tracing=True,
            trace_sample_rate=1.0, serve_goodput=True))
    clock = FakeClock()
    srv = serving(tiny_engine, clock=clock)
    obs = get_session()
    packed = srv._decode_operands([])
    before = clock.reads
    p0 = time.perf_counter()
    with obs.span("serving/decode") as parent:
        tok, t0, t1 = srv._run_program(obs, "serving/decode",
                                       srv._decode, packed, srv._base_rng)
    p1 = time.perf_counter()
    assert clock.reads - before == 3 and t1 - t0 == 2.0
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


def test_nothing_recording_builds_nothing_at_the_new_sites(tiny_engine,
                                                           monkeypatch):
    """No session, no capture: ``.../apply`` and ``.../dispatch`` are the
    shared no-op, and the operands are never counted."""
    from deepspeed_tpu.serving import api

    def counted(call_args):
        raise AssertionError("host operands counted with nothing recording")

    monkeypatch.setattr(api, "_host_operands", counted)
    srv = serving(tiny_engine)
    obs = get_session()
    handed, span = [], obs.span

    def spy(name, *a, **kw):
        got = span(name, *a, **kw)
        handed.append((name, got))
        return got

    monkeypatch.setattr(obs, "span", spy)
    h = srv.submit(np.arange(1, 20), max_new_tokens=3)
    srv.run()
    assert len(h.tokens) == 3 and recorded_spans() == []
    assert {"serving/prefill_chunk/apply", "serving/decode/apply",
            "serving/decode/dispatch"} <= {name for name, _ in handed}
    assert all(got is NOOP_SPAN for _, got in handed)
    srv.close()


class ScriptedClock:
    """An engine clock that advances by what the test says at each
    reading (0 once the script is read out)."""

    def __init__(self):
        self.now, self.script = 100.0, []

    def __call__(self):
        self.now += self.script.pop(0) if self.script else 0.0
        return self.now


@pytest.fixture
def package_log(caplog):
    """The package's logger does not propagate: caplog's handler on it."""
    from deepspeed_tpu.utils.logging import logger

    logger.addHandler(caplog.handler)
    yield caplog
    logger.removeHandler(caplog.handler)


def _warned(log):
    return [r.getMessage() for r in log.records if r.levelname == "WARNING"]


def _run_with(srv, clock, script):
    """One decode program with the engine's three clock readings moved on
    by `script`: before the call, after it, tokens on the host."""
    clock.script = list(script)
    srv._run_program(get_session(), "serving/decode", srv._decode,
                     srv._decode_operands([]), srv._base_rng)


@pytest.mark.parametrize("script,held", [
    ((0.0, 0.7, 0.1), "call 0.700 s"),
    ((0.0, 0.1, 2.5), "fetch 2.500 s"),
    ((0.0, 0.6, 0.5), "call 0.600 s and fetch 0.500 s"),
    ((0.0, 0.49, 0.49), None),
], ids=["call", "fetch", "both", "neither"])
def test_a_program_that_held_the_engine_is_counted(tiny_engine, package_log,
                                                   script, held):
    """Half a second or more in the jitted call, or from its return to the
    tokens on the host: counted on the engine and logged once, with no
    session and no capture."""
    clock = ScriptedClock()
    srv = serving(tiny_engine, clock=clock)
    # a call that compiles is set-up, however long: the program's first,
    # and its only one since the arena is made committed (PR 61; a fresh
    # arena had it compile again at its second call)
    _run_with(srv, clock, (0.0, 30.0, 0.01))
    assert srv._decode._cache_size() == 1
    _run_with(srv, clock, (0.0, 0.1, 0.01))
    assert srv._decode._cache_size() == 1
    assert srv.holds == 0 and not _warned(package_log)
    srv._iterations = 41
    _run_with(srv, clock, script)
    assert recorded_spans() == []
    warned = _warned(package_log)
    if held is None:
        assert srv.holds == 0 and not warned
    else:
        assert srv.holds == 1 and len(warned) == 1
        assert "serving/decode" in warned[0] and held in warned[0]
        assert "iteration 41" in warned[0]
    srv.close()


def test_a_call_that_compiled_and_waits_for_its_tokens_is_a_hold(
        tiny_engine, package_log):
    """Only the CALL is excused where it compiled."""
    clock = ScriptedClock()
    srv = serving(tiny_engine, clock=clock)
    _run_with(srv, clock, (0.0, 30.0, 0.8))
    (warned,) = _warned(package_log)
    assert srv.holds == 1
    assert "fetch 0.800 s" in warned and "call" not in warned
    srv.close()


def test_the_iteration_span_carries_the_holds(tiny_engine, tmp_path):
    configure_observability(ObservabilityConfig(
        enabled=True, output_dir=str(tmp_path / "obs"),
        recompile_watchdog=False, flight_recorder=False,
        hang_watchdog=False))
    clock = ScriptedClock()
    srv = serving(tiny_engine, clock=clock)
    srv.submit(np.arange(1, 20), max_new_tokens=3)
    srv.run()
    _run_with(srv, clock, (0.0, 0.1, 0.9))
    srv.submit(np.arange(1, 20), max_new_tokens=2)
    srv.run()
    its = [s["attrs"]["holds"] for s in recorded_spans()
           if s["name"] == "serving/iteration"]
    assert its[0] == 0 and its[-1] == 1 and its == sorted(its)
    assert get_registry().counter("serving/holds").value(
        program="serving/decode") == 1
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
    assert [counts(e[3], cpu=True) for e in line
            if e[0] == "train_batch"] == [{"step": 1}, {"step": 2}]


def test_generate_ttft_needs_no_telemetry(tiny_engine):
    assert not get_session().enabled
    out, ttft = tiny_engine.generate(np.arange(1, 9)[None], max_new_tokens=2,
                                     return_ttft=True)
    assert out.shape == (1, 2) and ttft > 0
