"""Triggered deep profiling tests — ``observability/profiler.py`` (ISSUE-20).

Four layers, matching the subsystem's own:

* trace **parsing** in isolation — the committed miniature trace fixture
  (per-program device/host seconds, op hotspots, module-level fallback,
  compile-flood skip) and the tolerant XSpace wire reader on both crafted
  and garbage bytes;
* the **trigger state machine** on a fake clock and fake trace hooks —
  burn fires once then cools down, budget exhaustion, schedule cadence,
  steady-recompile pending, hang pre-fire, keep-last-K pruning: no wall
  time, no jax.profiler;
* the **live CPU capture smoke** — a burn-triggered window on a real
  serving engine produces a parsed ``profile_summary.json`` joining
  measured seconds against the tpucost prediction for >= 4 registry
  entries, rendered by the report CLI;
* the **boot recommendations path** — ``init_serving(recommendations=)``
  applies valid shape knobs with provenance and refuses stale /
  under-evidenced artifacts with a named reason; plus the disabled-path
  zero-overhead contract.
"""

import glob
import gzip
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.config.base import ConfigError
from deepspeed_tpu.config.config import (ObservabilityConfig,
                                         ProfilingConfig, ServingConfig,
                                         TuneConfig)
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.observability import (configure_observability,
                                         get_registry, get_session,
                                         reset_session)
from deepspeed_tpu.observability import profiler as profiler_mod
from deepspeed_tpu.observability.hangdetect import HangWatchdog
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.profiler import (Capture, DeepProfiler,
                                                  PROFILE_FORMAT,
                                                  entry_program_map,
                                                  parse_trace_dir,
                                                  summarize_capture)
from deepspeed_tpu.observability.report import (crash_report, report,
                                                summarize_profiling)
from deepspeed_tpu.observability.timeseries import TimeSeriesStore
from deepspeed_tpu.serving import ServingEngine

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "..", "fixtures",
                           "profile_capture")


@pytest.fixture(autouse=True)
def _obs_isolation():
    reset_session()
    get_registry().reset()
    yield
    reset_session()
    get_registry().reset()


@pytest.fixture(scope="module")
def tiny_engine():
    return init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)


def serving(tiny_engine, spec="off", **cfg):
    defaults = dict(block_size=16, num_blocks=64, max_seqs=4,
                    max_model_len=128, prefill_chunk=16, max_queue=64)
    defaults.update(cfg)
    speculative = {"mode": spec, "num_draft_tokens": 4}
    return ServingEngine(tiny_engine,
                         ServingConfig(speculative=speculative, **defaults))


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeTracer:
    """Injectable start/stop trace hooks: records the capture dirs and, on
    stop, drops ``payload`` in as the trace artifact — the state machine
    runs with zero jax.profiler involvement."""

    def __init__(self, payload=None):
        self.dirs = []
        self.payload = payload
        self.active = False

    def start(self, path):
        assert not self.active, "overlapping start_trace"
        self.active = True
        self.dirs.append(path)

    def stop(self):
        assert self.active, "stop without start"
        self.active = False
        if self.payload is not None:
            d = os.path.join(self.dirs[-1], "plugins", "profile", "000")
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, "host.trace.json"), "w") as fh:
                json.dump(self.payload, fh)


def make_profiler(tmp_path, payload=None, timeseries=None, registry=None,
                  clock=None, **cfg):
    defaults = dict(enabled=True, window_iterations=4,
                    cooldown_iterations=50, check_interval_iterations=1,
                    capture_budget=8, keep_last=4, burn_ceiling=2.0)
    defaults.update(cfg)
    pc = ProfilingConfig(**defaults)
    pc.validate()
    ft = FakeTracer(payload)
    prof = DeepProfiler(pc, registry=registry, timeseries=timeseries,
                        output_dir=str(tmp_path),
                        clock=clock or FakeClock(),
                        start_trace=ft.start, stop_trace=ft.stop)
    return prof, ft


def burn_store(value=5.0, n=8):
    ts = TimeSeriesStore()
    for i in range(n):
        ts.observe("serve_goodput/ttft_slo_burn_rate/replica=0", value,
                   step=i)
    return ts


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


class TestProfilingConfig:
    def test_defaults_valid_and_disabled(self):
        cfg = ObservabilityConfig()
        cfg.validate()
        assert cfg.profiling.enabled is False

    def test_dict_coercion(self):
        cfg = ObservabilityConfig(profiling={"enabled": True,
                                             "window_iterations": 2})
        cfg.validate()
        assert isinstance(cfg.profiling, ProfilingConfig)
        assert cfg.profiling.window_iterations == 2

    @pytest.mark.parametrize("bad", [
        {"window_iterations": 0}, {"capture_budget": -1},
        {"keep_last": 0}, {"cooldown_iterations": -1},
        {"check_interval_iterations": 0}, {"hang_prefire_fraction": 1.5},
        {"window_wall_s": 0}, {"hotspot_top_k": 0},
        {"profile_every_steps": -2},
    ])
    def test_validation_rejects(self, bad):
        with pytest.raises(ConfigError):
            ProfilingConfig(**bad).validate()


# ---------------------------------------------------------------------------
# trace parsing (committed fixture, no jax)
# ---------------------------------------------------------------------------


class TestParseTrace:
    def test_fixture_attribution(self):
        parsed = parse_trace_dir(FIXTURE_DIR)
        progs = parsed["programs"]
        assert set(progs) == {"jit_decode", "jit_prefill_chunk"}
        dec = progs["jit_decode"]
        # op slices sum; the module-level 2000us event must NOT double
        # count on top of them
        assert dec["device_s"] == pytest.approx(0.002)
        assert dec["ops"] == {"fusion.1": pytest.approx(0.0015),
                              "dot.3": pytest.approx(0.0005)}
        assert dec["host_s"] == pytest.approx(0.005)
        assert dec["invocations"] == 2
        pre = progs["jit_prefill_chunk"]
        # no op slices -> module-level total is the device evidence
        assert pre["device_s"] == pytest.approx(0.004)
        assert pre["invocations"] == 1
        # the $-prefixed compile-flood event contributed nowhere
        assert parsed["trace_files"] == 1

    def test_gzipped_trace_parses_identically(self, tmp_path):
        with open(os.path.join(FIXTURE_DIR, "mini.trace.json")) as fh:
            doc = fh.read()
        with gzip.open(tmp_path / "host.trace.json.gz", "wt") as fh:
            fh.write(doc)
        parsed = parse_trace_dir(str(tmp_path))
        assert parsed["programs"]["jit_decode"]["device_s"] \
            == pytest.approx(0.002)

    def test_torn_artifact_skipped_not_fatal(self, tmp_path):
        (tmp_path / "torn.trace.json").write_text('{"traceEvents": [')
        parsed = parse_trace_dir(str(tmp_path))
        assert parsed["programs"] == {}

    def test_empty_dir(self, tmp_path):
        parsed = parse_trace_dir(str(tmp_path))
        assert parsed == {"programs": {}, "trace_files": 0, "events": 0}

    def test_xplane_wire_reader_finds_names(self, tmp_path):
        # field 1, wire type 2, payload "jit_decode" — a minimal valid
        # length-delimited protobuf record
        name = b"jit_decode"
        buf = bytes([0x0A, len(name)]) + name
        p = tmp_path / "x.xplane.pb"
        p.write_bytes(buf)
        assert profiler_mod._xplane_program_names(str(p)) == {"jit_decode"}

    def test_xplane_wire_reader_tolerates_garbage(self, tmp_path):
        p = tmp_path / "g.xplane.pb"
        p.write_bytes(bytes(range(256)) * 64)
        # must not raise, whatever it finds
        profiler_mod._xplane_program_names(str(p))

    def test_xplane_census_adds_zero_duration_row(self, tmp_path):
        name = b"jit_orphan"
        (tmp_path / "x.xplane.pb").write_bytes(
            bytes([0x0A, len(name)]) + name)
        parsed = parse_trace_dir(str(tmp_path))
        assert parsed["programs"]["jit_orphan"]["device_s"] == 0.0


class TestSummarizeCapture:
    def test_join_and_hotspots(self, monkeypatch):
        monkeypatch.setattr(
            profiler_mod, "entry_program_map",
            lambda: {"jit_decode": ["serving/decode",
                                    "serving/draft_decode"]})
        parsed = parse_trace_dir(FIXTURE_DIR)
        joined = []

        def cost_join(entry, measured_s):
            joined.append((entry, measured_s))
            return {"predicted_step_ms": 1.0, "bound": "hbm",
                    "model_error": measured_s / 1e-3}

        body = summarize_capture(parsed, top_k=1, cost_join=cost_join)
        row = body["entries"]["serving/decode"]
        assert row["program"] == "jit_decode"
        assert row["shared_with"] == ["serving/draft_decode"]
        assert row["invocations"] == 2
        assert row["measured_step_ms"] == pytest.approx(1.0)   # 2ms / 2
        assert row["hlo_hotspots"] == [
            {"op": "fusion.1", "seconds": pytest.approx(0.0015)}]
        assert row["bound"] == "hbm"
        assert joined == [("serving/decode", pytest.approx(0.001))]
        assert body["unmatched_programs"] == ["jit_prefill_chunk"]

    def test_cost_join_failure_is_missing_column(self, monkeypatch):
        monkeypatch.setattr(profiler_mod, "entry_program_map",
                            lambda: {"jit_decode": ["serving/decode"]})

        def bad_join(entry, measured_s):
            raise RuntimeError("no registry")

        body = summarize_capture(parse_trace_dir(FIXTURE_DIR),
                                 cost_join=bad_join)
        assert "predicted_step_ms" not in body["entries"]["serving/decode"]


# ---------------------------------------------------------------------------
# trigger state machine (fake clock, fake tracer)
# ---------------------------------------------------------------------------


class TestTriggers:
    def test_burn_fires_once_then_cools_down(self, tmp_path):
        prof, ft = make_profiler(tmp_path, timeseries=burn_store(),
                                 window_iterations=4,
                                 cooldown_iterations=50)
        prof.on_iteration(1)
        assert prof._open is not None
        assert prof.captures[0].trigger == "burn"
        # window closes after window_iterations ticks
        for it in range(2, 6):
            prof.on_iteration(it)
        assert prof._open is None
        assert len(prof.captures) == 1
        # burn still hot: nothing re-fires inside the cooldown
        for it in range(6, 51):
            prof.on_iteration(it)
        assert len(prof.captures) == 1
        prof.on_iteration(51)
        assert len(prof.captures) == 2

    def test_wall_clock_bound_closes_window(self, tmp_path):
        clk = FakeClock()
        prof, ft = make_profiler(tmp_path, timeseries=burn_store(),
                                 clock=clk, window_iterations=1000,
                                 window_wall_s=30.0)
        prof.on_iteration(1)
        assert prof._open is not None
        clk.advance(31.0)
        prof.on_iteration(2)
        assert prof._open is None
        assert prof.captures[0].wall_s == pytest.approx(31.0)

    def test_budget_exhaustion(self, tmp_path):
        prof, ft = make_profiler(tmp_path, timeseries=burn_store(),
                                 capture_budget=2, cooldown_iterations=1,
                                 window_iterations=1)
        for it in range(1, 200):
            prof.on_iteration(it)
        assert len(prof.captures) == 2
        assert prof._budget == 0

    def test_manual_bypasses_budget(self, tmp_path):
        prof, ft = make_profiler(tmp_path, capture_budget=1)
        prof._budget = 0          # drained by earlier triggered captures
        prof.request_capture("manual")
        prof.on_iteration(1)
        assert prof._open is not None and prof._budget == 0
        prof.close_window()
        assert prof.captures[0].trigger == "manual"

    def test_schedule_cadence(self, tmp_path):
        prof, ft = make_profiler(tmp_path, profile_every_steps=10,
                                 window_iterations=2,
                                 cooldown_iterations=1)
        for it in range(1, 25):
            prof.on_iteration(it)
        assert [c.opened_iteration for c in prof.captures] == [10, 20]
        assert all(c.trigger == "schedule" for c in prof.captures)

    def test_steady_recompile_sets_pending(self, tmp_path):
        prof, ft = make_profiler(tmp_path)
        prof.on_compile(1.0, "train_batch", steady=False)
        prof.on_iteration(1)
        assert prof._open is None
        prof.on_compile(1.0, "train_batch", steady=True)
        prof.on_iteration(2)
        assert prof._open is not None
        assert prof.captures[0].trigger == "recompile"

    def test_summary_time_compiles_do_not_retrigger(self, tmp_path):
        prof, ft = make_profiler(tmp_path, window_iterations=1)
        prof.open_window("manual")
        # a cost-vector compile during close_window's summary must not
        # queue the next capture — simulate via the _summarizing flag
        prof._summarizing = True
        prof.on_compile(1.0, "tpucost", steady=True)
        prof._summarizing = False
        assert prof._pending is None

    def test_keep_last_k_pruning(self, tmp_path):
        prof, ft = make_profiler(tmp_path, keep_last=2)
        for _ in range(5):
            assert prof.open_window("manual") is not None
            prof.close_window()
        dirs = sorted(glob.glob(os.path.join(prof.trace_dir, "capture-*")))
        assert len(dirs) == 2
        assert dirs[-1].endswith("capture-005-manual")

    def test_pruning_never_removes_open_window(self, tmp_path):
        prof, ft = make_profiler(tmp_path, keep_last=1)
        prof.open_window("manual")
        prof.close_window()
        cap = prof.open_window("manual")
        assert os.path.isdir(cap.dir)
        prof.close_window()

    def test_single_window_at_a_time(self, tmp_path):
        prof, ft = make_profiler(tmp_path)
        assert prof.open_window("manual") is not None
        assert prof.open_window("manual") is None
        assert len(prof.captures) == 1

    def test_hang_prefire_opens_window_and_latches(self, tmp_path):
        # no iterations tick in this test, so zero the iteration-denominated
        # cooldown: the watchdog latch is the once-per-stall guard here
        prof, ft = make_profiler(tmp_path, cooldown_iterations=0)
        clk = FakeClock()
        wd = HangWatchdog(clock=clk, timeout_floor_s=10.0)
        wd.prefire_fraction = 0.5
        wd.on_prefire = lambda stalled_span, waited, deadline: \
            prof.on_hang_prefire(stalled_span, waited, deadline)
        wd.heartbeat("train_batch")
        clk.advance(6.0)                 # past 50% of the 10s deadline
        assert wd.check() is False       # not fired — but prefired
        assert prof._open is not None
        assert prof.captures[0].trigger == "hang_prefire"
        wd.check()                       # latched: no second window
        assert len(prof.captures) == 1
        clk.advance(5.0)
        assert wd.check() is True        # the real fire still happens
        # a new stall (fresh heartbeat) re-arms the prefire latch
        wd.heartbeat("train_batch")
        prof.close_window()
        clk.advance(6.0)
        wd.check()
        assert len(prof.captures) == 2

    def test_bundle_context_flushes_open_hang_window(self, tmp_path):
        prof, ft = make_profiler(tmp_path)
        prof.on_hang_prefire("train_batch", 6.0, 10.0)
        assert prof._open is not None
        ctx = prof.bundle_context()
        assert prof._open is None        # closed so the trace flushed
        assert ctx is not None and ctx["captures"][0]["status"] in (
            "empty", "parsed")

    def test_close_flushes_and_publishes(self, tmp_path):
        reg = MetricsRegistry()
        prof, ft = make_profiler(tmp_path, registry=reg)
        prof.open_window("manual")
        prof.close()
        assert prof._open is None
        assert not ft.active
        caps = reg.counter("profile/captures").series()
        assert sum(caps.values()) == 1

    def test_summary_written_and_metrics_published(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setattr(profiler_mod, "entry_program_map",
                            lambda: {"jit_decode": ["serving/decode"]})
        monkeypatch.setattr(
            profiler_mod, "_tpucost_join",
            lambda entry, s: {"predicted_step_ms": 2.0, "bound": "hbm",
                              "model_error": 0.5, "measured_mfu": 0.1,
                              "mfu_ceiling": 0.4})
        with open(os.path.join(FIXTURE_DIR, "mini.trace.json")) as fh:
            payload = json.load(fh)
        reg = MetricsRegistry()
        prof, ft = make_profiler(tmp_path, payload=payload, registry=reg)
        prof.open_window("manual")
        summary = prof.close_window()
        assert summary["format"] == PROFILE_FORMAT
        assert summary["capture"]["status"] == "parsed"
        on_disk = json.load(open(prof.summary_path))
        assert on_disk["entries"]["serving/decode"]["model_error"] == 0.5
        assert prof.captures[0].entries_matched == 1
        g = reg.gauge("profile/model_error").series()
        assert list(g.values()) == [0.5]
        # the report CLI renders these same records as == profiling ==
        out = summarize_profiling(reg.snapshot())
        assert "== profiling ==" in out
        assert "serving/decode" in out and "manual=1" in out


# ---------------------------------------------------------------------------
# disabled path — zero overhead
# ---------------------------------------------------------------------------


class TestDisabledPath:
    def test_disabled_session_wires_nothing(self):
        sess = get_session()
        assert sess.profiler is None

    def test_enabled_session_without_profiling_gate(self, tmp_path):
        sess = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path)))
        assert sess.profiler is None
        assert sess.hang is None or sess.hang.on_prefire is None

    def test_profiling_off_streams_bit_identical(self, tiny_engine,
                                                 tmp_path):
        prompt = np.arange(24) % 250
        want = np.asarray(tiny_engine.generate(
            prompt[None], max_new_tokens=6))[0]
        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path)))
        srv = serving(tiny_engine)
        got = srv.submit(prompt, max_new_tokens=6).result()
        np.testing.assert_array_equal(np.asarray(got), want)
        # no profiler => no capture dirs, no trace starts
        assert not os.path.isdir(os.path.join(str(tmp_path), "profile"))


# ---------------------------------------------------------------------------
# live CPU capture smoke (real jax.profiler, real engine)
# ---------------------------------------------------------------------------


class TestLiveCaptureSmoke:
    def test_burn_triggered_capture_joins_cost_model(self, tiny_engine,
                                                     tmp_path):
        sess = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path),
            tune=TuneConfig(enabled=True),
            profiling=ProfilingConfig(
                enabled=True, window_iterations=10,
                check_interval_iterations=1, cooldown_iterations=10_000,
                burn_ceiling=2.0, sigusr2=False)))
        assert sess.profiler is not None
        srv = serving(tiny_engine, spec="ngram")
        rng = np.random.RandomState(0)
        pat = rng.randint(0, 250, (6,))

        def workload():
            srv.submit(np.tile(pat, 6)[:30], max_new_tokens=8, n=2)
            srv.submit(rng.randint(0, 250, (20,)), max_new_tokens=8)
            srv.run()
            srv.score_logprobs(np.arange(2, 40) % 250)

        # warmup OUTSIDE any window: every program compiles here, so the
        # captured window sees steady-state executions (whose trace events
        # carry hlo_module attribution) and zero compile flood
        workload()
        srv.spec_suspended = True     # warm the plain decode program too
        srv.submit(rng.randint(0, 250, (12,)), max_new_tokens=4)
        srv.run()
        srv.spec_suspended = False
        # a hot burn series makes the NEXT engine tick open the window
        for i in range(8):
            sess.timeseries.observe(
                "serve_goodput/ttft_slo_burn_rate/replica=0", 5.0, step=i)
        workload()                    # runs inside the capture window
        srv.spec_suspended = True
        srv.submit(rng.randint(0, 250, (12,)), max_new_tokens=4)
        srv.run()
        srv.spec_suspended = False
        prof = sess.profiler
        assert prof.captures and prof.captures[0].trigger == "burn"
        if prof._open is not None:    # drain: the window closes in-test
            prof.close_window()
        summary = prof.latest_summary
        assert summary is not None and summary["capture"]["status"] == \
            "parsed"
        entries = summary["entries"]
        assert len(entries) >= 4, sorted(entries)
        # measured + predicted joined for at least 4 registry entries
        paired = [e for e, row in entries.items()
                  if row.get("measured_step_ms") is not None
                  and row.get("predicted_step_ms") is not None]
        assert len(paired) >= 4, (sorted(entries), paired)
        for e in paired:
            assert entries[e]["model_error"] > 0
        # the ledger + per-entry table render in the report CLI
        sess.dump_metrics()
        out = report([sess.metrics_path()])
        assert "== profiling ==" in out
        assert "burn=1" in out
        for e in paired[:2]:
            assert e in out
        # and the summary staples into crash bundles when a recorder is
        # present (here: render the staple directly)
        assert prof.bundle_context() is summary

    def test_entry_program_map_covers_serving(self, tiny_engine):
        configure_observability(ObservabilityConfig(enabled=True))
        srv = serving(tiny_engine, spec="ngram")
        emap = entry_program_map()
        assert emap.get("jit_decode") == ["serving/decode"]
        assert emap.get("jit_prefill_chunk") == ["serving/prefill_chunk"]
        assert emap.get("jit_verify") == ["serving/verify"]
        assert emap.get("jit_score_chunk") == ["serving/score_chunk"]
        assert emap.get("jit_cow_copy") == ["serving/cow_copy"]
        del srv


# ---------------------------------------------------------------------------
# crash-bundle rendering (satellite: the PR-18 timeseries digest + the
# profile staple surface in `report --crash-dump`)
# ---------------------------------------------------------------------------


class TestCrashBundleRendering:
    def _bundle(self, tmp_path, manifest):
        d = tmp_path / "bundle"
        d.mkdir()
        manifest.setdefault("reason", "hang")
        with open(d / "MANIFEST.json", "w") as fh:
            json.dump(manifest, fh)
        return str(d)

    def test_timeseries_digest_rendered(self, tmp_path):
        man = {"timeseries": {
            "series": 2, "points_total": 40, "dropped_series": 0,
            "series_stats": {
                "serve_goodput/ttft_slo_burn_rate/replica=0": {
                    "n": 20, "last": 5.0, "ewma": 4.2, "slope": 0.3,
                    "tail": [[1, 3.0], [2, 4.0], [3, 5.0]]},
                "serving/queue_depth": {
                    "n": 20, "last": 1.0, "ewma": 1.0, "slope": 0.0,
                    "tail": []}}}}
        out = crash_report(self._bundle(tmp_path, man))
        assert "== metric trajectories ==" in out
        assert "ttft_slo_burn_rate" in out
        assert "slope=+0.3" in out
        # most-volatile ranks first
        assert out.index("ttft_slo_burn_rate") < out.index("queue_depth")

    def test_profile_staple_rendered(self, tmp_path):
        man = {"profile_summary": {
            "format": 1,
            "capture": {"seq": 2, "trigger": "hang_prefire",
                        "status": "parsed", "wall_s": 1.25},
            "captures": [{"seq": 1, "trigger": "burn",
                          "opened_iteration": 10, "status": "parsed"}],
            "entries": {"serving/decode": {
                "device_s": 0.5, "measured_step_ms": 2.0,
                "predicted_step_ms": 1.0, "model_error": 2.0}}}}
        out = crash_report(self._bundle(tmp_path, man))
        assert "== profiling staple ==" in out
        assert "hang_prefire" in out and "serving/decode" in out
        assert "err=2.0x" in out

    def test_bundle_without_staples_unchanged(self, tmp_path):
        out = crash_report(self._bundle(tmp_path, {}))
        assert "metric trajectories" not in out
        assert "profiling staple" not in out


# ---------------------------------------------------------------------------
# boot recommendations (satellite: init_serving(recommendations=...))
# ---------------------------------------------------------------------------


def make_artifact(tmp_path, recs, fmt=1, name="tune_recommendations.json"):
    art = {"format": fmt, "generated_at_iteration": 500, "moves": 3,
           "rollbacks": 0, "objective": {"initial": 0.5, "last": 0.8},
           "knobs": {}, "signals": {}, "recommendations": recs}
    p = tmp_path / name
    with open(p, "w") as fh:
        json.dump(art, fh)
    return str(p)


SPEC_REC = {"knob": "speculative.num_draft_tokens", "kind": "shape",
            "current": 4, "recommended": 5,
            "reason": "near-unity draft acceptance",
            "evidence": {"acceptance_rate": 0.95, "proposed": 640}}
BLOCKS_REC = {"knob": "serving.num_blocks", "kind": "shape",
              "current": 64, "recommended": 80,
              "reason": "occupancy p99 near saturation",
              "evidence": {"occupancy_p99": 0.97}}
CHUNK_REC = {"knob": "serving.prefill_chunk", "kind": "shape",
             "current": 16, "recommended": 32,
             "reason": "settled on 2 chunks/iteration",
             "evidence": {"chunks_per_iteration": 2}}


def base_scfg(**kw):
    d = dict(block_size=16, num_blocks=64, max_seqs=4, max_model_len=128,
             prefill_chunk=16, max_queue=64,
             speculative={"mode": "ngram", "num_draft_tokens": 4})
    d.update(kw)
    scfg = ServingConfig(**d)
    scfg.validate()   # coerces the speculative dict; boot path does too
    return scfg


class TestRecommendationsApply:
    def test_valid_artifact_applies_all_three_knobs(self):
        from deepspeed_tpu.autotuning.livetuner import apply_recommendations

        scfg = base_scfg()
        applied, refused = apply_recommendations(
            scfg, {"recommendations": [SPEC_REC, BLOCKS_REC, CHUNK_REC]})
        assert not refused
        assert [a["knob"] for a in applied] == [
            "speculative.num_draft_tokens", "serving.num_blocks",
            "serving.prefill_chunk"]
        assert scfg.speculative.num_draft_tokens == 5
        assert scfg.num_blocks == 80
        assert scfg.prefill_chunk == 32
        scfg.validate()

    @pytest.mark.parametrize("rec,reason", [
        (dict(SPEC_REC, evidence={"acceptance_rate": 0.95, "proposed": 10}),
         "insufficient_evidence"),
        (dict(BLOCKS_REC, evidence={}), "insufficient_evidence"),
        (dict(CHUNK_REC, evidence={"chunks_per_iteration": 1}),
         "insufficient_evidence"),
        (dict(CHUNK_REC, recommended=24), "not_block_multiple"),
        (dict(BLOCKS_REC, recommended=4), "below_blocks_per_seq"),
        (dict(SPEC_REC, knob="serving.mesh"), "unknown_knob"),
        (dict(SPEC_REC, kind="online"), "not_a_shape_knob"),
        (dict(SPEC_REC, recommended=0), "invalid_value"),
    ])
    def test_refusals_named(self, rec, reason):
        from deepspeed_tpu.autotuning.livetuner import apply_recommendations

        scfg = base_scfg()
        applied, refused = apply_recommendations(
            scfg, {"recommendations": [rec]})
        assert not applied
        assert len(refused) == 1
        assert refused[0]["reason"].startswith(reason)
        # nothing moved
        assert scfg.speculative.num_draft_tokens == 4
        assert scfg.num_blocks == 64 and scfg.prefill_chunk == 16

    def test_spec_knob_refused_when_speculation_off(self):
        from deepspeed_tpu.autotuning.livetuner import apply_recommendations

        scfg = base_scfg(speculative={"mode": "off"})
        _, refused = apply_recommendations(
            scfg, {"recommendations": [SPEC_REC]})
        assert refused[0]["reason"] == "speculative_off"

    def test_format_version_mismatch_refused(self, tmp_path):
        from deepspeed_tpu.autotuning.livetuner import load_recommendations

        p = make_artifact(tmp_path, [SPEC_REC], fmt=99)
        with pytest.raises(ValueError, match="format_version"):
            load_recommendations(p)

    def test_discovery_picks_newest(self, tmp_path):
        from deepspeed_tpu.autotuning.livetuner import (
            discover_recommendations)

        old = tmp_path / "run1"
        new = tmp_path / "run2"
        old.mkdir(), new.mkdir()
        make_artifact(old, [])
        os.utime(old / "tune_recommendations.json", (1, 1))
        want = make_artifact(new, [SPEC_REC])
        assert discover_recommendations(str(tmp_path)) == want
        assert discover_recommendations(str(tmp_path / "empty")) is None

    def test_init_serving_applies_with_provenance(self, tmp_path):
        p = make_artifact(tmp_path, [SPEC_REC, CHUNK_REC])
        from deepspeed_tpu.serving import init_serving

        srv = init_serving("tiny", serving_config=dict(
            block_size=16, num_blocks=64, max_seqs=4, max_model_len=128,
            prefill_chunk=16,
            speculative={"mode": "ngram", "num_draft_tokens": 4}),
            recommendations=p, dtype=jnp.float32)
        assert srv.config.speculative.num_draft_tokens == 5
        assert srv.config.prefill_chunk == 32
        assert [a["knob"] for a in srv.recommendations_applied] == [
            "speculative.num_draft_tokens", "serving.prefill_chunk"]
        assert srv.recommendations_refused == []
        # provenance counters land in the process registry -> report line
        reg = get_registry()
        series = reg.counter("tune/recommendations_applied").series()
        assert sum(series.values()) == 2
        from deepspeed_tpu.observability.report import summarize_autotune
        out = summarize_autotune(reg.snapshot())
        assert "recommendations applied at boot" in out
        assert "speculative.num_draft_tokens" in out

    def test_init_serving_refuses_bad_artifact_and_boots(self, tmp_path):
        p = make_artifact(tmp_path, [SPEC_REC], fmt=99)
        from deepspeed_tpu.serving import init_serving

        srv = init_serving("tiny", serving_config=dict(
            block_size=16, num_blocks=64, max_seqs=4, max_model_len=128,
            prefill_chunk=16,
            speculative={"mode": "ngram", "num_draft_tokens": 4}),
            recommendations=p, dtype=jnp.float32)
        # configured shapes untouched; the refusal is named
        assert srv.config.speculative.num_draft_tokens == 4
        assert srv.recommendations_applied == []
        assert srv.recommendations_refused[0]["reason"].startswith(
            "format_version")
        series = get_registry().counter(
            "tune/recommendations_refused").series()
        assert sum(series.values()) == 1

    def test_init_serving_auto_without_artifact(self, tmp_path,
                                                monkeypatch):
        from deepspeed_tpu.serving import init_serving

        monkeypatch.chdir(tmp_path)   # no dstpu_obs dir here
        srv = init_serving("tiny", serving_config=dict(
            block_size=16, num_blocks=32, max_seqs=4, max_model_len=128,
            prefill_chunk=16), recommendations="auto", dtype=jnp.float32)
        assert srv.recommendations_applied == []
