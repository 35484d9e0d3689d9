"""Unit tests for the flight recorder, hang watchdog and goodput accountant
(`deepspeed_tpu/observability/{flightrecorder,hangdetect,goodput}.py`) plus
the `report --crash-dump` CLI.

The acceptance paths live here:

* a deliberately stalled step (a span that heartbeats once and never again)
  fires the hang watchdog within the configured deadline and produces a
  crash bundle the `report --crash-dump` CLI parses back to the stalled
  span name;
* an enabled CPU engine run publishes `goodput/goodput_fraction` and
  `goodput/mfu` to the MetricsRegistry;
* the disabled path wires nothing (no recorder, no watchdog, no accountant,
  no tracer hook) — zero per-step overhead.

Watchdog/goodput unit tests use an injectable fake clock — no real sleeps;
the single threaded end-to-end test bounds its wait at ~2 s worst case."""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.autotuning.cost_model import PEAK_FLOPS, peak_flops_for
from deepspeed_tpu.config.config import ObservabilityConfig
from deepspeed_tpu.models import simple_model
from deepspeed_tpu.observability import (configure_observability,
                                         get_registry, get_session,
                                         reset_session)
from deepspeed_tpu.observability import flightrecorder as fr_mod
from deepspeed_tpu.observability.flightrecorder import (FlightRecorder,
                                                        find_latest_bundle)
from deepspeed_tpu.observability.goodput import GoodputAccountant
from deepspeed_tpu.observability.hangdetect import HangWatchdog
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.report import crash_report, main as report_main
from deepspeed_tpu.observability.spans import SpanTracer
from deepspeed_tpu.profiling import compiled_cost


@pytest.fixture(autouse=True)
def _obs_isolation():
    reset_session()
    get_registry().reset()
    yield
    reset_session()
    get_registry().reset()


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


# ---------------------------------------------------------------------------
# flight recorder ring


class TestFlightRecorderRing:
    def test_eviction_order(self, tmp_path):
        rec = FlightRecorder(capacity=3, dump_dir=str(tmp_path))
        for i in range(5):
            rec.record("tick", i=i)
        evs = rec.snapshot()
        assert [e["i"] for e in evs] == [2, 3, 4]       # oldest evicted
        assert [e["seq"] for e in evs] == [3, 4, 5]     # seq keeps counting

    def test_span_events_mirror_open_stack(self, tmp_path):
        rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path))
        tr = SpanTracer(process_index=0)
        tr.on_event = rec.record_span
        outer = tr.span("train_batch", step=7).begin()
        inner = tr.span("train_batch/dispatch").begin()
        assert rec.innermost_open_span() == "train_batch/dispatch"
        (stack,) = rec.open_spans().values()
        assert stack == ["train_batch", "train_batch/dispatch"]
        inner.end()
        assert rec.innermost_open_span() == "train_batch"
        outer.end()
        assert rec.open_spans() == {}
        kinds = [e["kind"] for e in rec.snapshot()]
        assert kinds == ["span_begin", "span_begin", "span_end", "span_end"]
        assert rec.snapshot()[0]["step"] == 7

    def test_same_named_nested_spans_pop_by_identity(self, tmp_path):
        rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path))
        tr = SpanTracer(process_index=0)
        tr.on_event = rec.record_span
        outer = tr.span("retry").begin()
        inner = tr.span("retry").begin()
        inner.end()
        # the name-match pop would have collapsed the outer entry too
        (stack,) = rec.open_spans().values()
        assert stack == ["retry"]
        assert rec.innermost_open_span() == "retry"
        outer.end()
        assert rec.open_spans() == {}

    def test_log_lines_enter_ring(self, tmp_path):
        from deepspeed_tpu.utils.logging import logger as ds_logger

        rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
        rec.attach_logging(ds_logger)
        try:
            ds_logger.warning("something went sideways")
        finally:
            rec.detach_logging(ds_logger)
        (ev,) = [e for e in rec.snapshot() if e["kind"] == "log"]
        assert ev["level"] == "WARNING" and "sideways" in ev["message"]


# ---------------------------------------------------------------------------
# crash bundles


class TestCrashBundle:
    def _bundle(self, tmp_path, **kw):
        rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path / "crash"))
        tr = SpanTracer(process_index=0)
        tr.on_event = rec.record_span
        tr.span("train_batch", step=1).begin()
        tr.span("train_batch/dispatch").begin()
        return rec, rec.dump(**kw)

    def test_dump_bundle_contents(self, tmp_path):
        rec, bundle = self._bundle(tmp_path, reason="hang")
        man = json.load(open(os.path.join(bundle, "MANIFEST.json")))
        assert man["reason"] == "hang"
        # stalled span defaults to the innermost open span
        assert man["stalled_span"] == "train_batch/dispatch"
        (stack,) = man["open_spans"].values()
        assert stack == ["train_batch", "train_batch/dispatch"]
        assert man["environment"]["python"]
        events = [json.loads(l) for l in
                  open(os.path.join(bundle, "events.jsonl"))]
        assert [e["kind"] for e in events] == ["span_begin", "span_begin"]
        stacks = open(os.path.join(bundle, "stacks.txt")).read()
        assert "test_flightrecorder" in stacks     # this very test frame
        mem = json.load(open(os.path.join(bundle, "memory.json")))
        assert mem["host_rss_bytes"] > 0
        assert rec.dumps == [bundle]
        assert find_latest_bundle(str(tmp_path / "crash")) == bundle

    def test_dump_records_exception_and_audit_entries(self, tmp_path):
        from tools.tpuaudit.registry import clear_registry, register_entry_point

        try:
            register_entry_point(
                "t/unit", fn=lambda x: x,
                args=(jax.ShapeDtypeStruct((2,), jnp.float32),),
                tags={"engine": "test"})
            rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
            try:
                raise RuntimeError("boom at step 3")
            except RuntimeError as e:
                bundle = rec.dump(reason="exception", exc=e)
            man = json.load(open(os.path.join(bundle, "MANIFEST.json")))
            assert man["exception"]["type"] == "RuntimeError"
            assert "boom at step 3" in man["exception"]["message"]
            names = [e["name"] for e in man["audit_entries"]]
            assert "t/unit" in names
        finally:
            clear_registry()

    def test_dump_never_raises(self, tmp_path):
        rec = FlightRecorder(capacity=4,
                             dump_dir=str(tmp_path / "f" / "MANIFEST.json"))
        # dump_dir collides with a FILE path component -> makedirs fails
        (tmp_path / "f").mkdir()
        (tmp_path / "f" / "MANIFEST.json").write_text("not a dir")
        assert rec.dump(reason="broken") == ""

    def test_report_crash_dump_cli_round_trip(self, tmp_path):
        """Tier-1 smoke: dump a bundle, re-read it through the installed
        CLI in a fresh process (stdlib path — no jax needed to read)."""
        _, bundle = self._bundle(tmp_path, reason="hang",
                                 extra={"waited_s": 12.5, "deadline_s": 5.0})
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.observability", "report",
             "--crash-dump", bundle],
            capture_output=True, text=True, cwd="/root/repo", env=env)
        assert r.returncode == 0, r.stderr
        assert "stalled span: train_batch/dispatch" in r.stdout
        assert "silent for 12.5s" in r.stdout
        assert "== stack digest ==" in r.stdout

    def test_crash_report_in_process(self, tmp_path):
        _, bundle = self._bundle(tmp_path, reason="sigusr1")
        out = crash_report(bundle)
        assert "reason: sigusr1" in out
        assert "train_batch > train_batch/dispatch" in out

    def test_report_main_crash_dump_errors_cleanly(self, tmp_path, capsys):
        assert report_main(["--crash-dump", str(tmp_path)]) == 1
        assert report_main(["--crash-dump"]) == 2

    def test_sigusr1_dumps(self, tmp_path):
        rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
        assert fr_mod.install_sigusr1(rec)
        try:
            os.kill(os.getpid(), signal.SIGUSR1)
            deadline = time.monotonic() + 2.0
            while not rec.dumps and time.monotonic() < deadline:
                time.sleep(0.01)   # handler runs at a bytecode boundary
        finally:
            fr_mod.uninstall_sigusr1()
        assert rec.dumps
        man = json.load(open(os.path.join(rec.dumps[0], "MANIFEST.json")))
        assert man["reason"] == "sigusr1"


# ---------------------------------------------------------------------------
# hang watchdog (fake clock — no sleeps)


class TestHangWatchdog:
    def test_arm_heartbeat_fire_disarm(self, tmp_path):
        clock = FakeClock()
        rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                             clock=clock)
        reg = MetricsRegistry()
        fired = []
        wd = HangWatchdog(recorder=rec, registry=reg, timeout_factor=2.0,
                          timeout_floor_s=10.0, clock=clock,
                          on_fire=lambda **kw: fired.append(kw))
        assert not wd.check()                      # unarmed
        wd.heartbeat("train_batch/dispatch")
        clock.advance(5.0)
        assert not wd.check()                      # inside the deadline
        wd.heartbeat("train_batch/dispatch")       # heartbeat resets it
        clock.advance(9.0)
        assert not wd.check()
        clock.advance(2.0)                         # 11s silent > 10s floor
        assert wd.check()
        assert wd.fired == 1
        assert fired[0]["stalled_span"] == "train_batch/dispatch"
        assert reg.counter("hang/watchdog_fired").value(
            span="train_batch/dispatch") == 1
        man = json.load(open(os.path.join(fired[0]["bundle"],
                                          "MANIFEST.json")))
        assert man["reason"] == "hang"
        assert man["stalled_span"] == "train_batch/dispatch"
        # fired => disarmed: no repeat dumps for the same stall
        clock.advance(100.0)
        assert not wd.check()
        # a new heartbeat re-arms; disarm() suspends again
        wd.heartbeat("fwd")
        wd.disarm()
        clock.advance(1000.0)
        assert not wd.check()

    def test_deadline_follows_rolling_median(self):
        wd = HangWatchdog(timeout_factor=4.0, timeout_floor_s=1.0,
                          clock=FakeClock())
        assert wd.deadline_s() == 1.0              # floor: no history
        for secs in (2.0, 3.0, 100.0):             # median robust to outlier
            wd.note_step_time(secs)
        assert wd.deadline_s() == pytest.approx(4.0 * 3.0)
        wd2 = HangWatchdog(timeout_factor=2.0, timeout_floor_s=60.0,
                           clock=FakeClock())
        wd2.note_step_time(0.004)                  # fast steps: floor wins
        assert wd2.deadline_s() == 60.0

    def test_abort_uses_injected_exit(self, tmp_path):
        clock = FakeClock()
        codes = []
        wd = HangWatchdog(timeout_factor=2.0, timeout_floor_s=1.0,
                          abort=True, exit_code=113, clock=clock,
                          abort_fn=codes.append)
        wd.heartbeat("step")
        clock.advance(2.0)
        assert wd.check()
        assert codes == [113]

    def test_threaded_stall_detection_end_to_end(self, tmp_path):
        """The acceptance path: an enabled session with the hang watchdog
        on, a span that begins (one heartbeat) and never ends, detection
        within the configured deadline, and a bundle the report CLI parses
        back to the stalled span name."""
        sess = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path),
            hang_watchdog=True, hang_timeout_factor=2.0,
            hang_timeout_floor_s=0.05, hang_poll_interval_s=0.01))
        stuck = sess.span("train_batch/dispatch").begin()   # never ends
        deadline = time.monotonic() + 2.0
        while not sess.hang.fired and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sess.hang.fired == 1, "watchdog did not fire in 2s"
        assert sess.hang.last_fire["stalled_span"] == "train_batch/dispatch"
        bundle = sess.hang.last_fire["bundle"]
        out = crash_report(bundle)
        assert "stalled span: train_batch/dispatch" in out
        # the stall landed in the goodput badput buckets too
        assert sess.goodput.totals()["buckets"]["stall"] > 0
        stuck.end()
        reset_session()


# ---------------------------------------------------------------------------
# goodput accounting


class TestGoodput:
    def test_bucket_classification_and_gaps(self):
        reg = MetricsRegistry()
        acc = GoodputAccountant(reg)
        # step 1: h2d 0.1s + dispatch 0.8s inside a 1.0s train_batch
        acc.on_span("begin", "train_batch", t=10.0)
        acc.on_span("end", "train_batch/h2d", t=10.1, dur_s=0.1)
        acc.on_span("end", "train_batch/dispatch", t=10.9, dur_s=0.8)
        acc.on_span("end", "train_batch", t=11.0, dur_s=1.0)
        # 0.5s gap between steps => input_wait (dataloader)
        acc.on_span("begin", "train_batch", t=11.5)
        acc.on_span("end", "train_batch/dispatch", t=12.4, dur_s=0.9)
        acc.on_span("end", "train_batch", t=12.5, dur_s=1.0)
        # a checkpoint after the second step
        acc.on_span("end", "checkpoint/save", t=13.0, dur_s=0.5)
        tot = acc.totals()
        b = tot["buckets"]
        assert tot["steps"] == 2
        assert b["compute"] == pytest.approx(1.7)
        assert b["input_wait"] == pytest.approx(0.6)   # h2d + gap
        assert b["checkpoint"] == pytest.approx(0.5)
        assert tot["wall_s"] == pytest.approx(3.0)
        assert b["other"] == pytest.approx(3.0 - 1.7 - 0.6 - 0.5)
        assert tot["goodput_fraction"] == pytest.approx(1.7 / 3.0)

    def test_compile_seconds_deducted_from_compute(self):
        acc = GoodputAccountant(MetricsRegistry(), clock=FakeClock(0.0))
        acc.on_span("begin", "train_batch", t=0.0)
        # compile attributed to an open COMPUTE span: deducted from the
        # enclosing span's duration so the seconds are not double-counted
        acc.on_compile(3.0, where="train_batch/dispatch")
        acc.on_span("end", "train_batch/dispatch", t=4.0, dur_s=4.0)
        acc.on_span("end", "train_batch", t=4.0, dur_s=4.0)
        b = acc.totals()["buckets"]
        assert b["recompile"] == pytest.approx(3.0)
        assert b["compute"] == pytest.approx(1.0)  # not double-counted
        # compile OUTSIDE any compute span (engine build, warmup): pure
        # badput, no deduction from later compute spans
        acc.on_compile(1.0, where="<untraced>")
        acc.on_span("end", "train_batch/dispatch", t=6.0, dur_s=2.0)
        b = acc.totals()["buckets"]
        assert b["recompile"] == pytest.approx(4.0)
        assert b["compute"] == pytest.approx(3.0)

    def test_gap_does_not_double_count_bucketed_work(self):
        """A checkpoint (or eval, or between-step compile) inside the
        inter-step gap must land in ONE bucket, not checkpoint+input_wait."""
        acc = GoodputAccountant(MetricsRegistry(), clock=FakeClock(0.0))
        acc.on_span("begin", "train_batch", t=0.0)
        acc.on_span("end", "train_batch/dispatch", t=1.0, dur_s=1.0)
        acc.on_span("end", "train_batch", t=1.0, dur_s=1.0)
        # 2s gap holding a 1.2s checkpoint + 0.3s eval: input_wait = 0.5
        acc.on_span("end", "checkpoint/save", t=2.2, dur_s=1.2)
        acc.on_span("end", "eval", t=2.5, dur_s=0.3)
        acc.on_span("begin", "train_batch", t=3.0)
        acc.on_span("end", "train_batch/dispatch", t=4.0, dur_s=1.0)
        acc.on_span("end", "train_batch", t=4.0, dur_s=1.0)
        b = acc.totals()["buckets"]
        assert b["checkpoint"] == pytest.approx(1.2)
        assert b["compute"] == pytest.approx(2.3)   # dispatch + eval
        assert b["input_wait"] == pytest.approx(0.5)
        assert sum(b.values()) == pytest.approx(acc.totals()["wall_s"])

    def test_stall_extends_wall_and_never_double_counts(self):
        clock = FakeClock(0.0)
        acc = GoodputAccountant(MetricsRegistry(), clock=clock)
        acc.on_span("begin", "train_batch", t=0.0)
        # the dispatch wedges for 300 silent seconds; the watchdog fires
        clock.t = 301.0
        acc.on_stall(300.0, where="train_batch/dispatch")
        tot = acc.totals()
        assert tot["wall_s"] == pytest.approx(301.0)   # silence is wall time
        assert tot["buckets"]["stall"] == pytest.approx(300.0)
        # the run RESUMES: the blocked span's duration includes the silence,
        # which must not be re-counted as compute
        acc.on_span("end", "train_batch/dispatch", t=302.0, dur_s=302.0)
        acc.on_span("end", "train_batch", t=302.0, dur_s=302.0)
        b = acc.totals()["buckets"]
        assert b["compute"] == pytest.approx(2.0)
        assert sum(b.values()) == pytest.approx(acc.totals()["wall_s"])
        # a stall BETWEEN steps must not re-count as the next gap
        clock.t = 310.0
        acc.on_stall(8.0, where="train_batch")
        acc.on_span("begin", "train_batch", t=312.0)
        b = acc.totals()["buckets"]
        assert b["input_wait"] == pytest.approx(2.0)   # only the true gap

    def test_mfu_vs_cost_model_peak_on_known_flops_jit(self):
        """MFU math against an XLA-counted FLOPs number: a 64^3 matmul is
        exactly 2*64^3 flops by cost analysis; one synthetic 1-second step
        at that workload must read flops / PEAK_FLOPS[v5e]."""
        sds = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        compiled = jax.jit(lambda a, b: a @ b).lower(sds, sds).compile()
        flops = compiled_cost(compiled)["flops"]
        assert flops == pytest.approx(2 * 64 ** 3)
        reg = MetricsRegistry()
        acc = GoodputAccountant(reg)
        peak = PEAK_FLOPS["v5e"]
        acc.set_workload(tokens_per_step=64, flops_per_step=flops,
                         peak_flops=peak, source="xla")
        acc.on_span("begin", "train_batch", t=100.0)
        acc.on_span("end", "train_batch/dispatch", t=101.0, dur_s=1.0)
        acc.on_span("end", "train_batch", t=101.0, dur_s=1.0)
        tot = acc.publish()
        assert tot["mfu"] == pytest.approx(flops / peak)
        assert tot["tokens_per_sec"] == pytest.approx(64.0)
        assert reg.gauge("goodput/mfu").value() == pytest.approx(flops / peak)
        assert reg.gauge("goodput/seconds").value(
            bucket="compute") == pytest.approx(1.0)

    def test_peak_flops_lookup(self):
        assert peak_flops_for("TPU v5e") == PEAK_FLOPS["v5e"]
        assert peak_flops_for("TPU v5p chip") == PEAK_FLOPS["v5p"]
        assert peak_flops_for(None) == 197e12
        assert peak_flops_for("cpu") == 197e12     # unknown kind => default
        with pytest.raises(ValueError, match="TPU v9x"):
            peak_flops_for("TPU v9x")     # an unlisted TPU never defaults

    def test_session_routes_compile_and_publish_into_recorder(self, tmp_path):
        sess = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path)))
        sess._on_compile(2.0, "train_batch", False)
        assert sess.goodput.totals()["buckets"]["recompile"] == 2.0
        sess.registry.gauge("x").set(1.0)
        sess.registry.publish(step=3)
        kinds = {e["kind"] for e in sess.recorder.snapshot()}
        assert {"compile", "metric_publish"} <= kinds
        reset_session()


# ---------------------------------------------------------------------------
# steady-state recompile -> goodput badput (satellite)


class TestRecompileGoodputFeed:
    def test_steady_state_counter_and_badput_feed(self, tmp_path):
        sess = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path), steady_state_step=5))
        wd = sess.watchdog
        wd.note_step(6)
        reg = sess.registry
        # two distinct compiles at one site: first silent, repeat steady
        with sess.span("train_batch"):
            jax.jit(lambda x: x + jnp.float32(41))(
                jnp.ones(3)).block_until_ready()
            jax.jit(lambda x: x + jnp.float32(43))(
                jnp.ones(3)).block_until_ready()
        assert reg.counter("recompile/steady_state").value(
            where="train_batch") >= 1
        assert reg.counter("xla/steady_state_recompiles").value(
            where="train_batch") >= 1
        assert sess.goodput.totals()["buckets"]["recompile"] > 0
        reset_session()


# ---------------------------------------------------------------------------
# engine smoke: goodput on the enabled path, nothing on the disabled path


def _engine(tmp_path, enabled):
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 1,
           "steps_per_print": 1,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
           "observability": {"enabled": enabled,
                             "output_dir": str(tmp_path / "obs")}}
    engine, *_ = deepspeed_tpu.initialize(model=simple_model(hidden_dim=10),
                                          config=cfg)
    return engine


class TestEngineGoodputSmoke:
    def test_enabled_run_publishes_goodput_and_mfu(self, tmp_path, devices8):
        from deepspeed_tpu.models.simple import random_batches

        engine = _engine(tmp_path, enabled=True)
        obs = engine._obs
        assert obs.recorder is not None and obs.goodput is not None
        batches = random_batches(jax.random.PRNGKey(0), 3,
                                 engine.train_batch_size())
        it = iter(batches)
        for _ in range(3):
            engine.train_batch(data_iter=it)
        reg = obs.registry
        gf = reg.gauge("goodput/goodput_fraction").value()
        assert gf is not None and 0.0 < gf <= 1.0
        assert reg.gauge("goodput/mfu").value() > 0
        assert reg.gauge("goodput/tokens_per_sec").value() > 0
        assert reg.gauge("goodput/seconds").value(bucket="compute") > 0
        assert reg.gauge("goodput/steps").value() == 3
        # the metrics dump carries the goodput gauges for the report CLI
        path = obs.dump_metrics()
        names = {json.loads(l).get("name") for l in open(path)}
        assert "goodput/goodput_fraction" in names and "goodput/mfu" in names
        from deepspeed_tpu.observability.report import report as render

        assert "== goodput ==" in render([path])

    def test_train_batch_exception_dumps_flight_record(self, tmp_path,
                                                       devices8):
        engine = _engine(tmp_path, enabled=True)
        with pytest.raises(Exception):
            # mismatched feature dim => shape error at step trace time,
            # inside the train_batch span
            engine.train_batch(batch={
                "x": jnp.ones((1, engine.train_batch_size(), 99)),
                "y": jnp.ones((1, engine.train_batch_size(), 1))})
        assert engine._obs.recorder.dumps, "no crash bundle written"
        man = json.load(open(os.path.join(engine._obs.recorder.dumps[0],
                                          "MANIFEST.json")))
        assert man["reason"] == "train_batch-exception"
        assert man["exception"]["type"]

    def test_disabled_run_wires_nothing(self, tmp_path):
        engine = _engine(tmp_path, enabled=False)
        obs = engine._obs
        assert obs.recorder is None and obs.hang is None \
            and obs.goodput is None
        assert obs.tracer.on_event is None
        assert obs.registry.on_publish is None


# ---------------------------------------------------------------------------
# config gates


class TestConfigGates:
    def test_new_fields_validate(self):
        from deepspeed_tpu.config.base import ConfigError

        cfg = ObservabilityConfig.from_dict({})
        assert cfg.flight_recorder and cfg.goodput
        assert not cfg.hang_watchdog            # thread+abort: opt-in
        for bad in ({"flight_ring_size": 0}, {"hang_timeout_factor": 0},
                    {"hang_timeout_floor_s": 0}, {"hang_poll_interval_s": 0},
                    {"hang_exit_code": 0}, {"hang_exit_code": 300}):
            with pytest.raises(ConfigError):
                ObservabilityConfig.from_dict(bad)

    def test_gates_off_within_enabled_session(self, tmp_path):
        sess = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path),
            flight_recorder=False, goodput=False))
        assert sess.recorder is None and sess.goodput is None
        assert sess.tracer.on_event is None
        reset_session()

    def test_session_replacement_keeps_new_publish_hook(self, tmp_path):
        """The registry is a process singleton: closing the REPLACED session
        must not sever the live session's flight-recorder publish hook."""
        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "a")))
        new = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "b")))
        assert get_registry().on_publish == new._on_publish
        new.registry.gauge("g").set(1.0)
        new.registry.publish(step=1)
        assert any(e["kind"] == "metric_publish"
                   for e in new.recorder.snapshot())
        reset_session()
        assert get_registry().on_publish is None

    def test_non_current_session_does_not_steal_hooks(self, tmp_path):
        """configure_observability(..., make_current=False) promises to
        leave the current session alone — including the process-global
        publish hook and the SIGUSR1 recorder pointer."""
        live = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "live")))
        side = configure_observability(
            ObservabilityConfig(enabled=True, output_dir=str(tmp_path / "s")),
            make_current=False)
        assert get_session() is live
        assert get_registry().on_publish == live._on_publish
        assert fr_mod._ACTIVE_RECORDER is live.recorder
        side.close(export=False)
        assert get_registry().on_publish == live._on_publish
        reset_session()
