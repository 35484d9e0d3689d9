"""Unit tests for ``deepspeed_tpu/observability/`` — span tracer, metrics
registry, recompile watchdog, memory gauges, comm instrumentation, report CLI
and the engine-level smoke (the acceptance path: a CPU train run with
observability enabled produces a span JSONL + metrics JSONL that
``python -m deepspeed_tpu.observability report`` can summarize; disabled —
the default — writes nothing).

All CPU-safe: collectives run on the 8-virtual-device mesh, memory gauges hit
the stat-less CPU backend's no-op branch, and the watchdog forces a re-trace
by changing a static arg."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu import observability as obs_mod
from deepspeed_tpu.config.config import ObservabilityConfig
from deepspeed_tpu.models import simple_model
from deepspeed_tpu.observability import (Observability, configure_observability,
                                         get_registry, get_session,
                                         reset_session)
from deepspeed_tpu.observability.memory import record_memory
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.recompile import install as install_watchdog
from deepspeed_tpu.observability.report import report as render_report
from deepspeed_tpu.observability.spans import NOOP_SPAN, SpanTracer


@pytest.fixture(autouse=True)
def _obs_isolation():
    """The registry, session and watchdog are process-globals; every test in
    this module starts and ends clean."""
    reset_session()
    get_registry().reset()
    yield
    reset_session()
    get_registry().reset()


# ---------------------------------------------------------------------------
# spans


class TestSpans:
    def test_nesting_depth_and_parent(self):
        tr = SpanTracer(process_index=0)
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        recs = {r["name"]: r for r in tr.snapshot()}
        assert recs["outer"]["depth"] == 0
        assert "parent_id" not in recs["outer"]
        assert recs["inner"]["depth"] == 1
        assert recs["inner"]["parent_id"] == recs["outer"]["id"]
        # inner closed first (JSONL order), and nests inside outer's interval
        assert recs["inner"]["dur_us"] <= recs["outer"]["dur_us"]

    def test_jsonl_written_as_spans_close(self, tmp_path):
        """Tail safety: records land in the JSONL at close time, before any
        flush/close call — a killed run keeps what it measured."""
        path = str(tmp_path / "t.jsonl")
        tr = SpanTracer(jsonl_path=path, process_index=0)
        with tr.span("a"):
            pass
        with open(path) as fh:
            lines = [json.loads(l) for l in fh if l.strip()]
        assert [l["name"] for l in lines] == ["a"]
        tr.close()

    def test_disabled_tracer_hands_out_the_shared_noop(self):
        tr = SpanTracer(enabled=False, process_index=0)
        with tr.span("x") as s:
            pass
        assert s is NOOP_SPAN and s.duration_s == 0.0   # a caller that
        #   needs a time (generate()'s TTFT) reads the clock itself
        assert tr.snapshot() == []

    def test_rank_gating(self, tmp_path):
        tr = SpanTracer(jsonl_path=str(tmp_path / "r.jsonl"), process_index=1)
        with tr.span("x"):
            pass
        assert tr.snapshot() == []
        assert not os.path.exists(tmp_path / "r.jsonl")
        tr_all = SpanTracer(all_ranks=True, process_index=1)
        with tr_all.span("x"):
            pass
        assert tr_all.snapshot()[0]["pid"] == 1

    def test_non_lexical_begin_end(self):
        tr = SpanTracer(process_index=0)
        s = tr.span("profile").begin()
        assert tr.current_name() == "profile"
        s.end()
        assert tr.current_name() is None
        assert tr.snapshot()[0]["name"] == "profile"


# ---------------------------------------------------------------------------
# metrics registry


class TestMetricsRegistry:
    def test_counter_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("comm/bytes")
        c.inc(100, op="all_reduce")
        c.inc(50, op="all_reduce")
        c.inc(7, op="all_gather")
        assert c.value(op="all_reduce") == 150
        assert c.value(op="all_gather") == 7
        assert c.value(op="missing") == 0
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_last_write_wins(self):
        reg = MetricsRegistry()
        g = reg.gauge("loss")
        g.set(2.0)
        g.set(1.5)
        assert g.value() == 1.5
        assert g.value(other="label") is None

    def test_histogram_running_stats(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_ms")
        for v in (1.0, 3.0, 2.0):
            h.observe(v, op="x")
        st = h.stats(op="x")
        assert st == {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0}
        (rec,) = h.records()
        assert rec["mean"] == 2.0

    def test_memoized_by_name_and_kind_conflict(self):
        reg = MetricsRegistry()
        assert reg.counter("n") is reg.counter("n")
        with pytest.raises(TypeError):
            reg.gauge("n")

    def test_exporter_fan_out(self):
        class FakeWriter:
            def __init__(self):
                self.events = []

            def write_events(self, events):
                self.events.extend(events)

        reg = MetricsRegistry()
        w = FakeWriter()
        reg.attach_exporter(w)
        reg.gauge("loss").set(0.5)
        reg.counter("steps").inc()
        events = reg.publish(step=7)
        assert w.events == events
        assert ("loss", 0.5, 7) in w.events and ("steps", 1.0, 7) in w.events
        # names filter restricts the snapshot
        w.events.clear()
        reg.publish(step=8, names=["loss"])
        assert w.events == [("loss", 0.5, 8)]
        reg.detach_exporter(w)
        w.events.clear()
        reg.publish(step=9)
        assert w.events == []

    def test_dump_jsonl_round_trip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("c").inc(2, op="x")
        reg.histogram("h").observe(1.0)
        path = reg.dump_jsonl(str(tmp_path / "m.jsonl"), extra={"run": "t"})
        with open(path) as fh:
            recs = [json.loads(l) for l in fh]
        assert recs[0]["type"] == "meta" and recs[0]["run"] == "t"
        by_name = {r["name"]: r for r in recs[1:]}
        assert by_name["c"]["value"] == 2 and by_name["c"]["labels"] == {"op": "x"}
        assert by_name["h"]["count"] == 1


# ---------------------------------------------------------------------------
# recompile watchdog


class TestRecompileWatchdog:
    def test_static_arg_retrace_records_miss(self):
        reg = MetricsRegistry()
        wd = install_watchdog(registry=reg)

        f = jax.jit(lambda x, n: x * n, static_argnums=1)
        f(jnp.ones(4), 2).block_until_ready()
        first = wd.compile_count
        assert first >= 1
        f(jnp.ones(4), 3).block_until_ready()  # static-arg change => re-trace
        assert wd.compile_count > first
        assert reg.counter("xla/compiles").value(where="<untraced>") >= 2
        assert wd.compile_seconds > 0
        rep = wd.report()
        assert rep["compiles"] == wd.compile_count
        assert rep["per_site"]["<untraced>"]["count"] >= 2

    def test_compile_attributed_to_open_span(self):
        reg = MetricsRegistry()
        tr = SpanTracer(process_index=0)
        wd = install_watchdog(registry=reg, tracer=tr)
        with tr.span("train_batch"):
            jax.jit(lambda x: x + jnp.float32(17))(jnp.ones(3)).block_until_ready()
        assert wd.per_site.get("train_batch", {}).get("count", 0) >= 1
        assert reg.counter("xla/compiles").value(where="train_batch") >= 1

    def test_steady_state_recompile_warns(self, caplog):
        from deepspeed_tpu.utils.logging import logger as ds_logger

        reg = MetricsRegistry()
        wd = install_watchdog(registry=reg, steady_state_step=5)
        wd.note_step(6)
        # the package logger does not propagate; hook caplog's handler on it
        ds_logger.addHandler(caplog.handler)
        try:
            # a site's FIRST post-threshold compile is a legitimately new
            # function — no warning...
            jax.jit(lambda x: x - jnp.float32(23))(jnp.ones(3)).block_until_ready()
            assert wd.steady_state_compiles == 0
            assert not caplog.records
            # ...a REPEAT compile at the same site is a re-specialization
            jax.jit(lambda x: x - jnp.float32(31))(jnp.ones(3)).block_until_ready()
        finally:
            ds_logger.removeHandler(caplog.handler)
        assert wd.steady_state_compiles >= 1
        assert reg.counter("xla/steady_state_recompiles").value(
            where="<untraced>") >= 1
        assert any("steady-state recompilation" in r.message
                   for r in caplog.records)

    def test_uninstall_stops_counting(self):
        reg = MetricsRegistry()
        wd = install_watchdog(registry=reg)
        obs_mod.uninstall_watchdog()
        jax.jit(lambda x: x * jnp.float32(29))(jnp.ones(3)).block_until_ready()
        assert wd.compile_count == 0


# ---------------------------------------------------------------------------
# memory gauges


class TestMemory:
    def test_cpu_no_op_device_side_host_rss_recorded(self):
        reg = MetricsRegistry()
        # the CPU backend reports no allocator stats => device side no-ops
        assert record_memory(reg) is False
        rss = reg.gauge("mem/host_rss_bytes").value()
        assert rss is not None and rss > 0
        assert not any(m.name.startswith("mem/device/") for m in reg.metrics())


# ---------------------------------------------------------------------------
# comm instrumentation (CPU mesh)


class TestCommInstrumentation:
    def test_traced_collectives_publish_census(self, devices8, tmp_path):
        from deepspeed_tpu import comm
        from deepspeed_tpu.config.config import ParallelConfig
        from deepspeed_tpu.parallel import mesh as mesh_mod

        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path)))
        reg = get_session().registry
        m = mesh_mod.build_mesh(ParallelConfig())
        x = jnp.arange(8.0)
        f = shard_map(lambda v: comm.all_reduce(v, axis="data"),
                      mesh=m, in_specs=P("data"), out_specs=P())
        np.testing.assert_allclose(np.asarray(f(x)), [28.0])
        # census: recorded once per compiled program, with message bytes
        assert reg.counter("comm/ops").value(op="all_reduce") >= 1
        assert reg.counter("comm/bytes").value(op="all_reduce") > 0

    def test_disabled_session_records_nothing(self, devices8):
        from deepspeed_tpu import comm
        from deepspeed_tpu.config.config import ParallelConfig
        from deepspeed_tpu.parallel import mesh as mesh_mod

        reg = get_registry()
        m = mesh_mod.build_mesh(ParallelConfig())
        f = shard_map(lambda v: comm.all_gather(v, axis="data"),
                      mesh=m, in_specs=P("data"), out_specs=P("data"))
        f(jnp.arange(8.0)).block_until_ready()
        assert reg.counter("comm/ops").value(op="all_gather") == 0


# ---------------------------------------------------------------------------
# monitor writers as registry exporters + CSV lifecycle


class TestMonitorExport:
    def _csv_master(self, tmp_path):
        from deepspeed_tpu.config.config import MonitorConfig
        from deepspeed_tpu.monitor.monitor import MonitorMaster

        cfg = MonitorConfig.from_dict({
            "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                            "job_name": "job"}})
        return MonitorMaster(cfg)

    def test_registry_publish_reaches_csv(self, tmp_path):
        master = self._csv_master(tmp_path)
        reg = MetricsRegistry()
        reg.attach_exporter(master)
        reg.gauge("Train/Samples/train_loss").set(0.25)
        reg.publish(step=3)
        master.close()
        csv_path = tmp_path / "job" / "Train_Samples_train_loss.csv"
        rows = csv_path.read_text().strip().splitlines()
        assert rows[0].startswith("step,")
        assert rows[1] == "3,0.25"

    def test_csv_handles_flushed_and_closed(self, tmp_path):
        from deepspeed_tpu.monitor.monitor import CSVMonitor

        master = self._csv_master(tmp_path)
        csv_writer = next(w for w in master.writers
                          if isinstance(w, CSVMonitor))
        master.write_events([("m", 1.0, 1), ("m", 2.0, 2)])
        # write_events flushes: rows are on disk without close
        rows = (tmp_path / "job" / "m.csv").read_text().strip().splitlines()
        assert len(rows) == 3
        master.close()
        assert csv_writer._files == {}
        assert not csv_writer.enabled
        # close() is terminal: a late write_events is a silent no-op
        master.write_events([("m", 3.0, 3)])
        rows = (tmp_path / "job" / "m.csv").read_text().strip().splitlines()
        assert rows[-1] == "2,2.0"


# ---------------------------------------------------------------------------
# report CLI


class TestReportCli:
    def test_report_summarizes_spans_metrics_recompiles(self, tmp_path):
        path = tmp_path / "mix.jsonl"
        recs = [
            {"type": "span", "name": "fwd", "ts_us": 0, "dur_us": 1000,
             "depth": 1},
            {"type": "span", "name": "fwd", "ts_us": 2000, "dur_us": 3000,
             "depth": 1},
            {"type": "counter", "name": "comm/bytes",
             "labels": {"op": "all_reduce"}, "value": 4096},
            {"type": "gauge", "name": "loss", "labels": {}, "value": 0.5},
            {"type": "histogram", "name": "lat", "labels": {}, "count": 2,
             "sum": 3.0, "min": 1.0, "max": 2.0, "mean": 1.5},
            {"type": "counter", "name": "xla/compiles",
             "labels": {"where": "train_batch"}, "value": 2},
        ]
        path.write_text("".join(json.dumps(r) + "\n" for r in recs))
        out = render_report([str(path)])
        assert "== spans ==" in out and "fwd" in out and "2" in out
        assert "== counters ==" in out and "op=all_reduce" in out
        assert "== gauges ==" in out and "loss" in out
        assert "== histograms ==" in out
        assert "== recompiles ==" in out and "train_batch" in out

    def test_cli_entry(self, tmp_path):
        import subprocess
        import sys

        path = tmp_path / "t.jsonl"
        path.write_text(json.dumps(
            {"type": "span", "name": "s", "ts_us": 0, "dur_us": 10,
             "depth": 0}) + "\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.observability", "report",
             str(path)],
            capture_output=True, text=True, cwd="/root/repo", env=env)
        assert r.returncode == 0 and "== spans ==" in r.stdout

    def test_report_empty(self, tmp_path):
        path = tmp_path / "e.jsonl"
        path.write_text("")
        assert "no span or metric records" in render_report([str(path)])


# ---------------------------------------------------------------------------
# session + config gating


class TestSessionGating:
    def test_default_session_is_disabled_and_shared(self):
        s = get_session()
        assert not s.enabled
        assert get_session() is s
        assert s.metrics_path() is None

    def test_disabled_config_leaves_current_session_alone(self, tmp_path):
        live = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path)))
        assert get_session() is live
        off = configure_observability(ObservabilityConfig(enabled=False))
        assert not off.enabled
        assert get_session() is live   # telemetry-free engine kept the trace

    def test_replacing_enabled_session_closes_the_old_one(self, tmp_path):
        old = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "a")))
        new = configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "b")))
        assert get_session() is new
        # the replaced session is closed: its JSONL handle is released and
        # its (LIFO-last) atexit close can no longer overwrite live exports
        assert old._closed and old.tracer._fh is None
        assert not new._closed

    def test_dump_metrics_rank_gated(self, tmp_path):
        sess = Observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path)), process_index=1)
        sess.registry.counter("c").inc()
        assert sess.dump_metrics() is None     # all_ranks=False, rank 1
        assert not os.path.exists(tmp_path / "metrics.jsonl")
        sess.close(export=False)

    def test_host_timed_comm_metrics_separate_series(self, tmp_path):
        from deepspeed_tpu.comm.comm import _record_comm_metrics

        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path)))
        reg = get_session().registry
        _record_comm_metrics("all_reduce", "ckpt", 1024, latency_s=0.002)
        # host-timed calls must not pollute the per-compile census series
        assert reg.counter("comm/ops").value(op="all_reduce") == 0
        assert reg.counter("comm/host_ops").value(op="all_reduce") == 1
        assert reg.counter("comm/host_bytes").value(op="all_reduce") == 1024
        assert reg.histogram("comm/latency_ms").stats(op="ckpt")["count"] == 1

    def test_dump_jsonl_truncates_by_default(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        path = str(tmp_path / "m.jsonl")
        reg.dump_jsonl(path)
        reg.dump_jsonl(path)                  # snapshot: second dump replaces
        assert len(open(path).readlines()) == 1
        reg.dump_jsonl(path, append=True)     # trajectory mode is opt-in
        assert len(open(path).readlines()) == 2

    def test_config_validation(self):
        from deepspeed_tpu.config.base import ConfigError

        with pytest.raises(ConfigError):
            ObservabilityConfig.from_dict({"max_spans": 0})
        with pytest.raises(ConfigError):
            ObservabilityConfig.from_dict({"memory_poll_steps": 0})


# ---------------------------------------------------------------------------
# engine smoke (the acceptance path)


def _obs_engine(tmp_path, enabled=True):
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 1,
           "steps_per_print": 1,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
           "observability": {"enabled": enabled,
                             "output_dir": str(tmp_path / "obs")}}
    engine, *_ = deepspeed_tpu.initialize(model=simple_model(hidden_dim=10),
                                          config=cfg)
    return engine


class TestEngineSmoke:
    def test_enabled_run_produces_trace_and_metrics(self, tmp_path, devices8):
        from deepspeed_tpu import comm
        from deepspeed_tpu.models.simple import random_batches

        engine = _obs_engine(tmp_path)
        obs = engine._obs
        assert obs.enabled and get_session() is obs
        batches = random_batches(jax.random.PRNGKey(0), 4,
                                 engine.train_batch_size())
        it = iter(batches)
        for _ in range(2):
            engine.train_batch(data_iter=it)
        # fwd/bwd/step API spans
        engine.forward(next(it))
        engine.backward()
        engine.step()
        # one traced collective so the comm census lands in the same run
        m = engine.mesh
        shard_map(lambda v: comm.all_reduce(v, axis="data"), mesh=m,
                  in_specs=P("data"), out_specs=P())(jnp.arange(8.0))

        metrics_path = obs.dump_metrics()
        obs.flush()

        # span JSONL has the step phases
        with open(obs.tracer.jsonl_path) as fh:
            names = {json.loads(l)["name"] for l in fh if l.strip()}
        assert {"train_batch", "fwd", "bwd", "step"} <= names

        # metrics JSONL: loss gauge, comm census, memory gauge, >=1 compile
        with open(metrics_path) as fh:
            recs = [json.loads(l) for l in fh if l.strip()]
        by = {(r.get("name"), r["type"]): r for r in recs}
        assert ("Train/Samples/train_loss", "gauge") in by
        assert by[("comm/ops", "counter")]["value"] >= 1
        assert by[("comm/bytes", "counter")]["value"] > 0
        assert ("mem/host_rss_bytes", "gauge") in by
        compile_recs = [r for r in recs if r.get("name") == "xla/compiles"]
        assert sum(r["value"] for r in compile_recs) >= 1
        meta = recs[0]
        assert meta["type"] == "meta"
        assert meta["recompile_report"]["compiles"] >= 1

        # the report CLI summarizes the pair
        out = render_report([obs.tracer.jsonl_path, metrics_path])
        assert "train_batch" in out and "== recompiles ==" in out

    def test_disabled_run_writes_nothing(self, tmp_path):
        from deepspeed_tpu.models.simple import random_batches

        engine = _obs_engine(tmp_path, enabled=False)
        assert not engine._obs.enabled
        batches = random_batches(jax.random.PRNGKey(0), 1,
                                 engine.train_batch_size())
        engine.train_batch(data_iter=iter(batches))
        assert not os.path.exists(tmp_path / "obs")
        assert engine._obs.dump_metrics() is None

    def test_profile_double_start_guarded(self, tmp_path):
        engine = _obs_engine(tmp_path, enabled=False)
        engine._profiling = True   # simulate an active trace
        with pytest.raises(RuntimeError, match="already"):
            engine.start_profile()
        engine._profiling = False
        engine.stop_profile()      # no active trace: warns, does not raise

    def test_profile_dir_from_config(self, tmp_path):
        engine = _obs_engine(tmp_path, enabled=False)
        assert engine.config.observability.profile_dir == "/tmp/dstpu_trace"
        cfg = ObservabilityConfig.from_dict({"profile_dir": "/tmp/elsewhere"})
        assert cfg.profile_dir == "/tmp/elsewhere"
