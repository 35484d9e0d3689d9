"""tpucost unit tests: extraction helpers, roofline math, tolerance-band
baseline semantics (regression / stale-rot / prune), the injected-regression
acceptance fixture (dead donation + undeclared all-gather must fail the gate
naming entry, metric and delta), the autotuner calibration shim, and the
repo-wide gate (selftest engines vs the committed baseline — what makes
tier-1 enforce program-cost analysis)."""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tools.tpuaudit import clear_registry, register_entry_point
from tools.tpucost import baseline as baseline_mod
from tools.tpucost import extract, roofline
from tools.tpucost.cli import main as tpucost_main
from tools.tpucost.core import cost_entry, registry_cost_vector, run_cost

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def _clean_registry():
    clear_registry()
    yield
    clear_registry()


def sds(shape, dtype=jnp.float32, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def mesh2x4():
    devs = np.array(jax.devices()).reshape(2, 4)
    return Mesh(devs, ("data", "model"))


# ---------------------------------------------------------------------------
# extraction helpers


class TestExtract:
    def test_hlo_op_census_counts_and_async_folding(self):
        text = """
HloModule m
ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %c = f32[]{} constant(1)
  %b = f32[4]{0} broadcast(f32[] %c), dimensions={}
  %ag-start = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %p0), replica_groups={{0,1}}, dimensions={0}
  %ag-done = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ag-start)
  ROOT %add = f32[4]{0} add(f32[4]{0} %p0, f32[4]{0} %b)
}
"""
        census = extract.hlo_op_census(text)
        assert census["parameter"] == 1 and census["add"] == 1
        # -start counts once, -done is dropped
        assert census["all-gather"] == 1 and "all-gather-done" not in census

    def test_collective_census_bytes_and_axis(self):
        text = ("  %ag = f32[8,16]{1,0} all-gather(f32[2,16]{1,0} %x), "
                "replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}\n"
                "  %cp = bf16[32]{0} collective-permute(bf16[32]{0} %y), "
                "source_target_pairs={{0,1}}\n")
        census = extract.collective_census(
            text, axis_sizes={"data": 2, "model": 4})
        assert census["by_kind"]["all-gather"]["count"] == 1
        assert census["by_kind"]["all-gather"]["bytes"] == 8 * 16 * 4
        assert census["by_kind"]["collective-permute"]["bytes"] == 32 * 2
        # group of 4 matches exactly the model axis
        assert census["by_axis"]["model"] == 8 * 16 * 4
        assert census["total_bytes"] == 8 * 16 * 4 + 32 * 2

    def test_collective_census_iota_groups(self):
        text = ("  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), "
                "replica_groups=[4,2]<=[8], to_apply=%add\n")
        census = extract.collective_census(
            text, axis_sizes={"data": 2, "model": 4})
        assert census["by_axis"] == {"data": 512.0}

    def test_cost_and_memory_analysis_on_real_program(self):
        f = jax.jit(lambda s, x: (jax.tree.map(lambda a: a + x.sum(), s),
                                  x.sum()), donate_argnums=(0,))
        args = ({"w": sds((256, 256))}, sds((64,)))
        compiled = f.trace(*args).lower().compile()
        cost = extract.cost_analysis_dict(compiled)
        assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
        mem = extract.memory_analysis_dict(compiled)
        assert mem["argument_hbm_bytes"] >= 256 * 256 * 4
        # the donated state aliases its output: peak excludes one copy
        assert mem["alias_hbm_bytes"] >= 256 * 256 * 4
        assert mem["peak_hbm_bytes"] == (
            mem["argument_hbm_bytes"] + mem["output_hbm_bytes"]
            + mem["temp_hbm_bytes"] - mem["alias_hbm_bytes"])

    def test_program_hash_stable_and_distinct(self):
        assert extract.program_hash("abc") == extract.program_hash("abc")
        assert extract.program_hash("abc") != extract.program_hash("abd")


class TestRoofline:
    def test_compute_bound(self):
        b = roofline(flops=1e12, bytes_accessed=1.0, collective_bytes=0.0)
        assert b.bound == "compute" and b.mfu_ceiling == 1.0
        assert b.predicted_step_s == pytest.approx(1e12 / b.peak_flops)

    def test_hbm_bound_ceiling_below_one(self):
        b = roofline(flops=1e9, bytes_accessed=1e12, collective_bytes=0.0,
                     tokens_per_step=4096)
        assert b.bound == "hbm" and 0 < b.mfu_ceiling < 1
        assert b.predicted_tokens_per_sec == pytest.approx(
            4096 / b.predicted_step_s)

    def test_ici_bound(self):
        b = roofline(flops=1.0, bytes_accessed=1.0, collective_bytes=1e12)
        assert b.bound == "ici" and b.mfu_ceiling > 0


# ---------------------------------------------------------------------------
# baseline semantics


def _vec(entry="e", metrics=None, hlo_ops=None):
    from tools.tpucost.core import CostVector

    return CostVector(entry=entry, metrics=dict(metrics or {}),
                      hlo_ops=dict(hlo_ops or {}),
                      collectives={"total_bytes": 0.0, "by_kind": {},
                                   "by_axis": {}},
                      program_hash="h", compiled=True, predicted_step_s=1e-3,
                      mfu_ceiling=0.5, bound="hbm")


class TestBaselineSemantics:
    def test_identical_is_clean(self):
        v = _vec(metrics={"flops": 100.0, "peak_hbm_bytes": 1000.0})
        base = baseline_mod.records_of([v])
        findings, stale = baseline_mod.compare([v], base)
        assert findings == [] and stale == []

    def test_growth_beyond_band_fails_with_attribution(self):
        v0 = _vec(metrics={"flops": 100.0, "peak_hbm_bytes": 1000.0},
                  hlo_ops={"fusion": 3})
        base = baseline_mod.records_of([v0])
        v1 = _vec(metrics={"flops": 100.0, "peak_hbm_bytes": 1030.0},
                  hlo_ops={"fusion": 5, "all-gather": 1})
        findings, stale = baseline_mod.compare([v1], base)
        assert [f.key for f in findings] == ["e::peak_hbm_bytes"]
        msg = findings[0].render()
        assert "1,000 -> 1,030" in msg and "+3.00%" in msg
        assert "fusion +2" in msg and "all-gather +1" in msg

    def test_growth_within_band_is_clean(self):
        v0 = _vec(metrics={"peak_hbm_bytes": 1000.0})
        base = baseline_mod.records_of([v0])
        findings, stale = baseline_mod.compare(
            [_vec(metrics={"peak_hbm_bytes": 1015.0})], base)
        assert findings == [] and stale == []

    def test_exact_metric_any_growth_fails(self):
        v0 = _vec(metrics={"flops": 100.0})
        base = baseline_mod.records_of([v0])
        findings, _ = baseline_mod.compare([_vec(metrics={"flops": 101.0})],
                                           base)
        assert [f.key for f in findings] == ["e::flops"]

    def test_improvement_goes_stale_then_prunes(self):
        v0 = _vec(metrics={"flops": 100.0})
        base = baseline_mod.records_of([v0])
        v1 = _vec(metrics={"flops": 50.0})
        findings, stale = baseline_mod.compare([v1], base)
        assert findings == [] and stale == ["e::flops"]
        pruned = baseline_mod.pruned([v1], base)
        assert pruned["e"]["metrics"]["flops"] == 50.0
        findings, stale = baseline_mod.compare([v1], pruned)
        assert findings == [] and stale == []

    def test_prune_never_ratchets_up(self):
        v0 = _vec(metrics={"flops": 100.0})
        base = baseline_mod.records_of([v0])
        v_fat = _vec(metrics={"flops": 200.0})
        pruned = baseline_mod.pruned([v_fat], base)
        assert pruned["e"]["metrics"]["flops"] == 100.0
        findings, _ = baseline_mod.compare([v_fat], pruned)
        assert [f.key for f in findings] == ["e::flops"]

    def test_vanished_entry_stale_then_pruned_away(self):
        base = baseline_mod.records_of([_vec(metrics={"flops": 1.0})])
        findings, stale = baseline_mod.compare([], base)
        assert findings == [] and stale == ["e::flops"]
        assert baseline_mod.pruned([], base) == {}

    def test_new_entry_is_a_finding(self):
        findings, stale = baseline_mod.compare(
            [_vec(entry="new", metrics={"flops": 1.0})], {})
        assert [f.key for f in findings] == ["new::unbaselined"]

    def test_trace_error_gates(self):
        findings, _ = baseline_mod.compare([], {}, errors={"broken": "boom"})
        assert [f.key for f in findings] == ["broken::trace-error"]

    def test_out_of_scope_keys_untouched(self):
        base = baseline_mod.records_of([
            _vec(entry="a", metrics={"flops": 10.0}),
            _vec(entry="b", metrics={"flops": 10.0})])
        in_scope = lambda key: key.startswith("a::")   # noqa: E731
        findings, stale = baseline_mod.compare(
            [_vec(entry="a", metrics={"flops": 10.0})], base,
            in_scope=in_scope)
        assert findings == [] and stale == []
        pruned = baseline_mod.pruned(
            [_vec(entry="a", metrics={"flops": 10.0})], base,
            in_scope=in_scope)
        assert pruned["b"]["metrics"]["flops"] == 10.0


# ---------------------------------------------------------------------------
# cost vectors from the registry


class TestCostEntry:
    def test_vector_from_registered_entry(self):
        f = jax.jit(lambda s, x: (jax.tree.map(lambda a: a + x.sum(), s),
                                  x.sum()), donate_argnums=(0,))
        ep = register_entry_point(
            "fix/vec", fn=f, args=({"w": sds((128, 128))}, sds((8,))),
            donate_argnums=(0,), expected_collectives=None,
            tags={"tokens_per_step": 8})
        v = cost_entry(ep)
        assert v.compiled and v.metrics["flops"] > 0
        assert v.metrics["peak_hbm_bytes"] > 0
        assert v.metrics["hlo_op_count"] > 0 and v.metrics["jaxpr_eqns"] > 0
        assert v.mfu_ceiling > 0 and v.predicted_step_s > 0
        assert v.predicted_tokens_per_sec > 0
        assert len(v.program_hash) == 64

    def test_dropping_donation_grows_peak_hbm(self):
        args = ({"w": sds((256, 256))}, sds((8,)))

        def step(s, x):
            return jax.tree.map(lambda a: a + x.sum(), s), x.sum()

        donated = cost_entry(register_entry_point(
            "fix/don", fn=jax.jit(step, donate_argnums=(0,)), args=args,
            donate_argnums=(0,), expected_collectives=None))
        plain = cost_entry(register_entry_point(
            "fix/nodon", fn=jax.jit(step), args=args,
            expected_collectives=None))
        assert (plain.metrics["peak_hbm_bytes"]
                > donated.metrics["peak_hbm_bytes"])

    def test_uncompiled_entry_still_gets_flops(self):
        ep = register_entry_point(
            "fix/nocompile", fn=jax.jit(lambda x: (x @ x).sum()),
            args=(sds((64, 64)),), expected_collectives=None, compile=False)
        v = cost_entry(ep)
        assert not v.compiled
        assert v.metrics["flops"] > 0 and v.mfu_ceiling > 0
        assert "peak_hbm_bytes" not in v.metrics

    def test_registry_cost_vector_misses_return_none(self):
        assert registry_cost_vector("no/such/entry") is None

    def test_run_cost_reports_trace_errors(self):
        def boom():
            raise RuntimeError("kaput")

        ep = register_entry_point("fix/broken", build=boom,
                                  expected_collectives=None)
        vectors, errors = run_cost([ep], publish_metrics=False)
        assert vectors == [] and "kaput" in errors["fix/broken"]

    def test_publish_lands_in_metrics_registry(self):
        from deepspeed_tpu.observability import get_registry

        ep = register_entry_point(
            "pub/cost", fn=jax.jit(lambda x: x.sum()), args=(sds((32,)),),
            expected_collectives=None)
        run_cost([ep])
        g = get_registry().gauge("tpucost/pub/cost/flops")
        assert g.value() is not None and g.value() >= 0


# ---------------------------------------------------------------------------
# injected-regression acceptance fixture + CLI


class TestInjectedRegression:
    """Deliberately fatten one entry — drop its donation (peak HBM grows)
    and force an undeclared GSPMD all-gather (collective bytes grow) — and
    the gate must exit nonzero naming the entry, the metrics and the
    deltas."""

    def _register(self, fat: bool):
        mesh = mesh2x4()

        def step(state, batch):
            new = jax.tree.map(lambda a: a + batch.sum(), state)
            if fat:
                # replicate the sharded state: GSPMD inserts an all-gather
                new = {"w": jax.lax.with_sharding_constraint(
                    new["w"], NamedSharding(mesh, P(None, None)))}
            return new

        donate = () if fat else (0,)
        args = ({"w": sds((608, 608),
                          sharding=NamedSharding(mesh, P("model", None)))},
                sds((8,)))
        register_entry_point(
            "fix/step", fn=jax.jit(step, donate_argnums=donate), args=args,
            donate_argnums=donate, expected_collectives=None, mesh=mesh)

    def test_gate_names_entry_metric_and_delta(self, tmp_path, capsys):
        bl = tmp_path / "bl.json"
        self._register(fat=False)
        assert tpucost_main(["--baseline", str(bl),
                             "--write-baseline"]) == 0
        assert tpucost_main(["--baseline", str(bl)]) == 0
        capsys.readouterr()

        clear_registry()
        self._register(fat=True)
        rc = tpucost_main(["--baseline", str(bl)])
        out = capsys.readouterr().out
        assert rc == 1
        flagged = [l for l in out.splitlines() if "fix/step:" in l]
        assert any("peak_hbm_bytes" in l and "->" in l and "%" in l
                   for l in flagged), out
        assert any("collective_bytes" in l for l in flagged), out

    def test_clean_run_with_diff_and_json(self, tmp_path, capsys):
        bl = tmp_path / "bl.json"
        self._register(fat=False)
        assert tpucost_main(["--baseline", str(bl),
                             "--write-baseline"]) == 0
        capsys.readouterr()
        rc = tpucost_main(["--baseline", str(bl), "--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["new_findings"] == 0
        vec = out["entries"]["fix/step"]
        assert vec["mfu_ceiling"] > 0
        rc = tpucost_main(["--baseline", str(bl), "--diff"])
        assert rc == 0
        assert "unchanged" in capsys.readouterr().out

    def test_no_entries_errors(self):
        assert tpucost_main([]) == 2

    def test_partial_entries_write_merges_into_baseline(self, tmp_path,
                                                        capsys):
        """--entries X --write-baseline must not destroy the other
        entries' committed budgets."""
        bl = tmp_path / "bl.json"
        self._register(fat=False)
        register_entry_point(
            "fix/other", fn=jax.jit(lambda x: x.sum()), args=(sds((16,)),),
            expected_collectives=None)
        assert tpucost_main(["--baseline", str(bl),
                             "--write-baseline"]) == 0
        assert tpucost_main(["--baseline", str(bl), "--entries", "fix/step",
                             "--write-baseline"]) == 0
        entries = json.loads(bl.read_text())["entries"]
        assert set(entries) == {"fix/step", "fix/other"}
        assert tpucost_main(["--baseline", str(bl)]) == 0

    def test_prune_refuses_on_broken_entry(self, tmp_path, capsys):
        bl = tmp_path / "bl.json"
        self._register(fat=False)
        assert tpucost_main(["--baseline", str(bl),
                             "--write-baseline"]) == 0

        def boom():
            raise RuntimeError("kaput")

        register_entry_point("fix/broken", build=boom,
                             expected_collectives=None)
        assert tpucost_main(["--baseline", str(bl),
                             "--prune-baseline"]) == 2


def test_report_footer_pairs_measured_mfu_with_train_step():
    """The measured goodput/mfu must be compared against the TRAIN step's
    own ceiling, not whichever program has the largest one."""
    from deepspeed_tpu.observability.report import summarize_cost

    records = [
        {"type": "gauge", "name": "goodput/mfu", "labels": {}, "value": 0.35},
        {"type": "gauge", "name": "tpucost/train/step/mfu_ceiling",
         "labels": {}, "value": 0.41},
        {"type": "gauge", "name": "tpucost/inference/prefill/mfu_ceiling",
         "labels": {}, "value": 0.99},
    ]
    out = summarize_cost(records)
    assert "measured mfu = 0.3500 vs static ceiling 0.4100 (train/step)" \
        in out
    assert "0.9900" not in out.splitlines()[-1]


# ---------------------------------------------------------------------------
# autotuner calibration shim


class TestAutotunerShim:
    def _model_info(self):
        return {"num_params": 125e6, "hidden_size": 768, "num_layers": 12,
                "seq_length": 1024, "vocab_size": 50257}

    def test_calibrate_from_vector_switches_backend(self):
        from deepspeed_tpu.autotuning.cost_model import TpuCostModel

        m = TpuCostModel(model_info=self._model_info())
        assert m.backend == "static-tables"
        vec = _vec(metrics={"flops": 1e12})
        vec.tags["tokens_per_step"] = 32 * 1024
        assert m.calibrate_from_vector(vec)
        assert m.backend == "tpucost:h"
        cfg = {"train_micro_batch_size_per_gpu": 1}
        calibrated = m.predict_throughput(cfg)
        m2 = TpuCostModel(model_info=self._model_info())
        assert calibrated != m2.predict_throughput(cfg)

    def test_calibrate_rejects_vector_without_tokens(self):
        from deepspeed_tpu.autotuning.cost_model import TpuCostModel

        m = TpuCostModel(model_info=self._model_info())
        assert not m.calibrate_from_vector(_vec(metrics={"flops": 1e12}))
        assert m.backend == "static-tables"

    def test_tune_records_cost_backend(self, tmp_path):
        from deepspeed_tpu.autotuning.autotuner import Autotuner

        vec = _vec(metrics={"flops": 1e12})
        vec.tags["tokens_per_step"] = 32 * 1024
        tuner = Autotuner(
            {"autotuning": {"model_info": self._model_info()}},
            results_dir=str(tmp_path), runner=lambda name, cfg: 1.0)
        best, val = tuner.tune(
            space={"train_micro_batch_size_per_gpu": [1, 2]},
            tuner_type="model_based", num_trials=2, cost_vector=vec)
        assert val == 1.0
        summary = json.loads((tmp_path / "summary.json").read_text())
        assert summary["cost_backend"] == "tpucost:h"


# ---------------------------------------------------------------------------
# repo-wide gate (tier-1 acceptance)


class TestRepoGate:
    def test_selftest_engines_clean_under_committed_baseline(self, tmp_path):
        """Acceptance gate: every selftest entry (train/eval, pipeline x4,
        inference prefill/decode, serving prefill_chunk/decode) must produce
        a cost vector with a nonzero predicted-MFU ceiling, gate clean
        against the committed baseline, and surface in the report CLI's
        == cost == section."""
        jsonl = tmp_path / "cost_metrics.jsonl"
        proc = subprocess.run(
            [sys.executable, "-m", "tools.tpucost",
             "--config", "tools/tpuaudit/selftest_config.json",
             "--baseline", ".tpucost-baseline.json",
             "--metrics-jsonl", str(jsonl), "--format", "json"],
            cwd=REPO, capture_output=True, text=True, timeout=540,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, \
            f"tpucost gate failed:\n{proc.stdout}\n{proc.stderr}"
        out = json.loads(proc.stdout)
        entries = out["entries"]
        expected = {"train/step", "train/eval", "pipeline/loss_fn",
                    "pipeline/grad_fn", "pipeline/step", "pipeline/eval",
                    "inference/prefill", "inference/decode",
                    "serving/prefill_chunk", "serving/decode"}
        assert expected <= set(entries), sorted(entries)
        for name in expected:
            assert entries[name]["mfu_ceiling"] > 0, name
            assert entries[name]["metrics"]["flops"] > 0, name

        # the report CLI renders the dumped gauges as == cost ==
        rep = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.observability", "report",
             str(jsonl)],
            cwd=REPO, capture_output=True, text=True, timeout=120,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
        assert rep.returncode == 0, rep.stderr
        assert "== cost ==" in rep.stdout
        for name in expected:
            assert name in rep.stdout
