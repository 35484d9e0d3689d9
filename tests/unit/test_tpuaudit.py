"""tpuaudit unit tests: per-check positive/negative program fixtures,
registry + baseline semantics (incl. stale-entry rot), engine entry-point
registration across the three layers, and the repo-wide gate (the selftest
engines audited against the committed baseline — what makes tier-1 enforce
program-level analysis)."""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tools.tpuaudit import (clear_registry, get_entry_points,
                            register_entry_point, run_audit)
from tools.tpuaudit import baseline as baseline_mod
from tools.tpuaudit.checks import CHECKS
from tools.tpuaudit.cli import main as tpuaudit_main
from tools.tpuaudit.core import Finding, build_program, collect_collectives

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def _clean_registry():
    clear_registry()
    yield
    clear_registry()


def sds(shape, dtype=jnp.float32, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def audit_one(name="fixture", options=None, **kw):
    ep = register_entry_point(name, **kw)
    return run_audit([ep], options=options, publish_metrics=False)


def checks_of(findings):
    return sorted({f.check for f in findings})


def mesh2x4():
    devs = np.array(jax.devices()).reshape(2, 4)
    return Mesh(devs, ("data", "model"))


# ---------------------------------------------------------------------------
# check fixtures — a program that must trigger, and a clean twin


class TestUnexpectedCollective:
    def _reshard_fixture(self, expected):
        mesh = mesh2x4()

        def f(w, x):
            y = x @ w
            y = jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P(None, "model")))
            return y.sum()

        return audit_one(
            fn=jax.jit(f),
            args=(sds((256, 256), sharding=NamedSharding(mesh, P("model", None))),
                  sds((64, 256), sharding=NamedSharding(mesh, P("data", None)))),
            expected_collectives=expected)

    def test_positive_gspmd_inserted_all_gather(self):
        findings = self._reshard_fixture(frozenset())
        assert "unexpected-collective" in checks_of(findings)
        assert any("all-gather" in f.message for f in findings)

    def test_negative_declared_collectives(self):
        findings = self._reshard_fixture(
            frozenset({"all-gather", "all-reduce", "all-to-all",
                       "collective-permute"}))
        assert findings == []

    def test_explicit_shard_map_collective_without_compile(self):
        """shard_map collectives appear in the lowered StableHLO, so the
        census works even with compile=False."""
        from jax import shard_map

        mesh = mesh2x4()
        body = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P(),
                         check_vma=False, axis_names={"data"})
        findings = audit_one(fn=jax.jit(body), args=(sds((8,)),),
                             expected_collectives=frozenset(), compile=False)
        assert checks_of(findings) == ["unexpected-collective"]
        assert "all-reduce" in findings[0].message

    def test_none_disables_the_check(self):
        findings = self._reshard_fixture(None)
        assert findings == []


class TestDonation:
    def _state_fn(self, donate):
        def step(state, batch):
            return jax.tree.map(lambda a: a + 1.0, state), batch.sum()

        return dict(fn=jax.jit(step, donate_argnums=donate),
                    args=({"w": sds((600, 600))}, sds((4,))),
                    donate_argnums=donate, expected_collectives=frozenset())

    def test_positive_missed_donation(self):
        findings = audit_one(**self._state_fn(()))
        assert checks_of(findings) == ["missed-donation"]

    def test_negative_donated_state(self):
        assert audit_one(**self._state_fn((0,))) == []

    def test_threshold_hides_small_misses(self):
        def f(s, b):
            return s + 1.0, b.sum()

        findings = audit_one(fn=jax.jit(f), args=(sds((4,)), sds((4,))),
                             expected_collectives=frozenset())
        assert findings == []          # 16 bytes, far under the MiB default

    def test_positive_dead_donation(self):
        def f(x, dead):
            return x + 1.0

        findings = audit_one(
            fn=jax.jit(f, donate_argnums=(1,)),
            args=(sds((4,)), sds((600, 600), jnp.int32)),
            donate_argnums=(1,), expected_collectives=frozenset())
        assert checks_of(findings) == ["dead-donation"]
        assert "argument 1" in findings[0].message

    def test_negative_partial_alias_is_live(self):
        def f(state):
            return {"a": state["a"] * 2.0}

        findings = audit_one(
            fn=jax.jit(f, donate_argnums=(0,)),
            args=({"a": sds((8,)), "b": sds((3,), jnp.int32)},),
            donate_argnums=(0,), expected_collectives=frozenset())
        assert "dead-donation" not in checks_of(findings)

    def test_suppression_at_registration(self):
        spec = self._state_fn(())
        spec["suppress"] = frozenset({"missed-donation"})
        assert audit_one(**spec) == []


class TestHostCallback:
    def test_positive_debug_print(self):
        def f(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        findings = audit_one(fn=jax.jit(f), args=(sds((4,)),),
                             expected_collectives=frozenset())
        assert checks_of(findings) == ["host-callback-in-program"]
        assert "debug_print" in findings[0].message

    def test_positive_pure_callback_in_scan(self):
        def f(x):
            def body(c, _):
                y = jax.pure_callback(
                    lambda a: a, jax.ShapeDtypeStruct((4,), jnp.float32), c)
                return y, None

            out, _ = jax.lax.scan(body, x, None, length=3)
            return out

        findings = audit_one(fn=jax.jit(f), args=(sds((4,)),),
                             expected_collectives=frozenset())
        assert "pure_callback" in " ".join(f.message for f in findings)

    def test_negative_pure_program(self):
        findings = audit_one(fn=jax.jit(lambda x: jnp.sin(x).sum()),
                             args=(sds((4,)),),
                             expected_collectives=frozenset())
        assert findings == []


class TestWeakTypeCapture:
    def test_positive_python_float_arg(self):
        findings = audit_one(fn=jax.jit(lambda x, s: x * s),
                             args=(sds((4,)), 0.1),
                             expected_collectives=frozenset())
        assert checks_of(findings) == ["weak-type-capture"]
        assert "arg1" in findings[0].message

    def test_negative_array_scalar(self):
        findings = audit_one(fn=jax.jit(lambda x, s: x * s),
                             args=(sds((4,)), sds((), jnp.float32)),
                             expected_collectives=frozenset())
        assert findings == []


class TestImplicitPromotion:
    def test_positive_f64_program(self):
        from jax import enable_x64

        def build():
            return jax.jit(lambda x: x * 2.0), (sds((4,), jnp.float64),), {}

        ep = register_entry_point("fix/x64", build=build,
                                  expected_collectives=frozenset())
        with enable_x64():
            findings = run_audit([ep], publish_metrics=False)
        assert "implicit-promotion" in checks_of(findings)

    def test_negative_f32_program(self):
        findings = audit_one(fn=jax.jit(lambda x: x * 2.0),
                             args=(sds((4,)),),
                             expected_collectives=frozenset())
        assert findings == []


class TestBakedConstant:
    def test_positive_closure_capture(self):
        big = np.ones((600, 600), np.float32)     # 1.4 MiB

        def f(x):
            return x + jnp.asarray(big).sum()

        findings = audit_one(fn=jax.jit(f), args=(sds((4,)),),
                             expected_collectives=frozenset())
        assert checks_of(findings) == ["baked-constant"]

    def test_negative_passed_as_argument(self):
        findings = audit_one(fn=jax.jit(lambda x, t: x + t.sum()),
                             args=(sds((4,)), sds((600, 600))),
                             expected_collectives=frozenset())
        assert findings == []

    def test_threshold_option(self):
        small = np.ones((64,), np.float32)

        def f(x):
            return x + jnp.asarray(small).sum()

        findings = audit_one(fn=jax.jit(f), args=(sds((4,)),),
                             expected_collectives=frozenset(),
                             options={"max_const_bytes": 16})
        assert checks_of(findings) == ["baked-constant"]


class TestCollectiveCensus:
    def test_explicit_collective_not_double_counted(self):
        """An explicit shard_map collective appears in BOTH the lowered and
        the compiled text; the census must report it once, not twice."""
        from jax import shard_map

        mesh = mesh2x4()
        body = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P(),
                         check_vma=False, axis_names={"data"})
        ep = register_entry_point("fix/census", fn=jax.jit(body),
                                  args=(sds((8,)),),
                                  expected_collectives=frozenset())
        program = build_program(ep)
        found = collect_collectives(program.stablehlo, program.compiled_hlo)
        assert found.get("all-reduce") == 1


class TestStaleEngine:
    def test_dead_engine_entry_is_skipped(self):
        """Registration holds only a weakref; once the engine is collected
        the entry audits to nothing instead of erroring or pinning it."""
        import gc

        import deepspeed_tpu
        from deepspeed_tpu.models import simple_model

        cfg = {"train_micro_batch_size_per_gpu": 2,
               "steps_per_print": 10 ** 9,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
        engine, *_ = deepspeed_tpu.initialize(model=simple_model(hidden_dim=10),
                                              config=cfg)
        gb = engine.train_batch_size() // engine.gradient_accumulation_steps()
        engine.register_audit_entries({"x": np.zeros((gb, 10), np.float32),
                                       "y": np.zeros((gb, 1), np.float32)})
        del engine
        gc.collect()
        findings = run_audit(get_entry_points(["train/step", "train/eval"]),
                             publish_metrics=False)
        assert findings == []


class TestTraceError:
    def test_broken_entry_reports_not_raises(self):
        def build():
            raise RuntimeError("boom")

        ep = register_entry_point("fix/broken", build=build)
        findings = run_audit([ep], publish_metrics=False)
        assert checks_of(findings) == ["trace-error"]
        assert "boom" in findings[0].message


# ---------------------------------------------------------------------------
# registry + baseline


class TestRegistry:
    def test_replace_by_name_latest_wins(self):
        register_entry_point("a", fn=jax.jit(lambda x: x), args=(sds((2,)),))
        register_entry_point("a", fn=jax.jit(lambda x: x * 2),
                             args=(sds((3,)),))
        eps = get_entry_points(["a"])
        assert len(eps) == 1 and eps[0].build()[1][0].shape == (3,)

    def test_unknown_entry_raises(self):
        with pytest.raises(KeyError):
            get_entry_points(["nope"])

    def test_unknown_collective_kind_rejected(self):
        with pytest.raises(ValueError):
            register_entry_point("a", fn=jax.jit(lambda x: x),
                                 args=(sds((2,)),),
                                 expected_collectives=frozenset({"all-hands"}))


class TestBaseline:
    def _findings(self, n, entry="train/step", check="missed-donation"):
        return [Finding(check, entry, f"m{i}") for i in range(n)]

    def test_roundtrip_masks_budgeted(self, tmp_path):
        bl = tmp_path / "bl.json"
        baseline_mod.write(str(bl), self._findings(2))
        known = baseline_mod.load(str(bl))
        assert baseline_mod.new_findings(self._findings(2), known) == []
        assert len(baseline_mod.new_findings(self._findings(3), known)) == 1

    def test_stale_keys_detected(self, tmp_path):
        known = {"train/step::missed-donation": 2}
        assert baseline_mod.stale_keys([], known) == \
            ["train/step::missed-donation"]
        assert baseline_mod.stale_keys(self._findings(1), known) == []

    def test_stale_scoping(self):
        known = {"other/entry::missed-donation": 1}
        in_scope = lambda k: k.startswith("train/")
        assert baseline_mod.stale_keys([], known, in_scope=in_scope) == []

    def test_pruned_drops_and_clamps(self):
        known = {"a::c": 5, "b::c": 2}
        out = baseline_mod.pruned(self._findings(1, entry="a", check="c"),
                                  known)
        assert out == {"a::c": 1}


# ---------------------------------------------------------------------------
# engine entry points on the CPU mesh


class TestTrainEngineEntries:
    def _engine(self, extra=None):
        import deepspeed_tpu
        from deepspeed_tpu.models import simple_model

        cfg = {"train_micro_batch_size_per_gpu": 2,
               "steps_per_print": 10 ** 9,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
        cfg.update(extra or {})
        engine, *_ = deepspeed_tpu.initialize(model=simple_model(hidden_dim=10),
                                              config=cfg)
        return engine

    def _micro(self, engine):
        gb = engine.train_batch_size() // engine.gradient_accumulation_steps()
        return {"x": np.zeros((gb, 10), np.float32),
                "y": np.zeros((gb, 1), np.float32)}

    def test_register_and_audit_clean(self):
        engine = self._engine({"zero_optimization": {"stage": 3}})
        names = engine.register_audit_entries(self._micro(engine))
        assert names == ["train/step", "train/eval"]
        assert run_audit(get_entry_points(names),
                         publish_metrics=False) == []

    def test_zero3_step_declares_its_collectives(self):
        engine = self._engine({"zero_optimization": {"stage": 3}})
        engine.register_audit_entries(self._micro(engine))
        ep = get_entry_points(["train/step"])[0]
        program = build_program(ep)
        found = collect_collectives(program.stablehlo, program.compiled_hlo)
        assert set(found) <= set(ep.expected_collectives)
        if engine.mesh.size > 1:      # 8 virtual devices in this suite
            assert found, "expected SPMD collectives on a multi-device mesh"

    def test_train_batch_autoregisters(self):
        engine = self._engine()
        micro = self._micro(engine)
        batch = {k: jnp.asarray(v)[None] for k, v in micro.items()}
        engine.train_batch(batch=batch)
        assert "train/step" in {e.name for e in get_entry_points()}

    def test_step_entry_donates_train_state(self):
        engine = self._engine()
        engine.register_audit_entries(self._micro(engine))
        ep = get_entry_points(["train/step"])[0]
        assert ep.donate_argnums == (0, 1)

    def test_onebit_step_declares_compressed_exchange(self):
        engine = self._engine({"optimizer": {
            "type": "onebitadam", "params": {"lr": 1e-3, "freeze_step": 2}}})
        names = engine.register_audit_entries(self._micro(engine))
        ep = get_entry_points(["train/step"])[0]
        assert {"all-to-all", "all-gather"} <= set(ep.expected_collectives)
        assert run_audit(get_entry_points(names),
                         publish_metrics=False) == []


class TestPipelineEntries:
    @pytest.fixture()
    def engine(self, devices8):
        import deepspeed_tpu
        from deepspeed_tpu.models import create_model

        cfg = {"train_micro_batch_size_per_gpu": 1,
               "gradient_accumulation_steps": 2,
               "steps_per_print": 10 ** 9,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
               "parallel": {"pipeline_parallel_size": 2}}
        engine, *_ = deepspeed_tpu.initialize(
            model=create_model("tiny", dtype=jnp.float32, max_seq_len=32),
            config=cfg)
        return engine

    def test_pipelinize_registers_stage_fns(self, engine):
        names = {e.name for e in get_entry_points()}
        assert {"pipeline/loss_fn", "pipeline/grad_fn"} <= names

    def test_stage_fns_audit_clean(self, engine):
        eps = get_entry_points(["pipeline/loss_fn", "pipeline/grad_fn"])
        assert run_audit(eps, publish_metrics=False) == []

    def test_stage_program_contains_the_ring_permute(self, engine):
        ep = get_entry_points(["pipeline/grad_fn"])[0]
        program = build_program(ep)
        found = collect_collectives(program.stablehlo, program.compiled_hlo)
        assert "collective-permute" in found

    def test_undeclared_permute_fails(self, engine):
        ep = get_entry_points(["pipeline/loss_fn"])[0]
        ep.expected_collectives = frozenset({"all-reduce", "all-gather"})
        findings = run_audit([ep], publish_metrics=False)
        assert checks_of(findings) == ["unexpected-collective"]
        assert "collective-permute" in findings[0].message


class TestInferenceEntries:
    def test_register_and_audit_clean(self):
        from deepspeed_tpu.inference import init_inference

        engine = init_inference(model="tiny", max_out_tokens=128)
        names = engine.register_audit_entries(batch_size=1, prompt_len=16,
                                              max_new_tokens=4)
        assert names == ["inference/prefill", "inference/decode"]
        assert run_audit(get_entry_points(names),
                         publish_metrics=False) == []

    def test_prefill_donates_the_kv_arena(self):
        from deepspeed_tpu.inference import init_inference

        engine = init_inference(model="tiny", max_out_tokens=128)
        engine.register_audit_entries(batch_size=1, prompt_len=16)
        ep = get_entry_points(["inference/prefill"])[0]
        assert ep.donate_argnums == (3,)
        program = build_program(ep)
        assert any(program.donated), "cache leaves should be donated"


class TestMetricsPublication:
    def test_findings_land_in_registry(self):
        from deepspeed_tpu.observability import get_registry

        def f(x):
            jax.debug.print("{x}", x=x)
            return x

        ep = register_entry_point("pub/test", fn=jax.jit(f), args=(sds((2,)),),
                                  expected_collectives=frozenset())
        before = get_registry().counter("tpuaudit/findings").value(
            entry="pub/test", check="host-callback-in-program")
        run_audit([ep])
        after = get_registry().counter("tpuaudit/findings").value(
            entry="pub/test", check="host-callback-in-program")
        assert after == before + 1


# ---------------------------------------------------------------------------
# CLI surface + repo-wide gate


class TestCli:
    def _register_bad_entry(self):
        mesh = mesh2x4()

        def f(w, x):
            return jax.lax.with_sharding_constraint(
                x @ w, NamedSharding(mesh, P(None, "model"))).sum()

        register_entry_point(
            "fix/reshard", fn=jax.jit(f),
            args=(sds((256, 256), sharding=NamedSharding(mesh, P("model", None))),
                  sds((64, 256), sharding=NamedSharding(mesh, P("data", None)))),
            expected_collectives=frozenset())

    def test_undeclared_all_gather_exits_nonzero(self, capsys):
        """Acceptance fixture: an entry whose program contains an undeclared
        all-gather must fail the gate."""
        self._register_bad_entry()
        rc = tpuaudit_main(["--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert any("all-gather" in f["message"] for f in out["findings"])

    def test_baselined_fixture_passes_then_goes_stale(self, tmp_path, capsys):
        self._register_bad_entry()
        bl = tmp_path / "bl.json"
        assert tpuaudit_main(["--baseline", str(bl),
                              "--write-baseline"]) == 0
        assert tpuaudit_main(["--baseline", str(bl)]) == 0
        capsys.readouterr()
        # "fix" the entry: re-register with the collectives declared
        clear_registry()
        self._register_bad_entry()
        get_entry_points(["fix/reshard"])[0].expected_collectives = frozenset(
            {"all-gather", "all-reduce", "all-to-all", "collective-permute"})
        rc = tpuaudit_main(["--baseline", str(bl)])
        assert rc == 1
        assert "stale baseline entry" in capsys.readouterr().out
        assert tpuaudit_main(["--baseline", str(bl),
                              "--prune-baseline"]) == 0
        assert tpuaudit_main(["--baseline", str(bl)]) == 0
        assert json.loads(bl.read_text())["counts"] == {}

    def test_list_checks_names_all(self, capsys):
        assert tpuaudit_main(["--list-checks"]) == 0
        out = capsys.readouterr().out
        for name in ("unexpected-collective", "missed-donation",
                     "dead-donation", "host-callback-in-program",
                     "weak-type-capture", "implicit-promotion",
                     "baked-constant"):
            assert name in out
        assert len(CHECKS) >= 7

    def test_select_unknown_check_errors(self):
        assert tpuaudit_main(["--select", "not-a-check"]) == 2

    def test_no_entries_errors(self):
        assert tpuaudit_main([]) == 2


class TestRepoGate:
    def test_selftest_engines_clean_under_baseline(self):
        """Acceptance gate: the selftest config builds train (ZeRO-3, 8
        virtual devices), pipeline-parallel and inference engines; their
        registered entry points must audit clean against the committed
        baseline. An undeclared collective / donation miss / host callback
        introduced in any engine layer fails this test (and tier-1)."""
        proc = subprocess.run(
            [sys.executable, "-m", "tools.tpuaudit",
             "--config", "tools/tpuaudit/selftest_config.json",
             "--baseline", ".tpuaudit-baseline.json", "--devices", "8"],
            cwd=REPO, capture_output=True, text=True, timeout=540,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
        assert proc.returncode == 0, \
            f"tpuaudit found new issues:\n{proc.stdout}\n{proc.stderr}"
