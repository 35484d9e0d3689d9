"""tpulint unit tests: per-rule positive/negative fixtures, suppressions,
baseline semantics, and the repo-wide gate (the linter run against
``deepspeed_tpu/`` with the committed baseline must be clean — this test is
what makes tier-1 enforce static analysis)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from tools.tpulint import analyze_source
from tools.tpulint import baseline as baseline_mod
from tools.tpulint.cli import main as tpulint_main
from tools.tpulint.core import RULES, Finding

REPO = Path(__file__).resolve().parents[2]


def rules_of(source, **kw):
    return sorted({f.rule for f in analyze_source(source, **kw)})


# ---------------------------------------------------------------------------
# rule fixtures


class TestHostSyncInJit:
    def test_positive_item_in_decorated_jit(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x.item()\n")
        assert "host-sync-in-jit" in rules_of(src)

    def test_positive_np_asarray_reachable_through_helper(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def helper(x):\n"
            "    return np.asarray(x)\n"
            "def step(x):\n"
            "    return helper(x)\n"
            "fast = jax.jit(step)\n")
        assert "host-sync-in-jit" in rules_of(src)

    def test_positive_float_cast(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return float(x)\n")
        assert "host-sync-in-jit" in rules_of(src)

    def test_negative_outside_jit(self):
        src = (
            "import numpy as np\n"
            "def log_metrics(x):\n"
            "    return float(np.asarray(x).mean()), x.item()\n")
        assert rules_of(src) == []

    def test_negative_jnp_inside_jit(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return jnp.asarray(x) + jnp.float32(1.0)\n")
        assert rules_of(src) == []


class TestImpureJit:
    def test_positive_print_time_random(self):
        src = (
            "import jax, time, random\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    print('hi')\n"
            "    t = time.time()\n"
            "    r = random.random()\n"
            "    return x\n")
        findings = [f for f in analyze_source(src) if f.rule == "impure-jit"]
        assert len(findings) == 3

    def test_positive_attribute_mutation(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(self, x):\n"
            "    self.cache = x\n"
            "    return x\n")
        assert "impure-jit" in rules_of(src)

    def test_positive_global(self):
        src = (
            "import jax\n"
            "N = 0\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    global N\n"
            "    N = 1\n"
            "    return x\n")
        assert "impure-jit" in rules_of(src)

    def test_negative_jax_debug_print(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    jax.debug.print('x={x}', x=x)\n"
            "    return x\n")
        assert rules_of(src) == []

    def test_negative_print_outside_jit(self):
        src = (
            "import time\n"
            "def report():\n"
            "    print(time.time())\n")
        assert rules_of(src) == []


class TestMissingDonation:
    def test_positive_decorator_form(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def step(params, batch):\n"
            "    return params\n")
        assert "missing-donation" in rules_of(src)

    def test_positive_call_wrapping_new_name(self):
        src = (
            "import jax\n"
            "def update(opt_state, grads):\n"
            "    new_opt_state = grads\n"
            "    return new_opt_state\n"
            "fast = jax.jit(update)\n")
        assert "missing-donation" in rules_of(src)

    def test_negative_with_donate_argnums(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, donate_argnums=(0,))\n"
            "def step(params, batch):\n"
            "    return params\n"
            "def update(opt_state, g):\n"
            "    return opt_state\n"
            "fast = jax.jit(update, donate_argnums=(0,))\n")
        assert rules_of(src) == []

    def test_negative_no_roundtrip(self):
        # takes params but returns a loss — nothing to donate
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "@jax.jit\n"
            "def eval_step(params, batch):\n"
            "    return jnp.sum(batch)\n")
        assert rules_of(src) == []


class TestUnknownMeshAxis:
    DECL = 'MODEL_AXIS = "model"\nDATA_AXIS = "data"\n'

    def test_positive_typo_in_partition_spec(self):
        src = (self.DECL +
               "from jax.sharding import PartitionSpec as P\n"
               "spec = P('modle', None)\n")
        assert "unknown-mesh-axis" in rules_of(src)

    def test_positive_collective_axis_kwarg(self):
        src = (self.DECL +
               "import jax\n"
               "def f(x):\n"
               "    return jax.lax.psum(x, axis_name='dataa')\n")
        assert "unknown-mesh-axis" in rules_of(src)

    def test_negative_declared_axes(self):
        # (hardcoded-partition-spec still fires on the literal axes — this
        # fixture only cares that the axes are KNOWN)
        src = (self.DECL +
               "from jax.sharding import PartitionSpec as P\n"
               "spec = P(('data',), 'model')\n")
        assert "unknown-mesh-axis" not in rules_of(src)

    def test_negative_without_any_declaration(self):
        # no mesh in the analyzed set -> nothing to validate against
        src = ("from jax.sharding import PartitionSpec as P\n"
               "spec = P('anything')\n")
        assert "unknown-mesh-axis" not in rules_of(src)


class TestHardcodedPartitionSpec:
    SRC = ('MODEL_AXIS = "model"\n'
           "from jax.sharding import PartitionSpec as P\n"
           "spec = P('model', None)\n")

    def test_positive_literal_axis(self):
        assert "hardcoded-partition-spec" in rules_of(self.SRC)

    def test_positive_tuple_axes(self):
        src = ("from jax.sharding import PartitionSpec as P\n"
               "spec = P(('expert', 'data'))\n")
        assert "hardcoded-partition-spec" in rules_of(src)

    def test_negative_axis_constant(self):
        # placement through the named constants stays allowed — only the
        # string literals bypass the registry
        src = ('MODEL_AXIS = "model"\n'
               "from jax.sharding import PartitionSpec as P\n"
               "spec = P(MODEL_AXIS)\n")
        assert "hardcoded-partition-spec" not in rules_of(src)

    def test_negative_empty_spec(self):
        src = ("from jax.sharding import PartitionSpec as P\n"
               "spec = P()\n")
        assert "hardcoded-partition-spec" not in rules_of(src)

    def test_negative_in_rule_registry(self):
        assert "hardcoded-partition-spec" not in rules_of(
            self.SRC, path="deepspeed_tpu/parallel/rules.py")

    def test_negative_in_tests(self):
        assert "hardcoded-partition-spec" not in rules_of(
            self.SRC, path="tests/unit/test_something.py")

    def test_inline_suppression(self):
        src = ("from jax.sharding import PartitionSpec as P\n"
               "spec = P('model')  # tpulint: disable=hardcoded-partition-spec\n")
        assert "hardcoded-partition-spec" not in rules_of(src)


class TestDeprecatedJaxApi:
    def test_positive_tree_map(self):
        src = ("import jax\n"
               "out = jax.tree_map(lambda v: v, {})\n")
        assert "deprecated-jax-api" in rules_of(src)

    def test_positive_pjit_import(self):
        src = "from jax.experimental.pjit import pjit\n"
        assert "deprecated-jax-api" in rules_of(src)

    def test_positive_maps_import(self):
        src = "import jax.experimental.maps\n"
        assert "deprecated-jax-api" in rules_of(src)

    def test_positive_experimental_shard_map_import(self):
        src = "from jax.experimental.shard_map import shard_map\n"
        assert "deprecated-jax-api" in rules_of(src)

    def test_positive_tpu_compiler_params(self):
        src = ("from jax.experimental.pallas import tpu as pltpu\n"
               "p = pltpu.TPUCompilerParams(dimension_semantics=())\n")
        assert "deprecated-jax-api" in rules_of(src)

    def test_negative_modern_apis(self):
        src = ("import jax\n"
               "from jax import shard_map\n"
               "from jax.experimental.pallas import tpu as pltpu\n"
               "out = jax.tree.map(lambda v: v, {})\n"
               "out2 = jax.tree_util.tree_map(lambda v: v, {})\n"
               "p = pltpu.CompilerParams(dimension_semantics=())\n")
        assert rules_of(src) == []


class TestKeyReuse:
    def test_positive_reuse(self):
        src = (
            "import jax\n"
            "def f():\n"
            "    key = jax.random.PRNGKey(0)\n"
            "    a = jax.random.normal(key, (2,))\n"
            "    b = jax.random.uniform(key, (2,))\n"
            "    return a + b\n")
        assert "key-reuse" in rules_of(src)

    def test_negative_split(self):
        src = (
            "import jax\n"
            "def f():\n"
            "    key = jax.random.PRNGKey(0)\n"
            "    k1, k2 = jax.random.split(key)\n"
            "    a = jax.random.normal(k1, (2,))\n"
            "    b = jax.random.uniform(k2, (2,))\n"
            "    return a + b\n")
        assert rules_of(src) == []

    def test_negative_rebound_key(self):
        src = (
            "import jax\n"
            "def f():\n"
            "    key = jax.random.PRNGKey(0)\n"
            "    a = jax.random.normal(key, (2,))\n"
            "    key = jax.random.PRNGKey(1)\n"
            "    b = jax.random.normal(key, (2,))\n"
            "    return a + b\n")
        assert rules_of(src) == []


class TestWallclockTimingWithoutSync:
    RULE = "wallclock-timing-without-sync"

    def test_positive_unfenced_delta(self):
        src = (
            "import time\n"
            "import jax\n"
            "def bench(step, batch):\n"
            "    t0 = time.perf_counter()\n"
            "    for _ in range(10):\n"
            "        loss = step(batch)\n"
            "    dt = time.perf_counter() - t0\n"
            "    return dt\n")
        assert self.RULE in rules_of(src)

    def test_positive_delta_nested_in_append(self):
        src = (
            "import time\n"
            "import jax\n"
            "def bench(step, batch, out):\n"
            "    t0 = time.perf_counter()\n"
            "    step(batch)\n"
            "    out.append(time.perf_counter() - t0)\n")
        assert self.RULE in rules_of(src)

    def test_positive_work_after_last_fence(self):
        # one early fence does not bless work dispatched after it
        src = (
            "import time\n"
            "import jax\n"
            "def bench(step1, step2, batch):\n"
            "    t0 = time.perf_counter()\n"
            "    a = step1(batch)\n"
            "    jax.block_until_ready(a)\n"
            "    b = step2(batch)\n"
            "    return time.perf_counter() - t0\n")
        assert self.RULE in rules_of(src)

    def test_negative_block_until_ready_fence(self):
        src = (
            "import time\n"
            "import jax\n"
            "def bench(step, batch):\n"
            "    t0 = time.perf_counter()\n"
            "    loss = step(batch)\n"
            "    jax.block_until_ready(loss)\n"
            "    return time.perf_counter() - t0\n")
        assert self.RULE not in rules_of(src)

    def test_negative_float_materialisation_fence(self):
        src = (
            "import time\n"
            "import jax\n"
            "def bench(step, batch):\n"
            "    t0 = time.perf_counter()\n"
            "    loss = step(batch)\n"
            "    float(loss)\n"
            "    return time.perf_counter() - t0\n")
        assert self.RULE not in rules_of(src)

    def test_negative_local_helper_that_fences(self):
        src = (
            "import time\n"
            "import jax\n"
            "def bench(step, batch):\n"
            "    def run():\n"
            "        jax.block_until_ready(step(batch))\n"
            "    run()\n"
            "    t0 = time.perf_counter()\n"
            "    run()\n"
            "    return time.perf_counter() - t0\n")
        assert self.RULE not in rules_of(src)

    def test_negative_module_without_jax(self):
        src = (
            "import time\n"
            "def bench(parse, data):\n"
            "    t0 = time.perf_counter()\n"
            "    out = parse(data)\n"
            "    return time.perf_counter() - t0\n")
        assert self.RULE not in rules_of(src)

    def test_negative_no_calls_between(self):
        src = (
            "import time\n"
            "import jax\n"
            "def f():\n"
            "    t0 = time.perf_counter()\n"
            "    x = 1 + 2\n"
            "    return time.perf_counter() - t0\n")
        assert self.RULE not in rules_of(src)


# ---------------------------------------------------------------------------
# suppressions + baseline


class TestSuppression:
    SRC = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item(){comment}\n")

    def test_same_line(self):
        src = self.SRC.format(
            comment="  # tpulint: disable=host-sync-in-jit")
        assert rules_of(src) == []

    def test_previous_comment_line(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    # intentional scalar readback. tpulint: disable=host-sync-in-jit\n"
            "    return x.item()\n")
        assert rules_of(src) == []

    def test_wrong_rule_does_not_mask(self):
        src = self.SRC.format(comment="  # tpulint: disable=impure-jit")
        assert rules_of(src) == ["host-sync-in-jit"]

    def test_disable_all(self):
        src = self.SRC.format(comment="  # tpulint: disable=all")
        assert rules_of(src) == []


class TestBaseline:
    def _findings(self, n, path="a.py", rule="host-sync-in-jit"):
        return [Finding(rule, path, i + 1, 0, "m") for i in range(n)]

    def test_baselined_findings_masked(self, tmp_path):
        bl = tmp_path / "bl.json"
        baseline_mod.write(str(bl), self._findings(2))
        known = baseline_mod.load(str(bl))
        assert baseline_mod.new_findings(self._findings(2), known) == []

    def test_over_budget_fails(self, tmp_path):
        bl = tmp_path / "bl.json"
        baseline_mod.write(str(bl), self._findings(1))
        known = baseline_mod.load(str(bl))
        assert len(baseline_mod.new_findings(self._findings(2), known)) == 1

    def test_fixes_only_lower_counts_pass(self, tmp_path):
        bl = tmp_path / "bl.json"
        baseline_mod.write(str(bl), self._findings(3))
        known = baseline_mod.load(str(bl))
        assert baseline_mod.new_findings(self._findings(1), known) == []

    def test_cli_roundtrip(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\n@jax.jit\ndef f(x):\n    return x.item()\n")
        bl = tmp_path / "bl.json"
        assert tpulint_main([str(bad), "--root", str(tmp_path)]) == 1
        assert tpulint_main([str(bad), "--root", str(tmp_path),
                             "--baseline", str(bl), "--write-baseline"]) == 0
        assert tpulint_main([str(bad), "--root", str(tmp_path),
                             "--baseline", str(bl)]) == 0
        data = json.loads(bl.read_text())
        assert data["counts"] == {"bad.py::host-sync-in-jit": 1}

    def test_stale_baseline_entry_fails_the_gate(self, tmp_path, capsys):
        """Fixing a finding without regenerating the baseline leaves a stale
        budget that would silently re-admit regressions — the gate errors."""
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\n@jax.jit\ndef f(x):\n    return x.item()\n")
        bl = tmp_path / "bl.json"
        assert tpulint_main([str(bad), "--root", str(tmp_path),
                             "--baseline", str(bl), "--write-baseline"]) == 0
        bad.write_text("import jax\n@jax.jit\ndef f(x):\n    return x\n")
        capsys.readouterr()
        assert tpulint_main([str(bad), "--root", str(tmp_path),
                             "--baseline", str(bl)]) == 1
        assert "stale baseline entry" in capsys.readouterr().out

    def test_prune_baseline_drops_stale_keys(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\n@jax.jit\ndef f(x):\n    return x.item()\n")
        bl = tmp_path / "bl.json"
        assert tpulint_main([str(bad), "--root", str(tmp_path),
                             "--baseline", str(bl), "--write-baseline"]) == 0
        bad.write_text("import jax\n@jax.jit\ndef f(x):\n    return x\n")
        assert tpulint_main([str(bad), "--root", str(tmp_path),
                             "--baseline", str(bl), "--prune-baseline"]) == 0
        assert json.loads(bl.read_text())["counts"] == {}
        assert tpulint_main([str(bad), "--root", str(tmp_path),
                             "--baseline", str(bl)]) == 0

    def test_deleted_file_under_analyzed_dir_is_stale(self, tmp_path, capsys):
        """Deleting a file is the most common source of baseline rot — its
        keys are in scope when the run covers the enclosing directory."""
        bad = tmp_path / "pkg" / "bad.py"
        bad.parent.mkdir()
        bad.write_text("import jax\n@jax.jit\ndef f(x):\n    return x.item()\n")
        bl = tmp_path / "bl.json"
        assert tpulint_main([str(tmp_path / "pkg"), "--root", str(tmp_path),
                             "--baseline", str(bl), "--write-baseline"]) == 0
        bad.unlink()
        capsys.readouterr()
        assert tpulint_main([str(tmp_path / "pkg"), "--root", str(tmp_path),
                             "--baseline", str(bl)]) == 1
        assert "stale baseline entry" in capsys.readouterr().out
        assert tpulint_main([str(tmp_path / "pkg"), "--root", str(tmp_path),
                             "--baseline", str(bl), "--prune-baseline"]) == 0
        assert json.loads(bl.read_text())["counts"] == {}

    def test_prune_missing_baseline_is_an_error(self, tmp_path, capsys):
        a = tmp_path / "a.py"
        a.write_text("x = 1\n")
        assert tpulint_main([str(a), "--root", str(tmp_path),
                             "--baseline", str(tmp_path / "nope.json"),
                             "--prune-baseline"]) == 2

    def test_partial_run_does_not_condemn_out_of_scope_keys(self, tmp_path,
                                                            capsys):
        """Linting one file with a baseline that also budgets another file
        must not flag the other file's keys as stale."""
        a = tmp_path / "a.py"
        b = tmp_path / "b.py"
        src = "import jax\n@jax.jit\ndef f(x):\n    return x.item()\n"
        a.write_text(src)
        b.write_text(src)
        bl = tmp_path / "bl.json"
        assert tpulint_main([str(a), str(b), "--root", str(tmp_path),
                             "--baseline", str(bl), "--write-baseline"]) == 0
        assert tpulint_main([str(a), "--root", str(tmp_path),
                             "--baseline", str(bl)]) == 0


# ---------------------------------------------------------------------------
# CLI surface


class TestCli:
    def test_json_format(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\nout = jax.tree_map(lambda v: v, {})\n")
        rc = tpulint_main([str(bad), "--root", str(tmp_path),
                           "--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["new_findings"] == 1
        assert out["findings"][0]["rule"] == "deprecated-jax-api"

    def test_select_unknown_rule_errors(self, capsys):
        assert tpulint_main(["--select", "not-a-rule"]) == 2

    def test_list_rules_names_all_seven(self, capsys):
        assert tpulint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for name in ("host-sync-in-jit", "impure-jit", "missing-donation",
                     "unknown-mesh-axis", "deprecated-jax-api", "key-reuse",
                     "wallclock-timing-without-sync"):
            assert name in out
        assert len(RULES) >= 7


# ---------------------------------------------------------------------------
# repo-wide gate


class TestRepoGate:
    def test_source_tree_clean_under_baseline(self):
        """Acceptance gate: the committed tree + committed baseline lint
        clean. A new host sync / impurity / donation miss in deepspeed_tpu/
        fails this test (and therefore tier-1)."""
        proc = subprocess.run(
            [sys.executable, "-m", "tools.tpulint", "deepspeed_tpu/",
             "--baseline", ".tpulint-baseline.json"],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, \
            f"tpulint found new issues:\n{proc.stdout}\n{proc.stderr}"

    def test_lint_script_gate(self):
        """scripts/lint.sh (the CI entry point) must pass on the tree."""
        proc = subprocess.run(
            ["bash", "scripts/lint.sh"],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, \
            f"scripts/lint.sh failed:\n{proc.stdout}\n{proc.stderr}"

    def test_every_scanned_path_exists(self):
        """Both gates skip a path that is not there without a word, so a
        deleted or renamed file would leave its scan behind as a no-op:
        what ``scripts/lint.sh`` hands tpulint and what ``metricsdoc`` scans
        by default must be in the tree."""
        import re

        from tools.tpulint.metricsdoc import DEFAULT_PATHS

        script = (REPO / "scripts" / "lint.sh").read_text()
        call = re.search(r"python -m tools\.tpulint \\\n(.*?)--baseline",
                         script, re.S).group(1)
        scanned = call.replace("\\\n", " ").split()
        assert scanned, "no path parsed out of scripts/lint.sh"
        for path in [*scanned, *DEFAULT_PATHS]:
            assert (REPO / path).exists(), path

    def test_seeded_violation_detected(self, tmp_path):
        """A seeded .item() inside a jitted fn must be flagged as NEW even
        with the committed baseline in effect."""
        bad = tmp_path / "seeded.py"
        bad.write_text(
            "import jax\n"
            "@jax.jit\n"
            "def train_step(params, batch):\n"
            "    loss = batch.item()\n"
            "    return params\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.tpulint", str(bad),
             "--baseline", ".tpulint-baseline.json", "--root", str(tmp_path)],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 1
        assert "host-sync-in-jit" in proc.stdout
