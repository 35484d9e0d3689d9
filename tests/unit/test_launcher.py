"""Launcher tests — mirror of reference tests/unit/launcher/
(test_ds_arguments.py, test_multinode_runner.py: generated-command
assertions, no cluster needed) plus a real 2-process local smoke test
(the DistributedExec pattern driven through the actual CLI)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from deepspeed_tpu.launcher.launch import (build_rank_env, decode_world_info,
                                           encode_world_info)
from deepspeed_tpu.launcher.multinode import PDSHRunner, SSHRunner
from deepspeed_tpu.launcher.runner import (build_node_cmd, fetch_hostfile,
                                           filter_hosts, parse_args)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class TestHostfile:
    def test_parse(self, tmp_path):
        hf = tmp_path / "hostfile"
        hf.write_text("worker-0 slots=4\nworker-1 slots=4\n# comment\n\n")
        assert fetch_hostfile(str(hf)) == {"worker-0": 4, "worker-1": 4}

    def test_duplicate_host_rejected(self, tmp_path):
        hf = tmp_path / "hostfile"
        hf.write_text("w0 slots=2\nw0 slots=2\n")
        with pytest.raises(ValueError, match="duplicate"):
            fetch_hostfile(str(hf))

    def test_localhost_fallback(self):
        env = os.environ.pop("TPU_WORKER_HOSTNAMES", None)
        try:
            assert fetch_hostfile(None) == {"localhost": 1}
        finally:
            if env is not None:
                os.environ["TPU_WORKER_HOSTNAMES"] = env

    def test_tpu_pod_env(self):
        os.environ["TPU_WORKER_HOSTNAMES"] = "t0,t1,t2,t3"
        try:
            assert fetch_hostfile(None) == {"t0": 1, "t1": 1, "t2": 1, "t3": 1}
        finally:
            del os.environ["TPU_WORKER_HOSTNAMES"]

    def test_filters(self):
        hosts = {"a": 1, "b": 1, "c": 1}
        assert filter_hosts(hosts, "a,b", None, -1) == {"a": 1, "b": 1}
        assert filter_hosts(hosts, None, "b", -1) == {"a": 1, "c": 1}
        assert filter_hosts(hosts, None, None, 2) == {"a": 1, "b": 1}
        with pytest.raises(ValueError):
            filter_hosts(hosts, "zzz", None, -1)


class TestWorldInfo:
    def test_roundtrip(self):
        wi = {"worker-0": 2, "worker-1": 2}
        assert decode_world_info(encode_world_info(wi)) == wi

    def test_rank_assignment(self):
        wi = {"w0": 2, "w1": 3}
        envs = build_rank_env(wi, "w1", "10.0.0.1", 29500)
        assert [e["RANK"] for e in envs] == ["2", "3", "4"]
        assert all(e["WORLD_SIZE"] == "5" for e in envs)
        assert all(e["MASTER_ADDR"] == "10.0.0.1" for e in envs)
        assert [e["LOCAL_RANK"] for e in envs] == ["0", "1", "2"]

    def test_unknown_node_rejected(self):
        with pytest.raises(ValueError):
            build_rank_env({"w0": 1}, "nope", "addr", 1)


class TestMultinodeCommands:
    def _args(self):
        return parse_args(["--master_port", "29501", "train.py", "--flag"])

    def test_node_cmd(self):
        args = self._args()
        cmd = build_node_cmd(args, {"h0": 1, "h1": 1}, "h0")
        assert cmd[1:3] == ["-m", "deepspeed_tpu.launcher.launch"]
        assert "--world_info" in cmd
        i = cmd.index("--world_info")
        assert decode_world_info(cmd[i + 1]) == {"h0": 1, "h1": 1}
        assert cmd[-2:] == ["train.py", "--flag"]

    def test_pdsh_cmd(self):
        runner = PDSHRunner(exports={"PYTHONPATH": "/x"})
        cmds = runner.get_cmd(["h0", "h1"],
                              {h: ["python", "-m", "mod"] for h in ["h0", "h1"]})
        assert len(cmds) == 1
        cmd = cmds[0]
        assert cmd[0] == "pdsh"
        assert cmd[cmd.index("-w") + 1] == "h0,h1"
        assert "export PYTHONPATH=/x;" in cmd[-1]
        assert "export DSTPU_NODE_NAME=%h;" in cmd[-1]

    def test_openmpi_cmd(self):
        from deepspeed_tpu.launcher.multinode import OpenMPIRunner

        runner = OpenMPIRunner(exports={"PYTHONPATH": "/x"})
        cmds = runner.get_cmd(["h0", "h1"],
                              {h: ["python", "-m", "mod"] for h in ["h0", "h1"]})
        assert len(cmds) == 1
        cmd = cmds[0]
        assert cmd[:5] == ["mpirun", "-n", "2", "-npernode", "1"]
        assert cmd[cmd.index("-host") + 1] == "h0,h1"
        assert "PYTHONPATH=/x" in cmd[cmd.index("-x") + 1:]
        assert cmd[-3:-1] == ["bash", "-c"]
        assert "DSTPU_NODE_NAME=$(hostname)" in cmd[-1]

    def test_mpich_cmd(self):
        from deepspeed_tpu.launcher.multinode import MPICHRunner

        cmds = MPICHRunner(exports={"A": "1"}).get_cmd(
            ["h0"], {"h0": ["python", "x.py"]})
        cmd = cmds[0]
        assert cmd[:5] == ["mpirun", "-n", "1", "-ppn", "1"]
        i = cmd.index("-genv")
        assert cmd[i + 1:i + 3] == ["A", "1"]

    def test_slurm_cmd(self):
        from deepspeed_tpu.launcher.multinode import SlurmRunner

        cmds = SlurmRunner(exports={"A": "1"}).get_cmd(
            ["h0", "h1"], {h: ["python", "x.py"] for h in ["h0", "h1"]})
        cmd = cmds[0]
        assert cmd[:3] == ["srun", "-n", "2"]
        assert cmd[cmd.index("--nodelist") + 1] == "h0,h1"
        assert any(a.startswith("--export=ALL,") and "A=1" in a for a in cmd)

    def test_get_runner_names(self):
        from deepspeed_tpu.launcher.multinode import get_runner

        for name in ("pdsh", "ssh", "openmpi", "mpich", "slurm"):
            assert get_runner(name).name == name
        import pytest as _pytest
        with _pytest.raises(ValueError, match="unknown launcher"):
            get_runner("mvapich2")

    def test_ssh_cmd(self):
        runner = SSHRunner()
        cmds = runner.get_cmd(["h0", "h1"],
                              {h: ["python", "-m", "mod"] for h in ["h0", "h1"]})
        assert len(cmds) == 2
        assert cmds[0][0] == "ssh" and cmds[0][-2] == "h0"
        assert "export DSTPU_NODE_NAME=h0;" in cmds[0][-1]


@pytest.mark.slow
def test_local_two_process_smoke(tmp_path):
    """End-to-end: the CLI spawns 2 local processes x 4 virtual CPU devices
    that rendezvous via jax.distributed and psum across the 8-device global
    mesh (reference DistributedExec, driven through the real launcher)."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        sys.path.insert(0, %r)
        import jax
        jax.config.update("jax_platforms", "cpu")
        from deepspeed_tpu import comm
        comm.init_distributed()
        assert jax.process_count() == 2, jax.process_count()
        assert len(jax.devices()) == 8, len(jax.devices())
        import numpy as np
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        ones = jax.jit(
            lambda: jax.lax.with_sharding_constraint(
                jnp.ones((8,)), NamedSharding(mesh, P("data"))).sum())()
        assert float(ones) == 8.0
        print(f"SMOKE-OK rank={jax.process_index()}", flush=True)
    """ % REPO))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "deepspeed-tpu"),
         "--num_procs", "2", "--cpu_devices_per_proc", "4",
         "--master_port", "29517", str(script)],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.count("SMOKE-OK") == 2, out.stdout + out.stderr


def test_ds_report_cli():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds-tpu-report")],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr
    assert "flash_attention" in out.stdout
    assert "jax version" in out.stdout


class TestElasticAgent:
    """Reference elasticity/elastic_agent.py:28 semantics: worker failure →
    group restart with re-rendezvous, up to max_restarts; resume from the
    latest checkpoint; membership shrink recomputes the elastic micro
    batch."""

    @pytest.mark.slow
    def test_kill_worker_restarts_and_resumes(self, tmp_path):
        from deepspeed_tpu.launcher.elastic_agent import (ElasticAgent,
                                                          ElasticAgentConfig)

        log = tmp_path / "steps.jsonl"
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""
            import json, os, sys
            sys.path.insert(0, %r)
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import jax.numpy as jnp
            import deepspeed_tpu as ds
            from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                          build_model)

            ckpt_root, log_path, total = sys.argv[1], sys.argv[2], int(sys.argv[3])
            rank = os.environ["RANK"]
            restart = int(os.environ["DSTPU_RESTART_COUNT"])
            ckpt = os.path.join(ckpt_root, f"rank{rank}")
            model = build_model(TransformerConfig(
                vocab_size=64, hidden_size=16, num_layers=2, num_heads=2,
                max_seq_len=16))
            engine, *_ = ds.initialize(model=model, config={
                "train_micro_batch_size_per_gpu": 2, "steps_per_print": 1000,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
            engine.load_checkpoint(ckpt)          # no-op on first run
            start = engine.global_steps
            rng = np.random.default_rng(0)
            for step in range(start, total):
                loss = float(engine.train_batch(
                    batch={"input_ids": rng.integers(0, 64, (1, 2, 16))}))
                engine.save_checkpoint(ckpt)
                with open(log_path, "a") as f:
                    f.write(json.dumps({"rank": rank, "restart": restart,
                                        "step": step}) + chr(10))
                if step == 2 and restart == 0 and rank == "0":
                    os._exit(17)                  # simulated worker death
            print("WORKER-DONE", rank, flush=True)
        """ % REPO))
        agent = ElasticAgent(
            [sys.executable, str(script), str(tmp_path / "ck"), str(log),
             "5"],
            nprocs=2,
            config=ElasticAgentConfig(max_restarts=2, master_port=29530),
            env_base={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                      "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
        rc = agent.run()
        assert rc == 0
        assert agent.restart_count == 1
        lines = [json.loads(l)
                 for l in log.read_text().splitlines()]
        r0 = [l for l in lines if l["rank"] == "0"]
        # incarnation 0 died after step 2; incarnation 1 RESUMED at step 3
        # (checkpoint restore), not step 0
        steps_by_restart = {}
        for l in r0:
            steps_by_restart.setdefault(l["restart"], []).append(l["step"])
        assert steps_by_restart[0] == [0, 1, 2]
        assert steps_by_restart[1][0] == 3, steps_by_restart
        assert steps_by_restart[1][-1] == 4

    @__import__('pytest').mark.slow
    def test_membership_shrink_recomputes_micro(self, tmp_path):
        from deepspeed_tpu.launcher.elastic_agent import (ElasticAgent,
                                                          ElasticAgentConfig)

        probe = tmp_path / "probe.py"
        # workers only survive at world size <= 2 — the agent must shrink
        # membership to the next VALID elastic world size and re-spawn with
        # the recomputed micro batch in the env
        probe.write_text(textwrap.dedent("""
            import json, os, sys
            with open(sys.argv[1], "a") as f:
                f.write(json.dumps({
                    "world": os.environ["WORLD_SIZE"],
                    "micro": os.environ.get("DSTPU_ELASTIC_MICRO"),
                    "port": os.environ["MASTER_PORT"]}) + chr(10))
            sys.exit(0 if int(os.environ["WORLD_SIZE"]) <= 2 else 1)
        """))
        log = tmp_path / "probe.jsonl"
        elastic = {"elasticity": {
            "enabled": True, "max_train_batch_size": 16,
            "micro_batch_sizes": [2, 4], "min_gpus": 1, "max_gpus": 8,
            "version": 0.1}}
        agent = ElasticAgent(
            [sys.executable, str(probe), str(log)], nprocs=4,
            config=ElasticAgentConfig(max_restarts=3, min_workers=1,
                                      master_port=29540,
                                      elastic_config=elastic))
        rc = agent.run()
        assert rc == 0
        assert agent._world == 2 and agent.restart_count == 1
        lines = [json.loads(l)
                 for l in log.read_text().splitlines()]
        # re-rendezvous: the port moved between incarnations
        assert lines[0]["port"] != lines[-1]["port"]
        assert lines[-1]["world"] == "2"
        assert lines[-1]["micro"] is not None

    def test_max_restarts_exhausted(self, tmp_path):
        from deepspeed_tpu.launcher.elastic_agent import (ElasticAgent,
                                                          ElasticAgentConfig,
                                                          WorkerGroupFailure)

        agent = ElasticAgent(
            [sys.executable, "-c", "import sys; sys.exit(3)"], nprocs=1,
            config=ElasticAgentConfig(max_restarts=1, master_port=29550,
                                      backoff_base_s=0.01))
        with pytest.raises(WorkerGroupFailure, match="max_restarts"):
            agent.run()
        assert agent.restart_count == 1


class TestAgentRestartHardening:
    """PR-9 satellite: exponential backoff with jitter between respawns and
    the max-restarts-per-window circuit breaker (with a flight-recorder
    bundle naming the last failure on trip), plus the eviction-request
    control channel the fleet-health straggler policy drives."""

    def _agent(self, tmp_path, cmd=None, nprocs=1, clock=None, **cfg):
        from deepspeed_tpu.launcher.elastic_agent import (ElasticAgent,
                                                          ElasticAgentConfig)
        import random

        cfg.setdefault("master_port", 29555)
        cfg.setdefault("agent_dir", str(tmp_path / "agent"))
        sleeps = []
        agent = ElasticAgent(
            cmd or [sys.executable, "-c", "import sys; sys.exit(3)"],
            nprocs=nprocs, config=ElasticAgentConfig(**cfg),
            clock=clock or (lambda: 0.0),
            sleep_fn=sleeps.append, rng=random.Random(0))
        agent._test_sleeps = sleeps
        return agent

    def test_backoff_ladder_with_jitter(self, tmp_path):
        from deepspeed_tpu.launcher.elastic_agent import WorkerGroupFailure

        agent = self._agent(tmp_path, max_restarts=4, backoff_base_s=1.0,
                            backoff_max_s=3.0, backoff_jitter=0.25)
        with pytest.raises(WorkerGroupFailure, match="max_restarts"):
            agent.run()
        sleeps = agent._test_sleeps
        assert len(sleeps) == 4
        # exponential ladder 1, 2, 3(cap), 3(cap) — each with up to +25%
        for got, base in zip(sleeps, (1.0, 2.0, 3.0, 3.0)):
            assert base <= got <= base * 1.25, (sleeps)
        # jitter actually applied (not all exactly at base)
        assert any(got > base for got, base in zip(sleeps,
                                                   (1.0, 2.0, 3.0, 3.0)))

    def test_circuit_breaker_trips_with_bundle(self, tmp_path):
        from deepspeed_tpu.launcher.elastic_agent import WorkerGroupFailure

        agent = self._agent(tmp_path, max_restarts=10, backoff_base_s=0.0,
                            restart_window_s=60.0,
                            max_restarts_per_window=3)
        with pytest.raises(WorkerGroupFailure, match="circuit breaker"):
            agent.run()
        # 3 respawns inside the window are ALLOWED; the 4th attempt trips
        assert agent.restart_count == 3
        # the bundle names the last failure
        crash_dir = tmp_path / "agent" / "crash"
        bundles = list(crash_dir.glob("crash-*restart-breaker*"))
        assert bundles, list(crash_dir.iterdir())
        manifest = json.loads((bundles[0] / "MANIFEST.json").read_text())
        assert manifest["reason"] == "restart-breaker"
        extra = manifest["extra"]
        assert extra["last_failure"]["rc"] == 3
        assert extra["restarts_in_window"] == 4

    def test_breaker_window_slides(self, tmp_path):
        """Restarts spread WIDER than the window never trip the breaker."""
        t = [0.0]

        def clock():
            t[0] += 100.0   # each poll/restart 100s apart > 60s window
            return t[0]

        agent = self._agent(tmp_path, max_restarts=4, backoff_base_s=0.0,
                            restart_window_s=60.0,
                            max_restarts_per_window=2, clock=clock)
        from deepspeed_tpu.launcher.elastic_agent import WorkerGroupFailure

        # exhausts max_restarts (the total budget) WITHOUT a breaker trip
        with pytest.raises(WorkerGroupFailure, match="max_restarts"):
            agent.run()

    @pytest.mark.parametrize("max_restarts", [2, 0])
    def test_eviction_request_restarts_with_shrink(self, tmp_path,
                                                   max_restarts):
        """An evict.json dropped into the agent dir (what
        session.TrainingSession's straggler policy writes via
        request_eviction) kills + re-rendezvouses at a smaller
        membership. max_restarts=0: a DELIBERATE eviction does not consume
        the crash budget — remediation must work even with no crash
        restarts left."""
        import json as _json
        import threading
        import time as _time

        from deepspeed_tpu.launcher.elastic_agent import (ElasticAgent,
                                                          ElasticAgentConfig,
                                                          request_eviction)

        agent_dir = tmp_path / "agent"
        agent_dir.mkdir()
        log = tmp_path / "probe.jsonl"
        # workers: finish instantly at world <= 2, otherwise linger
        probe = tmp_path / "probe.py"
        probe.write_text(
            "import json, os, sys, time\n"
            "with open(sys.argv[1], 'a') as fh:\n"
            "    fh.write(json.dumps({'world': os.environ['WORLD_SIZE'],\n"
            "        'agent_dir': os.environ.get('DSTPU_AGENT_DIR')})\n"
            "        + chr(10))\n"
            "if int(os.environ['WORLD_SIZE']) <= 2:\n"
            "    sys.exit(0)\n"
            "time.sleep(30)\n")
        agent = ElasticAgent(
            [sys.executable, str(probe), str(log)], nprocs=3,
            config=ElasticAgentConfig(
                max_restarts=max_restarts, min_workers=1, master_port=29556,
                monitor_interval=0.05, backoff_base_s=0.01,
                agent_dir=str(agent_dir)))

        def drop_request():
            # DEFLAKED (was: a fixed 0.7s sleep): on a loaded box spawning
            # 3 interpreters can take longer than any fixed sleep, and a
            # request dropped before every worker has written its probe
            # line makes the `lines[0]["world"] == "3"` assertion race the
            # restart. Wait for the OBSERVABLE condition instead — all 3
            # incarnation-0 workers logged — before requesting eviction.
            deadline = _time.monotonic() + 30.0
            while _time.monotonic() < deadline:
                try:
                    if len(log.read_text().splitlines()) >= 3:
                        break
                except OSError:
                    pass
                _time.sleep(0.05)
            request_eviction(1, reason="test straggler", step=7,
                             agent_dir=str(agent_dir))

        t = threading.Thread(target=drop_request)
        t.start()
        rc = agent.run()
        t.join()
        assert rc == 0
        assert agent.evictions == 1 and agent.restart_count == 1
        assert agent._world == 2
        assert agent.last_failure["kind"] == "eviction"
        assert agent.last_failure["rank"] == 1
        lines = [_json.loads(l) for l in log.read_text().splitlines()]
        assert lines[0]["world"] == "3" and lines[-1]["world"] == "2"
        # workers saw the control-channel contract
        assert lines[0]["agent_dir"] == str(agent_dir)

    def test_eviction_ignored_when_membership_cannot_shrink(self, tmp_path):
        """min_workers unset (the default): honouring an eviction would
        respawn the same membership — straggler included — and churn
        forever; the agent must drop the request instead."""
        import threading
        import time as _time

        from deepspeed_tpu.launcher.elastic_agent import (ElasticAgent,
                                                          ElasticAgentConfig,
                                                          request_eviction)

        agent_dir = tmp_path / "agent"
        agent_dir.mkdir()
        agent = ElasticAgent(
            [sys.executable, "-c", "import time; time.sleep(2)"], nprocs=2,
            config=ElasticAgentConfig(
                max_restarts=2, master_port=29558, monitor_interval=0.05,
                agent_dir=str(agent_dir)))

        def drop():
            _time.sleep(0.4)
            request_eviction(1, reason="slow", agent_dir=str(agent_dir))

        t = threading.Thread(target=drop)
        t.start()
        rc = agent.run()
        t.join()
        assert rc == 0
        assert agent.evictions == 0 and agent.restart_count == 0
        assert agent._world == 2

    def test_request_eviction_without_agent_is_dropped(self, monkeypatch):
        from deepspeed_tpu.launcher.elastic_agent import request_eviction

        monkeypatch.delenv("DSTPU_AGENT_DIR", raising=False)
        assert request_eviction(3, reason="no agent") is None

    def test_stale_eviction_request_cleared_on_failure_restart(self,
                                                               tmp_path):
        """An evict.json racing a worker crash must not survive the crash
        restart — left behind it would trigger a second, spurious shrink
        on the next healthy poll."""
        from deepspeed_tpu.launcher.elastic_agent import request_eviction

        agent = self._agent(
            tmp_path, cmd=[sys.executable, "-c", "import sys; sys.exit(0)"],
            max_restarts=3, backoff_base_s=0.0)
        request_eviction(1, reason="raced by a crash",
                         agent_dir=agent.agent_dir)
        req = os.path.join(agent.agent_dir, "evict.json")
        assert os.path.exists(req)
        agent._restart("worker exit rc=7", shrink=True)   # the CRASH path
        agent._terminate_all()
        assert not os.path.exists(req)
        assert agent.evictions == 0   # the stale request was never honoured
