"""Mesh construction + collective-API tests on the 8-virtual-device CPU mesh —
analog of reference tests/unit/comm/test_dist.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.config.config import ParallelConfig
from deepspeed_tpu.parallel import mesh as mesh_mod


def build(pp=1, tp=1, sp=1, dp=0):
    return mesh_mod.build_mesh(ParallelConfig(
        pipeline_parallel_size=pp, tensor_parallel_size=tp,
        sequence_parallel_size=sp, data_parallel_size=dp))


def test_build_mesh_default(devices8):
    m = build()
    assert m.shape["data"] == 8
    assert m.shape["model"] == 1


def test_build_mesh_3d(devices8):
    m = build(pp=2, tp=2)
    assert dict(m.shape) == {"pipe": 2, "expert": 1, "data": 2, "seq": 1, "model": 2}


def test_build_mesh_invalid(devices8):
    with pytest.raises(ValueError):
        build(pp=3)


def test_all_reduce_psum(devices8):
    m = build()
    x = jnp.arange(8.0)

    f = shard_map(lambda v: comm.all_reduce(v, axis="data"),
                  mesh=m, in_specs=P("data"), out_specs=P())

    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full((1,), 28.0))


def test_all_reduce_avg_max_min(devices8):
    m = build()
    x = jnp.arange(8.0)
    avg = shard_map(lambda v: comm.all_reduce(v, op=comm.ReduceOp.AVG, axis="data"),
                    mesh=m, in_specs=P("data"), out_specs=P())(x)
    np.testing.assert_allclose(np.asarray(avg), [3.5])
    mx = shard_map(lambda v: comm.all_reduce(v, op=comm.ReduceOp.MAX, axis="data"),
                   mesh=m, in_specs=P("data"), out_specs=P())(x)
    np.testing.assert_allclose(np.asarray(mx), [7.0])
    mn = shard_map(lambda v: comm.all_reduce(v, op=comm.ReduceOp.MIN, axis="data"),
                   mesh=m, in_specs=P("data"), out_specs=P())(x)
    np.testing.assert_allclose(np.asarray(mn), [0.0])


def test_all_gather(devices8):
    m = build()
    x = jnp.arange(8.0)
    f = shard_map(lambda v: comm.all_gather(v, axis="data"),
                  mesh=m, in_specs=P("data"), out_specs=P(None), check_vma=False)
    out = f(x)
    assert out.shape == (8,)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))


def test_reduce_scatter(devices8):
    m = build()
    # every shard holds the full vector; reduce_scatter sums and splits
    x = jnp.tile(jnp.arange(8.0), (8, 1))
    f = shard_map(lambda v: comm.reduce_scatter(v[0], axis="data"),
                  mesh=m, in_specs=P("data", None), out_specs=P("data"))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 8)


def test_all_to_all(devices8):
    m = build()
    x = jnp.arange(64.0).reshape(8, 8)
    f = shard_map(lambda v: comm.all_to_all(v, axis="data", split_dim=1, concat_dim=0),
                  mesh=m, in_specs=P("data", None), out_specs=P("data", None))
    out = f(x)
    # all_to_all is its own inverse transpose-wise: verify via double application
    g = shard_map(lambda v: comm.all_to_all(v, axis="data", split_dim=0, concat_dim=1),
                  mesh=m, in_specs=P("data", None), out_specs=P("data", None))
    back = g(out)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x))


def test_broadcast(devices8):
    m = build()
    x = jnp.arange(8.0)
    f = shard_map(lambda v: comm.broadcast(v, src=3, axis="data"),
                  mesh=m, in_specs=P("data"), out_specs=P("data"))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))


def test_ppermute_ring(devices8):
    m = build(pp=8, dp=1)
    x = jnp.arange(8.0)
    f = shard_map(lambda v: comm.send_next(v, axis="pipe"),
                  mesh=m, in_specs=P("pipe"), out_specs=P("pipe"))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))
    b = shard_map(lambda v: comm.send_prev(v, axis="pipe"),
                  mesh=m, in_specs=P("pipe"), out_specs=P("pipe"))
    np.testing.assert_allclose(np.asarray(b(x)), np.roll(np.arange(8.0), -1))


def test_collectives_identity_outside_mesh():
    x = jnp.arange(4.0)
    np.testing.assert_allclose(np.asarray(comm.all_reduce(x)), np.asarray(x))
    np.testing.assert_allclose(np.asarray(comm.all_gather(x)), np.asarray(x))


def test_groups_accessors(devices8):
    m = build(pp=2, tp=2)
    mesh_mod.set_mesh(m)
    assert mesh_mod.get_data_parallel_world_size() == 2
    assert mesh_mod.get_model_parallel_world_size() == 2
    assert mesh_mod.get_pipe_parallel_world_size() == 2
    assert mesh_mod.get_world_size() == 8


def test_comms_logger_bw_math():
    from deepspeed_tpu.comm.comms_logging import calc_bw_log
    size, algbw, busbw = calc_bw_log("all_reduce", 1000, 1e-3, 8)
    # allreduce: 2x data volume, busbw factor (n-1)/n
    assert algbw == pytest.approx(2 * 1000 / 1e-3 * 8 / 1e9)
    assert busbw == pytest.approx(algbw * 7 / 8)


def test_comm_benchmark_sweep(devices8):
    """ds_bench analog: every op sweeps and reports positive busbw with the
    logger's own bandwidth factors."""
    from deepspeed_tpu.comm.benchmark import OPS, run_comm_benchmark
    from deepspeed_tpu.config.config import ParallelConfig
    from deepspeed_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(ParallelConfig(data_parallel_size=8))
    results = run_comm_benchmark(ops=list(OPS), axis="data",
                                 minsize_log2=10, maxsize_log2=11,
                                 trials=2, warmups=1, mesh=mesh, quiet=True)
    assert len(results) == len(OPS) * 2
    for r in results:
        assert r["world"] == 8
        assert r["latency_ms"] > 0 and r["busbw_gbps"] > 0
    # all_reduce busbw factor (n-1)/n vs its algbw (values are rounded to
    # 6 decimals in the record, so compare loosely on the largest message)
    ar = [r for r in results if r["op"] == "all_reduce"][-1]
    assert abs(ar["busbw_gbps"] / ar["algbw_gbps"] - 7 / 8) < 0.1


def test_comm_benchmark_correctness(devices8):
    """The benchmarked programs compute the real collectives (a sweep that
    times wrong math would be worthless): spot-check all_reduce output."""
    import jax

    from deepspeed_tpu.comm.benchmark import _build
    from deepspeed_tpu.config.config import ParallelConfig
    from deepspeed_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(ParallelConfig(data_parallel_size=8))
    prog, x = _build("all_reduce", "data", mesh, 128, jnp.float32)
    out = np.asarray(jax.block_until_ready(prog(x)))
    np.testing.assert_allclose(out, np.full(128, 8.0))


def test_ds_ssh_cli(tmp_path, capsys):
    """ds_ssh analog: hostfile fan-out command construction + the
    missing-hostfile failure mode."""
    from deepspeed_tpu.launcher.tools import run_on_all_hosts, ssh_cli_main

    hf = tmp_path / "hostfile"
    hf.write_text("worker-0 slots=4\nworker-1 slots=4\n")
    rc = run_on_all_hosts(["echo", "hi there"], hostfile=str(hf),
                          dry_run=True)
    assert rc == 0
    out = capsys.readouterr().out
    assert "worker-0" in out and "worker-1" in out
    assert "'hi there'" in out or "hi\\ there" in out   # quoted
    assert run_on_all_hosts(["echo"], hostfile=str(tmp_path / "nope")) == 1
    err = capsys.readouterr().err
    assert "Missing hostfile" in err
    rc = ssh_cli_main(["-f", str(hf), "--dry-run", "uptime"])
    assert rc == 0
