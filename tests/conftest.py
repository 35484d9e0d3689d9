"""Test harness configuration.

The reference tests multi-rank behavior by forking N local processes with a
fake NCCL rendezvous (tests/unit/common.py:86 DistributedExec). On TPU the
equivalent — and much faster — trick is a single process with N virtual CPU
devices: every "distributed" test becomes a single-process mesh test
(SURVEY.md §4 lesson). These env vars must be set before jax initializes.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# the config API takes precedence over whatever the interpreter started with
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end tests")


def pytest_addoption(parser):
    parser.addoption(
        "--stress", action="store_true", default=False,
        help="wrap serving/fleet locks in a seeded LockPerturber: "
             "deterministic GIL-yield points at lock boundaries widen "
             "race windows in the threaded chaos tests")
    parser.addoption(
        "--stress-seed", type=int, default=1234,
        help="LCG seed for --stress yield-point placement")


@pytest.fixture
def stress_perturber(request):
    """A seeded LockPerturber under ``--stress``, else None. Tests that
    accept it instrument their engines/routers when present — the same
    test body runs plain in tier-1 and perturbed in the chaos gate."""
    if not request.config.getoption("--stress"):
        return None
    from deepspeed_tpu.observability.faultinject import LockPerturber

    return LockPerturber(seed=request.config.getoption("--stress-seed"))


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    yield
    from deepspeed_tpu.parallel import mesh

    mesh.reset_mesh()


@pytest.fixture(autouse=True)
def _collector_unwatched(request, monkeypatch):
    """A collection can fall into any test, and while a tracer records it
    closes as a ``runtime/gc`` span in whatever record that test counts. So
    only the tests that are about it (a class with ``watches_collector``) let
    a tracer hook ``gc.callbacks``; everywhere else the tracers watch
    nothing, as in an untraced run."""
    if not getattr(request.cls, "watches_collector", False):
        from deepspeed_tpu.observability import spans

        monkeypatch.setattr(spans, "_gc_watch", lambda tracer, on: None)
    yield


@pytest.fixture
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
