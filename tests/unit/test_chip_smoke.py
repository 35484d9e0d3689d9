"""CPU rehearsal of ``chip_smoke.py``'s control flow.

The program has no option that lets it carry on without a TPU. What it must
find on the machine sits in ``chip_smoke.TARGET``, and this test steers that
to the CPU backend: the jnp attention path, the Pallas interpreter for the
kernel phase, markers the CPU's compiled text holds. The phases then run
through the same entry points at the ``TINY`` preset. A chip run it is not.
"""

import json

import jax
import pytest

import chip_smoke


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setitem(chip_smoke.TARGET, "platform", "cpu")
    monkeypatch.setitem(chip_smoke.TARGET, "attention_impl", "jnp")
    monkeypatch.setitem(chip_smoke.TARGET, "interpret", True)
    # no Pallas kernel in a CPU program, and tiny-opt's params all sit
    # under the ZeRO-3 persistence threshold: nothing is gathered
    in_program = dict.fromkeys(chip_smoke.TARGET["in_program"],
                               ("HloModule",))
    in_program["zero3"] = ("HloModule", "all-reduce")
    monkeypatch.setitem(chip_smoke.TARGET, "in_program", in_program)


def test_unsteered_run_fails_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "need a tpu device" in out.err


@pytest.mark.parametrize("phase", ["kernels", "train", "serve", "zero"])
def test_phase_runs_at_tiny_size(on_cpu, capsys, phase):
    if phase == "kernels":
        chip_smoke.phase_kernels()
    elif phase == "zero":
        chip_smoke.phase_zero(chip_smoke.TINY["zero"], 0, jax.devices()[:4])
    else:
        getattr(chip_smoke, f"phase_{phase}")(chip_smoke.TINY[phase], 0)
    assert f"[{phase}" in capsys.readouterr().out


def _stub_phases(monkeypatch, ran):
    for name in ("kernels", "train", "serve", "zero"):
        monkeypatch.setattr(
            chip_smoke, f"phase_{name}",
            lambda *a, _n=name, **k: ran.append(_n))


def test_last_line_is_the_result_and_nothing_else(on_cpu, monkeypatch,
                                                  capsys):
    ran = []
    _stub_phases(monkeypatch, ran)
    assert chip_smoke.main([]) == 0
    assert ran == ["kernels", "train", "serve"]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": len(jax.devices())}}


def test_four_chips_runs_only_the_zero_phase(on_cpu, monkeypatch, capsys):
    ran = []
    _stub_phases(monkeypatch, ran)
    assert chip_smoke.main(["--chips", "4"]) == 0
    assert ran == ["zero"]
    assert '"ok": true' in capsys.readouterr().out


def test_failed_phase_exits_nonzero_without_a_result(on_cpu, monkeypatch,
                                                     capsys):
    ran = []
    _stub_phases(monkeypatch, ran)

    def broken(*a, **k):
        chip_smoke.check(False, "loss did not fall")

    monkeypatch.setattr(chip_smoke, "phase_train", broken)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "loss did not fall" in out.err
    assert "serve" not in ran
