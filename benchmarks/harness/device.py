"""The machine a run finds: it has to be the chip, and a chip whose peaks
the benchmark's own table (`peaks.json`) knows. No fallback: a run that
finds anything else raises `NoChip` and prints no result."""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

from .spec import BENCH_DIR

# What a run must find. tests/benchmark_harness steers this to rehearse the
# control flow on the CPU; no option of the command does. A run that was
# steered reports no time, rate or share (run.finish drops them).
TARGET = {"platform": "tpu"}


class NoChip(RuntimeError):
    pass


def require(chips: int) -> List[Any]:
    import jax

    devs = jax.devices()
    if devs[0].platform != TARGET["platform"]:
        raise NoChip(f"need a {TARGET['platform']} device, JAX found "
                     f"'{devs[0].platform}'")
    if len(devs) < chips:
        raise NoChip(f"need {chips} chip(s), JAX found {len(devs)}")
    return devs[:chips]


def peaks(kind: str) -> Dict[str, float]:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise NoChip(f"device_kind '{kind}' is not in benchmarks/peaks.json "
                     f"(known: {sorted(table)}); no default is assumed")
    return table[kind]


def describe(devs: List[Any]) -> Dict[str, Any]:
    """The `device` object of the result line; `memory_peak_bytes` is the
    peak on the fullest chip."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def compile_cache_dir(root: str) -> str:
    """JAX's persistent cache: where `JAX_COMPILATION_CACHE_DIR` says, else
    one fixed directory inside the checkout (the path is part of the cache's
    key). Set in the environment BEFORE jax is imported, so the program,
    which sets a directory of its own only when that variable is unset,
    takes this one."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


class CompileCounter:
    """Counts what JAX compiles (or loads from the persistent cache) from
    `arm()` on: inside a measured window there should be none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.armed = False
        self.count = 0
        self.total = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == self.EVENT:
            self.total += 1
            if self.armed:
                self.count += 1

    def arm(self):
        self.armed, self.count = True, 0

    def disarm(self):
        self.armed = False


class SetUp:
    """The clock of `setup_s`: from the start of the process to the first
    measured step or request, with the seconds of each phase on the way
    (printed on an earlier line, so that a slow set-up can be told apart)."""

    def __init__(self, t_process_start: float):
        import time

        self.clock = time.perf_counter
        self.t0 = self.last = t_process_start
        self.phases = {}

    def mark(self, phase: str) -> None:
        now = self.clock()
        self.phases[phase] = round(now - self.last, 3)
        self.last = now

    def done(self, phase: str) -> float:
        """The window opens now: returns `setup_s`."""
        import json

        self.mark(phase)
        print(json.dumps({"setup_phases_s": self.phases}), flush=True)
        return self.last - self.t0
