"""From the profiler's trace to events the reducers read.

`Tracer` takes a `jax.profiler` trace of a few seconds in the middle of the
window (only under `--trace 1`); `load` turns the `.xplane.pb` it wrote into
a `Trace`: per device the operations (`XLA Ops`) and the programs
(`XLA Modules`) with start and duration in seconds, and the host's spans by
thread line. A `Trace` can also be read from a small JSON file, which is how
the tests hold the reducers to known numbers.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import time
from typing import Any, Dict, List, Optional, Tuple

from . import stats

Event = Tuple[str, float, float]      # name, start_s, duration_s
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_OP_NAME = re.compile(r"^%?([A-Za-z0-9_.\-]+)")
# operations that only hold others (their time is their children's)
CONTAINERS = ("while", "conditional", "call")
# the harness's own host spans (TraceAnnotation in train_cell / serve_cell)
HARNESS_SPANS = ("train/", "serve/")


def op_name(raw: str) -> str:
    """'%fusion.436 = bf16[...] fusion(...)' or 'fusion.436' -> 'fusion.436'."""
    m = _OP_NAME.match(raw)
    return m.group(1) if m else raw


def op_label(raw: str) -> str:
    """'%f.1 = bf16[4,8]{1,0} fusion(...)' -> 'f.1_fusion_bf16_4_8_'."""
    parts = [op_name(raw)]
    _, _, rest = raw.partition(" = ")
    opcode = re.search(r" ([a-z][a-z0-9\-]*)\(", rest)
    shape = re.search(r"[a-z0-9]+\[[0-9,]*\]", rest)
    parts += [m.group(1) if m.groups() else m.group(0)
              for m in (opcode, shape) if m]
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", " ".join(parts))[:64]


def base_name(raw: str) -> str:
    """'flash_attention_fwd.13' -> 'flash_attention_fwd';
    'jit_train_step(1234)' -> 'jit_train_step'."""
    return re.sub(r"(\.\d+)+$", "", op_name(raw).split("(")[0])


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]        # device index -> operations
    modules: Dict[int, List[Event]]    # device index -> programs
    host: Dict[str, List[Event]]       # host thread line -> spans

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def window(self) -> Tuple[float, float]:
        """First start to last end of any device operation."""
        evs = [e for d in self.ops.values() for e in d]
        if not evs:
            return (0.0, 0.0)
        return (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))

    def busy_s(self, dev: int) -> float:
        return stats.union_seconds([(s, s + d) for _, s, d in self.ops[dev]])

    def leaf_ops(self, dev: int) -> List[Event]:
        return [e for e in self.ops[dev]
                if not base_name(e[0]).startswith(CONTAINERS)]

    def op_seconds(self, dev: int, kernel: str) -> Tuple[float, int]:
        """Total seconds and number of events of the operations whose name,
        less its numeric suffix, is `kernel`."""
        durs = [d for n, _, d in self.ops[dev] if base_name(n) == kernel]
        return sum(durs), len(durs)

    def module_events(self, dev: int, module: str) -> List[Event]:
        return [e for e in self.modules.get(dev, [])
                if base_name(e[0]) == module]

    # -- the result line's `breakdown` ---------------------------------
    def top_ops(self, n: int = 10) -> List[List[Any]]:
        """The `n` device operations with most time, averaged over chips,
        named `<op>_<opcode>_<result shape>` in the characters of a name."""
        total: Dict[str, float] = {}
        for dev in self.devices:
            for name, _, d in self.leaf_ops(dev):
                total[name] = total.get(name, 0.0) + d
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[op_label(name), secs / len(self.devices)]
                for name, secs in top]

    def idle_gaps(self, n: int = 10) -> List[List[Any]]:
        """Idle time of the first device by what the host was doing: each gap
        between operations goes to the innermost of the harness's own spans
        (`HARNESS_SPANS`) that covers its middle; a gap
        in no such span, to the longest host span of any thread that covers
        its middle (named `thread:<line>:<span>`), else `_none_`."""
        dev = self.devices[0]
        lo, hi = self.window()
        own, other = [], []
        for line, evs in self.host.items():
            for name, s, d in evs:
                if d <= 0:
                    continue
                (own if name.startswith(HARNESS_SPANS) else other).append(
                    (s, s + d, name, line))
        total: Dict[str, float] = {}
        for g0, g1 in stats.gaps([(s, s + d) for _, s, d in self.ops[dev]],
                                 lo, hi):
            mid = (g0 + g1) / 2
            cover = [x for x in own if x[0] <= mid <= x[1]]
            if cover:
                label = min(cover, key=lambda x: x[1] - x[0])[2]
            else:
                cover = [x for x in other if x[0] <= mid <= x[1]]
                label = "_none_"
                if cover:
                    x = min(cover, key=lambda x: x[1] - x[0])
                    label = re.sub(r"[^A-Za-z0-9_.\-:]+", "_",
                                   f"thread:{x[3]}:{x[2]}")[:64]
            total[label] = total.get(label, 0.0) + (g1 - g0)
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    # -- a small recorded trace, as JSON --------------------------------
    @classmethod
    def from_json(cls, doc: Dict[str, Any]) -> "Trace":
        ev = lambda xs: [(str(n), float(s), float(d)) for n, s, d in xs]
        return cls(ops={int(k): ev(v) for k, v in doc["ops"].items()},
                   modules={int(k): ev(v) for k, v in doc["modules"].items()},
                   host={k: ev(v) for k, v in doc["host"].items()})


def load(xplane_path: str) -> Trace:
    """Read an `.xplane.pb`. Of the host's spans only the harness's own are
    kept and those that lasted 50 us or more (the rest are many and name
    nothing)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                into = (ops if line.name == OPS_LINE else modules
                        ).setdefault(dev, [])
                for e in line.events:
                    into.append((e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9))
            modules.setdefault(dev, [])
        elif plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                keep = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events
                        if e.name.startswith(HARNESS_SPANS)
                        or e.duration_ns >= 50_000]
                if keep:
                    host[f"{line.name}#{i}"] = keep
    return Trace(ops=ops, modules=modules, host=host)


class Tracer:
    """`--trace 1`: one `jax.profiler` trace from `start_at` seconds into
    the window, for `length` seconds or more (the loop calls `maybe_start`
    and `maybe_stop` between its pieces of work, with the seconds since the
    window opened). `--trace 0`: does nothing."""

    def __init__(self, on: bool, out_dir: str, start_at: float,
                 length: float):
        self.on, self.dir = on, out_dir
        self.start_at, self.length = start_at, length
        self.state = "idle" if on else "done"
        self.t_start = self.t_stop = None     # host perf_counter

    def maybe_start(self, since_open: float) -> None:
        if self.state == "idle" and since_open >= self.start_at:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # the TraceMe spans are enough
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_start = time.perf_counter()
            self.opened_at = since_open
            self.state = "tracing"

    def maybe_stop(self, since_open: float) -> None:
        if (self.state == "tracing"
                and since_open >= self.opened_at + self.length):
            self.stop()

    def stop(self) -> None:
        if self.state == "tracing":
            import jax

            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.state = "done"

    def xplane(self) -> Optional[str]:
        if not self.on or self.t_stop is None:
            return None
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None
