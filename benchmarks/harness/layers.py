"""Per-layer metrics: each is a data file (`layer_metrics/<name>.json`)
that names a reducer (`reducers/<reducer>.py`) and its arguments. A reducer
reads the trace, the counters or the request records through a `Context`
and returns a number, or None where it finds nothing to read; the harness
then leaves that metric out of the line."""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from .spec import BENCH_DIR, SpecError
from .trace import Trace


@dataclasses.dataclass
class Context:
    cell: Any                           # spec.Cell
    chips: int
    peaks: Dict[str, float]             # this device_kind's row of peaks.json
    counters: Dict[str, float]          # what the cell's loop counted
    model_config: Any                   # the program's TransformerConfig
    trace: Optional[Trace] = None
    records: List[Any] = dataclasses.field(default_factory=list)
    #   serving: one serve_cell.Record per request sent
    traced: Optional[Tuple[float, float]] = None
    #   host perf_counter at the trace's start and stop

    def module_of(self, program: str) -> str:
        """The name in the trace of a program the system registers
        (`train/step` -> `jit_train_step`), from the benchmark's own file
        `programs/<program>.json`."""
        path = os.path.join(BENCH_DIR, "programs", f"{program}.json")
        if not os.path.exists(path):
            raise SpecError(f"no file benchmarks/programs/{program}.json "
                            f"names the module of program '{program}'")
        with open(path) as f:
            return json.load(f)["module"]

    def program_events(self, dev: int, program: str, must_run: bool = True):
        """The executions of `program` on `dev` in the trace. A program that
        a metric says must have run and that the trace does not hold is an
        error, not a metric left out: the name is then wrong, or the program
        was renamed."""
        module = self.module_of(program)
        events = self.trace.module_events(dev, module)
        if must_run and not events:
            raise MissingProgram(
                f"the trace holds no execution of '{module}' ({program}) on "
                f"device {dev}; it holds "
                f"{sorted({e[0] for e in self.trace.modules.get(dev, [])})}")
        return events


class MissingProgram(RuntimeError):
    pass


def reducer(name: str):
    return importlib.import_module(f"benchmarks.reducers.{name}")


def read_all(ctx: Context) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in ctx.cell.per_layer:
        r = m["reader"]
        value = reducer(r["reducer"]).reduce(ctx, **r.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
