#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by the names in BENCHMARK.json,
makes weights and inputs from --seed, warms the cell's own shapes (set-up),
measures for --seconds, checks the outputs, and prints one JSON object as
the last line of stdout. Needs the TPU: on anything else it exits non-zero
with no result line. See README.md beside this file.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def run_cell(spec, workload: str, seed: int, seconds: float, trace: bool,
             t_process_start: float) -> dict:
    """The whole of one run; returns the result object. Raises `NoChip`
    where the machine is not what the cell needs."""
    from benchmarks.harness import device, layers
    from benchmarks.harness import trace as trace_mod

    setup = device.SetUp(t_process_start)
    cell = spec.cell(workload)
    device.compile_cache_dir(spec.root)     # before anything imports jax
    devs = device.require(cell.chips)
    setup.mark("import_and_device")
    real = devs[0].platform == "tpu"
    peaks = device.peaks(devs[0].device_kind) if real else {}
    counter = device.CompileCounter()
    kind = cell.traffic["kind"]
    if kind == "train":
        from benchmarks.harness import train_cell as runner
    else:
        from benchmarks.harness import serve_cell as runner
    # traces are large and tracing slows the host: a short piece from the
    # middle of the window, as long as the kind of cell needs
    tracer = trace_mod.Tracer(
        trace, os.path.join(spec.root, ".bench_trace", workload),
        start_at=min(4.0, seconds / 3), length=runner.TRACE_SECONDS)
    got = runner.run(cell, seed, seconds, tracer, devs, counter, setup)

    result = {"correct": not got["problems"], "attempted": got["attempted"],
              "failed": got["failed"], "device": device.describe(devs)}
    for p in got["problems"]:
        print(f"NOT CORRECT: {p}", file=sys.stderr, flush=True)
    if not trace:
        wanted = {m["name"]: m for m in cell.end_to_end}
        metrics = {k: {"value": float(v), "unit": wanted[k]["unit"]}
                   for k, v in got["end_to_end"].items() if k in wanted}
    else:
        ctx = layers.Context(
            cell=cell, chips=len(devs), peaks=peaks,
            counters=got["counters"], model_config=got["model_config"],
            records=got.get("records", []))
        path = tracer.xplane()
        if path is not None and real:
            ctx.trace = trace_mod.load(path)
            ctx.traced = (tracer.t_start, tracer.t_stop)
        if ctx.trace is not None and ctx.trace.devices:
            lo, hi = ctx.trace.window()
            busy = [ctx.trace.busy_s(d) for d in ctx.trace.devices]
            result["device"]["busy_s"] = sum(busy) / len(busy)
            result["device"]["window_s"] = hi - lo
            result["breakdown"] = {
                "device_ops": ctx.trace.top_ops(10),
                "idle_gaps": ctx.trace.idle_gaps(10)}
        metrics = layers.read_all(ctx)
    if not real:
        # a rehearsal on the CPU: counts only, never a time, rate or share
        sources = {m["name"]: m["source"]
                   for m in cell.end_to_end + cell.per_layer}
        metrics = {k: v for k, v in metrics.items()
                   if sources[k] == "program_counter"}
        result["rehearsal"] = True
    result["metrics"] = metrics
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import device, layers, spec as spec_mod

    try:
        spec = spec_mod.Spec(REPO_ROOT)
        seconds = (args.seconds if args.seconds is not None
                   else float(spec.doc["run_seconds"]))
        result = run_cell(spec, args.workload, args.seed, seconds,
                          bool(args.trace), T_PROCESS_START)
    except (device.NoChip, spec_mod.SpecError,
            layers.MissingProgram) as e:
        print(f"benchmark cannot run: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
