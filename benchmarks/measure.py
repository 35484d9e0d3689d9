#!/usr/bin/env python3
"""Several runs of one or more cells in one call, each run a new process of
`run.py`, one after the other (this parent never imports JAX, so it never
holds the chip). Prints each run's result line and, per cell, set and
metric, the median, the spread as the contract measures it (inter-quartile
distance over the median) and what decides a new cell's admission: the
narrower of that spread and the spread without the run farthest from the
median (`stats.trimmed_spread`), as a share and in the metric's unit. With
two sets or more it prints, for each end-to-end metric with a bound but
`setup_s` (which the check judges by its medians alone),

    ADMISSION <cell> <metric> mean=<unit> half_bound=<unit> ok|too_noisy

where `mean` is the mean of the sets' trimmed spreads and `half_bound` is
half of the metric's bound in `BENCHMARK.json` times the median of all runs:
a new cell, or one measured anew, is refused above it. Writes everything to
`--out`.

    python benchmarks/measure.py --workload <cell> [--workload ...] \
        --sets 2 --runs 6 [--seconds S] [--trace-runs 1] --out chiprun_out/m

Run `s` of every set uses seed `--seed0 + s`, so the sets share their seeds.
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks.harness.stats import (  # noqa: E402  (no JAX in there)
    spread, trimmed_spread)


def set_summary(values):
    """One set's runs of one metric: the median, the contract's spread and
    the spread that admission reads, as a share and in the metric's unit."""
    out = {"median": statistics.median(values), "spread": None,
           "trimmed": None, "trimmed_unit": None, "first": values[0],
           "n": len(values)}
    if len(values) > 1:
        out["spread"] = spread(values)
        out["trimmed"] = trimmed_spread(values)
        out["trimmed_unit"] = out["trimmed"] * out["median"]
    return out


def admission(trimmed_units, bound, median):
    """The check's rule for a cell that is new or measured anew, in the words
    of its refusal at PR 35: the mean of the sets' spreads (each without its
    farthest run where that narrows it) may be at most 50% of the bound, the
    bound being the metric's share of the median of the runs."""
    mean = statistics.mean(trimmed_units)
    half = 0.5 * bound * median
    return {"mean": mean, "half_bound": half, "over_by": mean / half - 1.0,
            "verdict": "ok" if mean <= half else "too_noisy"}


def bounds():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]
                if m["name"] != "setup_s"}


def one_run(workload, seed, seconds, trace, log):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--trace", str(int(trace))]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    t0 = time.time()
    p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE, text=True)
    wall = time.time() - t0
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    rec = {"workload": workload, "seed": seed, "trace": int(trace),
           "rc": p.returncode, "wall_s": round(wall, 2), "result": None,
           "notes": []}
    for ln in lines:
        if ln.startswith("{"):
            try:
                doc = json.loads(ln)
            except json.JSONDecodeError:
                continue
            if "metrics" in doc:
                rec["result"] = doc
            else:
                rec["notes"].append(doc)
    if p.returncode != 0 or rec["result"] is None:
        rec["stderr_tail"] = p.stderr[-3000:]
    log.write(json.dumps(rec) + "\n")
    log.flush()
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=2147483700)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace-runs", type=int, default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="copy the traced run's .xplane.pb into --out")
    ap.add_argument("--stop-on-fail", action="store_true",
                    help="end the call at the first run that fails")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    summary, bound_of = {}, bounds()
    with open(os.path.join(args.out, "runs.jsonl"), "a") as log:
        for w in args.workload:
            per_set = []
            for s in range(args.sets):
                vals = {}
                for r in range(args.runs):
                    rec = one_run(w, args.seed0 + r, args.seconds, False, log)
                    res = rec["result"] or {}
                    print(w, f"set{s} run{r} rc={rec['rc']} wall={rec['wall_s']}",
                          json.dumps({k: v["value"] for k, v in
                                      res.get("metrics", {}).items()}),
                          "correct=", res.get("correct"), flush=True)
                    if rec["rc"] != 0:
                        print(rec.get("stderr_tail", "")[-1500:], flush=True)
                        if args.stop_on_fail:
                            return 1
                    for k, v in res.get("metrics", {}).items():
                        vals.setdefault(k, []).append(v["value"])
                per_set.append(vals)
            for t in range(args.trace_runs):
                rec = one_run(w, args.seed0 + 100 + t, args.seconds, True, log)
                print(w, f"trace{t} rc={rec['rc']} wall={rec['wall_s']}",
                      json.dumps(rec["result"]), flush=True)
                if rec["rc"] != 0:
                    print(rec.get("stderr_tail", "")[-1500:], flush=True)
                    if args.stop_on_fail:
                        return 1
                if args.keep_trace:
                    src = os.path.join(ROOT, ".bench_trace", w)
                    for dirpath, _, files in os.walk(src):
                        for f in files:
                            if f.endswith(".xplane.pb"):
                                shutil.copy(os.path.join(dirpath, f),
                                            os.path.join(args.out,
                                                         f"{w}.xplane.pb"))
            sets = [{k: set_summary(v) for k, v in vals.items()}
                    for vals in per_set]
            summary[w] = {"sets": sets, "admission": {}}
            print("SUMMARY", w, json.dumps(sets), flush=True)
            for k, bound in bound_of.items():
                units = [s[k]["trimmed_unit"] for s in sets if k in s]
                if len(units) < 2 or None in units:
                    continue
                runs = [v for vals in per_set for v in vals.get(k, ())]
                adm = admission(units, bound, statistics.median(runs))
                summary[w]["admission"][k] = adm
                print("ADMISSION", w, k, f"mean={adm['mean']:.6g}",
                      f"half_bound={adm['half_bound']:.6g}", adm["verdict"],
                      flush=True)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
