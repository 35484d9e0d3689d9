#!/usr/bin/env python3
"""Several runs of one or more cells in one call, each run a new process of
`run.py`, one after the other (this parent never imports JAX, so it never
holds the chip). Prints each run's result line and, per cell and metric,
the median and the spread as the contract measures it (inter-quartile
distance over the median); writes everything to `--out`.

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

from benchmarks.harness.stats import spread  # noqa: E402  (no JAX in there)


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
    summary = {}
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
            summary[w] = [
                {k: {"median": statistics.median(v),
                     "spread": spread(v) if len(v) > 1 else None,
                     "first": v[0], "n": len(v)} for k, v in vals.items()}
                for vals in per_set]
            print("SUMMARY", w, json.dumps(summary[w]), flush=True)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
