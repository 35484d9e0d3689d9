"""A tiny copy of the benchmark for the CPU: the real BENCHMARK.json and data
files with the sizes cut (each configuration's own `tiny` model, short
sequences, few clients), written into a temporary root. Derived from the real
files, so a cell that a later PR adds is rehearsed too, whatever its family."""

import copy
import json
import os
import shutil

from benchmarks.harness.spec import REPO_ROOT, share_ways

TINY_SERVING = {"num_blocks": 40, "block_size": 16, "max_seqs": 4,
                "prefill_chunk": 32, "max_model_len": 128}


def shrink_traffic(t: dict) -> dict:
    t = copy.deepcopy(t)
    if t["kind"] == "train":
        t["sequence"], t["steps_per_group"] = 64, 2
        t["engine"]["train_micro_batch_size_per_gpu"] = 2
        t["engine"].pop("bf16", None)
        t["model_options"] = {}
        t["reference"]["loss_atol"] = 1e-3   # float32 on both sides here
    else:
        t["clients"], t["requests"] = 3, 8
        t["prompt_tokens"] = {"dist": "uniform", "min": 10, "max": 70}
        t["output_tokens"] = {"dist": "uniform", "min": 3, "max": 9}
        # long enough for every variant of the two programs to have compiled
        # before the window opens, also when six test workers share the host
        t["warm_loop_s"] = 1.5
        t["reference"]["logprob_atol"] = 1e-3
        if "arrivals" in t:
            t["arrivals"]["rate"] = 20.0
    return t


def tiny_config(cfg: dict) -> dict:
    """The configuration's rehearsal, written as a configuration: its `tiny`
    model in place of the real one, and `published` holding that model's
    sizes. So the file validates under the same rules as the real one, and
    the reference is called with the small model's arguments. Where the file
    states a `share`, the block stays and `published` holds the WHOLE count
    of each divided key: the smallest number the small model runs under it
    (a router may keep the whole) times that key's own ways."""
    cfg = copy.deepcopy(cfg)
    tiny = cfg["tiny"]
    cfg["model"] = {k: tiny[k] for k in ("preset", "dtype", "overrides")}
    for key, source in cfg["widths"].items():
        cfg["published"][source] = tiny["overrides"][key]
    for source, ways in share_ways(cfg).items():
        cfg["published"][source] = ways * min(
            tiny["overrides"][k] for k, s in cfg["widths"].items()
            if s == source)
    for key, other in cfg.get("equal_widths", {}).items():
        cfg["published"][key] = cfg["published"][other]
    for arg, value in tiny["reference_args"].items():
        cfg["published"][cfg["reference_args"][arg]["published"]] = value
    if "serving" in cfg:
        cfg["serving"] = dict(TINY_SERVING)
    return cfg


def make_root(tmp: str) -> str:
    real = json.load(open(os.path.join(REPO_ROOT, "BENCHMARK.json")))
    bench = os.path.join(REPO_ROOT, real["paths"][0])
    out = os.path.join(tmp, real["paths"][0])
    os.makedirs(os.path.join(out, "configs"))
    os.makedirs(os.path.join(out, "traffic"))
    shutil.copytree(os.path.join(bench, "layer_metrics"),
                    os.path.join(out, "layer_metrics"))
    shutil.copytree(os.path.join(bench, "reducers"),
                    os.path.join(out, "reducers"))
    shutil.copytree(os.path.join(bench, "references"),
                    os.path.join(out, "references"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for c in real["configs"]:
        cfg = json.load(open(os.path.join(REPO_ROOT, c["file"])))
        json.dump(tiny_config(cfg), open(os.path.join(tmp, c["file"]), "w"))
    for w in real["workloads"]:
        t = json.load(open(os.path.join(bench, "traffic",
                                        f"{w['traffic']}.json")))
        t = shrink_traffic(t)
        json.dump(t, open(os.path.join(out, "traffic",
                                       f"{w['traffic']}.json"), "w"))
    json.dump(real, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return tmp
