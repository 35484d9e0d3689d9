"""The cell `ouro-2.6b.serve-short-r16` as files: the configuration is the
source's `config.json` key for key with NOTHING cut (48 layers, 4 passes,
the whole vocabulary), the traffic is what its issue names, the arena holds
every row's whole length at once in 192 pools, the cell reports what its
entries say (each found BY NAME, never by its place in a list), the three
readers it brings give hand-reckoned numbers, and the cost file counts
4 x 48 walks of a row's pages a decode step. (That the cell runs end to end
at its `tiny` size, `correct` included, is also `test_benchmark_harness.py`'s,
which finds every cell by name; here the tiny rehearsal is held to what is
this cell's own: three passes, a threshold under 1, the spans' counts.)"""

import json
import os
import types

import pytest

import bench_tiny
import live_document
from benchmarks.harness import layers, spec as spec_mod
from benchmarks.reducers import (looped_decode_attention_cost,
                                 paged_attention_cost,
                                 paged_decode_attention_cost)

SPEC = spec_mod.Spec()
CONFIG = "ouro-2.6b"
CELL = CONFIG + ".serve-short-r16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("looped_decode_attention_roofline", "looped_attention_time_pct",
       "loop_passes_per_step")
# the program's sizes at the published widths, as the cost functions read them
MODEL = types.SimpleNamespace(num_layers=48, loop_passes=4, num_heads=16,
                              num_kv_heads=16, head_dim=128)


def test_the_file_is_the_sources_config_and_nothing_is_cut():
    cfg = SPEC.cell(CELL).config
    published = cfg["published"]
    assert cfg["reduced"] == {} and "share" not in cfg
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["model"]["overrides"] == {
        "hidden_size": 2048, "ffn_hidden_size": 5632, "num_heads": 16,
        "num_kv_heads": 16, "head_size": 128, "vocab_size": 49152,
        "num_layers": 48, "loop_passes": 4, "loop_exit_threshold": 1}
    assert cfg["model"]["dtype"] == "bfloat16"
    # the passes and the threshold are held to the source like sizes, and
    # reach the reference from `published`, never from the program
    assert cfg["widths"]["loop_passes"] == "total_ut_steps"
    assert cfg["widths"]["loop_exit_threshold"] == "early_exit_threshold"
    assert set(cfg["reference_args"]) == {
        "total_ut_steps", "early_exit_threshold", "rms_norm_eps",
        "rope_theta", "num_attention_heads", "num_key_value_heads"}
    # what the catalog's config does not carry is said, not silently chosen
    for key in ("norms", "passes", "exit_gate", "biases", "weights", "dtype"):
        assert cfg["assumed"][key]
    entry = live_document.named(SPEC.doc["configs"], CONFIG)
    assert entry["reduced"] == [] and entry["file"].endswith(CONFIG + ".json")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row():
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "Ouro-2.6B")
    cfg = SPEC.cell(CELL).config
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    assert live_document.named(SPEC.doc["configs"], CONFIG)["source"] \
        == row["source_url"]
    from deepspeed_tpu.models.presets import transformer_config

    program = transformer_config(cfg["model"]["preset"])
    for key, source in cfg["widths"].items():
        assert getattr(program, key) == row["config"][source], key
    assert program.norm_position == "sandwich" and not program.layer_pattern


def test_a_pass_fewer_is_refused_before_a_run(tmp_path):
    """`loop_passes` is mapped like a size: a file that runs three passes
    for the published four does not validate."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmarks"))
    for sub in ("configs", "traffic", "layer_metrics", "reducers",
                "references"):
        os.symlink(SPEC.path(sub), os.path.join(root, "benchmarks", sub))
    doc = json.loads(json.dumps(SPEC.doc))
    entry = live_document.named(doc["configs"], CONFIG)
    entry["file"] = "ouro-3-passes.json"
    cfg = json.loads(json.dumps(SPEC.cell(CELL).config))
    cfg["model"]["overrides"]["loop_passes"] = 3
    doc["paths"] = ["benchmarks", "."]
    json.dump(cfg, open(os.path.join(root, entry["file"]), "w"))
    entry["file"] = "./" + entry["file"]
    json.dump(doc, open(os.path.join(root, "BENCHMARK.json"), "w"))
    with pytest.raises(spec_mod.SpecError, match="loop_passes is 3"):
        spec_mod.Spec(root).validate()


def test_the_traffic_is_what_the_issue_names():
    t = SPEC.cell(CELL).traffic
    assert (t["kind"], t["clients"], t["requests"], t["pairing_seed"],
            t["warm_loop_s"]) == ("closed_loop", 16, 64, 1, 8)
    assert t["prompt_tokens"] == {"dist": "log_uniform", "min": 32,
                                  "max": 128}
    assert t["output_tokens"] == {"dist": "log_uniform", "min": 64,
                                  "max": 192}
    assert t["sampling"] == {"temperature": 0.0}
    assert t["reference"]["max_tokens"] == 320
    assert t["reference"]["reason"] and t["reference"]["logprob_atol"] > 0
    assert "shared_prefix" not in t


def test_every_row_fits_the_arena_at_once():
    """16 rows of `max_model_len` tokens are all the blocks there are (the
    engine adds the scratch block): no request is ever preempted, whatever
    the seed's order; a prompt is ONE chunk; the pages are 8.08 GB."""
    cell = SPEC.cell(CELL)
    s, t = cell.config["serving"], cell.traffic
    assert s["max_seqs"] == t["clients"] == 16
    assert s["num_blocks"] * s["block_size"] \
        == s["max_seqs"] * s["max_model_len"]
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] \
        <= s["max_model_len"]
    assert t["prompt_tokens"]["max"] <= s["prefill_chunk"]
    assert t["reference"]["max_tokens"] <= s["max_model_len"]
    arena = (s["num_blocks"] + 1) * s["block_size"] * 1_572_864
    assert arena == 321 * 24 * 2 ** 20
    assert s["arena_share_of_chip"] == pytest.approx(
        arena / 16_911_433_728, abs=1e-4)


def test_the_cell_reports_what_its_entries_say():
    cell = SPEC.cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == ["itl_p50_ms",
                                                          "setup_s"]
    assert live_document.named(SPEC.doc["workloads"], CELL)["chips"] == 1
    names = {m["name"] for m in cell.per_layer}
    assert {"serve_decode_iter_ms", "serve_idle_pct",
            "serve_compiles_in_window", "serve_preemptions",
            "serve_host_decode_ms", "serve_arena_resident_pct"} <= names
    assert set(NEW) <= names
    # other models' kernels, routers and states are not this cell's
    assert not {n for n in names if n.startswith((
        "moe_", "kda_", "mamba", "ssm_", "recurrent_", "train_", "flash_",
        "shared_kv", "window_", "serve_state_"))}
    assert all(m["moves"] == "itl_p50_ms" for m in cell.per_layer)
    # every reader that every other serving cell carries, this one does too
    others = [c for c in live_document.serving_cells(SPEC) if c != CELL]
    for m in SPEC.doc["per_layer"]:
        if all(c in m["workloads"] for c in others):
            assert CELL in m["workloads"], m["name"]


@pytest.mark.parametrize("name", NEW)
def test_the_metric_is_declared_and_equal_to_its_file(name):
    m = live_document.is_what_its_file_gives(SPEC, name, cells=[CELL])
    assert m["moves"] == "itl_p50_ms"
    assert (m["unit"] == "%") if name.endswith(("_roofline", "_pct")) \
        else m["unit"] == "count"
    assert m["layer"] in ("kernels", "model")
    r = SPEC.reader(name)
    assert os.path.exists(SPEC.path("reducers", r["reducer"] + ".py"))
    assert hasattr(layers.reducer(r["reducer"]), "reduce")
    if "cost" in r.get("args", {}):
        assert hasattr(layers.reducer(r["args"]["cost"]), "total")


def test_no_other_cell_reports_the_three():
    for w in SPEC.doc["workloads"]:
        if w["name"] != CELL:
            assert not set(NEW) & {m["name"]
                                   for m in SPEC.cell(w["name"]).per_layer}


def test_the_kernel_keeps_its_name_and_the_program_its_file():
    """The walk is `paged_decode_attention`, the kernel of the other dense
    and expert cells: both readers find it by that name, inside executions
    of `serving/decode`."""
    roof = SPEC.reader("looped_decode_attention_roofline")
    share = SPEC.reader("looped_attention_time_pct")
    accepted = SPEC.reader("paged_decode_attention_roofline")
    assert roof["args"]["kernel"] == accepted["args"]["kernel"] \
        == "paged_decode_attention"
    assert roof["args"]["cost"] == "looped_decode_attention_cost"
    assert share["args"] == {"kernels": ["paged_decode_attention"],
                             "program": "serving/decode"}
    assert os.path.exists(SPEC.path("programs", "serving", "decode.json"))


def _ctx(model_config, records=(), traced=None):
    return layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                          counters={}, model_config=model_config,
                          records=list(records), traced=traced)


def _row(prompt, times):
    return types.SimpleNamespace(prompt_len=prompt, token_times=list(times))


# two requests; the traced second is [10, 11]: the first has tokens 2 and 3
# in it (contexts 100 + 2 and 100 + 3), the second its first token (a
# prefill's, no decode row) and token 1 (context 60 + 1)
RECORDS = [_row(100, [9.0, 9.5, 10.2, 10.8, 11.5]), _row(60, [10.1, 10.9])]


def test_the_cost_file_counts_four_times_forty_eight_walks():
    ctx = _ctx(MODEL, RECORDS, traced=(10.0, 11.0))
    ops, nbytes = looped_decode_attention_cost.total(ctx, calls=3 * 4)
    rows = [paged_attention_cost.decode_row(ctx, n) for n in (102, 103, 61)]
    assert ops == 192 * sum(o for o, _ in rows)
    assert nbytes == 192 * sum(b for _, b in rows)
    # by hand: 4 N D flops a key; pages of 16 tokens, K and V, 2,048 wide in
    # bfloat16, and the queries in and the outputs out
    assert ops == 192 * 4 * 2048 * (102 + 103 + 61)
    assert nbytes == 192 * (2 * (112 + 112 + 64) * 2048 * 2
                            + 3 * 2 * 2048 * 2)
    # four times what the accepted cost file counts for a stack run once
    once_ops, once_bytes = paged_decode_attention_cost.total(ctx, calls=3)
    assert (ops, nbytes) == (4 * once_ops, 4 * once_bytes)
    # memory-bound by far: a flop and a bit a byte
    assert ops / nbytes < 2


@pytest.mark.parametrize("why", ["no-records", "no-traced-seconds",
                                 "no-token-inside"])
def test_the_cost_file_finds_nothing_to_read(why):
    records, traced = RECORDS, (10.0, 11.0)
    if why == "no-records":
        records = []
    elif why == "no-traced-seconds":
        traced = None
    else:
        traced = (20.0, 21.0)
    assert looped_decode_attention_cost.total(
        _ctx(MODEL, records, traced=traced), calls=1) is None


def test_the_roofline_and_the_share_on_a_small_trace():
    """Three walks of 30 us inside two executions of `serving/decode` of
    100 us each, and a walk outside any (another program's: the share does
    not count it; the roofline, which the kernel's name alone finds, does)."""
    from benchmarks.harness.trace import Trace

    module = json.load(open(SPEC.path("programs", "serving",
                                      "decode.json")))["module"]
    walk = "paged_decode_attention"
    trace = Trace(
        ops={0: [(walk, 10.00001, 30e-6), (walk + ".1", 10.00005, 30e-6),
                 (walk, 10.50001, 30e-6), (walk, 10.9, 30e-6)]},
        modules={0: [(module, 10.0, 100e-6), (module, 10.5, 100e-6)]},
        host={})
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = layers.Context(cell=SPEC.cell(CELL), chips=1, peaks=peaks,
                         counters={}, model_config=MODEL,
                         records=RECORDS, traced=(10.0, 11.0), trace=trace)
    share = SPEC.reader("looped_attention_time_pct")
    got = layers.reducer(share["reducer"]).reduce(ctx, **share["args"])
    assert got == pytest.approx(100 * 90 / 200)
    roof = SPEC.reader("looped_decode_attention_roofline")
    got = layers.reducer(roof["reducer"]).reduce(ctx, **roof["args"])
    _, nbytes = looped_decode_attention_cost.total(ctx, calls=4)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 120e-6)
    # no trace: both are left out of the line
    bare = _ctx(MODEL, RECORDS, traced=(10.0, 11.0))
    for r in (share, roof):
        assert layers.reducer(r["reducer"]).reduce(bare, **r["args"]) is None


def test_the_tiny_rehearsal_runs_three_passes_under_a_threshold(
        tmp_path, monkeypatch, capsys):
    """The cell end to end at its `tiny` size on the CPU, through `run.py`'s
    own path with a capture open: `correct` against the reference (three
    passes, a threshold of 0.6 handed over from `published`), no failed
    request, nothing preempted, and `loop_passes_per_step` as the reader
    finds it in the spans the program recorded (a CPU run's line keeps
    counts only, so the reader is asked here)."""
    import time

    from benchmarks import run as bench_run
    from benchmarks.harness import device

    root = bench_tiny.make_root(str(tmp_path))
    monkeypatch.setitem(device.TARGET, "platform", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(root, ".jax_cache"))
    tiny = spec_mod.Spec(root)
    tiny.validate()
    cell = tiny.cell(CELL)
    assert cell.config["published"]["total_ut_steps"] == 3
    assert cell.config["published"]["early_exit_threshold"] == 0.6
    assert cell.config["model"]["overrides"]["loop_passes"] == 3
    result = bench_run.run_cell(tiny, CELL, 2 ** 31 + 11, 3.0, True,
                                time.perf_counter())
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["rehearsal"] and result["metrics"]["serve_preemptions"][
        "value"] == 0
    r = tiny.reader("loop_passes_per_step")
    got = layers.reducer(r["reducer"]).reduce(
        layers.Context(cell=cell, chips=1, peaks={}, counters={},
                       model_config=None), **r["args"])
    assert got == 3.0
