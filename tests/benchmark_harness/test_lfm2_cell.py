"""The cell `lfm2-8b-a1b-d12.serve-docs-r16` as files: the configuration is
the source's `config.json` key for key with the ONE cut it lists (the first
12 of 24 layers, the first stage of a two-stage pipeline; every width, all
32 experts, both leading dense layers and the whole vocabulary as published;
no `share`: nothing is divided); the traffic is what its issue names; the
arena holds every row's whole length at once in 3 pools of keys and values,
and the state slots a tail of two rows a convolution layer and nothing else;
the cell reports what its entries say (each found BY NAME, never by its
place in a list); the four readers it brings are what their files give, and
the two that read the program's spans have their known number in
`fixtures/spans/conv_rows_and_chunk_experts.json`. (That the cell runs end
to end at its `tiny` size, `correct` included, is also
`test_benchmark_harness.py`'s, which finds every cell by name; here the
tiny rehearsal is held to what is this cell's own: the spans' counts.)"""

import json
import os

import pytest

import bench_tiny
import live_document
from benchmarks.harness import layers, spec as spec_mod

SPEC = spec_mod.Spec()
CONFIG = "lfm2-8b-a1b-d12"
CELL = CONFIG + ".serve-docs-r16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("moe_expert_time_pct.chunk", "paged_prefill_attention_time_pct.chunk",
       "moe_rows_per_expert.chunk", "serve_conv_rows_per_step")


def test_the_file_is_the_sources_config_with_the_cut_it_lists():
    cfg = SPEC.cell(CELL).config
    published = cfg["published"]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types"}
    as_run = {"num_hidden_layers": 12,
              "layer_types": published["layer_types"][:12]}
    for key, value in published.items():
        assert cfg[key] == as_run.get(key, value), key
    assert "share" not in cfg               # nothing is divided
    over = cfg["model"]["overrides"]
    # every width, all 32 experts and 4 a token, both leading dense layers
    assert [over[k] for k in (
        "hidden_size", "dense_ffn_hidden_size", "ffn_hidden_size",
        "num_heads", "num_kv_heads", "moe_num_experts", "moe_top_k",
        "num_dense_layers", "shortconv_taps", "vocab_size", "num_layers")] \
        == [2048, 7168, 1792, 32, 8, 32, 4, 2, 3, 65536, 12]
    assert cfg["model"]["dtype"] == "bfloat16"
    # three whole periods of c c A c
    assert as_run["layer_types"] == ["conv", "conv", "full_attention",
                                     "conv"] * 3
    # what the catalog's config does not carry is said, not silently chosen
    for key in ("tie_word_embeddings", "head_dim", "qk_layernorm", "router",
                "short_convolution", "norms", "dtype", "weights"):
        assert cfg["assumed"][key]
    assert cfg["deployment"]
    entry = live_document.named(SPEC.doc["configs"], CONFIG)
    assert sorted(entry["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert entry["file"].endswith(CONFIG + ".json")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row():
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "LFM2-8B-A1B")
    cfg = SPEC.cell(CELL).config
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    assert live_document.named(SPEC.doc["configs"], CONFIG)["source"] \
        == row["source_url"]
    from deepspeed_tpu.models.presets import (_SIZES, lfm2_runs,
                                              transformer_config)

    program = transformer_config(cfg["model"]["preset"])
    preset = _SIZES[cfg["model"]["preset"]]
    for key, source in cfg["widths"].items():
        got = getattr(program, key, preset.get(key))
        assert got == row["config"][source], key
    assert list(preset["layer_types"]) == row["config"]["layer_types"]
    assert program.layer_runs == lfm2_runs(row["config"]["layer_types"],
                                           row["config"]["num_dense_layers"])
    assert (program.moe_routed_scale, program.rope_theta, program.norm_eps,
            program.moe_norm_topk_prob, program.moe_router_bias) == (
        row["config"]["routed_scaling_factor"], row["config"]["rope_theta"],
        row["config"]["norm_eps"], row["config"]["norm_topk_prob"],
        row["config"]["use_expert_bias"])
    assert program.qk_norm == "head" and program.tie_embeddings


def test_the_traffic_is_what_the_issue_names():
    t = SPEC.cell(CELL).traffic
    assert (t["kind"], t["clients"], t["requests"]) == ("closed_loop", 16, 64)
    # the issue's form, or its ONE named fallback (a narrower band of
    # prompts): which was admitted is PERF.md's to say
    assert t["prompt_tokens"] in (
        {"dist": "uniform", "min": 4096, "max": 8192},
        {"dist": "uniform", "min": 6144, "max": 8192})
    assert t["output_tokens"] == {"dist": "uniform", "min": 32, "max": 64}
    assert t["sampling"] == {"temperature": 0.0}
    assert t["reference"]["max_tokens"] == 8320
    assert t["reference"]["reason"] and t["reference"]["logprob_atol"] > 0
    assert "shared_prefix" not in t


def test_every_row_fits_the_arena_at_once():
    """16 rows of `max_model_len` tokens are all the blocks there are (the
    engine adds the scratch block): no request is ever preempted, whatever
    the seed's order; the pools are 3 layers x K and V x 512 values of
    bfloat16 a token, 0.82 GB."""
    cell = SPEC.cell(CELL)
    s, t = cell.config["serving"], cell.traffic
    assert s["max_seqs"] == t["clients"] == 16
    assert s["num_blocks"] * s["block_size"] \
        == s["max_seqs"] * s["max_model_len"]
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] \
        <= s["max_model_len"] == t["reference"]["max_tokens"]
    assert s["prefill_chunk"] == 1024
    arena = (s["num_blocks"] + 1) * s["block_size"] * 3 * 2 * 512 * 2
    assert s["arena_share_of_chip"] == pytest.approx(
        arena / 16_911_433_728, abs=1e-4)
    from benchmarks.harness.program import build_model
    from deepspeed_tpu.inference.kv_cache import (paged_cache_memory_bytes,
                                                  state_pool_memory_bytes)
    import jax.numpy as jnp

    cfg = build_model(cell).config
    assert paged_cache_memory_bytes(cfg, s["num_blocks"] + 1,
                                    s["block_size"], jnp.bfloat16) == arena
    # 17 slots of 9 convolution layers x 2 rows x 2,048 values: no state
    assert state_pool_memory_bytes(cfg, s["max_seqs"] + 1, jnp.bfloat16) \
        == 17 * 73_728


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
    # other models' kernels and states are not this cell's
    assert not {n for n in names if n.startswith((
        "kda_", "mamba", "ssm_", "recurrent_", "train_", "flash_",
        "shared_kv", "window_", "looped_", "loop_", "latent_",
        "moe_held_", "moe_zero_"))}
    assert all(m["moves"] == "itl_p50_ms" for m in cell.per_layer)
    # every reader that every other serving cell carries, this one does too
    others = [c for c in live_document.serving_cells(SPEC) if c != CELL]
    for m in SPEC.doc["per_layer"]:
        if all(c in m["workloads"] for c in others):
            assert CELL in m["workloads"], m["name"]


@pytest.mark.parametrize("name", NEW)
def test_the_metric_is_declared_and_equal_to_its_file(name):
    m = live_document.is_what_its_file_gives(SPEC, name, cells=[CELL])
    assert m["moves"] == "itl_p50_ms" and m["layer"] == "model"
    r = SPEC.reader(name)
    assert os.path.exists(SPEC.path("reducers", r["reducer"] + ".py"))
    assert hasattr(layers.reducer(r["reducer"]), "reduce")
    # every one reads a program that has its file, on a reducer that exists
    program = r["args"].get("program", r["args"].get("span"))
    assert os.path.exists(SPEC.path("programs", *program.split("/"))
                          + ".json")


def test_no_other_cell_reports_the_four():
    for w in SPEC.doc["workloads"]:
        if w["name"] != CELL:
            assert not set(NEW) & {m["name"]
                                   for m in SPEC.cell(w["name"]).per_layer}


def test_the_chunk_shares_on_a_small_trace():
    """Two chunks of 10 ms with 4 ms of grouped matmuls and 2.5 ms of the
    paged prefill kernel in each, a decode step whose grouped matmul must
    not count, and a kernel outside any program."""
    from benchmarks.harness.trace import Trace

    chunk = json.load(open(SPEC.path("programs", "serving",
                                     "prefill_chunk.json")))["module"]
    step = json.load(open(SPEC.path("programs", "serving",
                                    "decode.json")))["module"]
    gmm, attn = "moe_grouped_matmul", "paged_prefill_attention"
    trace = Trace(
        ops={0: [(gmm, 10.001, 2e-3), (gmm + ".1", 10.004, 2e-3),
                 (attn, 10.007, 2.5e-3),
                 (gmm, 10.0201, 5e-3),                  # in a decode step
                 (gmm, 10.051, 4e-3), (attn, 10.056, 2.5e-3),
                 (attn, 10.9, 2.5e-3)]},                # in no program
        modules={0: [(chunk, 10.0, 10e-3), (step, 10.02, 10e-3),
                     (chunk, 10.05, 10e-3)]},
        host={})
    ctx = layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                         counters={}, model_config=None, trace=trace)
    for name, want in (("moe_expert_time_pct.chunk", 40.0),
                       ("paged_prefill_attention_time_pct.chunk", 25.0)):
        r = SPEC.reader(name)
        assert layers.reducer(r["reducer"]).reduce(ctx, **r["args"]) \
            == pytest.approx(want)
        bare = layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                              counters={}, model_config=None)
        assert layers.reducer(r["reducer"]).reduce(bare, **r["args"]) is None


def test_the_tiny_rehearsal_counts_convolution_rows_and_chunk_experts(
        tmp_path, monkeypatch, capsys):
    """The cell end to end at its `tiny` size on the CPU, through `run.py`'s
    own path with a capture open: `correct` against the reference, no failed
    request, nothing preempted, and the two span readers as they find the
    counts the program recorded: 5 convolution layers a real row, and a
    chunk's assignments over the experts it touched (3 a token over 5
    expert layers of 8 experts: 10-70 tokens, so 3.75 to 26 rows an
    expert)."""
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
    assert cell.config["published"]["layer_types"] == [
        "conv", "conv", "full_attention", "conv", "conv", "full_attention",
        "conv"]
    result = bench_run.run_cell(tiny, CELL, 2 ** 31 + 11, 3.0, True,
                                time.perf_counter())
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["rehearsal"] and result["metrics"]["serve_preemptions"][
        "value"] == 0

    def read(name):
        r = tiny.reader(name)
        return layers.reducer(r["reducer"]).reduce(
            layers.Context(cell=cell, chips=1, peaks={}, counters={},
                           model_config=None), **r["args"])

    assert 5.0 <= read("serve_conv_rows_per_step") <= 5.0 * 4
    assert 3.0 < read("moe_rows_per_expert.chunk") < 27.0
