"""The cell `longcat-flash-chat-ep32-d4.serve-ctx4k-r32` as files: the
configuration is the source's `config.json` key for key with the three cuts
it lists (4 of 28 double layers, 16 of 512 routed experts held, an eighth of
the vocabulary) as one of 32 chips that share each layer, stated in the
OBJECT form of `share.divided`; the traffic is what its issue names; the
arena holds every row's whole length at once in 8 pools of latents and no
keys or values; the cell reports what its entries say (each found BY NAME,
never by its place in a list); the three readers it brings give
hand-reckoned numbers, and the cost file counts what the mathematics of an
absorbed read needs. (That the cell runs end to end at its `tiny` size,
`correct` included, is also `test_benchmark_harness.py`'s, which finds every
cell by name; here the tiny rehearsal is held to what is this cell's own:
the placement over the ROUTED outputs alone and the spans' counts.)"""

import json
import os
import types

import pytest

import bench_tiny
import live_document
from benchmarks.harness import layers, spec as spec_mod
from benchmarks.reducers import latent_decode_attention_cost

SPEC = spec_mod.Spec()
CONFIG = "longcat-flash-chat-ep32-d4"
CELL = CONFIG + ".serve-ctx4k-r32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("latent_decode_attention_roofline", "latent_attention_time_pct",
       "moe_zero_assignments_pct")
AS_RUN = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
# the program's sizes at the published widths, as the cost function reads them
MODEL = types.SimpleNamespace(num_layers=4, num_heads=64, kv_lora_rank=512,
                              rotary_dim=64)


def test_the_file_is_the_sources_config_with_the_cuts_it_lists():
    cfg = SPEC.cell(CELL).config
    published = cfg["published"]
    assert set(cfg["reduced"]) == set(AS_RUN)
    for key, value in published.items():
        assert cfg[key] == AS_RUN.get(key, value), key
    share = cfg["share"]
    assert share["chips"] == 32 and share["placement"] == "balanced"
    assert share["divided"] == {"n_routed_experts": 32, "vocab_size": 8}
    assert spec_mod.share_ways(cfg) == {"n_routed_experts": 32,
                                        "vocab_size": 8}
    assert spec_mod.expert_ways(cfg) == 32
    over = cfg["model"]["overrides"]
    # every width, the router's 512 + 256 outputs and its 12 a token
    assert [over[k] for k in (
        "hidden_size", "dense_ffn_hidden_size", "ffn_hidden_size",
        "num_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "rotary_dim", "v_head_dim", "moe_num_experts", "moe_zero_experts",
        "moe_top_k")] == [6144, 12288, 2048, 64, 1536, 512, 128, 64, 128,
                          512, 256, 12]
    assert over["moe_experts_held"] * 32 == published["n_routed_experts"]
    assert over["vocab_size"] * 8 == published["vocab_size"]
    assert over["num_layers"] == spec_mod.MIN_LAYERS_OF_A_SHARE
    assert cfg["model"]["dtype"] == "bfloat16"
    # what the catalog's config does not carry is said, not silently chosen
    for key in ("dtype", "mla_scales", "inner_norm_eps", "attention", "rope",
                "router", "shortcut", "cached", "weights", "mtp"):
        assert cfg["assumed"][key]
    assert cfg["deployment"] and cfg["share"]["how"]
    entry = live_document.named(SPEC.doc["configs"], CONFIG)
    assert sorted(entry["reduced"]) == sorted(AS_RUN)
    assert entry["file"].endswith(CONFIG + ".json")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row():
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "LongCat-Flash-Chat")
    cfg = SPEC.cell(CELL).config
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    assert live_document.named(SPEC.doc["configs"], CONFIG)["source"] \
        == row["source_url"]
    from deepspeed_tpu.models.presets import transformer_config

    program = transformer_config(cfg["model"]["preset"])
    for key, source in cfg["widths"].items():
        if key != "moe_experts_held":       # the whole model holds them all
            assert getattr(program, key) == row["config"][source], key
    assert program.layer_pattern == ("shortcut",)
    assert (program.moe_routed_scale, program.rope_theta,
            program.norm_eps) == (row["config"]["routed_scaling_factor"],
                                  row["config"]["rope_theta"],
                                  row["config"]["rms_norm_eps"])


def test_the_traffic_is_what_the_issue_names():
    t = SPEC.cell(CELL).traffic
    assert (t["kind"], t["clients"], t["requests"], t["pairing_seed"]) \
        == ("closed_loop", 32, 128, 1)
    # the issue's form, or its ONE named fallback (narrower bands, the same
    # means): which was admitted is PERF.md's to say
    assert (t["prompt_tokens"], t["output_tokens"]) in (
        ({"dist": "uniform", "min": 3072, "max": 4096},
         {"dist": "uniform", "min": 768, "max": 1024}),
        ({"dist": "uniform", "min": 3584, "max": 4096},
         {"dist": "uniform", "min": 896, "max": 1024}))
    assert t["sampling"] == {"temperature": 0.0}
    assert t["reference"]["max_tokens"] == 5120
    assert t["reference"]["reason"] and t["reference"]["logprob_atol"] > 0
    assert "shared_prefix" not in t


def test_every_row_fits_the_arena_at_once():
    """32 rows of `max_model_len` tokens are all the blocks there are (the
    engine adds the scratch block): no request is ever preempted, whatever
    the seed's order; the pools are 8 x 640 lanes of bfloat16 a token (576
    values kept), 1.68 GB."""
    cell = SPEC.cell(CELL)
    s, t = cell.config["serving"], cell.traffic
    assert s["max_seqs"] == t["clients"] == 32
    assert s["num_blocks"] * s["block_size"] \
        == s["max_seqs"] * s["max_model_len"]
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] \
        == s["max_model_len"] == t["reference"]["max_tokens"]
    assert s["prefill_chunk"] == 1024
    arena = (s["num_blocks"] + 1) * s["block_size"] * 8 * 640 * 2
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
    # a share's touched experts are read over the HELD ones, never the
    # router's width; other models' kernels and states are not this cell's
    assert "moe_experts_touched_pct" not in names
    assert not {n for n in names if n.startswith((
        "kda_", "mamba", "ssm_", "recurrent_", "train_", "flash_",
        "shared_kv", "window_", "serve_state_", "looped_", "loop_",
        "paged_", "latent_moe_"))}
    assert all(m["moves"] == "itl_p50_ms" for m in cell.per_layer)
    # every reader that every other serving cell carries, this one does too
    others = [c for c in live_document.serving_cells(SPEC) if c != CELL]
    for m in SPEC.doc["per_layer"]:
        if all(c in m["workloads"] for c in others):
            assert CELL in m["workloads"], m["name"]


@pytest.mark.parametrize("name", NEW)
def test_the_metric_is_declared_and_equal_to_its_file(name):
    m = live_document.is_what_its_file_gives(SPEC, name, cells=[CELL])
    assert m["moves"] == "itl_p50_ms" and m["unit"] == "%"
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


def test_the_kernel_has_a_name_of_its_own():
    """The absorbed walk is `latent_decode_attention` in a trace, whatever
    implements it: both readers find it by that name, inside executions of
    `serving/decode`, and no accepted reader's kernel is called so."""
    from deepspeed_tpu.ops.paged_decode_attention import LATENT_DECODE

    roof = SPEC.reader("latent_decode_attention_roofline")
    share = SPEC.reader("latent_attention_time_pct")
    assert roof["args"] == {"kernel": LATENT_DECODE,
                            "cost": "latent_decode_attention_cost"}
    assert share["args"] == {"kernels": [LATENT_DECODE],
                             "program": "serving/decode"}
    assert SPEC.reader("paged_decode_attention_roofline")["args"][
        "kernel"] != LATENT_DECODE
    assert os.path.exists(SPEC.path("programs", "serving", "decode.json"))


def _ctx(model_config, records=(), traced=None):
    return layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                          counters={}, model_config=model_config,
                          records=list(records), traced=traced)


def _row(prompt, times):
    return types.SimpleNamespace(prompt_len=prompt, token_times=list(times))


# two requests; the traced second is [10, 11]: the first has tokens 2 and 3
# in it (contexts 4000 + 2 and 4000 + 3), the second its first token (a
# prefill's, no decode row) and token 1 (context 3100 + 1)
RECORDS = [_row(4000, [9.0, 9.5, 10.2, 10.8, 11.5]), _row(3100, [10.1, 10.9])]


def test_the_cost_file_counts_what_the_mathematics_needs():
    ctx = _ctx(MODEL, RECORDS, traced=(10.0, 11.0))
    ops, nbytes = latent_decode_attention_cost.total(ctx, calls=3 * 8)
    contexts = (4002, 4003, 3101)
    # by hand: 8 pools; a head's score over 576 values and its sum over 512,
    # 2 flops each, 64 heads: 139,264 a cached token; 1,152 B a token read
    # ONCE, whole pages of 16, and the row's queries in and latents out
    assert ops == 8 * 139_264 * sum(contexts)
    assert nbytes == 8 * (1152 * (4016 + 4016 + 3104)
                          + 3 * 2 * 64 * (576 + 512))
    assert 115 < ops / nbytes < 122     # near the chip's ridge of 240
    # and not what the first form moves: the pad lanes and the second copy
    assert nbytes < 8 * 2 * 640 * 2 * sum(contexts)


@pytest.mark.parametrize("why", ["no-records", "no-traced-seconds",
                                 "no-token-inside", "no-latent-pool"])
def test_the_cost_file_finds_nothing_to_read(why):
    records, traced, model = RECORDS, (10.0, 11.0), MODEL
    if why == "no-records":
        records = []
    elif why == "no-traced-seconds":
        traced = None
    elif why == "no-token-inside":
        traced = (20.0, 21.0)
    else:       # another model's config, the parent's: no such size
        model = types.SimpleNamespace(num_layers=4, num_heads=64)
    assert latent_decode_attention_cost.total(
        _ctx(model, records, traced=traced), calls=1) is None


def test_the_roofline_and_the_share_on_a_small_trace():
    """Three walks of 300 us inside two executions of `serving/decode` of
    1,000 us each, and a walk outside any (the share does not count it; the
    roofline, which the kernel's name alone finds, does)."""
    from benchmarks.harness.trace import Trace

    module = json.load(open(SPEC.path("programs", "serving",
                                      "decode.json")))["module"]
    walk = "latent_decode_attention"
    trace = Trace(
        ops={0: [(walk, 10.0001, 300e-6), (walk + ".1", 10.0005, 300e-6),
                 (walk, 10.5001, 300e-6), (walk, 10.9, 300e-6)]},
        modules={0: [(module, 10.0, 1000e-6), (module, 10.5, 1000e-6)]},
        host={})
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = layers.Context(cell=SPEC.cell(CELL), chips=1, peaks=peaks,
                         counters={}, model_config=MODEL,
                         records=RECORDS, traced=(10.0, 11.0), trace=trace)
    share = SPEC.reader("latent_attention_time_pct")
    got = layers.reducer(share["reducer"]).reduce(ctx, **share["args"])
    assert got == pytest.approx(100 * 900 / 2000)
    roof = SPEC.reader("latent_decode_attention_roofline")
    got = layers.reducer(roof["reducer"]).reduce(ctx, **roof["args"])
    ops, nbytes = latent_decode_attention_cost.total(ctx, calls=4)
    assert got == pytest.approx(
        100 * max(ops / 197e12, nbytes / 819e9) / 1200e-6)
    assert nbytes / 819e9 > ops / 197e12    # by a factor of two: bytes bind
    # no trace: both are left out of the line
    bare = _ctx(MODEL, RECORDS, traced=(10.0, 11.0))
    for r in (share, roof):
        assert layers.reducer(r["reducer"]).reduce(bare, **r["args"]) is None


def test_the_tiny_rehearsal_places_the_routed_outputs_and_counts_zeros(
        tmp_path, monkeypatch, capsys):
    """The cell end to end at its `tiny` size on the CPU, through `run.py`'s
    own path with a capture open: the placement over 8 held of 256 routed
    outputs (the 128 zero-computation ones stay behind them), `correct`
    against the reference, no failed request, nothing preempted, and
    `moe_zero_assignments_pct` as the reader finds it in the spans the
    program recorded: 128 of 384 outputs are zero-computation ones, and at a
    width of 64 the scores are so flat that the choice-only bias moves the
    share far from a third."""
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
    assert cell.config["share"]["divided"] == {"n_routed_experts": 32,
                                               "vocab_size": 8}
    assert cell.config["published"]["n_routed_experts"] == 256
    assert cell.config["published"]["zero_expert_num"] == 128
    result = bench_run.run_cell(tiny, CELL, 2 ** 31 + 11, 3.0, True,
                                time.perf_counter())
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["rehearsal"] and result["metrics"]["serve_preemptions"][
        "value"] == 0
    placed = next(json.loads(ln)["placement"] for ln in out.splitlines()
                  if ln.startswith('{"placement"'))
    assert placed["experts_held"] == 8
    assert len(placed["held_part_of_assignments"]) == 4
    r = tiny.reader("moe_zero_assignments_pct")
    got = layers.reducer(r["reducer"]).reduce(
        layers.Context(cell=cell, chips=1, peaks={}, counters={},
                       model_config=None), **r["args"])
    assert 5.0 < got < 60.0
