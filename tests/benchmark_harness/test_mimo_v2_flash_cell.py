"""The cell `mimo-v2-flash-ep16-d7.serve-ctx8k-r32` as files: the
configuration is the source's `config.json` key for key with the three cuts
it lists (7 of 48 layers: layer 0 and one whole period; 16 of 256 experts
held, an eighth of the vocabulary: one of 16 chips that share each layer);
every width, both counts of key-value heads, the window, both rope bases and
the router's 256 outputs as published; the traffic is what its issue names
(or its ONE named fallback); the arena holds pages for the 2 full layers and
a ring a slot for the 5 window layers, with no state beside them; the cell
reports what its entries say (each found BY NAME, never by its place in a
list); the five readers it brings are what their files give, the two
rooflines give hand-reckoned numbers with the layers OF THEIR FORM, and the
span reader has its known number in `fixtures/spans/ring_cache_share.json`.
(That the cell runs end to end at its `tiny` size, `correct` included, is
also `test_benchmark_harness.py`'s, which finds every cell by name; here the
tiny rehearsal is held to what is this cell's own: the rings' share.)"""

import json
import os
import types

import pytest

import bench_tiny
import live_document
from benchmarks.harness import layers, spec as spec_mod
from benchmarks.reducers import (full_kv_decode_attention_cost,
                                 mimo_v2_flash_costs,
                                 window_sink_decode_attention_cost)

SPEC = spec_mod.Spec()
CONFIG = "mimo-v2-flash-ep16-d7"
CELL = CONFIG + ".serve-ctx8k-r32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROOFLINES = ("window_sink_decode_attention_roofline",
             "full_kv_decode_attention_roofline")
SHARES = ("window_attention_time_pct", "full_attention_time_pct")
NEW = ROOFLINES + SHARES + ("serve_ring_cache_share_pct",)
# the program's sizes at the published widths, as the cost functions read them
MODEL = types.SimpleNamespace(
    layer_pattern=("full_dense",) + ("swa",) * 5 + ("full",),
    num_heads=64, num_kv_heads=4, window_kv_heads=8, head_dim=192,
    v_head_dim=128, attention_window=128, window_sink=True)


def test_the_file_is_the_sources_config_with_the_cuts_it_lists():
    cfg = SPEC.cell(CELL).config
    published = cfg["published"]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    as_run = {"num_hidden_layers": 7, "n_routed_experts": 16,
              "vocab_size": 19072}
    for key, value in published.items():
        assert cfg[key] == as_run.get(key, value), key
    share = cfg["share"]
    assert share["chips"] == 16 and share["placement"] == "balanced"
    assert share["divided"] == {"n_routed_experts": 16, "vocab_size": 8}
    over = cfg["model"]["overrides"]
    assert [over[k] for k in (
        "hidden_size", "dense_ffn_hidden_size", "ffn_hidden_size",
        "num_heads", "num_kv_heads", "window_kv_heads", "head_size",
        "v_head_dim", "attention_window", "moe_num_experts",
        "moe_experts_held", "moe_top_k", "vocab_size", "num_layers")] \
        == [4096, 16384, 2048, 64, 4, 8, 192, 128, 128, 256, 16, 8, 19072, 7]
    assert cfg["model"]["dtype"] == "bfloat16"
    assert cfg["equal_widths"] == {
        "swa_num_attention_heads": "num_attention_heads",
        "swa_head_dim": "head_dim", "swa_v_head_dim": "v_head_dim"}
    # 2 of 7 layers are full where the source has 9 of 48, and the first six
    # published layers hold four window layers: both said in the file
    said = cfg["reduced"]["num_hidden_layers"]
    assert "9 of 48" in said and "four window layers" in said
    for key in ("dtype", "rope", "value_scale", "window", "sink", "heads",
                "router", "ffn", "norms", "mtp", "weights"):
        assert cfg["assumed"][key]
    assert cfg["deployment"]
    entry = live_document.named(SPEC.doc["configs"], CONFIG)
    assert sorted(entry["reduced"]) == ["n_routed_experts",
                                        "num_hidden_layers", "vocab_size"]
    assert entry["file"].endswith(CONFIG + ".json")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row():
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "MiMo-V2-Flash")
    cfg = SPEC.cell(CELL).config
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    assert live_document.named(SPEC.doc["configs"], CONFIG)["source"] \
        == row["source_url"]
    from deepspeed_tpu.models.presets import (_SIZES, mimo_runs,
                                              transformer_config)

    program = transformer_config(cfg["model"]["preset"])
    preset = _SIZES[cfg["model"]["preset"]]
    for key, source in cfg["widths"].items():
        if key != "moe_experts_held":       # 0 in the whole model: all held
            assert getattr(program, key, preset.get(key)) \
                == row["config"][source], key
    published = row["config"]
    assert list(preset["hybrid_layer_pattern"]) \
        == published["hybrid_layer_pattern"]
    assert list(preset["moe_layer_freq"]) == published["moe_layer_freq"]
    assert program.layer_runs == mimo_runs(
        published["hybrid_layer_pattern"], published["moe_layer_freq"], 48)
    assert (program.rope_theta, program.window_rope_theta, program.norm_eps,
            program.value_scale, program.moe_norm_topk_prob,
            program.window_sink, program.rotary_dim) == (
        published["rope_theta"], published["swa_rope_theta"],
        published["layernorm_epsilon"], published["attention_value_scale"],
        published["norm_topk_prob"],
        published["add_swa_attention_sink_bias"],
        int(published["head_dim"] * published["partial_rotary_factor"]))
    assert not program.tie_embeddings and program.moe_router_bias
    assert program.moe_score_func == published["scoring_func"]


def test_the_traffic_is_what_the_issue_names():
    t = SPEC.cell(CELL).traffic
    assert (t["kind"], t["clients"], t["requests"], t["pairing_seed"]) == (
        "closed_loop", 32, 128, 1)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 6144,
                                  "max": 8192}
    # the issue's form, or its ONE named fallback (shorter answers): which
    # was admitted is PERF.md's to say
    assert t["output_tokens"] in (
        {"dist": "uniform", "min": 1536, "max": 2048},
        {"dist": "uniform", "min": 1024, "max": 1536})
    assert t["sampling"] == {"temperature": 0.0}
    assert t["reference"]["max_tokens"] == 10240
    assert t["reference"]["reason"] and t["reference"]["logprob_atol"] > 0
    assert "shared_prefix" not in t


def test_every_row_fits_the_arena_at_once():
    """32 rows of `max_model_len` tokens are all the blocks there are (the
    engine adds the scratch block): no request is ever preempted; the pages
    are 2 full layers x (768 + 512) lanes of bfloat16 a token, the rings 5
    window layers x (1,536 + 1,024) lanes for 72 pages a slot, and the
    slots hold nothing else."""
    cell = SPEC.cell(CELL)
    s, t = cell.config["serving"], cell.traffic
    assert s["max_seqs"] == t["clients"] == 32
    assert s["num_blocks"] * s["block_size"] \
        == s["max_seqs"] * s["max_model_len"]
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] \
        <= s["max_model_len"] == t["reference"]["max_tokens"]
    assert s["prefill_chunk"] == 1024
    arena = (s["num_blocks"] + 1) * s["block_size"] * 2 * (768 + 512) * 2
    assert s["arena_share_of_chip"] == pytest.approx(
        arena / 16_911_433_728, abs=1e-4)
    from benchmarks.harness.program import build_model
    from deepspeed_tpu.inference.kv_cache import (paged_cache_memory_bytes,
                                                  ring_blocks,
                                                  state_pool_memory_bytes)
    from deepspeed_tpu.models.transformer import recurrent_layers
    import jax.numpy as jnp

    cfg = build_model(cell).config
    assert recurrent_layers(cfg) == (None, ())
    assert paged_cache_memory_bytes(cfg, s["num_blocks"] + 1,
                                    s["block_size"], jnp.bfloat16) == arena
    ring = ring_blocks(cfg, s["prefill_chunk"], s["block_size"])
    assert ring == 72
    assert state_pool_memory_bytes(
        cfg, s["max_seqs"] + 1, jnp.bfloat16, (ring, s["block_size"])) \
        == (1 + 33 * 72) * 16 * 5 * (1536 + 1024) * 2 + 33 * 4


def test_the_cell_reports_what_its_entries_say():
    cell = SPEC.cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == ["itl_p50_ms",
                                                          "setup_s"]
    assert live_document.named(SPEC.doc["workloads"], CELL)["chips"] == 1
    names = {m["name"] for m in cell.per_layer}
    assert {"serve_decode_iter_ms", "serve_idle_pct",
            "serve_compiles_in_window", "serve_preemptions",
            "serve_host_decode_ms", "serve_arena_resident_pct",
            "moe_expert_time_pct", "moe_load_imbalance_pct",
            "serve_state_resident_pct"} <= names
    assert set(NEW) <= names
    # another family's cost functions, or one that counts `num_layers`
    assert not names & {"window_decode_attention_roofline",
                        "paged_decode_attention_roofline",
                        "paged_prefill_attention_roofline",
                        "shared_kv_decode_attention_roofline",
                        "serve_window_resident_pct"}
    assert not {n for n in names if n.startswith((
        "kda_", "mamba", "ssm_", "recurrent_", "train_", "flash_",
        "shared_kv", "looped_", "loop_", "latent_", "moe_zero_"))}
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
    assert m["layer"] == ("kernels" if name in ROOFLINES else
                          "model" if name in SHARES else "serving engine")
    r = SPEC.reader(name)
    assert os.path.exists(SPEC.path("reducers", r["reducer"] + ".py"))
    assert hasattr(layers.reducer(r["reducer"]), "reduce")
    if name in ROOFLINES:
        assert hasattr(layers.reducer(r["args"]["cost"]), "total")
        return
    if name in SHARES:      # a device program, found by its file
        assert os.path.exists(SPEC.path(
            "programs", *r["args"]["program"].split("/")) + ".json")
    else:                   # the iteration's span, which is no program
        assert r["args"]["span"] == "serving/iteration"


def test_no_other_cell_reports_the_five():
    for w in SPEC.doc["workloads"]:
        if w["name"] != CELL:
            assert not set(NEW) & {m["name"]
                                   for m in SPEC.cell(w["name"]).per_layer}


def _ctx(model_config, records=(), traced=None):
    return layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                          counters={}, model_config=model_config,
                          records=list(records), traced=traced)


def _row(prompt, times):
    return types.SimpleNamespace(prompt_len=prompt, token_times=list(times))


# two requests; the traced second is [10, 11]: the first has tokens 2 and 3
# in it (contexts 100 + 2 and 100 + 3), the second its first token (a
# prefill's, no decode row) and token 1 (context 8000 + 1)
RECORDS = [_row(100, [9.0, 9.5, 10.2, 10.8, 11.5]),
           _row(8000, [10.1, 10.9])]


def test_a_forms_walk_counts_the_layers_of_that_form():
    """Operations `heads x 2 x seen x (192 + 128)`; bytes the whole pages a
    walk touches at K x 192 and K x 128 lanes with K the FORM's own, the
    sinks of a window layer, q in and o out; times 5 window layers or 2
    full ones, never 7."""
    ctx = _ctx(MODEL, RECORDS, traced=(10.0, 11.0))
    assert mimo_v2_flash_costs.decode_contexts(ctx) == [102, 103, 8001]
    w_ops, w_bytes = window_sink_decode_attention_cost.total(ctx, calls=15)
    f_ops, f_bytes = full_kv_decode_attention_cost.total(ctx, calls=6)
    assert w_ops == 5 * 64 * 2 * 320 * (102 + 103 + 128)
    assert f_ops == 2 * 64 * 2 * 320 * (102 + 103 + 8001)
    io = 64 * 320 * 2
    # context 8,001 under a window of 128: keys 7873..8000, pages 492..500;
    # contexts 102 and 103: 7 pages each, whole
    assert w_bytes == 5 * ((7 + 7 + 9) * 16 * 8 * 320 * 2
                           + 3 * (io + 4 * 64))
    assert f_bytes == 2 * ((7 + 7 + 501) * 16 * 4 * 320 * 2 + 3 * io)
    # a window layer's bytes do not grow with the row; a full layer's do
    far = _ctx(MODEL, [_row(9000, [9.0, 10.5])], traced=(10.0, 11.0))
    near = _ctx(MODEL, [_row(600, [9.0, 10.5])], traced=(10.0, 11.0))
    w = [window_sink_decode_attention_cost.total(c, 5) for c in (far, near)]
    f = [full_kv_decode_attention_cost.total(c, 2) for c in (far, near)]
    assert w[0][0] == w[1][0] and abs(w[0][1] - w[1][1]) <= 5 * 16 * 5120
    assert f[0][1] > 10 * f[1][1]


@pytest.mark.parametrize("why,model", [
    ("the-parents-program", types.SimpleNamespace(
        layer_pattern=MODEL.layer_pattern, num_heads=64, num_kv_heads=4,
        head_dim=192, attention_window=128)),
    ("another-familys-rings", types.SimpleNamespace(
        layer_pattern=("mamba1", "swa", "mamba1", "full", "gmu", "cross"),
        num_heads=40, num_kv_heads=20, head_dim=64, attention_window=512,
        window_sink=False, window_kv_heads=None, v_head_dim=0)),
    ("a-stack-of-one-kind", types.SimpleNamespace(
        layer_pattern=(), num_heads=32, num_kv_heads=32, head_dim=64))])
def test_a_program_without_such_layers_gives_nothing(why, model):
    ctx = _ctx(model, RECORDS, traced=(10.0, 11.0))
    assert window_sink_decode_attention_cost.total(ctx, calls=5) is None
    assert full_kv_decode_attention_cost.total(ctx, calls=2) is None
    assert full_kv_decode_attention_cost.total(_ctx(MODEL, RECORDS),
                                               calls=2) is None


def test_the_shares_on_a_small_trace():
    """A decode step of 10 ms with 1 ms of window walks and 2 ms of full
    walks, a chunk whose kernels must not count, and a walk in no program."""
    from benchmarks.harness.trace import Trace

    chunk = json.load(open(SPEC.path("programs", "serving",
                                     "prefill_chunk.json")))["module"]
    step = json.load(open(SPEC.path("programs", "serving",
                                    "decode.json")))["module"]
    win, full = "window_decode_attention", "full_kv_decode_attention"
    trace = Trace(
        ops={0: [(win, 10.001, 0.5e-3), (win + ".1", 10.002, 0.5e-3),
                 (full, 10.004, 2e-3),
                 (win, 10.021, 5e-3),                   # in a chunk
                 (full, 10.9, 2e-3)]},                  # in no program
        modules={0: [(step, 10.0, 10e-3), (chunk, 10.02, 10e-3)]},
        host={})
    ctx = layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                         counters={}, model_config=None, trace=trace)
    for name, want in (("window_attention_time_pct", 10.0),
                       ("full_attention_time_pct", 20.0)):
        r = SPEC.reader(name)
        assert layers.reducer(r["reducer"]).reduce(ctx, **r["args"]) \
            == pytest.approx(want)
        bare = layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                              counters={}, model_config=None)
        assert layers.reducer(r["reducer"]).reduce(bare, **r["args"]) is None


def test_the_tiny_rehearsal_reads_the_rings_share(tmp_path, monkeypatch,
                                                  capsys):
    """The cell end to end at its `tiny` size on the CPU, through `run.py`'s
    own path with a capture open: `correct` against the reference, no failed
    request, nothing preempted, every slot a row's, and the rings' share of
    the resident cache as the program's spans give it: 5 window layers of
    4 x (24 + 16) lanes for at most (8 + 32) / 16 = 3 pages a row, beside 3
    full layers of 2 x (24 + 16) lanes for every block a row holds."""
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
    assert cell.config["published"]["hybrid_layer_pattern"] == [
        0, 1, 1, 0, 1, 1, 1, 0]
    assert cell.config["published"]["n_routed_experts"] == 128
    result = bench_run.run_cell(tiny, CELL, 2 ** 31 + 13, 3.0, True,
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

    share = read("serve_ring_cache_share_pct")
    # a row of n tokens: 5 x 160 lanes x min(n, 48) over that plus 3 x 80
    # lanes x its blocks of 16: from 77% (one page) down to 62% (80 tokens)
    assert 55.0 < share < 80.0
    assert 0 < read("serve_state_resident_pct") <= 100
