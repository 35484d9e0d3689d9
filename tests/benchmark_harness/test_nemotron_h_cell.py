"""The cell `nemotron-3-super-120b-a12b-ep8-d11.serve-decode-r64-ssm` as
files: the configuration is the source's `config.json` with the four cuts it
lists and nothing else, the traffic is what its issue names, the arena holds
every row's longest sequence at once, and the ops/bytes functions of the two
rooflines it brings give hand-reckoned numbers on recorded spans. (That the
cell runs end to end at its `tiny` size, `correct` included, is
`test_benchmark_harness.py`'s, which finds every cell by name.)"""

import json
import os
import types

import pytest

from benchmarks.harness import layers, spec as spec_mod
from benchmarks.reducers import (latent_moe_grouped_matmul_cost,
                                 mamba2_decode_step_cost,
                                 moe_grouped_matmul_cost)

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
CONFIG = "nemotron-3-super-120b-a12b-ep8-d11"
CELL = CONFIG + ".serve-decode-r64-ssm"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FIXTURE = json.load(open(os.path.join(
    HERE, "fixtures", "spans", "ssm_rows_and_latent_experts.json")))
AS_RUN = {"num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEM*EME",
          "n_routed_experts": 64, "vocab_size": 16384}
# the program's sizes at the published widths, as the cost functions read them
MODEL = types.SimpleNamespace(
    mamba_num_heads=128, mamba_head_dim=64, mamba_state_size=128,
    mamba_n_groups=8, moe_latent_size=1024, ffn_hidden_size=2688,
    hidden_size=4096, activation="relu2")


def test_the_file_is_the_sources_config_with_the_cuts_it_lists():
    cfg = SPEC.cell(CELL).config
    published = cfg["published"]
    assert set(cfg["reduced"]) == set(AS_RUN)
    for key, value in published.items():
        assert cfg[key] == AS_RUN.get(key, value), key
    # the cut is a PREFIX of the published order, in the published mix
    assert published["hybrid_override_pattern"].startswith(
        AS_RUN["hybrid_override_pattern"])
    assert len(published["hybrid_override_pattern"]) \
        == published["num_hidden_layers"] == 88
    assert [AS_RUN["hybrid_override_pattern"].count(c) for c in "ME*"] \
        == [5, 5, 1]
    assert cfg["share"]["chips"] == 8
    assert sorted(cfg["share"]["divided"]) == ["n_routed_experts",
                                               "vocab_size"]
    over = cfg["model"]["overrides"]
    # every width, the router's 512 outputs and its 22 a token as published
    assert [over[k] for k in (
        "hidden_size", "ffn_hidden_size", "moe_latent_size",
        "moe_shared_ffn_hidden_size", "head_size", "num_heads",
        "num_kv_heads", "mamba_num_heads", "mamba_head_dim",
        "mamba_state_size", "mamba_n_groups", "moe_num_experts", "moe_top_k",
        "moe_shared_experts", "moe_routed_scale")] \
        == [4096, 2688, 1024, 5376, 128, 32, 2, 128, 64, 128, 8, 512, 22, 1,
            5]
    assert over["moe_experts_held"] * 8 == published["n_routed_experts"]
    assert over["vocab_size"] * 8 == published["vocab_size"]
    assert over["num_layers"] == 11
    # what is left out is said, not silently dropped
    assert "LEFT OUT" in cfg["assumed"]["mtp"]
    assert "num_nextn_predict_layers" not in cfg["widths"].values()


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row():
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    cfg = SPEC.cell(CELL).config
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    entry = next(c for c in SPEC.doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    # the program's preset carries the same published order
    from deepspeed_tpu.models.presets import _SIZES, nemotron_h_pattern

    assert _SIZES["nemotron-3-super-120b-a12b"]["layer_pattern"] \
        == nemotron_h_pattern(row["config"]["hybrid_override_pattern"])


def test_the_traffic_is_what_the_issue_names():
    t = SPEC.cell(CELL).traffic
    assert (t["kind"], t["clients"], t["requests"], t["pairing_seed"],
            t["warm_loop_s"]) == ("closed_loop", 64, 256, 1, 8)
    assert t["prompt_tokens"] == {"dist": "log_uniform", "min": 32,
                                  "max": 512}
    assert t["output_tokens"] == {"dist": "log_uniform", "min": 128,
                                  "max": 1024}
    assert t["sampling"] == {"temperature": 0.0}
    assert t["reference"]["reason"] and t["reference"]["logprob_atol"] > 0
    # the lengths of the Solar cell's traffic, number for number
    other = SPEC.cell("solar-open2-250b-ep8-d4.serve-decode-r64").traffic
    for key in ("kind", "clients", "requests", "prompt_tokens",
                "output_tokens", "pairing_seed", "sampling", "warm_loop_s"):
        assert t[key] == other[key], key


def test_every_row_fits_the_arena_at_once():
    """64 rows of `max_model_len` tokens are all the blocks there are: no
    request is ever preempted, whatever the seed's order."""
    s = SPEC.cell(CELL).config["serving"]
    assert s["max_seqs"] == SPEC.cell(CELL).traffic["clients"] == 64
    assert s["num_blocks"] * s["block_size"] \
        == s["max_seqs"] * s["max_model_len"]


def test_the_cell_reports_what_its_entries_say():
    cell = SPEC.cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == ["itl_p50_ms",
                                                          "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"mamba2_decode_step_roofline", "ssm_state_time_pct",
            "latent_moe_grouped_matmul_roofline",
            "moe_held_experts_touched_pct", "serve_state_resident_pct",
            "moe_expert_time_pct", "moe_load_imbalance_pct",
            "serve_decode_iter_ms", "serve_host_prefill_ms"} <= names
    # the accepted expert roofline reckons hidden x expert width, four times
    # this model's work; the delta rule's metrics read another kernel
    assert not {"moe_grouped_matmul_roofline", "kda_decode_step_roofline",
                "recurrent_state_time_pct",
                "moe_experts_touched_pct"} & names
    assert all(m["moves"] == "itl_p50_ms" for m in cell.per_layer)
    # the three this cell brought, each found by NAME: where an entry stands
    # in the list, and how many follow it, is a later PR's to change
    by_name = {m["name"]: m for m in SPEC.doc["per_layer"]}
    for name in ("mamba2_decode_step_roofline", "ssm_state_time_pct",
                 "latent_moe_grouped_matmul_roofline"):
        assert by_name[name]["workloads"] == [CELL], name


def _ctx(model_config, traced=None):
    return layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                          counters={}, model_config=model_config,
                          traced=traced)


def _spans(monkeypatch, spans):
    from deepspeed_tpu import observability

    monkeypatch.setattr(observability, "recorded_spans", lambda: list(spans))


def test_mamba2_cost_counts_states_once_in_and_once_out(monkeypatch):
    _spans(monkeypatch, FIXTURE["spans"])
    ops, nbytes = mamba2_decode_step_cost.total(
        _ctx(MODEL, traced=tuple(FIXTURE["traced"])), calls=10)
    # the two decode steps inside the traced second; the chunk's states did
    # not go through this kernel, the empty step advanced none
    pairs = 240 + 320
    assert ops == pairs * 128 * 5 * 64 * 128
    # a head: 32 KiB of state in and out, dt x, the decay and y (64 each);
    # a group: B and C (128 each)
    assert nbytes == pairs * 4 * (128 * (2 * 64 * 128 + 3 * 64)
                                  + 8 * 2 * 128)
    # 4.19 MB of state a (row, layer), read and written: the bytes are
    # nearly all state, and the kernel is memory-bound by two orders
    assert 0.98 < pairs * 128 * 2 * 64 * 128 * 4 / nbytes < 1.0
    assert ops / nbytes < 1.0


def test_latent_expert_cost_counts_latent_wide_matrices(monkeypatch):
    _spans(monkeypatch, FIXTURE["spans"])
    ctx = _ctx(MODEL, traced=tuple(FIXTURE["traced"]))
    ops, nbytes = latent_moe_grouped_matmul_cost.total(ctx, calls=30)
    assigned = 660 + 880 + 2750         # two steps and the chunk
    touched = 300 + 310 + 320
    assert ops == assigned * 2 * 2 * 1024 * 2688
    assert nbytes == 2 * (touched * 2 * 1024 * 2688
                          + assigned * 2 * (1024 + 2688))
    # the accepted function reckons hidden x expert width: four times the
    # matrices this model has, which is why the cell does not list its metric
    wide_ops, wide_bytes = moe_grouped_matmul_cost.total(ctx, calls=30)
    assert wide_ops == 4 * ops
    assert wide_bytes > 3.9 * nbytes


@pytest.mark.parametrize("why", ["no-spans", "no-such-layers",
                                 "a-program-before-the-counts"])
def test_the_costs_find_nothing_to_read(why, monkeypatch):
    """The parent commit, a model without such layers: the metric is left
    out, nothing raises."""
    cfg = MODEL
    spans = list(FIXTURE["spans"])
    if why == "no-spans":
        spans = []
    elif why == "no-such-layers":
        cfg = types.SimpleNamespace(ffn_hidden_size=1280, hidden_size=4096,
                                    activation="swiglu")  # the parent's
    else:
        spans = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                                if k not in ("ssm_rows", "moe_assignments")})
                 for s in spans]
    _spans(monkeypatch, spans)
    ctx = _ctx(cfg, traced=tuple(FIXTURE["traced"]))
    assert mamba2_decode_step_cost.total(ctx, calls=1) is None
    assert latent_moe_grouped_matmul_cost.total(ctx, calls=1) is None
