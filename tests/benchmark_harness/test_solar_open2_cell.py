"""The cell `solar-open2-250b-ep8-d4.serve-decode-r64` as files: the
configuration is the source's `config.json` with the four cuts it lists and
nothing else, the traffic is what its issue names, the arena holds every
row's longest sequence at once, and the ops/bytes function of the new kernel
gives known numbers on recorded spans. (That the cell runs end to end at its
`tiny` size, `correct` included, is `test_benchmark_harness.py`'s, which
finds every cell by name.)"""

import json
import os
import types

import pytest

from benchmarks.harness import layers, spec as spec_mod
from benchmarks.reducers import kda_decode_step_cost

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = spec_mod.Spec()
CELL = "solar-open2-250b-ep8-d4.serve-decode-r64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FIXTURE = json.load(open(os.path.join(
    HERE, "fixtures", "spans", "recurrent_state_and_held_experts.json")))
AS_RUN = {"num_hidden_layers": 4, "gqa_layers": [0], "n_routed_experts": 40,
          "vocab_size": 24576}


def test_the_file_is_the_sources_config_with_the_cuts_it_lists():
    cfg = SPEC.cell(CELL).config
    published = cfg["published"]
    assert set(cfg["reduced"]) == set(AS_RUN)
    for key, value in published.items():
        assert cfg[key] == AS_RUN.get(key, value), key
    assert cfg["share"]["chips"] == 8
    assert sorted(cfg["share"]["divided"]) == ["n_routed_experts",
                                               "vocab_size"]
    over = cfg["model"]["overrides"]
    # every width, the router's 320 outputs and its 8 a token as published
    assert [over[k] for k in ("hidden_size", "ffn_hidden_size", "head_size",
                              "num_heads", "num_kv_heads", "moe_num_experts",
                              "moe_top_k", "moe_shared_experts")] \
        == [4096, 1280, 128, 64, 8, 320, 8, 1]
    assert over["moe_experts_held"] * 8 == published["n_routed_experts"]
    assert over["vocab_size"] * 8 == published["vocab_size"]
    # one whole period, the softmax layer first
    period = published["gqa_interval"] + 1
    assert over["num_layers"] == period
    assert published["gqa_layers"] == list(range(0, 48, period))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row():
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    cfg = SPEC.cell(CELL).config
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    entry = next(c for c in SPEC.doc["configs"]
                 if c["name"] == "solar-open2-250b-ep8-d4")
    assert entry["source"] == row["source_url"]


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


def test_every_row_fits_the_arena_at_once():
    """64 rows of `max_model_len` tokens are all the blocks there are: no
    request is ever preempted, whatever the seed's order."""
    s = SPEC.cell(CELL).config["serving"]
    assert s["max_seqs"] == SPEC.cell(CELL).traffic["clients"] == 64
    assert s["num_blocks"] * s["block_size"] \
        >= s["max_seqs"] * s["max_model_len"]
    t = SPEC.cell(CELL).traffic
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] \
        <= s["max_model_len"]


def test_the_cell_reports_what_its_entries_say():
    cell = SPEC.cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == ["itl_p50_ms",
                                                          "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"kda_decode_step_roofline", "recurrent_state_time_pct",
            "moe_held_experts_touched_pct", "serve_state_resident_pct",
            "moe_grouped_matmul_roofline", "moe_expert_time_pct",
            "moe_load_imbalance_pct", "serve_decode_iter_ms"} <= names
    # over a router's width a share's touched experts could never pass 12.5%
    assert "moe_experts_touched_pct" not in names
    assert all(m["moves"] == "itl_p50_ms" for m in cell.per_layer)


def _ctx(model_config, traced=None):
    return layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                          counters={}, model_config=model_config,
                          traced=traced)


def test_kda_cost_counts_states_once_in_and_once_out(monkeypatch):
    from deepspeed_tpu import observability

    monkeypatch.setattr(observability, "recorded_spans",
                        lambda: list(FIXTURE["spans"]))
    cfg = types.SimpleNamespace(kda_num_heads=64, kda_head_dim=128)
    ops, nbytes = kda_decode_step_cost.total(
        _ctx(cfg, traced=tuple(FIXTURE["traced"])), calls=6)
    pairs = 144 + 192           # the two steps inside the traced second
    assert ops == pairs * 64 * 7 * 128 * 128
    assert nbytes == pairs * 64 * 4 * (2 * 128 * 128 + 9 * 128)
    # 4.19 MB of state a (row, layer), read and written: the bytes are
    # nearly all state, and the kernel is memory-bound by two orders
    assert 0.96 < pairs * 64 * 2 * 128 * 128 * 4 / nbytes < 1.0
    assert ops / nbytes < 1.0


@pytest.mark.parametrize("why", ["no-spans", "no-such-layers",
                                 "a-program-before-the-counts"])
def test_kda_cost_finds_nothing_to_read(why, monkeypatch):
    """The parent commit, a model without recurrent layers: the metric is
    left out, nothing raises."""
    from deepspeed_tpu import observability

    cfg = types.SimpleNamespace(kda_num_heads=64, kda_head_dim=128)
    spans = list(FIXTURE["spans"])
    if why == "no-spans":
        spans = []
    elif why == "no-such-layers":
        cfg = types.SimpleNamespace()           # the parent's config
    else:
        spans = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                                if k != "recurrent_rows"}) for s in spans]
    monkeypatch.setattr(observability, "recorded_spans", lambda: spans)
    assert kda_decode_step_cost.total(
        _ctx(cfg, traced=tuple(FIXTURE["traced"])), calls=1) is None
