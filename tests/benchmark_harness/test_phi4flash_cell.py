"""The cell `phi-4-mini-flash-reasoning.serve-reason-r64` as files: the
configuration is the source's `config.json` key for key with NOTHING cut,
the traffic is what its issue names, the arena holds every row's longest
sequence at once, the cell reports what its entries say (each found BY NAME,
not by its place in a list), and the ops-and-bytes functions of the
rooflines it brings give hand-reckoned numbers. (That the cell runs end to
end at its `tiny` size, `correct` included, is `test_benchmark_harness.py`'s,
which finds every cell by name.)

Of the seven metrics PR 55 brought, six are entries of `BENCHMARK.json`
since PR 59, at the END of `per_layer` (the driver reads an entry anywhere
else as a change to what stood there), each listing this cell alone.
`serve_window_resident_pct` stays a FILE: it reads the resident keys over a
WINDOW's worth, and a row past its window holds up to a ring's 150% of that,
so it passes 100 by construction (130-138 on the chip); the span carries no
capacity to divide by, and the next `tracing` PR gives it one (`PERF.md`
section 7). Its fixture therefore stays beside `fixtures/spans/`, where every
fixture's metric must be a declared entry."""

import json
import os
import types

import pytest

import live_document
from benchmarks.harness import layers, spec as spec_mod
from benchmarks.reducers import (mamba1_chunk_scan_cost,
                                 mamba1_decode_step_cost, phi4flash_costs,
                                 shared_kv_decode_attention_cost,
                                 window_decode_attention_cost)

SPEC = spec_mod.Spec()
CONFIG = "phi-4-mini-flash-reasoning"
CELL = CONFIG + ".serve-reason-r64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
A_FILE = "serve_window_resident_pct"
DECLARED = ("shared_kv_time_pct", "mamba1_state_time_pct",
            "mamba1_decode_step_roofline", "window_decode_attention_roofline",
            "shared_kv_decode_attention_roofline",
            "mamba1_chunk_scan_roofline")
NEW = (A_FILE,) + DECLARED
# the program's sizes at the published widths, as the cost functions read them
MODEL = types.SimpleNamespace(
    layer_pattern=("mamba1", "swa") * 8 + ("mamba1", "full")
    + ("gmu", "cross") * 7,
    hidden_size=2560, mamba_expand=2, mamba_state_size=16, num_heads=40,
    num_kv_heads=20, head_dim=64, attention_window=512)


def test_the_file_is_the_sources_config_and_nothing_is_cut():
    cfg = SPEC.cell(CELL).config
    published = cfg["published"]
    assert cfg["reduced"] == {} and "share" not in cfg
    for key, value in published.items():
        assert cfg[key] == value, key
    over = cfg["model"]["overrides"]
    assert over == {"hidden_size": 2560, "ffn_hidden_size": 10240,
                    "num_heads": 40, "num_kv_heads": 20,
                    "attention_window": 512, "vocab_size": 200064,
                    "num_layers": 32}
    assert cfg["model"]["dtype"] == "bfloat16"
    # what the catalog's config does not carry is said, not silently chosen
    for key in ("mamba1", "layers", "attention", "weights", "dtype"):
        assert cfg["assumed"][key]
    entry = next(c for c in SPEC.doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["file"].endswith(CONFIG + ".json")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_published_is_the_catalogs_row():
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning")
    cfg = SPEC.cell(CELL).config
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    entry = next(c for c in SPEC.doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    # the program's preset carries the same sizes and the published order
    from deepspeed_tpu.models.presets import transformer_config

    program = transformer_config(cfg["model"]["preset"])
    for key, source in cfg["widths"].items():
        assert getattr(program, key) == row["config"][source], key
    assert program.layer_pattern == MODEL.layer_pattern


def test_the_traffic_is_what_the_issue_names():
    t = SPEC.cell(CELL).traffic
    assert (t["kind"], t["clients"], t["requests"], t["pairing_seed"],
            t["warm_loop_s"]) == ("closed_loop", 64, 256, 1, 8)
    assert t["prompt_tokens"] == {"dist": "log_uniform", "min": 64,
                                  "max": 512}
    # the issue's lengths, or its ONE named fallback (serve-decode-r64's)
    assert t["output_tokens"] in (
        {"dist": "log_uniform", "min": 512, "max": 2048},
        {"dist": "log_uniform", "min": 128, "max": 1024})
    assert t["sampling"] == {"temperature": 0.0}
    assert t["reference"]["max_tokens"] == 2304
    assert t["reference"]["reason"] and t["reference"]["logprob_atol"] > 0
    assert "shared_prefix" not in t


def test_every_row_fits_the_arena_at_once():
    """64 rows of `max_model_len` tokens are all the blocks there are: no
    request is ever preempted, whatever the seed's order; the longest
    request fits a row."""
    cell = SPEC.cell(CELL)
    s, t = cell.config["serving"], cell.traffic
    assert s["max_seqs"] == t["clients"] == 64
    assert s["num_blocks"] * s["block_size"] \
        == s["max_seqs"] * s["max_model_len"]
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] \
        <= s["max_model_len"]
    assert t["reference"]["max_tokens"] <= s["max_model_len"]


def test_the_cell_reports_what_its_entries_say():
    cell = SPEC.cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == ["itl_p50_ms",
                                                          "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"serve_state_resident_pct", "serve_decode_iter_ms",
            "serve_idle_pct", "serve_compiles_in_window",
            "serve_preemptions", "serve_host_decode_ms"} <= names
    # other models' kernels and routers are not this cell's
    assert not {n for n in names if n.startswith(("moe_", "kda_", "mamba2_",
                                                  "ssm_", "recurrent_",
                                                  "train_", "flash_"))}
    assert all(m["moves"] == "itl_p50_ms" for m in cell.per_layer)
    # the six of this cell's own that are declared list this cell alone
    assert set(DECLARED) <= names and A_FILE not in names
    # every accepted serve_* metric of the r64 cells gained this cell
    solar = "solar-open2-250b-ep8-d4.serve-decode-r64"
    for m in SPEC.doc["per_layer"]:
        if m["name"].startswith("serve_") and solar in m.get("workloads", ()):
            assert CELL in m["workloads"], m["name"]


@pytest.mark.parametrize("name", DECLARED)
def test_the_metric_is_declared_and_equal_to_its_file(name):
    m = live_document.is_what_its_file_gives(SPEC, name, cells=[CELL])
    assert m["moves"] == "itl_p50_ms"
    assert (m["unit"] == "%") if name.endswith("_roofline") else True
    assert m["layer"] in ("kernels", "model")


def test_the_ring_share_stays_a_file_because_it_passes_100():
    """A share of a WINDOW's worth that a ring of one and a half windows
    fills: the fixture's first iteration reads 122%. No entry until the span
    carries the ring's capacity."""
    assert live_document.entry(SPEC, A_FILE) is None
    r = SPEC.reader(A_FILE)
    assert (r["args"]["count"], r["args"]["over"]) == (
        "window_resident_tokens", "window_tokens_bound")
    first = RINGS["spans"][0]["attrs"]
    assert 100 * first[r["args"]["count"]] / first[r["args"]["over"]] > 100


@pytest.mark.parametrize("name", NEW)
def test_a_new_metrics_reducer_and_cost_function_are_there(name):
    r = SPEC.reader(name)
    assert name.endswith("_roofline") == (r["reducer"] == "kernel_roofline")
    assert os.path.exists(SPEC.path("reducers", r["reducer"] + ".py"))
    assert hasattr(layers.reducer(r["reducer"]), "reduce")
    if "cost" in r.get("args", {}):
        assert hasattr(layers.reducer(r["args"]["cost"]), "total")


def test_no_other_cell_reports_the_six():
    for w in SPEC.doc["workloads"]:
        if w["name"] != CELL:
            assert not set(NEW) & {m["name"]
                                   for m in SPEC.cell(w["name"]).per_layer}


def _ctx(model_config, records=(), traced=None):
    return layers.Context(cell=SPEC.cell(CELL), chips=1, peaks={},
                          counters={}, model_config=model_config,
                          records=list(records), traced=traced)


def _spans(monkeypatch, spans):
    from deepspeed_tpu import observability

    monkeypatch.setattr(observability, "recorded_spans", lambda: list(spans))


def _row(prompt, times):
    return types.SimpleNamespace(prompt_len=prompt, token_times=list(times))


# `serve_window_resident_pct`'s known number. The fixture lies BESIDE
# `fixtures/spans/`: what is in that directory must be a declared entry
# (`test_program_span_metrics.py::test_new_metrics_agree_with_their_files`),
# and this metric is a file (the docstring above says why).
RINGS = json.load(open(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "fixtures", "window_rings_spans.json")))


def _resident(ctx):
    r = SPEC.reader("serve_window_resident_pct")
    assert r["reducer"] == "span_count" and r["source"] == "program_span"
    return layers.reducer(r["reducer"]).reduce(ctx, **r["args"])


def test_the_ring_fixture_holds_what_a_known_number_needs():
    assert {"spans", "traced", "expect"} <= set(RINGS)
    assert list(RINGS["expect"]) == ["serve_window_resident_pct"]
    lo, hi = RINGS["traced"]
    assert any(lo <= s["start_s"] and s["end_s"] <= hi
               for s in RINGS["spans"])
    assert len({s["id"] for s in RINGS["spans"]}) == len(RINGS["spans"])


def test_resident_share_on_the_recorded_iterations(monkeypatch, capfd):
    """The mean of 122.07% (64 rows, rings past a window) and 58.59% (32
    rows short of one); the iteration with no live row, the parent's and
    the one after the traced second are no samples."""
    _spans(monkeypatch, RINGS["spans"])
    got = _resident(_ctx(MODEL, traced=tuple(RINGS["traced"])))
    assert got == pytest.approx(RINGS["expect"]["serve_window_resident_pct"],
                                rel=1e-9)
    assert got == pytest.approx((320000 / 262144 + 76800 / 131072) * 50)
    assert " samples" in capfd.readouterr().err


@pytest.mark.parametrize("why", ["nothing-recorded", "no-span-record",
                                 "the-parents-iterations"])
def test_resident_share_is_left_out_where_no_ring_is_counted(why,
                                                             monkeypatch):
    from deepspeed_tpu import observability

    if why == "no-span-record":
        monkeypatch.delattr(observability, "recorded_spans")
    elif why == "nothing-recorded":
        _spans(monkeypatch, [])
    else:
        _spans(monkeypatch, [
            dict(s, attrs={k: v for k, v in s["attrs"].items()
                           if not k.startswith("window_")})
            for s in RINGS["spans"]])
    assert _resident(_ctx(MODEL, traced=tuple(RINGS["traced"]))) is None
    if why != "the-parents-iterations":
        assert _resident(_ctx(MODEL)) is None


# two requests; the traced second is [10, 11]: the first has tokens 2 and 3
# in it (contexts 100 + 2 and 100 + 3), the second its first token (a
# prefill's, no decode row) and token 1 (context 1000 + 1)
RECORDS = [_row(100, [9.0, 9.5, 10.2, 10.8, 11.5]),
           _row(1000, [10.1, 10.9])]


def test_a_windows_bytes_do_not_grow_with_the_row(monkeypatch):
    ctx = _ctx(MODEL, RECORDS, traced=(10.0, 11.0))
    assert phi4flash_costs.decode_contexts(ctx) == [102, 103, 1001]
    # context 1,001 under a window of 512: keys 489..1000, pages 30..62
    ops, nbytes = phi4flash_costs.walk(ctx, 1001, 512)
    assert ops == 40 * 2 * 512 * (64 + 128)
    assert nbytes == 2 * 33 * 16 * 1280 * 2 + 40 * 128 * 2 * 2
    # the whole context: 63 pages
    ops, nbytes = phi4flash_costs.walk(ctx, 1001)
    assert ops == 40 * 2 * 1001 * 192
    assert nbytes == 2 * 63 * 16 * 1280 * 2 + 40 * 128 * 2 * 2
    w_ops, w_bytes = window_decode_attention_cost.total(ctx, calls=24)
    s_ops, s_bytes = shared_kv_decode_attention_cost.total(ctx, calls=24)
    # 8 window layers, 8 readers of the ONE pool; short rows cost the same
    assert w_ops == 8 * 40 * 2 * 192 * (102 + 103 + 512)
    assert s_ops == 8 * 40 * 2 * 192 * (102 + 103 + 1001)
    assert w_bytes < s_bytes
    one_row = _ctx(MODEL, [_row(2400, [9.0, 10.5])], traced=(10.0, 11.0))
    far = window_decode_attention_cost.total(one_row, calls=8)
    near = window_decode_attention_cost.total(
        _ctx(MODEL, [_row(600, [9.0, 10.5])], traced=(10.0, 11.0)), calls=8)
    assert far[0] == near[0] and abs(far[1] - near[1]) <= 8 * 2 * 16 * 5120


def test_the_scan_costs_count_states_once_in_and_once_out(monkeypatch):
    spans = [
        {"name": "serving/decode", "start_s": 10.1, "end_s": 10.2,
         "attrs": {"ssm_rows": 9 * 60}},
        {"name": "serving/decode", "start_s": 10.3, "end_s": 10.4,
         "attrs": {"ssm_rows": 9 * 64}},
        {"name": "serving/decode", "start_s": 10.5, "end_s": 10.6,
         "attrs": {"rows": 0}},
        {"name": "serving/prefill_chunk", "start_s": 10.6, "end_s": 10.7,
         "attrs": {"tokens": 200}},
        {"name": "serving/decode", "start_s": 12.0, "end_s": 12.1,
         "attrs": {"ssm_rows": 9 * 64}}]        # outside the traced second
    _spans(monkeypatch, spans)
    ctx = _ctx(MODEL, traced=(10.0, 11.0))
    pairs = 9 * 124
    ops, nbytes = mamba1_decode_step_cost.total(ctx, calls=18)
    assert ops == pairs * 6 * 16 * 5120
    assert nbytes == 4 * (pairs * (2 * 16 * 5120 + 3 * 5120 + 2 * 16)
                          + 18 * 16 * 5120)
    # 0.33 MB of state a (row, layer), read and written: nearly all the
    # bytes, and the kernel is memory-bound
    assert 0.9 < pairs * 2 * 16 * 5120 * 4 / nbytes < 1.0
    assert ops / nbytes < 1.0
    ops, nbytes = mamba1_chunk_scan_cost.total(ctx, calls=9)
    assert ops == 200 * 9 * 6 * 16 * 5120
    assert nbytes == 4 * (200 * 9 * (3 * 5120 + 32) + 9 * 3 * 16 * 5120)


@pytest.mark.parametrize("why", ["no-spans-no-records", "no-such-layers"])
def test_the_costs_find_nothing_to_read(why, monkeypatch):
    """The parent commit, a model without such layers: each metric is left
    out, nothing raises."""
    _spans(monkeypatch, [])
    cfg, records = MODEL, []
    if why == "no-such-layers":
        cfg = types.SimpleNamespace(num_heads=32, head_dim=64,
                                    num_kv_heads=32, hidden_size=2048)
        records = RECORDS
    ctx = _ctx(cfg, records, traced=(10.0, 11.0))
    for cost in (mamba1_decode_step_cost, mamba1_chunk_scan_cost,
                 window_decode_attention_cost,
                 shared_kv_decode_attention_cost):
        assert cost.total(ctx, calls=1) is None
