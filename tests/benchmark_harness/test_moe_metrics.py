"""The readers of the four `moe_*` per-layer metrics on inputs whose numbers
can be checked by hand: program spans with the routing counts, and a trace of
two serving programs that share the grouped-matmul kernel. A program that
records no such counts (the parent commit, a dense model) leaves each metric
out and raises nothing."""

import pytest

from benchmarks.harness import device, layers, spec as spec_mod
from benchmarks.harness import trace as trace_mod

SPEC = spec_mod.Spec()
CELL = "olmoe-1b-7b-d12.serve-decode"
METRICS = ["moe_grouped_matmul_roofline", "moe_expert_time_pct",
           "moe_experts_touched_pct", "moe_load_imbalance_pct"]

CFG = type("Cfg", (), dict(hidden_size=2048, ffn_hidden_size=1024,
                           activation="swiglu", moe_num_experts=64,
                           num_layers=12))()


def _span(i, name, start, **attrs):
    return {"name": name, "id": i, "start_s": start, "end_s": start + 0.02,
            "thread": "dstpu-serving", "attrs": attrs}


# two decode steps and one prefill chunk inside the traced seconds, one decode
# step outside them, one that found no row
SPANS = [
    _span(1, "serving/decode", 100.1, rows=16, max_rows=16,
          moe_assignments=1536, moe_experts_touched=672,
          moe_experts_total=768, moe_max_expert_rows=60),
    _span(2, "serving/decode", 100.2, rows=8, max_rows=16,
          moe_assignments=768, moe_experts_touched=384,
          moe_experts_total=768, moe_max_expert_rows=36),
    _span(3, "serving/prefill_chunk", 100.3, tokens=100,
          moe_assignments=9600, moe_experts_touched=768,
          moe_experts_total=768, moe_max_expert_rows=300),
    _span(4, "serving/decode", 99.0, rows=16, max_rows=16,
          moe_assignments=1536, moe_experts_touched=700,
          moe_experts_total=768, moe_max_expert_rows=70),
    _span(5, "serving/decode", 100.5, rows=0, max_rows=16),
]
TRACED = (100.0, 101.0)

# the kernel runs 3 ms inside each of two decode executions of 10 ms and 6 ms
# inside a prefill execution of 20 ms
TRACE = {
    "ops": {"0": [
        ["%moe_grouped_matmul.3 = bf16[1152,1024] custom-call(...)", 0.001, 0.003],
        ["%fusion.7 = bf16[16,2048] fusion(...)", 0.004, 0.006],
        ["%moe_grouped_matmul.4 = bf16[1152,2048] custom-call(...)", 0.021, 0.003],
        ["%moe_grouped_matmul.3 = bf16[4096,1024] custom-call(...)", 0.041, 0.006],
    ]},
    "modules": {"0": [["jit_decode(123)", 0.0, 0.01],
                      ["jit_decode(123)", 0.02, 0.01],
                      ["jit_prefill_chunk(456)", 0.04, 0.02]]},
    "host": {},
}


def _ctx(traced=TRACED, trace=True, model_config=CFG):
    return layers.Context(
        cell=SPEC.cell(CELL), chips=1, peaks=device.peaks("TPU v5 lite"),
        counters={}, model_config=model_config, traced=traced,
        trace=trace_mod.Trace.from_json(TRACE) if trace else None)


def _read(metric, ctx):
    r = SPEC.reader(metric)
    return layers.reducer(r["reducer"]).reduce(ctx, **r.get("args", {}))


@pytest.fixture
def program(monkeypatch):
    from deepspeed_tpu import observability

    def set_spans(spans):
        monkeypatch.setattr(observability, "recorded_spans",
                            lambda: list(spans))
    return set_spans


def test_the_cell_reports_the_four_metrics_and_the_files_agree():
    cell = SPEC.cell(CELL)
    assert {m["name"] for m in cell.per_layer} >= set(METRICS)
    assert SPEC.cell(CELL).config["reference"] == "olmoe"
    other = SPEC.cell("opt-1.3b.serve-decode")
    assert not {m["name"] for m in other.per_layer} & set(METRICS)


def test_the_file_holds_the_source_key_for_key_at_its_top_level():
    """The driver compares the file's top level with the source's config.json:
    every key of `published` stands there under the same name with the same
    value, but for those `reduced` names, which hold what the cell runs."""
    config = SPEC.cell(CELL).config
    reduced = set(config["reduced"])
    source_of = {source: key for key, source in config["widths"].items()}
    for key, value in config["published"].items():
        assert key in config, key
        if key in reduced:
            assert config[key] == config["model"]["overrides"][source_of[key]]
        else:
            assert config[key] == value, key


def test_known_numbers(program):
    program(SPANS)
    ctx = _ctx()
    # touched: (672/768 + 384/768) / 2
    assert _read("moe_experts_touched_pct", ctx) == pytest.approx(
        100 * (0.875 + 0.5) / 2)
    # largest over mean: 12 layers; 60/12 over 1536/672, 36/12 over 768/384
    assert _read("moe_load_imbalance_pct", ctx) == pytest.approx(
        100 * (5 / (1536 / 672) + 3 / 2.0) / 2)
    # the kernel inside decode: 6 ms of 20 ms; the chunk's 6 ms do not count
    assert _read("moe_expert_time_pct", ctx) == pytest.approx(30.0)
    # roofline: the three spans inside the traced second, all 12 ms of kernel
    assignments, touched = 1536 + 768 + 9600, 672 + 384 + 768
    ops = assignments * 3 * 2 * 2048 * 1024
    nbytes = 2 * (touched * 3 * 2048 * 1024 + assignments * 3 * 3072)
    least = max(ops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > ops / 197e12            # memory-bound here
    assert _read("moe_grouped_matmul_roofline", ctx) == pytest.approx(
        100 * least / 0.012)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_leaves_the_metric_out(metric, program, monkeypatch):
    from deepspeed_tpu import observability

    dense = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                            if not k.startswith("moe_")}) for s in SPANS]
    for spans in ([], dense):
        program(spans)
        if metric != "moe_expert_time_pct":     # reads the trace alone
            assert _read(metric, _ctx()) is None
    program(SPANS)
    assert _read(metric, _ctx(trace=False)) is None or metric in (
        "moe_experts_touched_pct", "moe_load_imbalance_pct")
    # the parent commit's program has no span record at all
    monkeypatch.delattr(observability, "recorded_spans")
    if metric != "moe_expert_time_pct":
        assert _read(metric, _ctx()) is None


def test_a_trace_without_the_kernel_leaves_its_share_out():
    bare = dict(TRACE, ops={"0": [TRACE["ops"]["0"][1]]})
    ctx = _ctx()
    ctx.trace = trace_mod.Trace.from_json(bare)
    assert _read("moe_expert_time_pct", ctx) is None
    assert _read("moe_grouped_matmul_roofline", ctx) is None
