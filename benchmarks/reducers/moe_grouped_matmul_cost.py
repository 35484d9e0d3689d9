"""Ops and bytes of `moe_grouped_matmul` over the traced window, from the
counts the PROGRAM put on its spans `serving/decode` and
`serving/prefill_chunk` (summed over the layers, real rows only):

- operations: every (token, expert) assignment passes through the expert's
  matrices, 2 x H x F each: three for a SwiGLU expert (gate, up, down), two
  otherwise;
- bytes: every expert that had a row has those matrices read once, in the
  model's dtype, plus each assignment's row into and out of each matmul.
  The rows a group is padded with to fill a tile cost time, not bytes that
  the algorithm needs, and are not counted.

A program that writes no such counts (a commit before them, a dense model)
gives None, and the metric is left out."""

from benchmarks.reducers import program_spans

SPANS = ("serving/decode", "serving/prefill_chunk")


def total(ctx, calls: int):
    assignments = touched = 0
    for span in SPANS:
        _, events = program_spans.recorded(ctx, span, "moe_assignments")
        assignments += sum(e["attrs"]["moe_assignments"] for e in events)
        touched += sum(e["attrs"]["moe_experts_touched"] for e in events)
    if not assignments:
        return None
    cfg = ctx.model_config
    H, F = cfg.hidden_size, cfg.ffn_hidden_size
    matrices = 3 if cfg.activation == "swiglu" else 2
    import jax.numpy as jnp

    itemsize = jnp.dtype(ctx.cell.config["model"]["dtype"]).itemsize
    ops = assignments * matrices * 2 * H * F
    nbytes = itemsize * (touched * matrices * H * F
                         + assignments * matrices * (H + F))
    return ops, nbytes
