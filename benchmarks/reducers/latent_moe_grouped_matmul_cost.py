"""Ops and bytes of `moe_grouped_matmul` over the traced window for a model
whose routed experts run in a LATENT (`moe_latent_size`: their matrices are
latent x expert width, not hidden x expert width, which is what
`moe_grouped_matmul_cost` reckons), from the counts the PROGRAM put on its
spans `serving/decode` and `serving/prefill_chunk` (summed over the layers,
real rows only):

- operations: every (token, expert) assignment that reached a held expert
  passes through the expert's matrices, 2 x Z x F each: two for an expert
  that is not gated (up, down), three for a gated one;
- bytes: every expert that had a row has those matrices read once, in the
  model's dtype, plus each assignment's row into and out of each matmul
  (Z + F values a matrix). The rows a group is padded with to fill a tile
  cost time, not bytes that the algorithm needs, and are not counted.

A program that writes no such counts (a commit before them, a dense model)
or whose experts run on the full width gives None, and the metric is left
out."""

from benchmarks.reducers import program_spans

SPANS = ("serving/decode", "serving/prefill_chunk")


def total(ctx, calls: int):
    cfg = ctx.model_config
    Z = getattr(cfg, "moe_latent_size", 0)
    if not Z:
        return None
    assignments = touched = 0
    for span in SPANS:
        _, events = program_spans.recorded(ctx, span, "moe_assignments")
        assignments += sum(e["attrs"]["moe_assignments"] for e in events)
        touched += sum(e["attrs"]["moe_experts_touched"] for e in events)
    if not assignments:
        return None
    F = cfg.ffn_hidden_size
    matrices = 3 if cfg.activation == "swiglu" else 2
    import jax.numpy as jnp

    itemsize = jnp.dtype(ctx.cell.config["model"]["dtype"]).itemsize
    ops = assignments * matrices * 2 * Z * F
    nbytes = itemsize * (touched * matrices * Z * F
                         + assignments * matrices * (Z + F))
    return ops, nbytes
