"""How the router spread one iteration's rows over the experts, from the
counts the program puts on `span` (`serving/decode`), each summed over the
layers and taken from the rows that hold a request: `moe_assignments`
((token, expert) pairs), `moe_experts_touched` (experts with a row),
`moe_experts_total` (experts x layers), `moe_max_expert_rows` (each layer's
largest expert). The mean over the span's events inside the traced seconds
that routed a row, in %:

- `touched`: experts with a row over all experts. An expert nobody chose is
  not read, so this is the share of the expert weights an iteration moves.
- `imbalance`: the rows of a layer's LARGEST expert over the mean rows of an
  expert that had any; 100 is an even spread. A grouped matmul's time follows
  its largest group's tiles, so an uneven iteration is a slow one.

A program that writes no such counts (a commit before them, a dense model)
gives None. The sample count goes to stderr."""

from benchmarks.reducers import program_spans


def reduce(ctx, span: str, stat: str):
    _, events = program_spans.recorded(ctx, span, "moe_assignments")
    program_spans.note_samples("moe_routing", f"{span}.{stat}", len(events))
    values = []
    for e in events:
        a = e["attrs"]
        if stat == "touched":
            values.append(100.0 * a["moe_experts_touched"]
                          / a["moe_experts_total"])
        elif stat == "imbalance":
            layers = a["moe_experts_total"] / ctx.model_config.moe_num_experts
            mean_rows = a["moe_assignments"] / a["moe_experts_touched"]
            values.append(100.0 * a["moe_max_expert_rows"] / layers
                          / mean_rows)
        else:
            raise ValueError(f"unknown statistic '{stat}'")
    return program_spans.statistic(values, "mean") if values else None
