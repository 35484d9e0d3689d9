"""Reductions and ops/bytes functions, one module each, found by name from
`layer_metrics/*.json`. A reduction has `reduce(ctx, **args)`; an ops/bytes
function has `total(ctx, calls)` returning (operations, bytes) on one device
over the traced window."""
