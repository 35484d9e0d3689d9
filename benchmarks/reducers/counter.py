"""A number the cell's own loop counted (compilations inside the window, the
whole-window rate): `ctx.counters[name]`."""


def reduce(ctx, name: str):
    return ctx.counters.get(name)
