"""Dispatches served from the packed-weight cache, in percent of all
dispatches (the program's ``DispatchStats``) over the window and its
drain."""


def read(ctx):
    n = ctx.jit["weight_hits"] + ctx.jit["weight_misses"]
    return 100.0 * ctx.jit["weight_hits"] / n if n else None
