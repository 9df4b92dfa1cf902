"""Ops per dispatched superkernel group: the mean of the program's
``JitStats.groups`` over the window and its drain."""


def read(ctx):
    n = ctx.jit["groups_count"]
    return ctx.jit["groups_total"] / n if n else None
