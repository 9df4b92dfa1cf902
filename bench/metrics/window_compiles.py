"""Executables compiled or loaded from the compilation cache inside the
window (jax.monitoring's backend-compile events). Warm-up should leave
none."""


def read(ctx):
    return float(sum(1 for t, _ in ctx.compiles if ctx.t0 <= t < ctx.t1))
