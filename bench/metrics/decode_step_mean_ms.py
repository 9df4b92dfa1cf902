"""Real time of a decode step, mean over the window and its drain, in ms:
from the engine admitting a decode program to the end of the token sync
that lands its tokens on the host (``JitStats.decode_step_s``)."""


def read(ctx):
    n = ctx.jit.get("decode_step_count")
    return 1e3 * ctx.jit["decode_step_total_s"] / n if n else None
