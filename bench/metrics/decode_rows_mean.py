"""Active rows per decode program the engine admitted over the window and
its drain (``JitStats.decode_rows / decode_programs``)."""


def read(ctx):
    n = ctx.jit.get("decode_programs")
    return ctx.jit["decode_rows"] / n if n else None
