"""Model FLOPs of the tokens delivered in the traced interval (prompt and
output, counted by ``work.py`` from the configuration's shapes: no
padding, no recompute), over the interval times the chip's peak, in
percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    tr = ctx.trace
    flops, _ = ctx.work_between(tr["t_lo"], tr["t_hi"])
    return 100.0 * flops / (tr["window_s"] * ctx.peaks["bf16_flops"])
