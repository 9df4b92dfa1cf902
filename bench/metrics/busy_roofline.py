"""The least time the served work of the traced interval needs,
max(FLOPs / peak, bytes / bandwidth) with both counted by ``work.py``
(bytes a lower bound), over the device's busy time in that interval, in
percent. Whatever implements the work, it cannot exceed 100; a reading
above is a counting fault."""


def read(ctx):
    if ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    tr = ctx.trace
    flops, nbytes = ctx.work_between(tr["t_lo"], tr["t_hi"])
    least = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / tr["busy_s"] if least > 0 else None
