"""Device seconds of the stacked hybrid decode body
(``jit_hybrid_decode_layers``, core/jit.py) over the device's busy seconds
in the traced interval, in percent: how much of the device's work the
hybrid template does. None where the trace holds no such body (a program
without the hybrid template, or a cell without a hybrid tenant)."""

BODY = "jit_hybrid_decode_layers"


def read(ctx):
    tr = ctx.trace
    if tr is None or tr["busy_s"] <= 0:
        return None
    # trace_reduce names each executable without its "(n)" suffix
    body = sum(s for name, s in tr["device_ops"] if name == BODY)
    return 100.0 * body / tr["busy_s"] if body > 0 else None
