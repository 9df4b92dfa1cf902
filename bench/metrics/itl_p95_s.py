"""Inter-token latency, 95th percentile over every gap between two
consecutive tokens of one request whose later token's sink call falls in
the window, over all requests (not a percentile of per-request means)."""
from runctx import p95


def read(ctx):
    gaps = [b - a for r in ctx.requests for a, b in zip(r.times, r.times[1:])
            if ctx.t0 <= b < ctx.t1]
    return p95(gaps) if gaps else None
