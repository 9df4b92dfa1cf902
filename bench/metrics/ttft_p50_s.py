"""Time to first token, median over every request due in the window: from
the request's scheduled due time to the sink call of its first token. A
request with no first token by the end of the drain counts with the time
it had waited by then, a lower bound of its real wait."""
import numpy as np


def read(ctx):
    due = [r for r in ctx.requests if ctx.t0 <= r.due < ctx.t1]
    if not due:
        return None
    return float(np.median([(r.times[0] if r.times else ctx.t_done) - r.due
                            for r in due]))
