"""Tokens per tenant decode step in the window, from the token stream
(``runctx.decode_steps`` says how steps are told apart)."""
from runctx import decode_steps


def read(ctx):
    ev = [e for e in ctx.events if ctx.t0 <= e[0] < ctx.t1]
    steps = decode_steps(ev)
    if not steps:
        return None
    return sum(len(rows) for _, rows in steps) / len(steps)
