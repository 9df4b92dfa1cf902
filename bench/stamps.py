"""The program's own timeline of each request and of each decode step, as
the metric readers in ``bench/metrics`` see it.

The engine stamps every ``ServeRequest`` on the host's monotonic clock, the
clock the token sink reads (``released_s``: taken from the door;
``admitted_s``: off the waiting list into a prefill; ``first_token_s``: the
first token on the host), and counts its decode programs in ``JitStats``
(``decode_programs``, ``decode_rows``, and ``decode_step_s``, the real time
from a program's admission to the end of the token sync that lands it).
``RunContext`` as ``bench/run.py`` builds it carries neither, so these
readers return None there; ``bench/lifecycle.py`` builds a
``StampedContext`` that carries both.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from runctx import Rec, RunContext

STAMPS = ("released_s", "admitted_s", "first_token_s")


@dataclasses.dataclass
class StampedContext(RunContext):
    # request id -> (released_s, admitted_s, first_token_s)
    stamps: Dict[int, Tuple[float, float, float]] = dataclasses.field(
        default_factory=dict)


def request_stamps(requests) -> Dict[int, Tuple[float, float, float]]:
    """The stamps of the program's ``ServeRequest``s, by request id."""
    return {r.req_id: tuple(getattr(r, k) for k in STAMPS)
            for r in requests}


def decode_counters(js) -> Dict[str, float]:
    """The decode-step counters of a ``JitStats``."""
    return {"decode_programs": js.decode_programs,
            "decode_rows": js.decode_rows,
            "decode_step_count": js.decode_step_s.count,
            "decode_step_total_s": js.decode_step_s.total}


def ttft_split(ctx) -> Optional[List[Tuple[Rec, float, float, float]]]:
    """(request, door lag, queue wait, prefill) of every request due in the
    window. The three sum to its time to first token as ``ttft_p50_s``
    counts it: a stamp not reached by the end of the drain reads as that
    end. None where the context carries no stamps."""
    stamps = getattr(ctx, "stamps", None)
    if not stamps:
        return None
    out = []
    for r in ctx.requests:
        if not ctx.t0 <= r.due < ctx.t1 or r.rid not in stamps:
            continue
        rel, adm, first = (s if math.isfinite(s) else ctx.t_done
                           for s in stamps[r.rid])
        out.append((r, rel - r.due, adm - rel, first - adm))
    return out or None


def part_median(ctx, part: int) -> Optional[float]:
    """The median of one part of the split (0 door lag, 1 queue wait, 2
    prefill)."""
    split = ttft_split(ctx)
    return float(np.median([x[1 + part] for x in split])) if split else None
