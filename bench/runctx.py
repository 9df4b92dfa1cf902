"""What one run recorded, as the metric readers in ``bench/metrics`` see it.

Every time here is the host's monotonic clock (``time.monotonic``), read by
the harness: a token's time is taken inside its sink call, after the
program has synced it to the host, so it is the client's time.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

import work

Event = Tuple[float, int, int, int]     # (time, request id, tenant, index)


@dataclasses.dataclass
class Rec:
    """One request of the window, as the client saw it."""
    rid: int
    tenant: int
    due: float               # scheduled arrival (absolute monotonic time)
    prompt_len: int
    output_len: int
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.output_len


def decode_steps(events: List[Event]) -> List[Tuple[int, List[Event]]]:
    """Group the token stream into tenant decode steps.

    The program delivers the tokens of one decode step back to back, one
    per active row, before anything else is delivered; a first token (index
    0) comes from a prefill, alone. So a step is a maximal run of
    consecutive non-first tokens of one tenant in which no request repeats.
    """
    steps: List[Tuple[int, List[Event]]] = []
    cur: List[Event] = []
    seen: set = set()
    for ev in events:
        _, rid, tenant, idx = ev
        if idx == 0:
            if cur:
                steps.append((cur[0][2], cur))
            cur, seen = [], set()
            continue
        if cur and (tenant != cur[0][2] or rid in seen):
            steps.append((cur[0][2], cur))
            cur, seen = [], set()
        cur.append(ev)
        seen.add(rid)
    if cur:
        steps.append((cur[0][2], cur))
    return steps


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, float), 95))


@dataclasses.dataclass
class RunContext:
    fleet: object
    window_s: float
    t0: float                            # window start (first arrival)
    t_done: float                        # the engine returned (drained)
    setup_s: float
    requests: List[Rec]
    events: List[Event]
    jit: Dict[str, float]                # counter deltas over the window
    compiles: List[Tuple[float, float]]  # (time, seconds) of each compile
    memory: Dict[str, int]
    peaks: Dict[str, float]
    trace: Optional[dict] = None         # trace_reduce.reduce + t_lo/t_hi

    @property
    def t1(self) -> float:
        return self.t0 + self.window_s

    def spec(self, tenant: int) -> dict:
        f = self.fleet
        return f.cfg["models"][f.tenant_model[tenant]]

    def by_rid(self) -> Dict[int, Rec]:
        return {r.rid: r for r in self.requests}

    def work_between(self, lo: float, hi: float) -> Tuple[float, float]:
        """(model FLOPs, least bytes) of the tokens delivered in [lo, hi):
        each first token brings its request's prefill, each decode step its
        rows' tokens. Weights are read once per program (prefill or decode
        step) of the busiest tenant that holds that weight tree."""
        recs = self.by_rid()
        ev = [e for e in self.events if lo <= e[0] < hi]
        steps = decode_steps(ev)
        flops = nbytes = 0.0
        programs: Dict[int, int] = {}
        for t in range(len(self.fleet.tenant_names)):
            spec = self.spec(t)
            prompts = [recs[e[1]].prompt_len for e in ev
                       if e[2] == t and e[3] == 0]
            rows = [[recs[e[1]].prompt_len + e[3] for e in step]
                    for tt, step in steps if tt == t]
            f, b = work.served_work(spec, prompts, rows)
            flops += f
            nbytes += b - work.weight_bytes(spec) * (len(prompts) + len(rows))
            tree = self.fleet.tree_of[t]
            programs[tree] = max(programs.get(tree, 0),
                                 len(prompts) + len(rows))
        for tree, n in programs.items():
            t = self.fleet.tree_of.index(tree)
            nbytes += work.weight_bytes(self.spec(t)) * n
        return flops, nbytes
