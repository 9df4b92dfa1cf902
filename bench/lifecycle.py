#!/usr/bin/env python3
"""One cell served as ``bench/run.py`` serves it, read through the
program's own request stamps, decode-step counters and spans.

    python3 bench/lifecycle.py --workload <name> --seed <n> \\
        --seconds <s> [--spans 0|1] [--trace 0|1]

``bench/run.py`` hands its metric readers a ``RunContext`` with no
stamps, and its counters lack the decode-step keys, so its runs cannot
read ``door_lag_p50_s``, ``queue_wait_p50_s``, ``prefill_p50_s``,
``decode_step_mean_ms`` or ``decode_rows_mean``. This tool serves the cell
through ``run.Session`` (the same weights, schedule, warm-up, window and
drain), reaches the engine through its ``on_engine`` hook, and reads the
cell's metrics of the kind ``--trace`` selects, as ``run.py`` does, with
those five. It also prints each window request's split of its time to
first token beside the client's own reading, so that the three parts can
be checked to sum to it.

``--spans 1`` turns the program's spans on (``ServingEngine.spans``).
``--trace 1`` records the traced interval as ``run.py`` does and adds the
program's span names found in it, each stamp's distance from the span it
is taken in once mapped through the ``bench.window`` span, and device 0's
time by op name. No correctness check is made. One JSON line is printed.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import math
import re
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import stamps as stamps_mod  # noqa: E402

NEW_METRICS = ("door_lag_p50_s", "queue_wait_p50_s", "prefill_p50_s",
               "decode_step_mean_ms", "decode_rows_mean")
PROGRAM_SPANS = ("engine.device_pass", "engine.token_sync",
                 "engine.declare_prefill", "engine.admit_prefill",
                 "engine.build_program", "jit.tick", "dispatch.execute",
                 "door.poll", "engine.prefill_land", "engine.retire",
                 "sched.decide", "engine.idle_wait")
# the spans each stamp is taken in (released_s right after door.poll),
# and the call around them: the profiler keeps a span only if it opened
# and closed while it recorded, so a stamp counts only between the first
# recorded opening and the last recorded close of that call
STAMP_SPANS = {"released_s": ("door.poll",),
               "admitted_s": ("engine.declare_prefill",
                              "engine.admit_prefill"),
               "first_token_s": ("engine.prefill_land",
                                 "engine.admit_prefill")}
OUTER_SPAN = {"released_s": "door.poll", "admitted_s": "engine.device_pass",
              "first_token_s": "engine.device_pass"}
TOP_OPS = 15


class Hook:
    """``run.Session``'s ``on_engine``: sets the program's spans, and keeps
    each epoch's door (its tickets hold the stamped requests, also after a
    drain cut short) and the engine's decode counters as the epoch opens.
    The window is the last epoch, after the warm-up."""

    def __init__(self, spans: bool):
        self.spans = spans
        self.doors: List = []
        self.before: List[Dict[str, float]] = []

    def __call__(self, eng) -> None:
        eng.spans = self.spans
        serve = eng.serve_forever

        def serve_forever(door, **kw):
            self.doors.append(door)
            self.before.append(stamps_mod.decode_counters(eng.jit_stats))
            return serve(door, **kw)
        eng.serve_forever = serve_forever

    def window_stamps(self):
        return stamps_mod.request_stamps(
            t.request for t in self.doors[-1].tickets.values())


def op_name(event: str) -> str:
    """An op event's instruction name without its number: the TPU's op
    line names each event by its HLO text, ``%coalesced_gemm.46 = ...``."""
    return re.sub(r"\.\d+$", "", event.split(" = ", 1)[0].lstrip("%"))


def trace_details(trace, t_lo: float, stamps) -> dict:
    """From a loaded trace (``trace_reduce.load``): the program's span names
    it holds; per stamp, its distance in ms from the nearest span it is
    taken in (0 inside it), the monotonic clock mapped onto the trace's by
    the ``bench.window`` span; and device 0's time by op name (``op_name``)."""
    import trace_reduce
    lo, hi = trace_reduce.window_of(trace)
    by_name = defaultdict(list)
    for n, s, e in trace.host:
        by_name[n].append((s, e))
    dist = {}
    for i, key in enumerate(stamps_mod.STAMPS):
        spans = [se for n in STAMP_SPANS[key] for se in by_name[n]]
        outer = by_name[OUTER_SPAN[key]]
        ms = []
        for st in stamps.values():
            if not math.isfinite(st[i]) or not spans or not outer:
                continue
            x = lo + (st[i] - t_lo) * 1e9
            if min(outer)[0] <= x <= max(e for _, e in outer):
                ms.append(min(max(s - x, x - e, 0.0) for s, e in spans)
                          * 1e-6)
        dist[key] = {"n": len(ms), "max_ms": max(ms, default=None),
                     "median_ms": float(np.median(ms)) if ms else None}
    ops = defaultdict(float)
    for n, s, e in trace.devices[0].ops:
        if e > lo and s < hi:
            ops[op_name(n)] += (min(e, hi) - max(s, lo)) * 1e-9
    return {"spans_seen": [n for n in PROGRAM_SPANS if by_name[n]],
            "stamp_to_span": dist,
            "op_names": sorted(ops.items(), key=lambda kv: -kv[1])[:TOP_OPS]}


def measure(cell: dict, seed: int, seconds: float, *, spans: bool,
            trace: bool, need_chip: bool = True,
            bench: Optional[dict] = None) -> dict:
    """One run of ``cell``; returns what ``main`` prints. ``need_chip``
    and ``bench`` as in ``run.run``."""
    from peaks import peaks
    import trace_reduce
    if bench is None:
        bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    jax = run.setup_jax(need_chip, cell["chips"])
    hook = Hook(spans)
    s = run.Session(jax, cell, seed, seconds, trace=trace, on_engine=hook)
    st = hook.window_stamps()
    after = stamps_mod.decode_counters(s.eng.jit_stats)
    jit = dict(s.jit, **{k: after[k] - hook.before[-1][k] for k in after})
    out = {"workload": cell["name"], "seed": seed, "spans": spans}
    reduced = None
    if s.tracer is not None and s.tracer.t_lo is not None:
        raw = trace_reduce.load(trace_reduce.find_xplane(str(s.tracer.dir)))
        out["trace"] = trace_details(raw, s.tracer.t_lo, st)
        reduced = s.tracer.reduce()
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    dev = jax.devices()[0]
    ctx = stamps_mod.StampedContext(
        fleet=s.fleet, window_s=seconds, t0=s.t0, t_done=s.t_done,
        setup_s=s.t0 - run.T_START, requests=list(s.recs.values()),
        events=s.events, jit=jit,
        compiles=[c[:2] for c in s.meter.events], memory=s.memory,
        peaks=peaks(dev.device_kind) if need_chip else {
            "bf16_flops": 1.0, "hbm_bytes_per_s": 1.0},
        trace=reduced, stamps=st)
    kind = "per_layer" if trace else "end_to_end"
    names = [m["name"] for m in run.cell_metrics(bench, kind, cell["name"])]
    metrics = {}
    for name in names + list(NEW_METRICS):
        v = run.reader(name)(ctx)
        if v is not None:
            metrics[name] = float(v)
    out["metrics"] = metrics
    rows = [{"rid": r.rid, "tenant": r.tenant, "prompt_len": r.prompt_len,
             "stamps": st[r.rid], "door_lag_s": lag, "queue_wait_s": wait,
             "prefill_s": pre,
             "ttft_s": r.times[0] - r.due if r.times else None}
            for r, lag, wait, pre in stamps_mod.ttft_split(ctx) or ()]
    out["requests"] = rows
    sums = [abs(x["door_lag_s"] + x["queue_wait_s"] + x["prefill_s"]
                - x["ttft_s"]) for x in rows if x["ttft_s"] is not None]
    out["split_vs_ttft_max_abs_s"] = max(sums, default=None)
    out["counters"] = {k: jit[k] for k in after}
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    t = time.monotonic()
    out = measure(cell, args.seed, args.seconds, spans=bool(args.spans),
                  trace=bool(args.trace), bench=bench)
    run.log(f"lifecycle: {time.monotonic() - t:.3f} s")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
