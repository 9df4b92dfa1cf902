#!/usr/bin/env python3
"""Find a cell's knee: serve its mix at several offered rates, one process.

    python3 bench/sweep.py --workload <name> --seconds <s> --seed <n> \\
        --rates <r> [<r> ...]

Per rate, one line: tokens/s, the TTFT median and p95, the inter-token
p95, the median request time (due to last token), the drain after the
window closed, and the requests completed per second over the window and
its drain. Far above the knee that completed rate is the highest rate the
system sustains; a cell is run at about four fifths of it, written as a
number into its traffic file, or into its configuration file where the
mix does not name the configuration (``traffic.offered``). The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    jax = run.setup_jax(True, cell["chips"])
    load = traffic.load_mix
    for rate in args.rates:
        def at_rate(name, rate=rate):
            mix = load(name)
            _, shares = traffic.offered(mix, cell["config"])
            mix.update(rate_per_s={cell["config"]: rate},
                       tenant_shares=shares)
            return mix
        traffic.load_mix = at_rate
        s = run.Session(jax, cell, args.seed, args.seconds)
        recs = list(s.recs.values())
        ttft = [r.times[0] - r.due for r in recs if r.times]
        gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])]
        total = [r.times[-1] - r.due for r in recs if r.done]
        served = sum(len(r.times) for r in recs)
        n_tok = sum(1 for t, *_ in s.events if s.t0 <= t < s.t0 + s.seconds)
        print(json.dumps({
            "rate": rate, "requests": len(recs),
            "finished": len(s.done()),
            "tokens_per_s": n_tok / args.seconds,
            "ttft_p50_s": float(np.median(ttft)),
            "ttft_p95_s": float(np.percentile(ttft, 95)),
            "itl_p95_s": float(np.percentile(gaps, 95)),
            "median_request_s": float(np.median(total)),
            "completed_tokens_per_s": served / (s.t_done - s.t0),
            "completed_per_s": len(s.done()) / (s.t_done - s.t0),
            "drain_s": s.t_done - s.t0 - args.seconds}), flush=True)
        del s          # its weights too, before the next rate draws its own
        gc.collect()
    traffic.load_mix = load
    return 0


if __name__ == "__main__":
    sys.exit(main())
