#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's widest logit gap and
the control's, over many seeds in one process.

    python3 bench/control.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...] [--controls fp8]

For each seed, the cell is served as ``bench/run.py`` serves it (the same
warm-up, window and load), the program's state is freed, and the sample of
served tokens is compared with the float32 reference (the lower reading)
and with the same reference under each lower-precision weight format (the
control, the upper reading). Both go through ``check.readings`` and
``check.correct``, as a run's numbers do: the program has to come out
correct and every control not. One JSON line per seed, then the largest
program gap and the smallest control gap per model. The benchmark's own
runs never run this; ``bench/tests`` runs it at a test size.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def readings(cell: dict, seeds, seconds: float, controls, *,
             need_chip: bool = True, on_engine=None):
    """Per seed: the gaps {model: {"program": {stat: value}, <format>:
    ...}} and whether each source comes out correct, {"program": bool,
    <format>: bool}."""
    import check
    jax = run.setup_jax(need_chip, cell["chips"])
    out = []
    for seed in seeds:
        s = run.Session(jax, cell, seed, seconds, on_engine=on_engine)
        n_failed = len(s.reqs) - len(s.done())
        s.free()
        gaps = s.compare(seed, controls)
        verdict = {src: check.correct(s.fleet,
                                      check.readings(s.fleet, gaps, src))
                   for src in ("program", *controls)}
        out.append({"seed": seed, "failed": n_failed, "correct": verdict,
                    "gaps": gaps})
        print(json.dumps(out[-1]), flush=True)
        del s
        gc.collect()
    return out


def summary(rows, controls) -> dict:
    """Per model and compared number: the largest program reading and the
    smallest reading of each control; and per source, the seeds on which it
    came out correct."""
    from check import STATS
    models = sorted({m for r in rows for m in r["gaps"]})
    out = {f"{stat}.{m}": {
        "program_max": max(r["gaps"][m]["program"][stat] for r in rows),
        **{f"{c}_min": min(r["gaps"][m][c][stat] for r in rows)
           for c in controls}}
        for m in models for stat in STATS}
    out["correct_on"] = {src: [r["seed"] for r in rows if r["correct"][src]]
                         for src in ("program", *controls)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["fp8"])
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    rows = readings(cell, args.seeds, args.seconds, args.controls)
    print(json.dumps({"summary": summary(rows, args.controls)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
