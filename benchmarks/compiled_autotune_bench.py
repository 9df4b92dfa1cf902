"""Compiled-lane + live collaborative autotuning acceptance (PR 9 gate).

Two parts, matching the two regimes documented in ``repro.kernels.ops``:

Part A — serving acceptance, runs on ANY host (interpret mode is fine
because every gate here is a correctness/caching property, not wall-clock):

  * >= 4-tenant co-tenancy: live tuning (collaborative AND greedy
    objectives) changes not a single greedy token vs the untuned engine;
  * one exhaustive search per distinct group signature — tune-cache
    misses == |signatures| on the first run;
  * steady state is FREE: re-running the identical trace on the tuned
    engine pays zero tune-cache misses (hit rate 1.0 >= (steps-1)/steps
    for any steps) and zero jitted-dispatch retraces;
  * the Table 1 modeled claim at realistic dims (k, n >= 2048): the
    collaboratively tuned tile strictly beats the greedy tile on the
    coalesced group, while the greedy tile strictly wins the isolated
    envelope GEMM — and for every signature the live tuner actually tuned,
    collaborative is never worse on its own group.

Part B — compiled-lane wall-clock, on a TPU backend only: the
collaboratively tuned tile must beat the greedy tile in wall-clock on a
G=6 coalesced superkernel at k = n = 2048, compiled (interpret=False), and
both tiles must agree numerically. Interpret-mode wall-clock comparisons
are meaningless (~2 ms/grid-step floor), so on any other backend this
part SKIPS — recorded in the JSON summary, exit 0 — rather than gating on
noise. On a TPU a kernel the compiler refuses fails the bench.

Run:  PYTHONPATH=src python benchmarks/compiled_autotune_bench.py [--quick]
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

try:                                     # via the run.py harness
    from benchmarks.common import (emit, header, tuning_summary,
                                   write_summary)
except ImportError:                      # standalone: python benchmarks/...
    from common import emit, header, tuning_summary, write_summary

from repro.configs import smoke_config
from repro.core import Autotuner, CostModel, GemmShape, V100
from repro.kernels.ops import execute_superkernel
from repro.models import Model
from repro.serving import ServingEngine, Tenant, two_wave_trace

CM = CostModel(V100)
# realistic-dims witness group for the Table 1 modeled claim: at small k
# the two objectives collapse to the same tile, so the claim is only
# meaningful at k, n >= 2048 (see kernels/ops.py's lane policy)
WITNESS = [GemmShape(16, 2048, 2048, dtype_bytes=4)] * 8


def _tokens(rep):
    return [r.tokens_out for r in sorted(rep.requests,
                                         key=lambda r: r.req_id)]


def _shapes(signature):
    return [GemmShape(m, n, k, dtype_bytes=d, layers=l)
            for m, n, k, d, l in signature]


# ---------------------------------------------------------------------------
# Part A: serving acceptance (any host)
# ---------------------------------------------------------------------------

def bench_serving(n_tenants: int, steps: int):
    cfg = smoke_config("gemma3-1b")
    mdl = Model(cfg, param_dtype=jnp.float32)
    params = mdl.init(jax.random.PRNGKey(0))
    names = [f"t{i}" for i in range(n_tenants)]

    def mk_engine(**kw):
        return ServingEngine([Tenant(n, mdl, params, cache_len=64,
                                     max_batch=2) for n in names],
                             mode="vliw", **kw)

    trace = two_wave_trace(names, [], 1e-5, prompt_len=8,
                           max_new_tokens=steps, slo_s=10.0)
    reps, engines, first_tune = {}, {}, {}
    for label, kw in (("untuned", {}),
                      ("collab", dict(live_tune=True)),
                      ("greedy", dict(live_tune=True,
                                      tune_objective="greedy"))):
        engines[label] = mk_engine(**kw)
        t0 = time.perf_counter()
        reps[label] = engines[label].run(trace)
        wall = time.perf_counter() - t0
        # snapshot: ServeReport.jit aliases the engine's LIVE cumulative
        # stats, which the steady-state rerun below keeps mutating
        tc = first_tune[label] = engines[label].jit.tune_cache.stats.copy()
        emit(f"compiled_autotune/serving/{label}/tenants={n_tenants}",
             wall * 1e6,
             f"steps={steps};tune_hits={tc.hits};tune_misses={tc.misses}"
             f";tune_hit_rate={tc.hit_rate:.3f}"
             f";retraces={reps[label].jit.dispatch.retraces}")
    # steady state: the SAME trace again on the tuned engine — every
    # signature is known, so tuning must cost nothing. ServeReport.jit is
    # engine-lifetime cumulative, so diff the caches around the rerun.
    jit = engines["collab"].jit
    tune_base = jit.tune_cache.stats.copy()
    dispatch_base = jit.executor.stats.copy()
    rep2 = engines["collab"].run(trace)
    rerun = {"tune": jit.tune_cache.stats - tune_base,
             "retraces": jit.executor.stats.retraces
                         - dispatch_base.retraces}
    tc2 = rerun["tune"]
    emit(f"compiled_autotune/serving/collab_rerun/tenants={n_tenants}",
         rep2.wall_time_s * 1e6,
         f"tune_hits={tc2.hits};tune_misses={tc2.misses}"
         f";retraces={rerun['retraces']}")
    return reps, engines, rerun, first_tune["collab"]


def check_serving(reps, engines, rerun, tc1, steps: int):
    ok = True
    if not (_tokens(reps["collab"]) == _tokens(reps["untuned"])
            == _tokens(reps["greedy"])):
        print("FAIL: live tuning changed greedy tokens vs the untuned "
              "engine", file=sys.stderr)
        ok = False
    jit = engines["collab"].jit
    n_sigs = len(jit.tuner.results)
    if not 0 < tc1.misses == n_sigs:
        print(f"FAIL: {tc1.misses} tune searches for {n_sigs} distinct "
              "group signatures (must be exactly one each)",
              file=sys.stderr)
        ok = False
    tc2 = rerun["tune"]
    hits_needed = (steps - 1) / steps
    if tc2.misses != 0 or tc2.hit_rate < hits_needed:
        print(f"FAIL: steady-state rerun paid {tc2.misses} tune "
              f"search(es), hit rate {tc2.hit_rate:.3f} < "
              f"{hits_needed:.3f}", file=sys.stderr)
        ok = False
    if rerun["retraces"] != 0:
        print(f"FAIL: {rerun['retraces']} jitted-dispatch "
              "retrace(s) on the steady-state rerun — tuned blocks are "
              "churning compile keys", file=sys.stderr)
        ok = False
    # modeled Table 1 direction on every signature the tuner actually saw,
    # evaluated under the engine's OWN cost model — the live tuner's argmin
    # is only guaranteed to win under the device model it minimized
    ecm = jit.cost
    eat = Autotuner(ecm)
    for res in jit.tuner.results.values():
        shapes = _shapes(res.signature)
        g = eat.tune_group(shapes, "greedy",
                           shared_operand=res.shared_operand)
        t_c = ecm.coalesced_time(shapes, res.block,
                                 shared_operand=res.shared_operand)
        t_g = ecm.coalesced_time(shapes, g,
                                 shared_operand=res.shared_operand)
        if t_c > t_g * (1 + 1e-9):
            print(f"FAIL: collaborative tile loses its own group "
                  f"{res.signature}: {t_c:.3e}s vs greedy {t_g:.3e}s",
                  file=sys.stderr)
            ok = False
    # strict separation at realistic dims (paper's V100 Table 1 setting)
    at = Autotuner(CM)
    collab = at.tune_group(WITNESS, "collaborative")
    greedy = at.tune_group(WITNESS, "greedy")
    t_c = CM.coalesced_time(WITNESS, collab)
    t_g = CM.coalesced_time(WITNESS, greedy)
    iso_c = CM.gemm_time(WITNESS[0], collab)
    iso_g = CM.gemm_time(WITNESS[0], greedy)
    emit("compiled_autotune/modeled_witness", t_c * 1e6,
         f"greedy_us={t_g * 1e6:.1f};speedup={t_g / t_c:.3f}x"
         f";iso_regression={iso_c / iso_g - 1.0:.2f}")
    if not (collab != greedy and t_c < t_g and iso_g < iso_c):
        print("FAIL: Table 1 direction lost at realistic dims: "
              f"collab={collab} greedy={greedy} group {t_c:.3e}/{t_g:.3e} "
              f"iso {iso_c:.3e}/{iso_g:.3e}", file=sys.stderr)
        ok = False
    return ok, {
        "tokens_identical": _tokens(reps["collab"]) ==
            _tokens(reps["untuned"]) == _tokens(reps["greedy"]),
        "first_run": {"hits": tc1.hits, "misses": tc1.misses,
                      "hit_rate": round(tc1.hit_rate, 4),
                      "signatures": n_sigs},
        "steady_rerun": {"hits": tc2.hits, "misses": tc2.misses,
                         "hit_rate": round(tc2.hit_rate, 4),
                         "retraces": rerun["retraces"]},
        "modeled_witness_speedup": t_g / t_c,
        "modeled_witness_iso_regression": iso_c / iso_g - 1.0,
    }


# ---------------------------------------------------------------------------
# Part B: compiled-lane wall-clock (TPU backend only)
# ---------------------------------------------------------------------------

def bench_compiled(iters: int):
    """Wall-clock collaborative vs greedy tiles on a compiled G=6
    superkernel at k = n = 2048 (>= 4-tenant co-tenancy, realistic dims)."""
    at = Autotuner(CM)
    group = [GemmShape(16, 2048, 2048, dtype_bytes=4)] * 6
    collab = at.tune_group(group, "collaborative")
    greedy = at.tune_group(group, "greedy")
    probs = []
    for i, s in enumerate(group):
        ka, kw = jax.random.split(jax.random.PRNGKey(i), 2)
        probs.append((jax.random.normal(ka, (s.m, s.k), jnp.float32),
                      jax.random.normal(kw, (s.k, s.n), jnp.float32)))

    def run(block):
        return execute_superkernel(probs, bm=block.bm, bn=block.bn,
                                   bk=block.bk, interpret=False)

    walls, outs = {}, {}
    for label, block in (("collab", collab), ("greedy", greedy)):
        outs[label] = jax.block_until_ready(run(block))   # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = run(block)
        jax.block_until_ready(out)
        walls[label] = (time.perf_counter() - t0) / iters * 1e6
        emit(f"compiled_autotune/compiled/{label}", walls[label],
             f"bm={block.bm};bn={block.bn};bk={block.bk};iters={iters}")
    ok = True
    for oc, og in zip(outs["collab"], outs["greedy"]):
        import numpy as np
        if not np.allclose(np.asarray(oc), np.asarray(og), rtol=1e-5,
                           atol=1e-5):
            print("FAIL: collaborative and greedy tiles disagree "
                  "numerically on the compiled lane", file=sys.stderr)
            ok = False
    if walls["collab"] >= walls["greedy"]:
        print(f"FAIL: collaborative tile not faster wall-clock under "
              f"co-tenancy: {walls['collab']:.1f}us vs greedy "
              f"{walls['greedy']:.1f}us", file=sys.stderr)
        ok = False
    return ok, {"collab_us": walls["collab"], "greedy_us": walls["greedy"],
                "speedup": walls["greedy"] / walls["collab"],
                "collab_block": [collab.bm, collab.bn, collab.bk],
                "greedy_block": [greedy.bm, greedy.bn, greedy.bk]}


# ---------------------------------------------------------------------------

def run_all(n_tenants: int, steps: int, iters: int) -> bool:
    lane = jax.default_backend() == "tpu"
    reps, engines, rerun, tc1 = bench_serving(n_tenants, steps)
    ok, serving_summary = check_serving(reps, engines, rerun, tc1, steps)
    if lane:
        ok_b, compiled_summary = bench_compiled(iters)
        ok = ok and ok_b
    else:
        compiled_summary = f"skipped ({jax.default_backend()} backend)"
        emit("compiled_autotune/compiled/skipped", 0.0,
             "no_compiled_pallas_lane")
    write_summary("compiled_autotune", {
        "ok": ok, "tenants": n_tenants, "steps": steps,
        "compiled_lane": lane,
        "serving": serving_summary,
        "compiled": compiled_summary,
        "tuning": tuning_summary(engines["collab"].jit),
    })
    return ok


def run() -> None:
    """Entry point for the benchmarks/run.py harness."""
    assert run_all(n_tenants=6, steps=6, iters=5), \
        "compiled autotune acceptance failed"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small configuration for the CI smoke run")
    args = ap.parse_args()
    n_tenants = 4 if args.quick else 6
    steps = 4 if args.quick else 8
    header()
    return 0 if run_all(n_tenants, steps, iters=3 if args.quick else 10) \
        else 1


if __name__ == "__main__":
    sys.exit(main())
