"""Serving launcher: bring up the multi-tenant OoO VLIW JIT engine.

By default every tenant is the reduced float32 ``smoke_config`` of its
architecture, sized for a CPU; ``--layers N`` serves the registry config
at its published widths in bf16 with only the depth cut to N layers (the
size for one TPU chip, e.g. ``--tenants yi-9b yi-9b --layers 8``: two
tenants sharing one params tree). Tenants of the same architecture share
one (model, params) pair. The ``--mode`` flag selects the multiplexing
regime so the paper's comparison can be reproduced from the command line.
``chip_smoke.py`` at the repository root drives this module's builders on
the chip.

JAX's persistent compilation cache is on (``enable_compile_cache``): in
``JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache/`` at
the root of the checkout.

Usage (trace replay — finite trace, virtual time):
  PYTHONPATH=src python -m repro.launch.serve \
      --tenants gemma3-1b yi-9b --mode vliw --requests 8 --rate 1e4

Usage (daemon mode — the real-clock serving front door):
  PYTHONPATH=src python -m repro.launch.serve \
      --tenants gemma3-1b yi-9b --daemon --duration 5 --rate 20 \
      --admission --stats-interval 1

``--daemon`` opens a ``FrontDoor`` on the real wall clock and serves until
``--duration`` seconds have elapsed (a feeder thread submits open-loop
Poisson traffic at ``--rate``; tokens stream out per request as they
retire). ``--admission`` turns on the SLO-tiered admission controller:
each request is admitted / degraded to a lower tier / shed AT THE DOOR
from the analytic cost model + arrival forecast, and the final report
shows per-tier attainment, goodput and shed counts (shed requests count
as SLO misses). ``--stats-interval`` prints a live heartbeat line while
the daemon runs.

Note on real-clock attainment: the daemon floors the modeled device
timelines at REAL elapsed time, and on a CPU smoke host actually
executing the reduced models takes orders of magnitude longer than the
modeled TPU-v5e service times — so millisecond-scale ``--slo-ms``
deadlines will all miss and attainment reads 0%. That is the clock
semantics working, not a bug; pass a host-realistic ``--slo-ms`` (or use
the virtual-clock bench ``benchmarks/e2e_slo_attainment.py``, which
replays the door deterministically on modeled time) to study attainment.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import threading
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, ModelConfig, get_config, smoke_config
from repro.models import Model
from repro.serving import (FrontDoor, ServeRequest, ServingEngine, Tenant,
                           make_trace)

# the checkout's own compile-cache directory (listed in .gitignore)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here. Otherwise the cache lives in ``CACHE_DIR``, one
    fixed directory inside the checkout — the path is part of the cache
    key, so it never depends on a temp dir, pid or time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def serving_config(arch: str, layers: Optional[int] = None) -> ModelConfig:
    """The config ``arch`` is served at: with ``layers`` None the reduced
    ``smoke_config`` (CPU size); otherwise the registry config at its
    published widths with only the depth cut to ``layers``, which must keep
    whole periods of the layer pattern."""
    if layers is None:
        return smoke_config(arch)
    full = get_config(arch)
    period = full.global_every if full.window_size else 1
    if not 1 <= layers <= full.num_layers or layers % period:
        raise ValueError(f"{arch}: cannot cut {full.num_layers} layers to "
                         f"{layers} (whole periods of {period} layers)")
    if layers == full.num_layers:
        return full
    return dataclasses.replace(full, name=f"{full.name}-{layers}l",
                               num_layers=layers)


def build_models(arch_names: Sequence[str], layers: Optional[int] = None,
                 seed: int = 1) -> Dict[str, Tuple[Model, dict]]:
    """One (model, params) pair per distinct architecture, weights drawn
    from ``PRNGKey(seed + i)`` for the i-th. Smoke configs hold float32
    weights; published widths (``layers`` given) hold bf16, initialized by
    one jitted call on the default device."""
    dtype = jnp.float32 if layers is None else jnp.bfloat16
    models = {}
    for i, arch in enumerate(dict.fromkeys(arch_names)):
        m = Model(serving_config(arch, layers), param_dtype=dtype)
        models[arch] = (m, jax.jit(m.init)(jax.random.PRNGKey(seed + i)))
    return models


def make_tenants(names: Sequence[str], archs: Sequence[str], models, *,
                 prompt_len: int, max_new_tokens: int) -> list:
    """Tenants ``names[i]`` serving ``models[archs[i]]``, each with four
    decode slots of a KV cache long enough for one prompt plus its
    generated tokens."""
    cache_len = max(32, prompt_len + max_new_tokens + 1)
    return [Tenant(n, *models[a], cache_len=cache_len, max_batch=4)
            for n, a in zip(names, archs)]


def _report_line(mode, rep, certify):
    line = (f"{mode:8s} modeled={rep.modeled_time_s*1e3:8.3f} ms  "
            f"mean_lat={rep.mean_latency*1e3:7.3f} ms  "
            f"p99={rep.p_latency(0.99)*1e3:7.3f} ms  "
            f"SLO={rep.slo_attainment:5.1%}  "
            f"tok/s={rep.tokens_per_s:9.0f}")
    if rep.jit:
        d = rep.jit.dispatch
        line += (f"  [superkernels={rep.jit.superkernels} "
                 f"group={rep.jit.mean_group:.2f} "
                 f"shared={rep.jit.shared_dispatches} "
                 f"wpack_hit={d.weight_hit_rate:.0%} "
                 f"retraces={d.retraces}]")
        if certify:
            line += (f"  [certified: checks={rep.jit.hazard_checks} "
                     f"violations={rep.jit.hazard_violations}]")
    return line


def _run_daemon(names, args, models) -> None:
    tenants = make_tenants(names, args.tenants, models,
                           prompt_len=args.prompt_len,
                           max_new_tokens=args.max_new_tokens)
    eng = ServingEngine(tenants, mode="vliw", certify=args.certify,
                        num_devices=args.num_devices,
                        admission_control=args.admission)
    door = FrontDoor()

    def feeder() -> None:
        # open-loop Poisson feeder on the real clock: arrivals keep
        # coming at --rate regardless of completions, until --duration
        rng = np.random.default_rng(0)
        deadline = args.duration
        t, rid = 0.0, 0
        import time as _t
        t0 = _t.monotonic()
        while True:
            t += rng.exponential(1.0 / args.rate)
            if t >= deadline:
                break
            pause = t - (_t.monotonic() - t0)
            if pause > 0:
                _t.sleep(pause)
            tier = int(rng.choice(3, p=[0.5, 0.3, 0.2]))
            door.submit(ServeRequest(
                rid, names[rid % len(names)], 0.0, args.prompt_len,
                args.max_new_tokens, slo_s=args.slo_ms / 1e3 * (2 ** tier),
                tier=tier))
            rid += 1
        door.close()

    def heartbeat(stats) -> None:
        print(f"  [t={stats['t']:6.2f}s] submitted={stats['submitted']:4d} "
              f"finished={stats['finished']:4d} shed={stats['shed']:3d} "
              f"inflight={stats['inflight']} waiting={stats['waiting']}")

    print(f"daemon: {len(names)} tenants, {args.rate:.0f} req/s open-loop "
          f"for {args.duration:.1f}s, admission="
          f"{'on' if args.admission else 'off'}\n")
    th = threading.Thread(target=feeder, daemon=True)
    th.start()
    rep = eng.serve_forever(door, on_stats=heartbeat,
                            stats_interval_s=args.stats_interval)
    th.join()
    print()
    print(_report_line("daemon", rep, args.certify))
    print(f"  served={len(rep.requests)} shed={rep.shed} "
          f"unfinished={rep.unfinished} "
          f"goodput={rep.goodput_rps:.1f} req/s")
    for tier, att in rep.tier_attainment().items():
        n = sum(1 for r in rep.requests
                if (r.degraded_from if r.degraded_from is not None
                    else r.tier) == tier)
        print(f"  tier {tier}: attainment={att:5.1%}  n={n}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", nargs="+", default=["gemma3-1b", "yi-9b"],
                    choices=list(ARCH_IDS))
    ap.add_argument("--layers", type=int, default=None,
                    help="serve each registry config at its published "
                         "widths in bf16 with the depth cut to N layers "
                         "(default: the reduced float32 smoke config)")
    ap.add_argument("--mode", choices=["time", "batched", "vliw", "all"],
                    default="all")
    ap.add_argument("--requests", type=int, default=4,
                    help="requests per tenant")
    ap.add_argument("--rate", type=float, default=1e4, help="arrivals/s")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=4)
    ap.add_argument("--slo-ms", type=float, default=5.0)
    ap.add_argument("--bursty", action="store_true")
    ap.add_argument("--num-devices", type=int, default=1,
                    help="serve on an N-device modeled mesh (vliw mode): "
                         "tenants are bin-packed onto per-device timelines "
                         "at admission; expert-parallel MoE tenants span "
                         "the mesh and pay the all-to-all collective")
    ap.add_argument("--certify", action="store_true",
                    help="record a ScheduleTrace and run the hazard "
                         "certifier per tick (vliw mode); raises on the "
                         "first illegal reordering")
    ap.add_argument("--daemon", action="store_true",
                    help="real-clock front door: serve open-loop traffic "
                         "from a feeder thread until --duration elapses "
                         "(vliw mode only)")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="daemon: seconds to keep the door open")
    ap.add_argument("--admission", action="store_true",
                    help="daemon: SLO-tiered admission control at the door "
                         "(admit / degrade / shed from the cost model)")
    ap.add_argument("--stats-interval", type=float, default=1.0,
                    help="daemon: seconds between live heartbeat lines")
    args = ap.parse_args()

    enable_compile_cache()
    models = build_models(args.tenants, args.layers)
    names = [f"t{i}:{a}" for i, a in enumerate(args.tenants)]

    if args.daemon:
        _run_daemon(names, args, models)
        return

    trace = make_trace(names, rate_hz=args.rate, n_per_tenant=args.requests,
                       prompt_len=args.prompt_len,
                       max_new_tokens=args.max_new_tokens,
                       slo_s=args.slo_ms / 1e3, bursty=args.bursty)
    print(f"{len(trace)} requests over {len(names)} tenants, "
          f"SLO {args.slo_ms} ms\n")

    modes = ["time", "batched", "vliw"] if args.mode == "all" else [args.mode]
    for mode in modes:
        tenants = make_tenants(names, args.tenants, models,
                               prompt_len=args.prompt_len,
                               max_new_tokens=args.max_new_tokens)
        # baseline modes define single-device round semantics; the mesh is
        # a vliw-engine feature
        n_dev = args.num_devices if mode == "vliw" else 1
        eng = ServingEngine(tenants, mode=mode, certify=args.certify,
                            num_devices=n_dev)
        # run() copies the trace internally — safe to reuse across modes
        rep = eng.run(trace)
        print(_report_line(mode, rep, args.certify))
        if rep.jit and rep.num_devices > 1:
            # per-device mesh breakdown: utilization + coalesced groups
            # (from the recorded trace when --certify) + placement
            groups = {d: [0, 0] for d in range(rep.num_devices)}
            if eng.last_trace is not None:
                for rec in eng.last_trace.dispatches:
                    groups[rec.device][0] += 1
                    groups[rec.device][1] += int(len(rec.ops) > 1)
            homed = {d: [] for d in range(rep.num_devices)}
            for name, pl in eng.placement.assignments.items():
                homed[pl.device].append(
                    name + (f"(x{pl.expert_span})" if pl.expert_span > 1
                            else ""))
            print(f"  mesh: {rep.num_devices} devices, "
                  f"skew={rep.device_skew:.2f}, "
                  f"collective={rep.jit.collective_time_s*1e6:.1f} us")
            for dd in range(rep.num_devices):
                gline = (f"groups={groups[dd][0]} "
                         f"coalesced={groups[dd][1]}  "
                         if eng.last_trace is not None else "")
                print(f"    dev{dd}: util={rep.device_util[dd]:5.1%}  "
                      f"busy={rep.device_busy_s[dd]*1e3:7.3f} ms  "
                      f"{gline}tenants={','.join(homed[dd]) or '-'}")


if __name__ == "__main__":
    main()
