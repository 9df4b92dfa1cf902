#!/usr/bin/env python3
"""Bring-up smoke: Yi-9B at published widths, served on a TPU through the
vliw engine with compiled Pallas kernels.

``python chip_smoke.py`` (one chip):

  * builds Yi-9B (``configs/yi_9b.py``) at its published widths — d_model
    4096, 32 query and 4 KV heads of 128, d_ff 11008, vocab 64000 — in bf16
    from a seed, with only the depth cut to 8 layers (one period of Yi's
    layer pattern is one layer, so 8 layers keep every kind of layer),
    through ``repro.launch.serve``'s builders;
  * serves two tenants that share that one params tree, so shared-operand
    superkernels run: 3 requests each, 128-token prompts (the declared
    prefill path through the JIT) and 16 new tokens, in vliw mode. The
    trace runs twice on one engine: the first run compiles, the second is
    the steady state the counters are read from;
  * checks that every request finished, that the two runs produced the same
    tokens, that the steady state coalesced at least one shared-operand
    superkernel, hit the packed-weight cache at least (steps-1)/steps of the
    time and retraced no dispatch body, and that one request's served
    prefill logits agree with ``Model.prefill`` on the same weights
    (float32, highest matmul precision);
  * prints the device, the depth cut, compile and wall-clock times, the
    superkernel counters and peak device memory, then, when every check
    passed, the result as its last line:
    ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

``python chip_smoke.py --four-chips`` runs only the multi-device path: four
tenants, each with its own Yi-9B weights (same cut), on ``num_devices=4``,
each tenant's params, KV cache and packed weights committed to its home
chip; every tenant's tokens and prefill logits are compared with the same
tenant served alone on chip 0. Its last line carries ``"count": 4``.

The script exits non-zero, printing no result, on any failed check and
whenever the JAX backend is not a TPU. Run it from the repository root.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.serve import (build_models, enable_compile_cache,  # noqa: E402
                                make_tenants)
from repro.models import Model  # noqa: E402
from repro.serving import ServingEngine, long_prompt_trace  # noqa: E402

ARCH = "yi-9b"
LAYERS = 8
# Served (bf16 weights, bf16 activations between f32-accumulated GEMMs) vs
# reference (the same weights in f32, highest matmul precision) prefill
# logits, as ||served - ref|| / ||ref||. bf16 keeps 8 significant bits
# (unit roundoff 2^-9 ~ 0.2%); the served path rounds every GEMM output,
# residual add and norm to bf16, about 10 roundings per layer, and 8 layers
# plus the unembed of independent roundings random-walk to ~sqrt(90) x 0.2%
# ~ 2%. 5% leaves that margin and still fails on a wrong weight slot, layer
# or position, which move the logits by O(100%). Float32 serving (the CPU
# smoke size) differs only in reduction order: 1e-3.
REL_TOL = {"bfloat16": 0.05, "float32": 1e-3}


def _fail(msg: str) -> bool:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    return False


def _capture_prefill_logits(eng: ServingEngine) -> dict:
    """Record, by request id, the logits each declared prefill hands the
    engine when it lands (the served first-token logits)."""
    seen = {}
    land = eng._on_prefill_complete

    def spy(t, prog, now):
        seen[prog.env["req"].req_id] = prog.env["logits"]
        return land(t, prog, now)

    eng._on_prefill_complete = spy
    return seen


class _CompileMeter:
    """Backend compiles (count, seconds) reported by jax.monitoring."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += secs

    def snapshot(self):
        return self.count, self.seconds


def _timed_run(eng, trace, tenants):
    t0 = time.perf_counter()
    rep = eng.run(trace)
    jax.block_until_ready([t.cache for t in tenants])
    return rep, time.perf_counter() - t0


def _tokens(rep) -> dict:
    return {r.req_id: r.tokens_out for r in rep.requests}


def _rel_err(served, ref) -> float:
    s = np.asarray(served, np.float64).ravel()
    r = np.asarray(ref, np.float64).ravel()
    return float(np.linalg.norm(s - r) / np.linalg.norm(r))


def reference_prefill_logits(model: Model, params, prompt) -> jax.Array:
    """``Model.prefill`` on ``params`` cast to float32 under the highest
    matmul precision: last-position logits [1, 1, V]."""
    ref_model = Model(model.cfg, param_dtype=jnp.float32)
    ref_params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(ref_model.prefill, static_argnames="cache_len")(
            ref_params, {"tokens": prompt}, cache_len=int(prompt.shape[1]))
    return jax.block_until_ready(logits)


def serve_check(layers=LAYERS, *, n_per_tenant: int = 3,
                prompt_len: int = 128, new_tokens: int = 16) -> bool:
    """The one-chip smoke (``layers=None`` serves the CPU smoke config)."""
    ok = True
    meter = _CompileMeter()
    models = build_models([ARCH], layers)
    model, params = models[ARCH]
    cfg = model.cfg
    print(f"config: {cfg.name}: d_model {cfg.d_model}, {cfg.num_heads} query "
          f"/ {cfg.num_kv_heads} KV heads of {cfg.resolved_head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, {jnp.dtype(model.dtype).name}"
          f"; depth cut from {get_config(ARCH).num_layers} to "
          f"{cfg.num_layers} layers", flush=True)
    names = [f"t0:{ARCH}", f"t1:{ARCH}"]
    tenants = make_tenants(names, [ARCH] * 2, models, prompt_len=prompt_len,
                           max_new_tokens=new_tokens)
    eng = ServingEngine(tenants, mode="vliw")
    served_logits = _capture_prefill_logits(eng)
    trace = long_prompt_trace(names, prompt_len=prompt_len,
                              max_new_tokens=new_tokens,
                              n_per_tenant=n_per_tenant, slo_s=10.0)

    c0 = meter.snapshot()
    rep1, wall1 = _timed_run(eng, trace, tenants)
    c1 = meter.snapshot()
    js = eng.jit_stats
    base = (js.superkernels, js.coalesced_groups, js.shared_dispatches,
            js.prefill_coalesced, js.dispatch.copy())
    rep2, wall2 = _timed_run(eng, trace, tenants)
    c2 = meter.snapshot()
    print(f"run 1 (cold, compiles included): wall clock {wall1:.3f} s "
          f"(host clock around block_until_ready); backend compiles "
          f"{c1[0] - c0[0]} taking {c1[1] - c0[1]:.3f} s", flush=True)
    print(f"run 2 (steady state): wall clock {wall2:.3f} s (host clock "
          f"around block_until_ready); backend compiles {c2[0] - c1[0]}",
          flush=True)
    sk, coal, shared, pre_coal = (js.superkernels - base[0],
                                  js.coalesced_groups - base[1],
                                  js.shared_dispatches - base[2],
                                  js.prefill_coalesced - base[3])
    disp = js.dispatch - base[4]
    print(f"steady state: superkernels {sk}, coalesced groups {coal}, "
          f"shared-operand groups {shared}, prefill-coalesced {pre_coal}",
          flush=True)
    need = (new_tokens - 1) / new_tokens
    print(f"steady state: packed-weight hit rate {disp.weight_hit_rate:.4f} "
          f"({disp.weight_hits} hits, {disp.weight_misses} misses; need >= "
          f"{need:.4f}), dispatch retraces {disp.retraces}", flush=True)
    n = len(trace)
    for label, rep in (("run 1", rep1), ("run 2", rep2)):
        done = len(rep.finished)
        print(f"{label}: {done}/{n} requests finished", flush=True)
        if done != n:
            ok = _fail(f"{label}: {n - done} request(s) unfinished")
    if len(served_logits) != n:
        ok = _fail(f"{n - len(served_logits)} request(s) skipped the "
                   f"declared prefill path")
    if _tokens(rep1) != _tokens(rep2):
        ok = _fail("the steady-state run produced different tokens")
    if shared < 1:
        ok = _fail("no shared-operand superkernel in the steady state")
    if disp.weight_hit_rate < need:
        ok = _fail(f"packed-weight hit rate {disp.weight_hit_rate:.4f} "
                   f"< {need:.4f}")
    if disp.retraces:
        ok = _fail(f"{disp.retraces} dispatch retrace(s) after warmup")
    dev = jax.devices()[0]
    mem = dev.memory_stats()
    print("peak device memory: " + (
        f"peak_bytes_in_use {mem['peak_bytes_in_use']} bytes "
        f"({mem['peak_bytes_in_use'] / 2**30:.3f} GiB) of bytes_limit "
        f"{mem['bytes_limit']}" if mem else "not reported by the backend"),
        flush=True)

    # prefill logits of the first request vs the float32 reference; the
    # engine (and its packed weights) goes first so the reference fits
    req = trace[0]
    prompt = eng.make_prompt(eng.tenants[req.tenant], req,
                             jax.random.PRNGKey(0))
    served = served_logits[req.req_id]
    del eng, tenants, rep1, rep2
    gc.collect()
    ref = reference_prefill_logits(model, params, prompt)[:, 0]
    err = _rel_err(served, ref)
    tol = REL_TOL[jnp.dtype(model.dtype).name]
    agree = int(jnp.argmax(served)) == int(jnp.argmax(ref))
    print(f"prefill logits, request {req.req_id} ({prompt_len} tokens) vs "
          f"Model.prefill (float32, highest precision): relative L2 error "
          f"{err:.3e} (limit {tol}), max |diff| "
          f"{float(jnp.max(jnp.abs(served.astype(jnp.float32) - ref))):.3e}"
          f", top-1 {'agrees' if agree else 'differs'}", flush=True)
    if not err <= tol:
        ok = _fail(f"prefill logits relative error {err:.6f} > {tol}")
    return ok


def _on_device(tree, dev) -> bool:
    return all(leaf.devices() == {dev} for leaf in jax.tree.leaves(tree))


def mesh_check(layers=LAYERS, *, n_per_tenant: int = 2,
               prompt_len: int = 128, new_tokens: int = 16) -> bool:
    """Four tenants with their own weights on a 4-device mesh vs each
    served alone on device 0 (``layers=None``: CPU smoke config)."""
    devs = jax.devices()
    if len(devs) < 4:
        return _fail(f"the four-device path needs 4 devices; "
                     f"{len(devs)} attached")
    names = [f"t{i}:{ARCH}" for i in range(4)]
    trace = long_prompt_trace(names, prompt_len=prompt_len,
                              max_new_tokens=new_tokens,
                              n_per_tenant=n_per_tenant, slo_s=10.0)
    kw = dict(prompt_len=prompt_len, max_new_tokens=new_tokens)
    ok = True

    solo = {}
    for i, name in enumerate(names):
        models = {name: build_models([ARCH], layers, seed=1 + i)[ARCH]}
        eng = ServingEngine(make_tenants([name], [name], models, **kw),
                            mode="vliw")
        logits = _capture_prefill_logits(eng)
        t0 = time.perf_counter()
        rep = eng.run([r for r in trace if r.tenant == name])
        print(f"{name} alone on {devs[0]}: wall clock "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        solo[name] = (_tokens(rep), {k: np.asarray(v, np.float32)
                                     for k, v in logits.items()})
        del eng, models, rep, logits
        gc.collect()

    # each tenant's weights are drawn on the chip placement will home it
    # on (the trace's first arrivals come in tenant order, so the
    # least-loaded placement binds t_i to device i; checked below)
    models = {}
    for i, name in enumerate(names):
        with jax.default_device(devs[i]):
            models[name] = build_models([ARCH], layers, seed=1 + i)[ARCH]
    tenants = make_tenants(names, names, models, **kw)
    eng = ServingEngine(tenants, mode="vliw", num_devices=4)
    logits = _capture_prefill_logits(eng)
    rep, wall = _timed_run(eng, trace, tenants)
    print(f"4-device mesh: wall clock {wall:.3f} s; "
          f"{len(rep.finished)}/{len(trace)} requests finished", flush=True)
    for d in devs[:4]:
        mem = d.memory_stats()
        print(f"{d}: peak_bytes_in_use "
              f"{mem['peak_bytes_in_use'] if mem else 'not reported'}",
              flush=True)
    if len(rep.finished) != len(trace):
        ok = _fail("unfinished requests on the mesh")
    tokens = _tokens(rep)
    packed = {}
    for key in eng.jit.weight_cache.keys():
        if key[0] in ("wpack", "wstack"):
            packed.setdefault(key[1], []).append(
                eng.jit.weight_cache.peek(key))
    for i, t in enumerate(tenants):
        home = eng.placement.assignments[t.name].device
        dev = devs[home]
        placed = (home == i and _on_device(t.params, dev)
                  and _on_device(t.cache, dev)
                  and bool(packed.get(home))
                  and _on_device(packed.get(home, []), dev))
        mine = [r.req_id for r in trace if r.tenant == t.name]
        ran_there = all(logits[r].devices() == {dev} for r in mine)
        same_tokens = all(tokens[r] == solo[t.name][0][r] for r in mine)
        diff = max(float(np.max(np.abs(np.asarray(logits[r], np.float32)
                                       - solo[t.name][1][r])))
                   for r in mine)
        print(f"{t.name}: home device {home} ({dev}); params, KV cache and "
              f"{len(packed.get(home, []))} packed weights there: {placed}; "
              f"prefill ran there: {ran_there}; tokens match alone on "
              f"device 0: {same_tokens}; prefill logits max |diff| {diff}",
              flush=True)
        if not (placed and ran_there):
            ok = _fail(f"{t.name}: state or dispatch not on device {i}")
        if not same_tokens or diff != 0.0:
            ok = _fail(f"{t.name}: results differ from the tenant served "
                       f"alone")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device mesh path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's backend is {dev.platform}",
              file=sys.stderr)
        return 2
    print(f"device_kind: {dev.device_kind} (platform {dev.platform}, "
          f"{jax.device_count()} device(s) attached)", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    ok = mesh_check() if args.four_chips else serve_check()
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
