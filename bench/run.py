#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip the process is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. The cell (``BENCHMARK.json``'s
``workloads``) names a fleet configuration (``bench/configs/<config>.json``)
and a traffic mix (``bench/traffic/<mix>.json``). The run:

1. fails, printing no result, unless JAX's devices are TPUs and as many as
   the cell asks for;
2. draws the fleet's weights and the window's schedule from ``--seed``;
3. warms up on a burst of the window's own shapes under another stream of
   the seed (every prompt length the window sends each tenant, every decode
   slot), which, with loading, is the set-up time;
4. serves the window through the program's real-clock daemon path,
   ``ServingEngine(mode="vliw").serve_forever(FrontDoor, MonotonicClock)``,
   with the schedule pre-loaded by ``FrontDoor.submit(at=...)`` and the
   door closing at the window's end, then lets the engine drain what it
   holds; every token is timed in its sink call, on the client's side;
5. with ``--trace 1``, records a profiler trace of a few seconds inside the
   window, with the harness's spans around the calls into each layer;
6. reads the cell's metrics (``bench/metrics/<name>.py``: the end-to-end
   ones with ``--trace 0``, the per-layer ones with ``--trace 1``), frees
   the program's state, and compares a sample of the served tokens with
   the plain float32 references (``bench/check.py``);
7. prints the compared numbers with their limits as its last lines on
   standard error, and one JSON line as the last line of standard output.

JAX's persistent compilation cache lives in ``.bench_cache/jax`` inside
the checkout, so only a checkout's first run compiles.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
TRACE_AT = 0.4          # the traced interval starts at this share of the
TRACE_S = 5.0           # window and lasts this long, or to its end
WARMUP_STREAM = 3       # the seed's stream that draws warm-up prompts
DRAIN_LIMIT_S = 60.0    # answers are waited for this long past the close


class NoChip(RuntimeError):
    pass


class DrainLimit(RuntimeError):
    """Raised from the token sink to stop a drain that outlasts
    DRAIN_LIMIT_S; the requests still open count as failed."""


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileMeter:
    """Every executable compiled or loaded from the persistent cache, with
    the monotonic time it was done (jax.monitoring events), and the names
    of those the persistent cache did not hold."""

    def __init__(self, jax):
        self.events: List = []
        self.cache_hits = 0
        self._hits_then = 0
        self.misses: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, fun_name="", **_):
        if name.endswith("backend_compile_duration"):
            self.events.append((time.monotonic(), secs, fun_name))
            if self.cache_hits == self._hits_then:
                self.misses.append(fun_name)
            self._hits_then = self.cache_hits

    def _event(self, name, **_):
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1


def reader(name: str) -> Callable:
    """The reader of metric ``name``: ``bench/metrics/<quantity>.py``, where
    the quantity is the name up to its first ``.``; ``ttft_p50_s.ssm`` is
    the quantity ``ttft_p50_s`` in the cells that list it, with a bound of
    its own."""
    quantity = name.split(".", 1)[0]
    path = BENCH / "metrics" / f"{quantity}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{quantity}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, kind: str, cell: str) -> List[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def check_chip(jax, chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's backend is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; {len(devs)} attached")


def _span(name: str, fn: Callable) -> Callable:
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


def add_spans(eng) -> None:
    """The harness's spans around the calls into each layer of the
    program, for the traced run: they label the trace's idle gaps."""
    for attr, name in (("_device_pass", "engine.device_pass"),
                       ("_consume", "engine.token_sync"),
                       ("_declare_prefill", "engine.declare_prefill"),
                       ("_admit", "engine.admit_prefill"),
                       ("_build_program", "engine.build_program")):
        if hasattr(eng, attr):
            setattr(eng, attr, _span(name, getattr(eng, attr)))
    ex = eng.jit.executor
    ex.execute = _span("dispatch.execute", ex.execute)
    session = eng.jit.session

    def traced_session(*a, **k):
        s = session(*a, **k)
        s.tick = _span("jit.tick", s.tick)
        return s
    eng.jit.session = traced_session


def serve_epoch(eng, names, reqs, *, close_at: float, on_token=None,
                clock=None):
    """One ``serve_forever`` epoch over a pre-loaded door."""
    from repro.serving import FrontDoor, ServeRequest
    door = FrontDoor()
    for r in reqs:
        door.submit(ServeRequest(r.rid, names[r.tenant], 0.0, r.prompt_len,
                                 r.output_len, r.slo_s), at=r.due_s,
                    on_token=on_token(r) if on_token else None)
    door.close(at=close_at)
    return eng.serve_forever(door, clock=clock)


def jit_counters(js) -> Dict[str, float]:
    return {"groups_count": js.groups.count, "groups_total": js.groups.total,
            "waste_count": js.padding_waste.count,
            "waste_total": js.padding_waste.total,
            "weight_hits": js.dispatch.weight_hits,
            "weight_misses": js.dispatch.weight_misses,
            "retraces": js.dispatch.retraces,
            "superkernels": js.superkernels,
            "prefill_coalesced": js.prefill_coalesced,
            "shared_dispatches": js.shared_dispatches}


class Tracer:
    """Starts the profiler at ``at`` and stops it at ``until`` (monotonic
    times), from the token sink, so the traced interval lies inside the
    window whatever the engine is doing."""

    def __init__(self, jax, directory: Path, at: float, until: float):
        self.jax, self.dir, self.at, self.until = jax, directory, at, until
        self.ann = None
        self.t_lo = self.t_hi = None

    def poll(self, now: float) -> None:
        if self.t_lo is None and now >= self.at:
            shutil.rmtree(self.dir, ignore_errors=True)
            # host spans and runtime events only: the Python tracer would
            # slow the host loop it is there to observe
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.jax.profiler.start_trace(str(self.dir),
                                          profiler_options=opts)
            self.ann = self.jax.profiler.TraceAnnotation("bench.window")
            self.ann.__enter__()
            self.t_lo = time.monotonic()
        elif self.t_lo is not None and self.t_hi is None \
                and now >= self.until:
            self.stop()

    def stop(self) -> None:
        if self.t_lo is None or self.t_hi is not None:
            return
        self.t_hi = time.monotonic()
        self.ann.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        import trace_reduce
        if self.t_lo is None:
            return None
        tr = trace_reduce.load(trace_reduce.find_xplane(str(self.dir)))
        out = trace_reduce.reduce(tr, trace_reduce.window_of(tr))
        out.update(t_lo=self.t_lo, t_hi=self.t_hi)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def setup_jax(need_chip: bool, chips: int):
    """Import JAX; on the chip path, refuse a missing chip first, then turn
    on the checkout's persistent compilation cache."""
    import jax
    if need_chip:
        check_chip(jax, chips)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class Session:
    """One cell served once: weights and schedule from the seed, warm-up,
    the timed window and its drain. What the client saw stays here for
    the metrics and the check; ``free`` drops the program's state."""

    def __init__(self, jax, cell: dict, seed: int, seconds: float, *,
                 trace: bool = False, on_engine: Optional[Callable] = None):
        import traffic
        from fleet import Fleet
        from repro.serving import MonotonicClock, ServingEngine
        from runctx import Rec

        self.jax, self.seconds = jax, seconds
        self.meter = CompileMeter(jax)
        self.fleet = fleet = Fleet(cell["config"], seed)
        log(f"weights: {time.monotonic() - T_START:.3f} s since start")
        self.mix = mix = traffic.load_mix(cell["traffic"])
        self.reqs = traffic.schedule(mix, cell["config"], seconds)
        warm = traffic.warmup_schedule(mix, self.reqs,
                                       fleet.cfg["max_batch"], rid0=10 ** 7)
        self.prompts = traffic.prompts(self.reqs, fleet.vocab(), seed)
        dev_prompts = {rid: jax.device_put(p) for rid, p in {
            **self.prompts,
            **traffic.prompts(warm, fleet.vocab(), seed, WARMUP_STREAM),
        }.items()}
        eng = self.eng = ServingEngine(fleet.tenants(), mode="vliw")
        eng.make_prompt = lambda tenant, req, rng: dev_prompts[req.req_id]
        if trace:
            add_spans(eng)
        if on_engine is not None:
            on_engine(eng)
        names = fleet.tenant_names

        rep = serve_epoch(eng, names, warm, close_at=0.0)
        if rep.unfinished:
            raise RuntimeError(f"warm-up: {rep.unfinished} of {len(warm)} "
                               f"requests unfinished")
        self.before = jit_counters(eng.jit_stats)
        log(f"warm-up: {len(warm)} requests, {len(self.meter.events)} "
            f"executables ({self.meter.cache_hits} from the persistent "
            f"cache; compiled: {sorted(set(self.meter.misses))}), "
            f"{time.monotonic() - T_START:.3f} s since start")

        self.recs: Dict[int, Rec] = {}
        self.events: List = []
        self.tracer: Optional[Tracer] = None

        def on_token(r):
            rec, events, tracer = self.recs[r.rid], self.events, self.tracer

            def sink(tok, _t):
                now = time.monotonic()
                if now > stop_at:
                    raise DrainLimit
                rec.times.append(now)
                rec.tokens.append(int(tok))
                events.append((now, rec.rid, rec.tenant,
                               len(rec.tokens) - 1))
                if tracer is not None:
                    tracer.poll(now)
            return sink

        clock = MonotonicClock()
        self.t0 = t0 = time.monotonic()
        stop_at = t0 + seconds + DRAIN_LIMIT_S
        for r in self.reqs:
            self.recs[r.rid] = Rec(r.rid, r.tenant, t0 + r.due_s,
                                   r.prompt_len, r.output_len)
        if trace:
            at = t0 + TRACE_AT * seconds
            self.tracer = Tracer(jax, TRACE_DIR, at,
                                 min(at + TRACE_S, t0 + seconds))
        try:
            serve_epoch(eng, names, self.reqs, close_at=seconds,
                        on_token=on_token, clock=clock)
        except DrainLimit:
            log(f"drain stopped {DRAIN_LIMIT_S} s after the close")
        self.t_done = time.monotonic()
        if self.tracer is not None:
            self.tracer.stop()
        mems = [d.memory_stats() or {} for d in jax.devices()]
        self.memory = {
            "peak": max(m.get("peak_bytes_in_use", 0) for m in mems),
            "limit": min((m["bytes_limit"] for m in mems
                          if "bytes_limit" in m), default=0)}
        after = jit_counters(eng.jit_stats)
        self.jit = {k: after[k] - self.before[k] for k in after}
        late = [c for c in self.meter.events if t0 <= c[0]]
        log(f"window: {len(self.reqs)} requests due in {seconds} s, "
            f"{len(self.done())} finished, {len(self.events)} tokens; "
            f"drain {self.t_done - t0 - seconds:.3f} s; {len(late)} "
            f"executables after the window opened "
            f"{sorted({c[2] for c in late})}; counters {self.jit}")

    def done(self):
        return [r for r in self.recs.values() if r.done]

    def served(self):
        import check
        return [check.Served(r.rid, r.tenant, self.prompts[r.rid][0],
                             r.tokens) for r in self.done()]

    def free(self) -> None:
        """Drop the engine with its KV and state caches and packed weights
        (the fleet's weights stay for the reference)."""
        self.eng = None
        gc.collect()

    def compare(self, seed: int, controls=()):
        import check
        return check.compare(self.fleet, self.served(), seed,
                             seq=self.fleet.cfg["cache_len"],
                             reads=self.mix["output_len"]["max"],
                             controls=controls)


def run(argv=None, *, need_chip: bool = True, bench: Optional[dict] = None,
        on_engine: Optional[Callable] = None) -> int:
    """One run; returns the exit code. ``need_chip=False`` (tests only)
    skips the look for a chip and the persistent cache; ``on_engine`` is
    called with the engine before the warm-up (tests plant faults there)."""
    args = parse(argv)
    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r}")
        return 2
    cell = cells[args.workload]
    try:
        jax = setup_jax(need_chip, cell["chips"])
    except NoChip as e:
        log(f"bench/run.py: {e}")
        return 3
    import check
    from peaks import peaks
    from runctx import RunContext

    dev = jax.devices()[0]
    log(f"jax: {time.monotonic() - T_START:.3f} s since start")
    s = Session(jax, cell, args.seed, args.seconds, trace=bool(args.trace),
                on_engine=on_engine)
    ctx = RunContext(
        fleet=s.fleet, window_s=args.seconds, t0=s.t0, t_done=s.t_done,
        setup_s=s.t0 - T_START, requests=list(s.recs.values()),
        events=s.events, jit=s.jit,
        compiles=[c[:2] for c in s.meter.events], memory=s.memory,
        peaks=peaks(dev.device_kind) if need_chip else {
            "bf16_flops": 1.0, "hbm_bytes_per_s": 1.0},
        trace=s.tracer.reduce() if s.tracer is not None else None)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, kind, args.workload):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    s.free()
    t_check = time.monotonic()
    readings = check.readings(s.fleet, s.compare(args.seed))
    log(f"check: {time.monotonic() - t_check:.3f} s")
    compared = [r for r in readings if r.limit is not None]
    correct = check.correct(s.fleet, readings)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": s.memory["peak"]}
    result = {"correct": correct, "attempted": len(s.reqs),
              "failed": len(s.reqs) - len(s.done()), "metrics": metrics,
              "device": device}
    if ctx.trace is not None:
        device.update(busy_s=ctx.trace["busy_s"],
                      window_s=ctx.trace["window_s"])
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["check"] = {r.name: {"value": r.value, "limit": r.limit}
                       for r in compared}
    for r in readings:
        if r.limit is None:
            log(f"not compared: {r.name} {r.value!r} ({r.tokens} served "
                f"tokens of {r.requests} requests)")
    for r in compared:
        log(f"check {r.name} {r.value!r} limit {r.limit!r} "
            f"({r.tokens} served tokens of {r.requests} requests)")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
