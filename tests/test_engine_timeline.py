"""The engine's own timeline: request stamps on the host's monotonic clock
in their order, the daemon heartbeat's real queue wait, and the program's
spans and device names (off, serving constructs no ``TraceAnnotation``;
on, every span the engine and the JIT open appears under its plain name;
each stacked body compiles to an executable of its own name)."""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.models import Model
from repro.serving import (FrontDoor, ServeRequest, ServingEngine, Tenant,
                           VirtualClock)

SPANS = ("engine.device_pass", "engine.token_sync", "engine.declare_prefill",
         "engine.admit_prefill", "engine.build_program", "jit.tick",
         "dispatch.execute", "door.poll", "engine.prefill_land",
         "engine.retire", "sched.decide", "engine.idle_wait")


@pytest.fixture(scope="module")
def models():
    out = {}
    for arch, seed in (("yi-9b", 1), ("mamba2-2.7b", 2)):
        m = Model(smoke_config(arch), param_dtype=jnp.float32)
        out[arch] = (m, m.init(jax.random.PRNGKey(seed)))
    return out


def _engine(models):
    """A dense tenant (declared prefill) and an SSM tenant (eager
    admission)."""
    (dm, dp), (sm, sp) = models["yi-9b"], models["mamba2-2.7b"]
    return ServingEngine([Tenant("d", dm, dp, cache_len=40, max_batch=2),
                          Tenant("s", sm, sp, cache_len=40, max_batch=2)],
                         mode="vliw")


def _serve(models, spans: bool, on_stats=None):
    """Four requests through the door, the first due after an idle
    start."""
    eng = _engine(models)
    eng.spans = spans
    door = FrontDoor()
    for i in range(4):
        door.submit(ServeRequest(i, "ds"[i % 2], 0.0, 20, 3, 10.0),
                    at=0.5 + 0.01 * i)
    door.close(at=1.0)
    rep = eng.serve_forever(door, clock=VirtualClock(), on_stats=on_stats,
                            stats_interval_s=0.0)
    assert rep.unfinished == 0
    return rep


def _in_order(r):
    return r.released_s <= r.admitted_s <= r.first_token_s


def test_daemon_stamps_and_heartbeat(models):
    beats = []
    rep = _serve(models, spans=False, on_stats=beats.append)
    assert all(_in_order(r) for r in rep.requests)
    assert all("device_time_s" not in b for b in beats)
    waits = [b["queue_wait_p50_s"] for b in beats
             if b["queue_wait_p50_s"] is not None]
    assert waits and min(waits) >= 0.0


def test_replay_stamps_its_copies_only(models):
    trace = [ServeRequest(i, "ds"[i % 2], 0.0, 20, 3, 10.0)
             for i in range(4)]
    rep = _engine(models).run(trace)
    assert all(_in_order(r) for r in rep.requests)
    # routed all at once: one release, before every admission
    assert max(r.released_s for r in rep.requests) \
        <= min(r.admitted_s for r in rep.requests)
    assert all(math.isnan(r.released_s) for r in trace)


class _Counted:
    """Stands in for ``jax.profiler.TraceAnnotation``, recording names."""
    names = []

    def __init__(self, name, **kwargs):
        self.names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def counted(monkeypatch):
    _Counted.names = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counted)
    return _Counted.names


def test_spans_off_construct_no_annotation(models, counted):
    _serve(models, spans=False)
    assert counted == []


def test_spans_on_name_every_span(models, counted):
    _serve(models, spans=True)
    assert set(SPANS) <= set(counted)
    # plain names only: no request or tenant id in any of them
    assert set(counted) == set(SPANS)


def test_stacked_bodies_carry_distinct_module_names(models, monkeypatch):
    """Each jitted body the JIT builds while serving is lowered on its first
    call: the dense decode, SSM decode and dense prefill bodies get module
    names of their own."""
    real_jit, modules = jax.jit, []

    def recording_jit(fun, *args, **kwargs):
        jitted = real_jit(fun, *args, **kwargs)
        if getattr(fun, "__module__", "") != "repro.core.jit":
            return jitted

        def call(*a, **k):
            if not lowered:
                text = jitted.lower(*a, **k).as_text()
                modules.append(re.search(r"module @(\S+)", text).group(1))
                lowered.append(True)
            return jitted(*a, **k)
        lowered = []
        return call
    monkeypatch.setattr(jax, "jit", recording_jit)
    _serve(models, spans=False)
    bodies = {"jit_dense_decode_layers", "jit_ssm_decode_layers",
              "jit_dense_prefill_layers"}
    assert bodies <= set(modules)
    assert "jit_scan_fn" not in modules
