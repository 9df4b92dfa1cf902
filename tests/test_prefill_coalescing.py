"""Prefill through the JIT (ISSUE 3): prompt GEMMs as first-class declared
ops that coalesce with decode (and other tenants' prefill) traffic, the
serving-metric bugfixes, and the event-loop stall guard."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.clustering import group_ops_exact
from repro.core.costmodel import GemmShape
from repro.core.jit import (VLIWJit, build_dense_prefill_template,
                            prefill_bucket, prefill_program_cache_key)
from repro.core.kernelspec import make_op
from repro.models import Model
from repro.serving import (ServeReport, ServeRequest, ServingEngine, Tenant,
                           long_prompt_trace)


@pytest.fixture(scope="module")
def dense_models():
    out = {}
    for arch, seed in (("gemma3-1b", 1), ("yi-9b", 2)):
        cfg = smoke_config(arch)
        m = Model(cfg, param_dtype=jnp.float32)
        out[arch] = (m, m.init(jax.random.PRNGKey(seed)))
    return out


def _tokens(rep):
    return [r.tokens_out for r in sorted(rep.requests,
                                         key=lambda r: r.req_id)]


# ---------------------------------------------------------------------------
# units: buckets + cross-aspect grouping
# ---------------------------------------------------------------------------

def test_prefill_bucket_powers_of_two():
    assert prefill_bucket(1) == 8
    assert prefill_bucket(8) == 8
    assert prefill_bucket(9) == 16
    assert prefill_bucket(33) == 64
    assert prefill_bucket(256) == 256
    assert prefill_bucket(257) == 512


def test_group_ops_exact_merges_prefill_gemms_with_decode_gemvs():
    """The coalescing key is (n, k, dtype) only: a 256-row prefill GEMM and
    a 4-row decode GEMV sharing weight dims land in ONE group (coalesced
    kernels concatenate along m), instead of being split by aspect."""
    dec = make_op(0, "gemv", GemmShape(4, 128, 128), op_kind="decode")
    pre = make_op(1, "gemm", GemmShape(256, 128, 128), op_kind="prefill")
    other = make_op(2, "gemm", GemmShape(256, 256, 128), op_kind="prefill")
    groups = group_ops_exact([dec, pre, other])
    assert len(groups) == 2
    assert sorted(len(v) for v in groups.values()) == [1, 2]
    merged = next(v for v in groups.values() if len(v) == 2)
    assert {o.op_kind for o in merged} == {"decode", "prefill"}


# ---------------------------------------------------------------------------
# the prefill program computes exactly what Model.prefill computes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", [5, 12, 13, 100])
def test_prefill_program_matches_model_prefill(s, rng):
    """A declared prefill program (padded to its bucket, run through real
    superkernel dispatches) reproduces Model.prefill's last-position logits
    and writes exactly the KV slot rows the analytic admission writes.
    Buckets 8, 16, 16 and 128: every length but 16 pads."""
    cfg = smoke_config("gemma3-1b")
    m = Model(cfg, param_dtype=jnp.float32)
    params = m.init(rng)
    prompt = jax.random.randint(jax.random.fold_in(rng, 7), (1, s), 0,
                                cfg.vocab_size)
    cache_len = max(32, s)
    want_logits, pc = m.prefill(params, {"tokens": prompt},
                                cache_len=cache_len)

    bucket = prefill_bucket(s)
    if s == 13:
        assert bucket == 16
    template = build_dense_prefill_template(m, params, bucket)
    cache = m.init_cache(2, cache_len)
    padded = jnp.pad(prompt, ((0, 0), (0, bucket - s)))
    prog = template.bind(stream_id=0, tokens=padded, cache=cache,
                         env_extra={"real_len": s, "slot": 1, "req": None})
    VLIWJit(max_group=8).run([prog])

    np.testing.assert_allclose(prog.env["logits"], want_logits[0],
                               rtol=2e-4, atol=2e-4)
    assert int(jnp.argmax(prog.env["logits"][0])) \
        == int(jnp.argmax(want_logits[0, -1]))
    got = prog.env["cache"]
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got["layers"][key][:, 1]),
                                   np.asarray(pc["layers"][key][:, 0]),
                                   rtol=2e-4, atol=2e-4)
        # the untouched slot's row stays zero (and so does the padded tail)
        assert np.all(np.asarray(got["layers"][key][:, 0]) == 0)
    assert int(got["pos"][1]) == s and int(got["pos"][0]) == 0


# ---------------------------------------------------------------------------
# engine: long prompts stay bit-identical across modes AND coalesce
# ---------------------------------------------------------------------------

def test_long_prompt_modes_identical_and_prefill_coalesces(dense_models):
    """Acceptance core: on a multi-tenant long-prompt trace, vliw dispatches
    at least one superkernel group containing a prefill op together with
    another tenant's op, and greedy tokens stay bit-identical across all
    three modes (prompt lengths jittered across prefill buckets)."""
    m1, p1 = dense_models["gemma3-1b"]
    m2, p2 = dense_models["yi-9b"]

    def tenants():
        return [Tenant("a", m1, p1, cache_len=64, max_batch=2),
                Tenant("b", m2, p2, cache_len=64, max_batch=2)]

    trace = long_prompt_trace(["a", "b"], prompt_len=40, max_new_tokens=3,
                              n_per_tenant=2, stagger_s=1e-6,
                              prompt_jitter=17, seed=3)
    assert len({prefill_bucket(r.prompt_len) for r in trace}) >= 1
    reps = {}
    for mode in ("time", "batched", "vliw"):
        eng = ServingEngine(tenants(), mode=mode)
        reps[mode] = eng.run(trace)
        assert all(len(r.tokens_out) == 3 for r in reps[mode].requests)
    assert _tokens(reps["time"]) == _tokens(reps["batched"]) \
        == _tokens(reps["vliw"])
    jit = reps["vliw"].jit
    assert jit.prefill_coalesced >= 1
    # declared prefill must not regress the makespan vs the analytic
    # serialized-prefill ablation of the same engine
    ablate = ServingEngine(tenants(), mode="vliw", declared_prefill=False)
    rep_ablate = ablate.run(trace)
    assert _tokens(rep_ablate) == _tokens(reps["vliw"])
    assert reps["vliw"].modeled_time_s <= rep_ablate.modeled_time_s * 1.001


def test_single_token_request_retires_at_prefill_completion(dense_models):
    """max_new_tokens=1 through the DECLARED path: the request's only token
    comes from the prefill program's logits, it never takes a decode slot,
    and it finishes at the completion event."""
    m1, p1 = dense_models["gemma3-1b"]

    def tenants():
        return [Tenant("a", m1, p1, cache_len=32, max_batch=2)]

    trace = [ServeRequest(0, "a", 0.0, 17, 1, 1.0)]
    reps = {}
    for mode in ("batched", "vliw"):
        eng = ServingEngine(tenants(), mode=mode)
        reps[mode] = eng.run(trace)
    assert _tokens(reps["batched"]) == _tokens(reps["vliw"])
    (req,) = reps["vliw"].requests
    assert len(req.tokens_out) == 1
    assert not math.isnan(req.finish_t)
    assert reps["vliw"].unfinished == 0


def test_prefill_templates_cached_per_bucket(dense_models):
    """Prompt lengths sharing a power-of-two bucket share ONE compiled
    prefill template (finite plan-cache key space); a new bucket compiles a
    new one."""
    m1, p1 = dense_models["gemma3-1b"]
    t = Tenant("a", m1, p1, cache_len=64, max_batch=4)
    eng = ServingEngine([t], mode="vliw")
    trace = [ServeRequest(0, "a", 0.0, 17, 2, 1.0),
             ServeRequest(1, "a", 0.1, 20, 2, 1.0),   # same bucket (32)
             ServeRequest(2, "a", 0.2, 33, 2, 1.0)]   # new bucket (64)
    eng.run(trace)
    pf_keys = [k for k in eng.jit.plan_cache.keys()
               if k[0] == "dense-prefill"]
    assert len(pf_keys) == 2
    assert {k[3] for k in pf_keys} == {32, 64}


# ---------------------------------------------------------------------------
# ServeReport metric bugfixes
# ---------------------------------------------------------------------------

def _req(rid, max_new, emitted, finish_t):
    r = ServeRequest(rid, "a", 0.0, 4, max_new, slo_s=2.0)
    r.tokens_out = [1] * emitted if emitted else None
    r.finish_t = finish_t
    return r


def test_tokens_per_s_counts_emitted_not_requested():
    """Regression: throughput used to count max_new_tokens even for
    unfinished / early-retired requests."""
    reqs = [_req(0, max_new=8, emitted=8, finish_t=1.0),
            _req(1, max_new=8, emitted=3, finish_t=float("nan")),
            _req(2, max_new=8, emitted=0, finish_t=float("nan"))]
    rep = ServeReport("vliw", reqs, modeled_time_s=1.0, wall_time_s=0.0)
    assert rep.tokens_per_s == pytest.approx(11.0)   # not 24.0


def test_latency_stats_count_unfinished_requests():
    """Regression (front-door sweep): ``mean_latency`` stays finished-only
    (a NaN finish used to poison the whole mean), but attainment and
    percentile latency now COUNT unfinished/shed requests — as misses and
    as +inf latencies — instead of silently excluding them, which inflated
    both the moment anything was dropped."""
    reqs = [_req(0, max_new=4, emitted=4, finish_t=1.0),
            _req(1, max_new=4, emitted=4, finish_t=3.0),
            _req(2, max_new=4, emitted=1, finish_t=float("nan"))]
    rep = ServeReport("vliw", reqs, modeled_time_s=1.0, wall_time_s=0.0)
    assert rep.unfinished == 1
    assert rep.mean_latency == pytest.approx(2.0)   # finished-only
    assert rep.p_latency(0.5) == pytest.approx(3.0)
    assert rep.p_latency(1.0) == math.inf            # the drop is visible
    # slo_s=2.0: req 0 meets (1.0), req 1 misses (3.0), req 2 never
    # finished — a miss, not an exclusion
    assert rep.slo_attainment == pytest.approx(1.0 / 3.0)

    none_done = ServeReport("vliw", [_req(0, 4, 1, float("nan"))],
                            modeled_time_s=1.0, wall_time_s=0.0)
    assert none_done.unfinished == 1
    assert math.isnan(none_done.mean_latency)
    assert none_done.p_latency(0.5) == math.inf
    assert none_done.slo_attainment == 0.0


# ---------------------------------------------------------------------------
# event-loop stall guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("declared", [True, False])
def test_event_loop_stall_guard_terminates(dense_models, declared):
    """A due request that can never be admitted (here: a tenant with zero
    decode slots), with pending exhausted and nothing inflight, must
    TERMINATE the event loop — the ``if not progressed`` branch used to
    spin forever when ``waiting`` stayed non-empty. The dropped request
    surfaces in ServeReport.unfinished."""
    m1, p1 = dense_models["gemma3-1b"]
    t = Tenant("a", m1, p1, cache_len=32, max_batch=0)
    eng = ServingEngine([t], mode="vliw", declared_prefill=declared)
    # prompt >= prefill_declare_min so declared=True exercises the
    # _declare_prefill no-free-slot refusal, not the analytic one
    trace = [ServeRequest(0, "a", 0.0, 16, 4, 1.0)]
    rep = eng.run(trace)                  # must return, not livelock
    assert rep.unfinished == 1
    assert math.isnan(rep.requests[0].finish_t)
