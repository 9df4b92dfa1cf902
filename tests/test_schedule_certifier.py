"""Schedule hazard certifier + dependence analysis (ISSUE 7 acceptance).

Real scheduler traces — recorded by ``ServingEngine(certify=True)`` and by
a raw ``JitSession(record_trace=True)`` — must certify clean; mutated
traces (same records, one illegal edit) must each be rejected with the
expected ``HazardViolation`` subclass. Mutation sites are chosen
property-style via ``_hypothesis_compat``: under real hypothesis the index
strategies explore the trace, under the fallback they sweep a fixed grid.
"""
import copy
import dataclasses
import math

import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st
from repro.analysis import (ConservationHazard, DeadlineHazard, DepEdge,
                            EnvAliasHazard, KVAliasHazard,
                            OperandIdentityHazard, ProgramOrderHazard,
                            build_depgraph, certify_trace,
                            check_conservation, cross_program_conflicts)
from repro.configs import smoke_config
from repro.core.jit import (JitStats, VLIWJit, build_dense_decode_program,
                            build_dense_decode_template,
                            build_dense_prefill_template,
                            build_hybrid_decode_template,
                            build_moe_decode_template,
                            build_ssm_decode_template)
from repro.models import Model
from repro.serving import ServeRequest, ServingEngine, Tenant, two_wave_trace


@pytest.fixture(scope="module")
def dense_models():
    out = {}
    for arch, seed in (("gemma3-1b", 1), ("yi-9b", 2)):
        cfg = smoke_config(arch)
        m = Model(cfg, param_dtype=jnp.float32)
        out[arch] = (m, m.init(jax.random.PRNGKey(seed)))
    return out


@pytest.fixture(scope="module")
def served(dense_models):
    """One real certified serve: two same-arch tenants (identical GEMM
    shapes, distinct weights) arriving together, so decode steps coalesce
    into cross-tenant groups — the regime every group-level hazard check
    is about. Returns (report, recorded ScheduleTrace)."""
    m, _ = dense_models["gemma3-1b"]
    p1 = m.init(jax.random.PRNGKey(11))
    p2 = m.init(jax.random.PRNGKey(12))
    eng = ServingEngine([Tenant("t1", m, p1, cache_len=32, max_batch=2),
                         Tenant("t2", m, p2, cache_len=32, max_batch=2)],
                        mode="vliw", certify=True)
    gap = 1.5 * eng._prefill_time(m.cfg, 8)
    trace = two_wave_trace(["t1", "t2"], ["t1", "t2"], gap, prompt_len=8,
                           max_new_tokens=4, slo_s=1.0)
    rep = eng.run(trace)
    return rep, eng.last_trace


def _prog_positions(trace):
    """(dispatch_idx, op_idx) sites per prog_uid, in dispatch order."""
    pos = {}
    for di, d in enumerate(trace.dispatches):
        for oi, op in enumerate(d.ops):
            if op.prog_uid:
                pos.setdefault(op.prog_uid, []).append((di, oi))
    return pos


def _coalesced_dispatches(trace):
    """Dispatch indices whose group spans >= 2 distinct programs."""
    return [di for di, d in enumerate(trace.dispatches)
            if len({op.prog_uid for op in d.ops if op.prog_uid}) >= 2]


def _replace_op(trace, di, oi, **changes):
    d = trace.dispatches[di]
    ops = list(d.ops)
    ops[oi] = dataclasses.replace(ops[oi], **changes)
    trace.dispatches[di] = dataclasses.replace(d, ops=tuple(ops))


# ---------------------------------------------------------------------------
# clean traces certify clean
# ---------------------------------------------------------------------------

def test_real_serving_trace_certifies_clean(served):
    rep, trace = served
    assert rep.jit.hazard_checks > 0
    assert rep.jit.hazard_violations == 0
    # the trace is a real one: coalesced cross-tenant groups, declared KV
    # footprints, and a full request lifecycle
    assert trace.dispatches and trace.req_admits and trace.req_retires
    assert _coalesced_dispatches(trace)
    assert any(pa.kv_writes for pa in trace.prog_admits)
    cert = certify_trace(trace, raise_on_violation=False)
    assert cert.violations == [] and cert.checks > 0


def test_raw_session_trace_certifies_clean(dense_models, rng):
    """The session-level trace path (no engine): two concurrent dense
    decode programs, driven to completion tick by tick."""
    m, params = dense_models["gemma3-1b"]
    batch = {"tokens": jax.random.randint(rng, (2, 8), 0, m.cfg.vocab_size)}
    _, cache = m.prefill(params, batch, cache_len=32)
    tok = jax.random.randint(jax.random.fold_in(rng, 3), (2, 1), 0,
                             m.cfg.vocab_size)
    jit = VLIWJit(max_group=8)
    session = jit.session(record_trace=True)
    for sid in (0, 1):
        session.admit(build_dense_decode_program(
            m, params, tok, copy.deepcopy(cache), stream_id=sid))
    now = 0.0
    while session.live:
        ev = session.tick(now)
        now = max(now, ev.t)
    assert session.trace.dispatches
    assert all(op.prog_uid for d in session.trace.dispatches
               for op in d.ops)
    cert = certify_trace(session.trace, raise_on_violation=False)
    assert cert.violations == [] and cert.checks > 0


def test_hazard_counters_fold_through_merge():
    a = JitStats(hazard_checks=3, hazard_violations=1)
    b = JitStats(hazard_checks=2, hazard_violations=0)
    assert a.merge(b) is a
    assert a.hazard_checks == 5 and a.hazard_violations == 1


# ---------------------------------------------------------------------------
# mutated traces are rejected with the expected violation class
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(0, 7))
def test_swapping_same_stream_ops_is_program_order_hazard(served, idx):
    """Reordering two ops of one program (the OoO move the scheduler must
    never make) is caught as a seq regression."""
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    progs = [(uid, ps) for uid, ps in sorted(_prog_positions(trace).items())
             if len(ps) >= 2]
    assert progs
    uid, ps = progs[idx % len(progs)]
    (d1, o1), (d2, o2) = ps[0], ps[-1]
    assert d1 != d2      # a legal trace never groups two same-stream ops
    a, b = trace.dispatches[d1].ops[o1], trace.dispatches[d2].ops[o2]
    _replace_op(trace, d1, o1, seq=b.seq, tag=b.tag)
    _replace_op(trace, d2, o2, seq=a.seq, tag=a.tag)
    with pytest.raises(ProgramOrderHazard):
        certify_trace(trace)
    cert = certify_trace(trace, raise_on_violation=False)
    assert any(isinstance(v, ProgramOrderHazard) for v in cert.violations)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 7))
def test_dropping_a_retire_is_conservation_hazard(served, idx):
    """Every admitted request must retire / evict / surface unfinished —
    deleting one retirement breaks the balance."""
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    assert trace.req_retires
    rid, _ = trace.req_retires.pop(idx % len(trace.req_retires))
    if rid in trace.evicted or rid in trace.unfinished:
        pytest.skip("request covered by another lifecycle set")
    with pytest.raises(ConservationHazard):
        check_conservation(trace)
    vs = check_conservation(trace, raise_on_violation=False)
    assert vs and all(isinstance(v, ConservationHazard) for v in vs)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 7))
def test_duplicate_admission_is_conservation_hazard(served, idx):
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    trace.req_admits.append(trace.req_admits[idx % len(trace.req_admits)])
    with pytest.raises(ConservationHazard):
        check_conservation(trace)


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 3))
def test_aliased_kv_slots_are_kv_hazard(served, idx):
    """Two tenants' programs claiming the same KV row must not share a
    concurrent group."""
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    cds = _coalesced_dispatches(trace)
    assert cds
    di = cds[idx % len(cds)]
    row = ("kv", "t1", 0)
    for oi in range(len(trace.dispatches[di].ops)):
        _replace_op(trace, di, oi, kv_writes=(row,))
    with pytest.raises(KVAliasHazard):
        certify_trace(trace)
    cert = certify_trace(trace, raise_on_violation=False)
    assert any(isinstance(v, KVAliasHazard) for v in cert.violations)


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 3))
def test_shared_weight_key_across_distinct_params_is_operand_hazard(
        served, idx):
    """A shared-operand dispatch whose ops resolved to different weight
    arrays would serve one tenant the other's weights."""
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    cds = _coalesced_dispatches(trace)
    assert cds
    di = cds[idx % len(cds)]
    d = trace.dispatches[di]
    for oi in range(len(d.ops)):
        _replace_op(trace, di, oi, weight_key=("shared", "wq"),
                    weight_id=(0xBAD + oi,))
    trace.dispatches[di] = dataclasses.replace(
        trace.dispatches[di], shared_operand=True)
    with pytest.raises(OperandIdentityHazard):
        certify_trace(trace)
    cert = certify_trace(trace, raise_on_violation=False)
    assert any(isinstance(v, OperandIdentityHazard)
               for v in cert.violations)


def test_shared_env_object_is_env_hazard(served):
    """Two programs writing the same key of one (supposedly private) env
    object in one group."""
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    di = _coalesced_dispatches(trace)[0]
    for oi in range(len(trace.dispatches[di].ops)):
        _replace_op(trace, di, oi, env_id=0xE17, env_writes=("x",))
    with pytest.raises(EnvAliasHazard):
        certify_trace(trace)


def test_undeclared_env_writes_alias_everything(served):
    """The conservative wildcard: an op with UNDECLARED writes conflicts
    with any declared writer of the same env object."""
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    di = _coalesced_dispatches(trace)[0]
    _replace_op(trace, di, 0, env_id=0xE17, env_writes=("*",))
    _replace_op(trace, di, 1, env_id=0xE17, env_writes=("hf",))
    with pytest.raises(EnvAliasHazard):
        certify_trace(trace)


def test_latest_start_regression_is_deadline_hazard(served):
    """latest_start_t must be non-decreasing within a program (the
    remaining GEMM-suffix critical path only shrinks)."""
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    progs = [(uid, ps) for uid, ps in sorted(_prog_positions(trace).items())
             if len(ps) >= 2]
    uid, ps = progs[0]
    (d1, o1), (d2, o2) = ps[0], ps[-1]
    first = trace.dispatches[d1].ops[o1]
    _replace_op(trace, d2, o2, latest_start_t=first.latest_start_t - 1.0)
    with pytest.raises(DeadlineHazard):
        certify_trace(trace)


def test_deadline_drift_is_deadline_hazard(served):
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    progs = [(uid, ps) for uid, ps in sorted(_prog_positions(trace).items())
             if len(ps) >= 2]
    uid, ps = progs[0]
    (d2, o2) = ps[-1]
    old = trace.dispatches[d2].ops[o2].deadline_t
    drifted = 0.5 * old if math.isfinite(old) else 1.0
    _replace_op(trace, d2, o2, deadline_t=drifted)
    with pytest.raises(DeadlineHazard):
        certify_trace(trace)


def test_same_stream_ops_in_one_group_is_program_order_hazard(served):
    """Packing two ops of one stream into a single concurrent group —
    even in the right order — executes an intra-stream dependence
    'simultaneously'."""
    _, trace0 = served
    trace = copy.deepcopy(trace0)
    progs = [(uid, ps) for uid, ps in sorted(_prog_positions(trace).items())
             if len(ps) >= 2]
    uid, ps = progs[0]
    (d1, o1), (d2, o2) = ps[0], ps[1]
    moved = trace.dispatches[d2].ops[o2]
    d = trace.dispatches[d1]
    trace.dispatches[d1] = dataclasses.replace(d, ops=d.ops + (moved,))
    with pytest.raises(ProgramOrderHazard):
        certify_trace(trace)


# ---------------------------------------------------------------------------
# engine-level satellites
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["vliw", "batched", "time"])
def test_duplicate_req_id_admission_rejected(dense_models, mode):
    """Request ids key prompt synthesis, eviction dedup and conservation;
    a colliding trace must be rejected up front in EVERY mode."""
    m, p = dense_models["gemma3-1b"]
    eng = ServingEngine([Tenant("a", m, p, cache_len=32, max_batch=2)],
                        mode=mode)
    reqs = [ServeRequest(5, "a", 0.0, 8, 2, 1.0),
            ServeRequest(5, "a", 1e-6, 8, 2, 1.0)]
    with pytest.raises(ValueError, match="duplicate req_id"):
        eng.run(reqs)


def test_unique_req_ids_still_admit(dense_models):
    m, p = dense_models["gemma3-1b"]
    eng = ServingEngine([Tenant("a", m, p, cache_len=32, max_batch=2)],
                        mode="vliw", certify=True)
    reqs = [ServeRequest(0, "a", 0.0, 8, 2, 1.0),
            ServeRequest(1, "a", 1e-6, 8, 2, 1.0)]
    rep = eng.run(reqs)
    assert rep.unfinished == 0
    assert rep.jit.hazard_violations == 0 and rep.jit.hazard_checks > 0


# ---------------------------------------------------------------------------
# static dependence graphs
# ---------------------------------------------------------------------------

class _St:
    """Minimal stage stand-in: only the declared access sets matter."""

    def __init__(self, tag, reads=None, writes=None):
        self.tag = tag
        if reads is not None:
            self.reads = tuple(reads)
        if writes is not None:
            self.writes = tuple(writes)


def test_depgraph_raw_war_waw():
    g = build_depgraph([_St("a", reads=("cache",), writes=("x",)),
                        _St("b", reads=("x",), writes=("h",)),
                        _St("c", reads=(), writes=("x",))])
    kinds = {(e.kind, e.src, e.dst, e.resource) for e in g.edges}
    assert ("RAW", 0, 1, "x") in kinds
    assert ("WAR", 1, 2, "x") in kinds
    assert ("WAW", 0, 2, "x") in kinds
    assert not g.conservative
    assert not g.unsourced_reads          # "cache" is bind-time


def test_depgraph_undeclared_stage_is_barrier():
    g = build_depgraph([_St("a", reads=(), writes=("x",)),
                        _St("mystery"),                   # undeclared
                        _St("c", reads=("x",), writes=("y",))])
    assert g.conservative == [1]
    assert any(e.kind == "WAW" and (e.src, e.dst) == (0, 1)
               for e in g.edges)
    # the wildcard writer is the last writer of everything it clobbered
    assert any(e.kind == "RAW" and (e.src, e.dst) == (1, 2)
               for e in g.edges)


def test_depgraph_flags_unsourced_reads():
    g = build_depgraph([_St("a", reads=("bogus",), writes=("x",))])
    assert g.unsourced_reads == [(0, "bogus")]


@pytest.mark.parametrize("build,arch", [
    (build_dense_decode_template, "gemma3-1b"),
    (build_moe_decode_template, "grok-1-314b"),
    (build_ssm_decode_template, "mamba2-2.7b"),
    (build_hybrid_decode_template, "falcon-h1-34b"),
    (build_dense_prefill_template, "gemma3-1b"),
], ids=["dense", "moe", "ssm", "hybrid", "prefill"])
def test_dense_decode_template_fully_declared(build, arch):
    """Every stage the builders emit (the four decode families and dense
    prefill) declares its access sets (no conservative wildcards) and
    every read has a source: an upstream writer or a bind-time binding."""
    m = Model(smoke_config(arch), param_dtype=jnp.float32)
    template = build(m, m.init(jax.random.PRNGKey(1)), 2)
    g = build_depgraph(template)
    assert not g.conservative
    assert not g.unsourced_reads
    # the spine is a RAW chain through "x" (embed -> layers -> final norm)
    assert any(e.kind == "RAW" and e.resource == "x" for e in g.edges)
    assert g.predecessors(len(g.labels) - 1)


def test_cross_program_conflicts_kv_and_env():
    env = {}
    a = _NSProg(kv_writes=(("kv", "t", 0),), env=env,
                stages=[_St("s", reads=(), writes=("x",))])
    b = _NSProg(kv_writes=(("kv", "t", 0), ("kv", "t", 1)), env={},
                stages=[])
    assert cross_program_conflicts(a, b) == [("kv", ("kv", "t", 0))]
    c = _NSProg(kv_writes=(), env=env,
                stages=[_St("s", reads=(), writes=("x", "y"))])
    assert ("env", "x") in cross_program_conflicts(a, c)
    d = _NSProg(kv_writes=(("kv", "u", 0),), env={}, stages=[])
    assert cross_program_conflicts(b, d) == []


class _NSProg:
    def __init__(self, kv_writes, env, stages):
        self.kv_writes = kv_writes
        self.env = env
        self.stages = stages


def test_served_programs_have_disjoint_footprints(served):
    """The engine's declared per-tenant KV rows really are disjoint across
    tenants — the static justification for cross-tenant coalescing."""
    _, trace = served
    by_stream = {}
    for pa in trace.prog_admits:
        by_stream.setdefault(pa.stream, set()).update(pa.kv_writes)
    streams = sorted(by_stream)
    assert len(streams) >= 2
    for i in streams:
        for j in streams:
            if i < j:
                assert not (by_stream[i] & by_stream[j])
