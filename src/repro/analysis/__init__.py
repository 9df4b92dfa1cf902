"""Static dependence analysis for the OoO VLIW JIT.

The JIT reorders aggressively — EDF anchoring, stagger/WAIT, cross-tenant
superkernel coalescing, shared-operand collapsing — and every reordering
is only legal because of invariants the runtime maintains implicitly (one
live op per stream, private program envs, per-tenant KV slots, weight keys
that really name one array). This package makes those invariants EXPLICIT
and machine-checkable, in three passes:

``repro.analysis.depgraph``
    Static read/write-set dependence analysis per ``KernelProgram``.
    Every stage the builders in ``core/jit.py`` emit declares the env
    keys and KV-cache resources it reads and writes (the optional
    ``reads``/``writes`` fields on ``GemmStage``/``GlueStage``/
    ``StackedGemmStage``); an undeclared stage conservatively aliases
    everything. The pass yields RAW/WAR/WAW edges within a program —
    the true dependence structure the scheduler's program-order rule
    over-approximates — plus cross-program KV-slot/env aliasing
    constraints between tenants.

``repro.analysis.certify``
    Dynamic schedule certification. ``JitSession(record_trace=True)``
    records a ``ScheduleTrace`` (program admissions, stagger/WAIT
    events, per-superkernel group membership with per-op
    ``(stream, prog_uid, tag, seq)`` identity); the certifier replays it
    and re-derives the legality of every out-of-order decision.
    ``ServingEngine(certify=True)`` runs the incremental certifier per
    tick and raises on the first violation.

``repro.analysis.lint``
    Tracer-hazard linter: an AST pass over ``src/repro`` flagging the
    jit-tracing bug classes this codebase has actually hit — jitted
    closures capturing param arrays as baked constants (the last-ulp
    drift class), plan-cache key functions missing fields that
    ``ProgramTemplate.bind`` does not rebind (the stale-template class),
    and glue math called eagerly outside a jitted body (the
    eager-vs-jitted last-ulp class). Runnable as
    ``python -m repro.analysis.lint [path] [--strict] [--json]``.

Hazard taxonomy (all subclasses of ``HazardViolation``; defined in
``repro.core.schedtrace`` so the runtime can raise them without importing
this package):

  * ``ProgramOrderHazard``    — per-stream program order broken: an op of
    one program ran before its predecessor (``seq`` regressed), a stream
    resumed a program it had already moved past, or two ops of one stream
    were packed into a single coalesced (concurrent) superkernel group.
  * ``KVAliasHazard``         — two ops in one group belong to programs
    whose declared KV write sets overlap (same cache owner + slot):
    concurrent writers to one KV row.
  * ``EnvAliasHazard``        — two ops in one group write the same key
    of the SAME program environment object (program envs are supposed to
    be private; a shared env dict aliases every key in it).
  * ``OperandIdentityHazard`` — a shared-operand dispatch
    (``clustering.shared_weight_key``) packed ops whose weight closures
    resolved to DIFFERENT arrays: the single weight load would silently
    serve the wrong tenant. Checked both statically by the certifier and
    at runtime by ``SuperkernelExecutor.execute``.
  * ``DeadlineHazard``        — EDF bookkeeping broke monotonicity:
    within one program the deadline must stay constant and
    ``latest_start_t`` must be non-decreasing in program order (the
    remaining GEMM-suffix critical path only shrinks as pc advances).
  * ``ConservationHazard``    — request accounting does not balance:
    every admitted request must retire, be evicted (exactly once), or
    surface in ``ServeReport.unfinished``; no request may be admitted or
    retired twice, nor retire/evict without admission.
"""
from repro.core.schedtrace import (ConservationHazard, DeadlineHazard,
                                   DispatchRecord, EnvAliasHazard,
                                   HazardViolation, KVAliasHazard,
                                   OperandIdentityHazard, OpRecord,
                                   ProgramAdmit, ProgramOrderHazard,
                                   ScheduleTrace)
from repro.analysis.certify import (ScheduleCertifier, certify_trace,
                                    check_conservation)
from repro.analysis.depgraph import (DepEdge, DepGraph, build_depgraph,
                                     cross_program_conflicts, stage_access)

__all__ = [
    "HazardViolation", "ProgramOrderHazard", "KVAliasHazard",
    "EnvAliasHazard", "OperandIdentityHazard", "DeadlineHazard",
    "ConservationHazard", "ScheduleTrace", "OpRecord", "DispatchRecord",
    "ProgramAdmit", "ScheduleCertifier", "certify_trace",
    "check_conservation", "DepEdge", "DepGraph", "build_depgraph",
    "cross_program_conflicts", "stage_access",
]
