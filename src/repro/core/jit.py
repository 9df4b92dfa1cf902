"""The OoO VLIW JIT runtime — real, event-driven execution path.

This is the paper's Figure 1 made concrete: multiple tenant streams, each an
*instruction stream* of declared kernel ops, multiplexed onto one device by
(a) clustering + coalescing compatible GEMMs into Pallas superkernels and
(b) OoO, SLO-aware interleaving of the streams.

Execution model (TPU adaptation, DESIGN.md §2): a tenant's decode step is
compiled into a ``KernelProgram`` — an alternating sequence of GEMM stages
(declared to the JIT, coalescible across tenants) and glue stages (norms,
rope, cache updates, softmax — executed eagerly per tenant). Prompt
prefills compile the same way (``build_dense_prefill_template``): the
prompt length is the GEMM m dimension, padded to a power-of-two bucket
(``prefill_bucket``), and the program epilogue writes the request's KV rows
into the tenant's slotted cache — so long prompts enter the live op pool
and coalesce with decode (and other tenants' prefill) traffic instead of
serializing the device (``JitStats.prefill_coalesced``).

Non-dense tenants are first-class streams too: MoE decode steps compile
with the router/dispatch as glue and 3·E per-expert FFN ``GemmStage``s
(``build_moe_decode_template`` — same expert GEMMs coalesce across tenants,
``JitStats.expert_coalesced``), and SSM (Mamba-2/SSD) decode steps compile
with the in/out projections declared and the selective-scan recurrence as
glue (``build_ssm_decode_template``) — the paper's heterogeneous-tenant
multiplexing scenario, not just same-family dense fleets.

The runtime is a **virtual-time event loop**, not a round barrier. A
``JitSession`` keeps the scheduler, the live op pool and the stats open
across calls so that:

  * programs are admitted **mid-flight** — a new tenant's ``KernelProgram``
    joins the live pool *between superkernel dispatches*, not at a round
    boundary (``JitStats.mid_flight_admissions`` counts these);
  * the caller feeds the next known future admission into
    ``OoOScheduler.next_arrival_t``, so the scheduler's stagger/WAIT branch
    (paper §5.2: "purposefully delays ill-fitting kernels for better
    coalescing at a slightly later time") executes on the real path
    (``JitStats.waits``);
  * per-request SLOs flow into per-op ``latest_start_t`` via the program's
    remaining-GEMM critical path, driving EDF anchoring and the eviction of
    already-missed stragglers (``JitStats.evictions``).

``VLIWJit.run`` is the closed-world convenience wrapper: it opens a session,
admits the given programs (plus an optional timed ``arrivals`` schedule) and
ticks the loop to completion.

Scheduler overhead stays off the critical path via the persistent plan
caches (core/plancache.py) owned by the ``VLIWJit`` and surviving sessions:
``plan_cache`` holds compiled ``ProgramTemplate``s — the serving engine
rebinds only per-step state (tokens, KV cache refs, deadlines) on
steady-state ticks — and ``block_plans`` memoizes the coalescer's
superkernel block choice per group signature. Per-session cache deltas are
reported in ``JitStats.plan_cache`` / ``JitStats.block_plans``.

Execution overhead stays off the critical path via the ``VLIWJit``-owned
``SuperkernelExecutor`` (core/dispatch.py): packed weight operands are
cached persistently (never re-staged in steady state), envelopes are
bucketed to powers of two, and the whole pack→kernel→unpack dispatch is
one jitted executable — so a stable trace runs zero-copy and zero-retrace
after warmup (``JitStats.dispatch``).

**Layer-stacked templates (scan-over-layers).** By default
(``stacked_layers=True`` throughout) the builders emit ONE scanned layer
body per homogeneous sub-stack of layers instead of ~6 stages per layer:
the params tree already stores weights stacked along a leading layer axis,
so a ``StackedGemmStage`` declares the whole sub-stack as one schedulable
op whose operands are the stacked ``blocks`` arrays ([L, k, n] per
projection, [L, E, k, n] for MoE expert packs) and whose execution is a
jitted ``jax.lax.scan`` over the layer axis — template build, trace size
and plan/weight-cache entries become O(1) in depth. Design points:

  * weight-key schema (``clustering.weight_key`` is the single
    constructor): stacked operands drop the layer index —
    ``(model, pid, "stack", lo, hi, name[, expert])`` names ONE stacked
    operand covering layers [lo, hi), so the dispatch executor caches
    O(#operands) packed entries per tenant instead of O(#operands · L);
  * sub-stack partitioning (``partition_layers``): non-homogeneous stacks
    — gemma-style local/global attention alternation — split into maximal
    homogeneous runs, each scanned separately (``is_global`` must be
    static inside one scan body);
  * scan carry layout: the residual stream ``x [B, d]`` is the carry;
    per-layer xs are the norm scales, the layer's KV (or conv/h) cache
    slices and the padded stacked weights; ys stack the per-layer cache
    updates, which the epilogue concatenates back into the tenant's cache
    — the same [L, ...] layout the per-layer path's ``jnp.stack`` built;
  * the scan body's GEMMs (``_scan_gemm``) replicate the dispatch
    executor's solo-dispatch bucketing EXACTLY (same m-tile bucket, same
    power-of-two envelopes, same block sizes), which is what makes the
    stacked path bit-identical to per-layer emission
    (tests/test_stacked_templates.py asserts logits AND cache identity
    for dense decode/prefill, MoE and SSM);
  * cost/coalescing granularity: a stacked op is charged as L sequential
    tile-waves (``GemmShape.layers``), clusters on its full stack
    signature (``clustering.coalesce_key``) so only same-depth-and-dims
    tenants coalesce entire stacks, and carries the dominant operand's
    shape for EDF/aspect bookkeeping.

``stacked_layers=False`` (builders + ServingEngine) keeps the per-layer
emission path alive as the bit-identity oracle.

Correctness: running a program must produce bit-comparable results to the
monolithic ``Model.decode_step`` (tests/test_jit_engine.py), regardless of
admission timing (tests/test_event_loop.py).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.autotuner import LiveTuner
from repro.core.clustering import (is_expert_op, op_weight_identity,
                                   op_weight_key, shared_weight_key,
                                   weight_key)
from repro.core.coalescer import Coalescer
from repro.core.costmodel import (BlockConfig, CostModel, GemmShape,
                                  attached_device)
from repro.core.dispatch import (DispatchStats, SuperkernelExecutor,
                                 _tile_bucket, device_weight_budget,
                                 envelope_bucket)
from repro.core.kernelspec import make_op, op_aspect
from repro.core.plancache import PlanCache, PlanCacheStats
from repro.core.scheduler import OoOScheduler, SchedulerConfig
from repro.core.schedtrace import (DispatchRecord, OpRecord, ProgramAdmit,
                                   ScheduleTrace)
from repro.kernels.coalesced_gemm import coalesced_gemm
from repro.models.layers import rmsnorm, apply_rope


# ---------------------------------------------------------------------------
# kernel programs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GemmStage:
    tag: str                       # cluster tag, e.g. "L3.ffn_gate"
    weight_key: Tuple              # identity key for operand sharing
    weight_fn: Callable[[], jax.Array]
    # consumes env, returns the activation matrix [m, k]
    input_fn: Callable[[Dict[str, Any]], jax.Array]
    # receives (env, gemm_output)
    output_fn: Callable[[Dict[str, Any], jax.Array], None]
    # statically-known problem shape; lets deadline annotation cost the
    # stage without materializing its weight (weight_fn may be non-trivial,
    # e.g. a tied-embedding transpose)
    shape: Optional[GemmShape] = None
    # declared access sets for static dependence analysis
    # (repro.analysis.depgraph): the env keys input_fn/output_fn touch —
    # plus the reserved "cache" / "new_layers" resources for stages that
    # read or update KV state. None (undeclared) means the analysis must
    # conservatively assume the stage aliases EVERYTHING; the builders in
    # this module declare every stage they emit.
    reads: Optional[Tuple] = None
    writes: Optional[Tuple] = None


@dataclasses.dataclass
class GlueStage:
    fn: Callable[[Dict[str, Any]], None]
    # declared access sets (see GemmStage.reads/writes): what the eager
    # glue closure reads and writes in the program env. Undeclared glue
    # aliases everything, which serializes it against every neighbor in
    # the dependence graph.
    reads: Optional[Tuple] = None
    writes: Optional[Tuple] = None


def partition_layers(flags: Sequence[bool]) -> List[Tuple[int, int]]:
    """Partition a layer-flag sequence into maximal homogeneous runs.

    Returns half-open ``(lo, hi)`` spans covering ``range(len(flags))``
    exactly once, in order, with the flag constant inside each span — the
    sub-stacks a non-homogeneous model (``layer_is_global`` alternation)
    scans separately, because the flag must be static inside one scan
    body. A homogeneous depth-L model yields the single span ``(0, L)``.
    """
    runs: List[Tuple[int, int]] = []
    lo = 0
    for i in range(1, len(flags)):
        if flags[i] != flags[lo]:
            runs.append((lo, i))
            lo = i
    if len(flags):
        runs.append((lo, len(flags)))
    return runs


@dataclasses.dataclass
class StackedOperand:
    """One stacked weight operand of a scanned layer body: a
    ``[Lsub, ..., k, n]`` array covering a homogeneous sub-stack of layers
    (MoE expert packs carry an extra expert axis). ``shape.layers`` counts
    the operand's sequential tile-waves — Lsub for dense operands,
    Lsub·E for expert packs (each scan step runs E expert GEMMs)."""

    tag: str                       # per-layer stage tag, e.g. "ffn_gate"
    weight_key: Tuple              # clustering.weight_key(..., stack=...)
    shape: GemmShape               # per-wave (m, n, k) with layers = waves
    # lazy builder of the raw stacked array (a [lo:hi) view of the params
    # tree's stacked blocks) — only runs on an operand-cache miss
    weight_fn: Callable[[], jax.Array]
    # identity guard: the ORIGINAL stacked params arrays (stable across
    # ticks) — never per-build slices, which would read as phantom
    # hot-swaps and repack the whole stack every tick
    guard: Tuple = ()


@dataclasses.dataclass
class StackedGemmStage:
    """One scanned layer body: a whole homogeneous sub-stack of layers as
    a single schedulable op (the stacked-template analogue of ~6·Lsub
    ``GemmStage``s). The session fetches each operand's padded stack from
    the executor's persistent cache (``SuperkernelExecutor.
    stacked_operand``) and calls ``run`` — a jitted ``jax.lax.scan`` whose
    body replays the per-layer math with ``_scan_gemm`` standing in for
    the executor's solo dispatch, bit-identically."""

    tag: str                       # body identity, e.g. "body_0_12"
    weight_key: Tuple              # clustering.weight_key("body", stack=...)
    operands: List[StackedOperand]
    layers: int                    # hi - lo
    # run(env, {operand tag -> padded stacked array}, executor): executes
    # the scan and writes results (residual stream, cache updates) to env
    run: Callable[[Dict[str, Any], Dict[str, jax.Array],
                   SuperkernelExecutor], None]
    # declared access sets (see GemmStage.reads/writes): a scanned body
    # reads the residual stream + cache slices and writes the residual
    # stream + its cache-update chunk
    reads: Optional[Tuple] = None
    writes: Optional[Tuple] = None


Stage = Any  # GemmStage | GlueStage | StackedGemmStage

# monotonically-increasing KernelProgram instance ids (trace identity)
_PROG_UIDS = itertools.count(1)


def _scan_gemm(a: jax.Array, w_pad: jax.Array, n_real: int, *, bm: int,
               bn: int, bk: int, interpret: bool) -> jax.Array:
    """One GEMM inside a scanned layer body, replicating the dispatch
    executor's solo dispatch EXACTLY — same m-tile bucket, same padded
    (K, N) envelope (``w_pad`` is one xs slice of a cached
    ``stacked_operand``), same block clamping — so a stacked body is
    bit-identical to the per-layer path dispatching each stage."""
    m = int(a.shape[0])
    K, N = int(w_pad.shape[-2]), int(w_pad.shape[-1])
    m_tiles = _tile_bucket([m], bm)
    ap = jnp.pad(a, ((0, m_tiles * bm - m), (0, K - int(a.shape[1]))))
    out = coalesced_gemm(ap, w_pad[None], jnp.zeros((m_tiles,), jnp.int32),
                         bm=bm, bn=min(bn, N), bk=min(bk, K),
                         interpret=interpret)
    return out[:m, :n_real]


def _stack_slice(arr: jax.Array, lo: int, hi: int) -> jax.Array:
    return arr if lo == 0 and hi == int(arr.shape[0]) else arr[lo:hi]


@dataclasses.dataclass
class KernelProgram:
    """One tenant step: stages + a private environment."""
    stream_id: int
    stages: List[Stage]
    env: Dict[str, Any]
    pc: int = 0
    slo_s: float = float("inf")
    arrival_t: float = 0.0
    # absolute request deadline; when left inf it falls back to
    # arrival_t + slo_s. Carrying it explicitly keeps the deadline exact
    # across successive step programs of one tenant (no float roundtrip
    # through slo_s = deadline - now), which the scheduler's per-
    # (stream, deadline) eviction dedup relies on.
    deadline_t: float = float("inf")
    batch: int = 1                 # activation rows (m) of every GEMM stage
    # serving phase this program implements: "decode" (one step of a slotted
    # batch) or "prefill" (a whole prompt pass whose epilogue writes the
    # request's KV rows into the tenant's cache). Plumbed onto every op the
    # program emits (KernelOp.op_kind) for the scheduler's coalescing stats.
    kind: str = "decode"
    # (req_id, final deadline) per request batched into this step. Plumbed
    # onto every KernelOp the program emits so the scheduler can account
    # SLO demotions per *request* — a straggler next to healthy batchmates
    # counts exactly once across steps, not zero times (hidden behind the
    # batch's healthy anchor deadline) or once per step.
    req_deadlines: Tuple = ()
    # KV-cache rows this program writes, as ("kv", owner, slot) resources —
    # the serving engine binds the tenant's cache identity + slot indices
    # (all batch rows for a decode step, the reserved slot for a prefill).
    # Ops inherit the set on their trace records; the schedule certifier
    # rejects any coalesced group whose members' sets overlap (two
    # concurrent writers to one KV row). Empty for raw programs — no
    # declared rows, no possible overlap.
    kv_writes: Tuple = ()
    # mesh placement: the device this program's ops execute on. Stamped by
    # JitSession.admit from the session's device id — one session drives
    # exactly one device's timeline, so a program never spans devices.
    device: int = 0
    # instance identity for trace records / program-order certification
    # (seq_index resets across a stream's successive step programs, so
    # (stream, seq) alone cannot express cross-program ordering)
    uid: int = dataclasses.field(
        default_factory=lambda: next(_PROG_UIDS), compare=False)
    _gemm_suffix: Optional[List[float]] = dataclasses.field(
        default=None, repr=False, compare=False)
    # set by ProgramTemplate.bind: programs bound from one template share
    # the template's memoized suffix instead of re-deriving it per step
    _suffix_fn: Optional[Callable[[CostModel], List[float]]] = \
        dataclasses.field(default=None, repr=False, compare=False)

    def done(self) -> bool:
        return self.pc >= len(self.stages)

    def advance_glue(self) -> Optional[Stage]:
        """Run glue stages until the next GEMM / stacked body stage (or
        completion)."""
        while self.pc < len(self.stages):
            st = self.stages[self.pc]
            if isinstance(st, (GemmStage, StackedGemmStage)):
                return st
            st.fn(self.env)
            self.pc += 1
        return None

    @property
    def effective_deadline(self) -> float:
        return self.deadline_t if math.isfinite(self.deadline_t) \
            else self.arrival_t + self.slo_s

    def remaining_gemm_time(self, cost: CostModel, pc: int) -> float:
        """Modeled critical-path seconds of the GEMM stages in
        ``stages[pc:]`` — the suffix the scheduler subtracts from the
        request deadline to get the current op's ``latest_start_t``."""
        if self._gemm_suffix is None:
            if self._suffix_fn is not None:
                self._gemm_suffix = self._suffix_fn(cost)
            else:
                self._gemm_suffix = _gemm_suffix_table(self.stages,
                                                       self.batch, cost)
        return self._gemm_suffix[pc]


def _gemm_suffix_table(stages: List[Stage], batch: int,
                       cost: CostModel) -> List[float]:
    """suffix[i] = modeled seconds of the GEMM stages in ``stages[i:]``."""
    suf = [0.0] * (len(stages) + 1)
    for i in range(len(stages) - 1, -1, -1):
        st = stages[i]
        dt = 0.0
        if isinstance(st, GemmStage):
            shape = st.shape
            if shape is None:
                w = st.weight_fn()
                shape = GemmShape(m=batch, n=int(w.shape[1]),
                                  k=int(w.shape[0]))
            dt = cost.gemm_time(shape)
        elif isinstance(st, StackedGemmStage):
            # every operand's GemmShape carries its wave count in .layers,
            # so the body's critical path is the plain sum of gemm_time
            dt = sum(cost.gemm_time(od.shape) for od in st.operands)
        suf[i] = suf[i + 1] + dt
    return suf


# ---------------------------------------------------------------------------
# program templates — the unit the plan cache stores
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramTemplate:
    """A compiled-once tenant step: the stage list, glue closures and weight
    keys, with NO per-step state. ``bind()`` rebinds only the per-step
    environment (tokens, KV cache refs, deadlines) into a fresh lightweight
    ``KernelProgram`` — the steady-state hot path does this instead of
    re-deriving the whole stage list every tick.

    Validity contract (what the cache key must capture): the stages close
    over the model config, the params tree and the batch size m. Everything
    that varies per step is read out of the program env. Templates are
    therefore keyed by (model identity, batch m, dtype, cache geometry) and
    identity-guarded on the params object (core/plancache.py).
    """

    stages: List[Stage]
    batch: int
    model_name: str = ""
    # "decode": batch = the slotted batch m, tokens bound as [m, 1];
    # "prefill": batch = the padded prompt length (prefill bucket), tokens
    # bound as [1, batch] — the prompt IS the GEMM m dimension.
    kind: str = "decode"
    _suffix: Optional[List[float]] = dataclasses.field(
        default=None, repr=False, compare=False)
    _suffix_cost_id: Optional[int] = dataclasses.field(
        default=None, repr=False, compare=False)

    def gemm_suffix(self, cost: CostModel) -> List[float]:
        """Memoized per cost model — bound programs share one table."""
        if self._suffix is None or self._suffix_cost_id != id(cost):
            self._suffix = _gemm_suffix_table(self.stages, self.batch, cost)
            self._suffix_cost_id = id(cost)
        return self._suffix

    def bind(self, *, stream_id: int, tokens: jax.Array, cache,
             slo_s: float = float("inf"), arrival_t: float = 0.0,
             deadline_t: float = float("inf"),
             req_deadlines: Tuple = (),
             kv_writes: Tuple = (),
             env_extra: Optional[Dict[str, Any]] = None) -> KernelProgram:
        """Instantiate one step: fresh env + deadlines, shared stages.

        ``env_extra`` merges additional per-step entries into the program
        env (the prefill path binds ``real_len`` / ``slot`` / ``req``);
        ``kv_writes`` declares the ("kv", owner, slot) cache rows this
        step writes (see KernelProgram.kv_writes)."""
        if self.kind == "prefill":
            assert int(tokens.shape[1]) == self.batch, \
                (tokens.shape, self.batch)
        else:
            assert int(tokens.shape[0]) == self.batch, \
                (tokens.shape, self.batch)
        env: Dict[str, Any] = {"tokens": tokens, "cache": cache,
                               "new_layers": {"k": [], "v": []}}
        if env_extra:
            env.update(env_extra)
        return KernelProgram(stream_id=stream_id, stages=self.stages,
                             env=env, slo_s=slo_s, arrival_t=arrival_t,
                             deadline_t=deadline_t, batch=self.batch,
                             kind=self.kind,
                             req_deadlines=tuple(req_deadlines),
                             kv_writes=tuple(kv_writes),
                             _suffix_fn=self.gemm_suffix)


def dense_program_cache_key(model, params, batch: int, cache, *,
                            stacked: bool = True) -> Tuple:
    """Plan-cache key for a dense decode template: (model identity, active
    batch m, dtype, cache geometry). Params identity is deliberately NOT in
    the key — a weight hot-swap lands on the same slot and is caught by the
    cache's identity guard (``guard=(model, params)`` at the lookup site),
    which invalidates (and counts) instead of silently serving stale
    closures. The guard also pins both objects, so ``id(model)`` here can
    never be a recycled address aliasing a dead model.

    The emission regime and depth are part of the key: a stacked and a
    per-layer template of the same model must never alias, and stacked
    geometry (sub-stack spans) is a function of num_layers."""
    kc = cache["layers"]["k"]
    return ("dense-decode", model.cfg.name, id(model), batch,
            str(params["embed"].dtype), str(kc.dtype), tuple(kc.shape),
            ("stacked", bool(stacked), model.cfg.num_layers))


# ---------------------------------------------------------------------------
# program builders for dense GQA (the real-execution demo family)
# ---------------------------------------------------------------------------

def _emit_dense_body(cfg: ModelConfig, params, stages: List[Stage], *,
                     m_rows: int, attend_for, ffn_for=None,
                     attend_reads: Tuple = ("wq", "wk", "wv", "cache")
                     ) -> None:
    """Emit the per-layer stage scaffolding shared by the dense DECODE and
    PREFILL builders: pre-norm, the wq/wk/wv projections, the phase-specific
    attention glue (``attend_for(l, lp, is_global)``), wo, post-norm and the
    gated FFN. There is deliberately exactly ONE copy of this: cross-phase
    operand sharing (a prefill op loading weights once with a decode op)
    requires both builders to emit byte-identical weight keys and tags, so
    the scaffolding must never drift between them.

    ``m_rows`` is the activation-row count of every GEMM stage — the slotted
    batch for decode, the padded prompt length for prefill.

    ``ffn_for(l, lp, stages)``, when given, replaces the dense gated-FFN
    emission for layer ``l`` (the MoE builder supplies the router glue +
    per-expert GemmStages); it consumes ``env['h2']`` (set by the post-attn
    glue) and must leave ``env['x']`` updated with the FFN residual. The
    attention scaffolding — weight keys and tags included — stays the
    shared copy, so MoE attention GEMMs coalesce with dense tenants'."""
    hd = cfg.resolved_head_dim
    blocks = params["blocks"]
    # weight identity includes the params object: two tenants of the same
    # architecture only share operands (and thus a single weight load in
    # the superkernel) when they literally serve the same weights
    pid = id(params)

    def glue(fn, reads=None, writes=None):
        stages.append(GlueStage(fn, reads=reads, writes=writes))

    def gemm(tag, wkey, wfn, infn, outfn, n, k, reads, writes):
        stages.append(GemmStage(tag, wkey, wfn, infn, outfn,
                                shape=GemmShape(m=m_rows, n=n, k=k),
                                reads=reads, writes=writes))

    for l in range(cfg.num_layers):
        lp = jax.tree_util.tree_map(lambda a, l=l: a[l], blocks)
        is_global = cfg.layer_is_global(l)

        def pre_attn(env, lp=lp):
            env["h"] = rmsnorm(env["x"], lp["ln1"], cfg.norm_eps)

        glue(pre_attn, reads=("x",), writes=("h",))
        for name, n_heads in (("wq", cfg.num_heads), ("wk", cfg.num_kv_heads),
                              ("wv", cfg.num_kv_heads)):
            gemm(f"attn_{name}", weight_key(cfg.name, pid, name, layer=l),
                 lambda lp=lp, name=name: lp["attn"][name],
                 lambda env: env["h"],
                 lambda env, out, name=name: env.__setitem__(name, out),
                 n_heads * hd, cfg.d_model, ("h",), (name,))

        # the attention glue's read set is phase-specific (decode streams
        # the slotted cache, prefill ropes by env positions) — the caller
        # passes the accurate set via attend_reads
        glue(attend_for(l, lp, is_global), reads=attend_reads,
             writes=("attn_out", "new_layers"))
        gemm("attn_wo", weight_key(cfg.name, pid, "wo", layer=l),
             lambda lp=lp: lp["attn"]["wo"],
             lambda env: env["attn_out"],
             lambda env, out: env.__setitem__("attn_proj", out),
             cfg.d_model, cfg.num_heads * hd, ("attn_out",), ("attn_proj",))

        def post_attn(env, lp=lp):
            env["x"] = env["x"] + env["attn_proj"]
            env["h2"] = rmsnorm(env["x"], lp["ln2"], cfg.norm_eps)

        glue(post_attn, reads=("x", "attn_proj"), writes=("x", "h2"))
        if ffn_for is not None:
            ffn_for(l, lp, stages)
            continue
        gemm("ffn_gate", weight_key(cfg.name, pid, "w_gate", layer=l),
             lambda lp=lp: lp["mlp"]["w_gate"],
             lambda env: env["h2"],
             lambda env, out: env.__setitem__("gate", out),
             cfg.d_ff, cfg.d_model, ("h2",), ("gate",))
        gemm("ffn_up", weight_key(cfg.name, pid, "w_up", layer=l),
             lambda lp=lp: lp["mlp"]["w_up"],
             lambda env: env["h2"],
             lambda env, out: env.__setitem__("up", out),
             cfg.d_ff, cfg.d_model, ("h2",), ("up",))

        def act(env):
            env["act"] = _silu_mul(env["gate"], env["up"])

        glue(act, reads=("gate", "up"), writes=("act",))
        gemm("ffn_down", weight_key(cfg.name, pid, "w_down", layer=l),
             lambda lp=lp: lp["mlp"]["w_down"],
             lambda env: env["act"],
             lambda env, out: env.__setitem__("down", out),
             cfg.d_model, cfg.d_ff, ("act",), ("down",))

        def post_ffn(env):
            env["x"] = env["x"] + env["down"]

        glue(post_ffn, reads=("x", "down"), writes=("x",))


# tied-embedding transposes, memoized per embed-array identity: every
# template of one (model, params) — decode at any batch size, prefill at
# any bucket — must hand out the SAME transposed array object, because the
# dispatch executor's packed-weight cache guards on weight-array identity;
# a per-template transpose would make batch-size alternation or
# prefill/decode interleaving look like a weight hot-swap and repack the
# model's largest matrix every flip. Both the embed and the transpose are
# held WEAKLY: the transpose stays alive exactly as long as some template
# closure references it, so discarding an engine/JIT frees its largest
# matrices instead of a module-level cache pinning them process-wide. The
# embed ref doubles as the id-recycling guard (a dead embed whose id is
# reused can never serve a stale transpose — its ref reads None).
_TIED_UNEMBED: Dict[int, Tuple["weakref.ref", "weakref.ref"]] = {}


def _tied_unembed(params) -> jax.Array:
    embed = params["embed"]
    ent = _TIED_UNEMBED.get(id(embed))
    if ent is not None:
        e, wT = ent[0](), ent[1]()
        if e is embed and wT is not None:
            return wT
    wT = embed.T
    if len(_TIED_UNEMBED) > 64:            # prune dead refs opportunistically
        for k in [k for k, (e, _) in _TIED_UNEMBED.items() if e() is None]:
            del _TIED_UNEMBED[k]
    _TIED_UNEMBED[id(embed)] = (weakref.ref(embed), weakref.ref(wT))
    return wT


def _emit_decode_embed(cfg: ModelConfig, params, stages: List[Stage]) -> None:
    """Token-embedding prologue shared by every DECODE builder (dense/MoE
    via the GQA scaffold, SSM): scaled embed of the step's [B, 1] tokens
    squeezed to [B, d], plus the cache-position snapshot."""

    def embed(env):
        x = params["embed"][env["tokens"]]
        env["x"] = (x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype))[:, 0]
        env["pos"] = env["cache"]["pos"]

    stages.append(GlueStage(embed, reads=("tokens", "cache"),
                            writes=("x", "pos")))


def _emit_final_logits(cfg: ModelConfig, params, stages: List[Stage], *,
                       m_rows: int) -> None:
    """Final-norm + unembed tail shared by every decode builder."""

    def final_norm(env):
        env["hf"] = rmsnorm(env["x"], params["final_norm"], cfg.norm_eps)

    stages.append(GlueStage(final_norm, reads=("x",), writes=("hf",)))
    _emit_unembed(cfg, params, stages, m_rows=m_rows)


def _emit_unembed(cfg: ModelConfig, params, stages: List[Stage], *,
                  m_rows: int) -> None:
    """Emit the unembedding GEMM over ``env['hf']`` into ``env['logits']``
    (shared by both builders; ``m_rows`` = the normed rows to unembed)."""
    pid = id(params)
    if cfg.tie_embeddings:
        # hoisted to template-build time AND shared across templates (see
        # _TIED_UNEMBED above): one O(vocab·d) transpose per params, one
        # stable array identity for the executor's weight guard
        wT = _tied_unembed(params)
        wfn, n = (lambda: wT), int(params["embed"].shape[0])
    else:
        wfn, n = (lambda: params["unembed"]), int(params["unembed"].shape[1])
    stages.append(GemmStage(
        "unembed", weight_key(cfg.name, pid, "unembed"), wfn,
        lambda env: env["hf"],
        lambda env, out: env.__setitem__("logits", out),
        shape=GemmShape(m=m_rows, n=n, k=cfg.d_model),
        reads=("hf",), writes=("logits",)))


def _gqa_decode_attend(cfg: ModelConfig, B: int, q_flat, k_flat, v_flat,
                       kc, vc, pos, is_global: bool, out_dtype
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer of single-token slotted-cache GQA attention: the PURE math
    shared verbatim by the per-layer glue (``_decode_attend_for``) and the
    stacked scan body — one copy so the two paths cannot drift. ``kc``/
    ``vc`` are the layer's cache slices [B, Hkv, S, hd]; returns
    (attn_out [B, H·hd], new kc, new vc)."""
    hd = cfg.resolved_head_dim
    q = q_flat.reshape(B, 1, cfg.num_heads, hd)
    k = k_flat.reshape(B, 1, cfg.num_kv_heads, hd)
    v = v_flat.reshape(B, 1, cfg.num_kv_heads, hd)
    posb = pos[:, None]
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)
    upd = jax.vmap(lambda c, kn, p: jax.lax.dynamic_update_slice(
        c, kn, (0, p, 0)))
    kc = upd(kc, k.transpose(0, 2, 1, 3).astype(kc.dtype), pos)
    vc = upd(vc, v.transpose(0, 2, 1, 3).astype(vc.dtype), pos)
    S = kc.shape[2]
    G = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(B, 1, cfg.num_kv_heads, G, hd)
    scores = jnp.einsum("bshgd,bhtd->bhgst", qg, kc,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    idx = jnp.arange(S)
    ok = idx[None, :] <= pos[:, None]
    if cfg.window_size > 0 and not is_global:
        ok = ok & (idx[None, :] > (pos[:, None] - cfg.window_size))
    scores = jnp.where(ok[:, None, None, None, :], scores, -2.0e38)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgst,bhtd->bshgd", p, vc.astype(jnp.float32))
    return (o.reshape(B, cfg.num_heads * hd).astype(out_dtype), kc, vc)


# ---------------------------------------------------------------------------
# jitted per-layer glue — the bit-identity bridge to the stacked regime
# ---------------------------------------------------------------------------
# XLA CPU contracts mul→add chains into FMAs (and loop-fuses
# transcendentals) when compiling a jitted program, but not when executing
# the same ops eagerly one by one — so per-layer glue running eager math
# computes different last-ulp bits than the SAME helper inlined in a jitted
# scan body. Standalone-jitting a helper is bitwise identical to inlining
# it in a jitted scan (measured on this backend), so the per-layer (oracle)
# glue calls these memoized jit wrappers instead of the raw helpers: both
# template regimes then execute jit-compiled bits and the
# stacked-vs-per-layer contract is exact token/cache equality.
# ModelConfig/SSMConfig/MoEConfig are frozen dataclasses, so configs key
# the memo by VALUE — two tenants of the same architecture share entries.
_GLUE_JITS: Dict[Tuple, Callable] = {}

# silu(gate) ⊙ up — the gated-FFN activation glue (dense layers and MoE
# per-expert stages). jax.jit traces lazily per (shape, dtype).
_silu_mul = jax.jit(lambda gate, up: jax.nn.silu(gate) * up)


def _jitted_decode_attend(cfg: ModelConfig, B: int, is_global: bool,
                          out_dtype) -> Callable:
    key = ("decode-attend", cfg, B, bool(is_global),
           jnp.dtype(out_dtype).name)
    fn = _GLUE_JITS.get(key)
    if fn is None:
        def attend(q, k, v, kc, vc, pos):
            return _gqa_decode_attend(cfg, B, q, k, v, kc, vc, pos,
                                      is_global, out_dtype)

        fn = _GLUE_JITS[key] = jax.jit(attend)
    return fn


def _jitted_prefill_attend(cfg: ModelConfig, Sp: int, is_global: bool,
                           out_dtype) -> Callable:
    key = ("prefill-attend", cfg, Sp, bool(is_global),
           jnp.dtype(out_dtype).name)
    fn = _GLUE_JITS.get(key)
    if fn is None:
        def attend(q, k, v, positions):
            return _causal_prefill_attend(cfg, Sp, q, k, v, positions,
                                          is_global, out_dtype)

        fn = _GLUE_JITS[key] = jax.jit(attend)
    return fn


def _jitted_moe_route(cfg: ModelConfig, B: int, C: int) -> Callable:
    from repro.models import moe as moe_lib
    mcfg = cfg.moe
    key = ("moe-route", cfg, B, C)
    fn = _GLUE_JITS.get(key)
    if fn is None:
        E, top_k, d = mcfg.num_experts, mcfg.top_k, cfg.d_model

        def route_dispatch(router_p, h2):
            weights, experts, _aux = moe_lib.route(router_p, h2, mcfg)
            xg = h2.reshape(1, B, d)
            wgt = weights.reshape(1, B, top_k)
            eg = experts.reshape(1, B, top_k)
            buf, meta = jax.vmap(
                lambda xx, ww, ee: moe_lib.dispatch_tokens(
                    xx, ww, ee, E, top_k, C))(xg, wgt, eg)
            return buf, meta, wgt

        fn = _GLUE_JITS[key] = jax.jit(route_dispatch)
    return fn


def _jitted_moe_combine(cfg: ModelConfig, B: int) -> Callable:
    from repro.models import moe as moe_lib
    key = ("moe-combine", cfg, B)
    fn = _GLUE_JITS.get(key)
    if fn is None:
        d = cfg.d_model

        def combine(out_buf, wgt, meta):
            return jax.vmap(
                lambda ob, ww, mm: moe_lib.combine_tokens(
                    ob, ww.reshape(-1), mm, B, d))(out_buf, wgt, meta)

        fn = _GLUE_JITS[key] = jax.jit(combine)
    return fn


def _jitted_ssm_core(cfg: ModelConfig) -> Callable:
    from repro.models import ssm as ssm_lib
    key = ("ssm-core", cfg)
    fn = _GLUE_JITS.get(key)
    if fn is None:
        scfg, d = cfg.ssm, cfg.d_model

        def core(mamba_p, zxbcdt, conv, h):
            return ssm_lib.decode_core(mamba_p, zxbcdt,
                                       {"conv": conv, "h": h}, scfg, d)

        fn = _GLUE_JITS[key] = jax.jit(core)
    return fn


def _decode_attend_for(cfg: ModelConfig, B: int):
    """Single-token slotted-cache attention glue factory, shared by the
    dense and MoE decode builders (MoE layers keep standard GQA attention,
    so both families must stay byte-identical here)."""

    def attend_for(l, lp, is_global):
        # one new token per row against the slotted cache, per-row positions
        def attend(env, l=l, is_global=is_global):
            cache = env["cache"]
            pos = jnp.broadcast_to(jnp.asarray(cache["pos"]), (B,))
            attn_out, kc, vc = _jitted_decode_attend(
                cfg, B, is_global, env["h"].dtype)(
                env["wq"], env["wk"], env["wv"],
                cache["layers"]["k"][l], cache["layers"]["v"][l], pos)
            env["new_layers"]["k"].append(kc)
            env["new_layers"]["v"].append(vc)
            env["attn_out"] = attn_out

        return attend

    return attend_for


def _stacked_dense_body_stage(model, params, B: int, lo: int, hi: int, *,
                              moe: bool = False) -> StackedGemmStage:
    """ONE scanned decode body covering layers [lo, hi) of a GQA model —
    the stacked replacement for ~6·Lsub (dense) or (4+3·E)·Lsub (MoE)
    per-layer stages. The scan body replays the per-layer math exactly:
    ``_scan_gemm`` for every projection (replicating the executor's solo
    dispatch), ``_gqa_decode_attend`` for attention, and the literal
    ``moe_lib`` route/dispatch/combine calls for the MoE FFN."""
    cfg: ModelConfig = model.cfg
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    eps = cfg.norm_eps
    blocks = params["blocks"]
    pid = id(params)
    Lsub = hi - lo
    is_global = bool(cfg.layer_is_global(lo))

    def sop(tag, name, arr, m, n, k, layers=Lsub):
        return StackedOperand(
            tag, weight_key(cfg.name, pid, name, stack=(lo, hi)),
            GemmShape(m=m, n=n, k=k, layers=layers),
            lambda a=arr: _stack_slice(a, lo, hi), (arr,))

    attn = blocks["attn"]
    operands = [
        sop("attn_wq", "wq", attn["wq"], B, cfg.num_heads * hd, d),
        sop("attn_wk", "wk", attn["wk"], B, cfg.num_kv_heads * hd, d),
        sop("attn_wv", "wv", attn["wv"], B, cfg.num_kv_heads * hd, d),
        sop("attn_wo", "wo", attn["wo"], B, d, cfg.num_heads * hd),
    ]
    if moe:
        from repro.models import moe as moe_lib
        mcfg = cfg.moe
        E, top_k = mcfg.num_experts, mcfg.top_k
        C = moe_lib.capacity(B, mcfg)
        mp = blocks["moe"]
        # expert packs keep the "expert_*" tags (clustering.is_expert_op
        # detects them through op.stack); layers = Lsub·E waves because
        # each scan step runs E per-expert GEMMs sequentially
        operands += [
            sop("expert_gate", "w_gate", mp["w_gate"], C, cfg.d_ff, d,
                Lsub * E),
            sop("expert_up", "w_up", mp["w_up"], C, cfg.d_ff, d, Lsub * E),
            sop("expert_down", "w_down", mp["w_down"], C, d, cfg.d_ff,
                Lsub * E),
        ]
        routers = _stack_slice(mp["router"], lo, hi)
    else:
        mlp = blocks["mlp"]
        operands += [
            sop("ffn_gate", "w_gate", mlp["w_gate"], B, cfg.d_ff, d),
            sop("ffn_up", "w_up", mlp["w_up"], B, cfg.d_ff, d),
            sop("ffn_down", "w_down", mlp["w_down"], B, d, cfg.d_ff),
        ]
    ln1s = _stack_slice(blocks["ln1"], lo, hi)
    ln2s = _stack_slice(blocks["ln2"], lo, hi)
    # one jitted scan per executor block signature, memoized for the
    # template's lifetime (templates live in the JIT's plan cache, so the
    # steady state reuses one compiled executable)
    jits: Dict[Tuple, Callable] = {}

    def make_scan(bm: int, bn: int, bk: int, interpret: bool):
        def gemm(a, w, n):
            return _scan_gemm(a, w, n, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)

        # every per-layer param enters as a jit ARGUMENT (via xs), never a
        # closure: XLA codegens array CONSTANTS differently than traced
        # arguments in the last ulp (measured on decode_core's einsum
        # chain), and the per-layer oracle's jitted glue receives the same
        # arrays as arguments — bit-identity requires matching regimes
        def scan_fn(x, pos_in, kc_full, vc_full, w, aux):
            pos = jnp.broadcast_to(pos_in, (B,))

            def body(carry, per):
                wl = per["w"]
                h = rmsnorm(carry, per["ln1"], eps)
                q = gemm(h, wl["attn_wq"], cfg.num_heads * hd)
                k = gemm(h, wl["attn_wk"], cfg.num_kv_heads * hd)
                v = gemm(h, wl["attn_wv"], cfg.num_kv_heads * hd)
                attn_out, kc_new, vc_new = _gqa_decode_attend(
                    cfg, B, q, k, v, per["kc"], per["vc"], pos, is_global,
                    h.dtype)
                x2 = carry + gemm(attn_out, wl["attn_wo"], d)
                h2 = rmsnorm(x2, per["ln2"], eps)
                if moe:
                    weights, experts, _aux = moe_lib.route(
                        per["router"], h2, mcfg)
                    xg = h2.reshape(1, B, d)
                    wgt = weights.reshape(1, B, top_k)
                    eg = experts.reshape(1, B, top_k)
                    buf, meta = jax.vmap(
                        lambda xx, ww, ee: moe_lib.dispatch_tokens(
                            xx, ww, ee, E, top_k, C))(xg, wgt, eg)
                    downs = []
                    for e in range(E):
                        ge = gemm(buf[0, e], wl["expert_gate"][e], cfg.d_ff)
                        ue = gemm(buf[0, e], wl["expert_up"][e], cfg.d_ff)
                        downs.append(gemm(jax.nn.silu(ge) * ue,
                                          wl["expert_down"][e], d))
                    out_buf = jnp.stack(downs, axis=0)[None]
                    y = jax.vmap(
                        lambda ob, ww, mm: moe_lib.combine_tokens(
                            ob, ww.reshape(-1), mm, B, d))(out_buf, wgt,
                                                           meta)
                    x3 = x2 + y.reshape(B, d).astype(h2.dtype)
                else:
                    gate = gemm(h2, wl["ffn_gate"], cfg.d_ff)
                    up = gemm(h2, wl["ffn_up"], cfg.d_ff)
                    x3 = x2 + gemm(jax.nn.silu(gate) * up, wl["ffn_down"],
                                   d)
                return x3, (kc_new, vc_new)

            xs = dict(aux, kc=kc_full[lo:hi], vc=vc_full[lo:hi], w=w)
            return jax.lax.scan(body, x, xs)

        return scan_fn

    aux = {"ln1": ln1s, "ln2": ln2s}
    if moe:
        aux["router"] = routers

    def run(env, padded, ex, block=None):
        # live-tuned tile override (JitSession._run_stacked): keyed beside
        # the executor defaults, so each distinct tuned config compiles
        # its scan body once and stable configs never retrace
        key = (ex.bm, ex.bn, ex.bk, ex.interpret) if block is None else \
            (block.bm, block.bn, block.bk, ex.interpret)
        fn = jits.get(key)
        if fn is None:
            fn = jits[key] = jax.jit(make_scan(*key))
        cache = env["cache"]
        x, (kc_new, vc_new) = fn(env["x"], jnp.asarray(cache["pos"]),
                                 cache["layers"]["k"], cache["layers"]["v"],
                                 padded, aux)
        env["x"] = x
        env["new_layers"]["k"].append(kc_new)
        env["new_layers"]["v"].append(vc_new)

    return StackedGemmStage(
        tag=f"body_{lo}_{hi}",
        weight_key=weight_key(cfg.name, pid, "body", stack=(lo, hi)),
        operands=operands, layers=Lsub, run=run,
        reads=("x", "cache"), writes=("x", "new_layers"))


def _build_stacked_gqa_decode_template(model, params, batch: int, *,
                                       moe: bool = False) -> ProgramTemplate:
    """Stacked counterpart of ``_build_gqa_decode_template``: one scanned
    body stage per homogeneous sub-stack instead of per-layer emission.
    The epilogue concatenates the bodies' [Lsub, ...] cache updates —
    the same [L, ...] layout the per-layer path's ``jnp.stack`` built."""
    cfg: ModelConfig = model.cfg
    stages: List[Stage] = []
    _emit_decode_embed(cfg, params, stages)
    for lo, hi in partition_layers(cfg.global_layer_flags()):
        stages.append(_stacked_dense_body_stage(model, params, batch,
                                                lo, hi, moe=moe))
    _emit_final_logits(cfg, params, stages, m_rows=batch)

    def finish(env):
        cache = env["cache"]
        env["cache"] = {
            "pos": cache["pos"] + 1,
            "layers": {
                "k": jnp.concatenate(env["new_layers"]["k"], axis=0),
                "v": jnp.concatenate(env["new_layers"]["v"], axis=0),
            },
        }

    stages.append(GlueStage(finish, reads=("cache", "new_layers"),
                            writes=("cache",)))
    return ProgramTemplate(stages=stages, batch=batch, model_name=cfg.name)


def _build_gqa_decode_template(model, params, batch: int, *,
                               ffn_for=None) -> ProgramTemplate:
    """Shared decode-template scaffold for every GQA-attention family:
    embed glue, the per-layer attention + FFN body (``ffn_for`` swaps the
    dense gated FFN for a family-specific emitter — MoE), final norm,
    unembed and the KV-cache write-back epilogue."""
    cfg: ModelConfig = model.cfg
    B = batch
    stages: List[Stage] = []

    _emit_decode_embed(cfg, params, stages)
    _emit_dense_body(cfg, params, stages, m_rows=B,
                     attend_for=_decode_attend_for(cfg, B), ffn_for=ffn_for)
    _emit_final_logits(cfg, params, stages, m_rows=B)

    def finish(env):
        cache = env["cache"]
        env["cache"] = {
            "pos": cache["pos"] + 1,
            "layers": {
                "k": jnp.stack(env["new_layers"]["k"]),
                "v": jnp.stack(env["new_layers"]["v"]),
            },
        }

    stages.append(GlueStage(finish, reads=("cache", "new_layers"),
                            writes=("cache",)))
    return ProgramTemplate(stages=stages, batch=B, model_name=cfg.name)


def build_dense_decode_template(model, params, batch: int, *,
                                stacked: bool = True) -> ProgramTemplate:
    """Compile the decode step of a dense GQA model into a ProgramTemplate.

    Equivalent to ``Model.decode_step`` but with every projection GEMM
    declared to the JIT. Supported: arch_type 'dense' (and the text path of
    'vlm'). Per-step inputs (tokens [B, 1], KV cache) are read from the
    bound program's env, so one template serves every steady-state step.

    ``stacked=True`` (default) emits one scanned body per homogeneous
    layer sub-stack — O(1)-in-depth build; ``stacked=False`` keeps the
    per-layer emission (the bit-identity oracle).
    """
    assert model.cfg.arch_type in ("dense", "vlm"), model.cfg.arch_type
    if stacked:
        return _build_stacked_gqa_decode_template(model, params, batch)
    return _build_gqa_decode_template(model, params, batch)


# ---------------------------------------------------------------------------
# non-dense decode programs: MoE and SSM tenants as first-class streams
# ---------------------------------------------------------------------------

def moe_program_cache_key(model, params, batch: int, cache, *,
                          stacked: bool = True) -> Tuple:
    """Plan-cache key for an MoE decode template. Same discipline as
    ``dense_program_cache_key`` (params identity lives in the lookup-site
    guard, not the key); the expert capacity C is a pure function of
    (batch, cfg.moe), both captured here via batch + model identity."""
    kc = cache["layers"]["k"]
    return ("moe-decode", model.cfg.name, id(model), batch,
            str(params["embed"].dtype), str(kc.dtype), tuple(kc.shape),
            ("stacked", bool(stacked), model.cfg.num_layers))


def build_moe_decode_template(model, params, batch: int, *,
                              stacked: bool = True) -> ProgramTemplate:
    """Compile the decode step of an MoE model into a ProgramTemplate.

    ``stacked=True`` (default) emits one scanned body per homogeneous
    sub-stack — the router/dispatch/combine glue runs INSIDE the scan body
    and the 3 expert packs become [Lsub, E, k, n] stacked operands;
    ``stacked=False`` keeps the per-layer 3·E-GemmStage emission below
    (the bit-identity oracle).

    Equivalent to ``Model.decode_step`` for arch_type 'moe': the attention
    scaffolding is the SAME emission as the dense builder (so MoE attention
    GEMMs coalesce with dense tenants'), while each layer's FFN becomes

      * a glue stage running the router + sort-based capacity dispatch
        (``moe_lib.route`` / ``dispatch_tokens`` — literally the code
        ``moe_ffn`` runs, so capacity/drop semantics cannot drift), then
      * 3·E declared per-expert ``GemmStage``s (gate/up/down over the
        [C, d] expert buffer) tagged ``expert_*`` with the expert index in
        the weight key — so the same expert's GEMMs share operands across
        tenants serving the same params, and coalesce with any tenant's
        GEMMs sharing their (n, k) (a dense FFN with the same d_ff does),
      * a combine glue scattering the weighted expert outputs back.

    Expert weight slices are materialized ONCE here at build time
    (``moe_lib.expert_ffn_weights``) and closed over, giving the dispatch
    executor's packed-weight cache stable array identities — a fresh slice
    per step would read as a phantom hot-swap and repack every tick.

    Within one program the expert GEMMs execute in program order (one live
    op per stream); the cross-tenant coalescing is the point
    (``JitStats.expert_coalesced``).
    """
    cfg: ModelConfig = model.cfg
    assert cfg.arch_type == "moe" and cfg.has_moe, cfg.arch_type
    if stacked:
        return _build_stacked_gqa_decode_template(model, params, batch,
                                                  moe=True)
    from repro.models import moe as moe_lib
    mcfg = cfg.moe
    B, d = batch, cfg.d_model
    E, top_k = mcfg.num_experts, mcfg.top_k
    # decode routes the step's B tokens as one group (moe_ffn's G=1 path)
    C = moe_lib.capacity(B, mcfg)
    pid = id(params)

    def ffn_for(l, lp, stages):
        moe_p = lp["moe"]
        sliced = [moe_lib.expert_ffn_weights(moe_p, e) for e in range(E)]

        def glue(fn, reads=None, writes=None):
            stages.append(GlueStage(fn, reads=reads, writes=writes))

        def route_dispatch(env, moe_p=moe_p):
            buf, meta, wgt = _jitted_moe_route(cfg, B, C)(
                moe_p["router"], env["h2"])
            env["moe_buf"], env["moe_meta"] = buf, meta
            env["moe_w"] = wgt
            env["moe_down"] = [None] * E

        glue(route_dispatch, reads=("h2",),
             writes=("moe_buf", "moe_meta", "moe_w", "moe_down"))
        for e in range(E):
            wg, wu, wd = sliced[e]
            stages.append(GemmStage(
                "expert_gate",
                weight_key(cfg.name, pid, "w_gate", layer=l, expert=e),
                lambda w=wg: w,
                lambda env, e=e: env["moe_buf"][0, e],
                lambda env, out, e=e: env.__setitem__(("moe_gate", e), out),
                shape=GemmShape(m=C, n=cfg.d_ff, k=d),
                reads=("moe_buf",), writes=(("moe_gate", e),)))
            stages.append(GemmStage(
                "expert_up",
                weight_key(cfg.name, pid, "w_up", layer=l, expert=e),
                lambda w=wu: w,
                lambda env, e=e: env["moe_buf"][0, e],
                lambda env, out, e=e: env.__setitem__(("moe_up", e), out),
                shape=GemmShape(m=C, n=cfg.d_ff, k=d),
                reads=("moe_buf",), writes=(("moe_up", e),)))

            def act(env, e=e):
                env[("moe_act", e)] = _silu_mul(env.pop(("moe_gate", e)),
                                                env.pop(("moe_up", e)))

            glue(act, reads=(("moe_gate", e), ("moe_up", e)),
                 writes=(("moe_act", e),))
            stages.append(GemmStage(
                "expert_down",
                weight_key(cfg.name, pid, "w_down", layer=l, expert=e),
                lambda w=wd: w,
                lambda env, e=e: env[("moe_act", e)],
                lambda env, out, e=e: env["moe_down"].__setitem__(e, out),
                shape=GemmShape(m=C, n=d, k=cfg.d_ff),
                reads=(("moe_act", e),), writes=("moe_down",)))

        def combine(env):
            out_buf = jnp.stack(env.pop("moe_down"), axis=0)[None]
            y = _jitted_moe_combine(cfg, B)(out_buf, env.pop("moe_w"),
                                            env.pop("moe_meta"))
            env.pop("moe_buf")
            env["x"] = env["x"] + y.reshape(B, d).astype(env["h2"].dtype)

        glue(combine, reads=("moe_down", "moe_w", "moe_meta", "moe_buf",
                             "x", "h2"),
             writes=("x",))

    return _build_gqa_decode_template(model, params, batch, ffn_for=ffn_for)


def ssm_program_cache_key(model, params, batch: int, cache, *,
                          stacked: bool = True) -> Tuple:
    """Plan-cache key for an SSM decode template: (model identity, batch,
    dtype, recurrent-cache geometry). Guard discipline as for dense."""
    cc = cache["layers"]["conv"]
    return ("ssm-decode", model.cfg.name, id(model), batch,
            str(params["embed"].dtype), str(cc.dtype), tuple(cc.shape),
            tuple(cache["layers"]["h"].shape),
            ("stacked", bool(stacked), model.cfg.num_layers))


def _build_stacked_ssm_decode_template(model, params, batch: int
                                       ) -> ProgramTemplate:
    """Stacked counterpart of the per-layer SSM builder: the whole
    attention-free stack is ONE homogeneous sub-stack, so a single scanned
    body stage declares the stacked in/out projections and runs the
    selective-scan recurrence (``ssm_lib.decode_core`` — the same single
    copy of the math) inside the scan body."""
    cfg: ModelConfig = model.cfg
    from repro.models import ssm as ssm_lib
    scfg = cfg.ssm
    B, d = batch, cfg.d_model
    d_inner = scfg.expand * d
    n_in = 2 * d_inner + 2 * scfg.d_state + scfg.num_heads(d)
    eps = cfg.norm_eps
    blocks = params["blocks"]
    mamba = blocks["mamba"]
    pid = id(params)
    L = cfg.num_layers
    lo, hi = 0, L
    stages: List[Stage] = []
    _emit_decode_embed(cfg, params, stages)

    def reset_layers(env):
        env["new_layers"] = {"conv": [], "h": []}

    stages.append(GlueStage(reset_layers, reads=(), writes=("new_layers",)))
    operands = [
        StackedOperand(
            "ssm_in_proj", weight_key(cfg.name, pid, "in_proj",
                                      stack=(lo, hi)),
            GemmShape(m=B, n=n_in, k=d, layers=L),
            lambda: mamba["in_proj"], (mamba["in_proj"],)),
        StackedOperand(
            "ssm_out_proj", weight_key(cfg.name, pid, "out_proj",
                                       stack=(lo, hi)),
            GemmShape(m=B, n=d, k=d_inner, layers=L),
            lambda: mamba["out_proj"], (mamba["out_proj"],)),
    ]
    # decode_core reads only the conv/dt/A/D/norm leaves; the projections
    # are the declared stacked operands above
    mamba_rest = {k: v for k, v in mamba.items()
                  if k not in ("in_proj", "out_proj")}
    ln1s = blocks["ln1"]
    jits: Dict[Tuple, Callable] = {}

    def make_scan(bm: int, bn: int, bk: int, interpret: bool):
        def gemm(a, w, n):
            return _scan_gemm(a, w, n, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)

        # per-layer params enter as jit ARGUMENTS (xs), not closures — XLA
        # codegens embedded constants differently in the last ulp than
        # traced arguments, which would break bit-identity with the
        # per-layer oracle's jitted decode_core glue
        def scan_fn(x, conv_full, h_full, w, aux):
            def body(carry, per):
                hh = rmsnorm(carry, per["ln1"], eps)
                zxbcdt = gemm(hh, per["w"]["ssm_in_proj"], n_in)
                y, new_c = ssm_lib.decode_core(
                    per["mamba"], zxbcdt,
                    {"conv": per["conv"], "h": per["h"]}, scfg, d)
                out = gemm(y, per["w"]["ssm_out_proj"], d)
                return carry + out, (new_c["conv"], new_c["h"])

            xs = dict(aux, conv=conv_full, h=h_full, w=w)
            return jax.lax.scan(body, x, xs)

        return scan_fn

    aux = {"ln1": ln1s, "mamba": mamba_rest}

    def run(env, padded, ex, block=None):
        # live-tuned tile override (JitSession._run_stacked): keyed beside
        # the executor defaults, so each distinct tuned config compiles
        # its scan body once and stable configs never retrace
        key = (ex.bm, ex.bn, ex.bk, ex.interpret) if block is None else \
            (block.bm, block.bn, block.bk, ex.interpret)
        fn = jits.get(key)
        if fn is None:
            fn = jits[key] = jax.jit(make_scan(*key))
        cache = env["cache"]
        x, (conv_new, h_new) = fn(env["x"], cache["layers"]["conv"],
                                  cache["layers"]["h"], padded, aux)
        env["x"] = x
        env["new_layers"]["conv"].append(conv_new)
        env["new_layers"]["h"].append(h_new)

    stages.append(StackedGemmStage(
        tag=f"body_{lo}_{hi}",
        weight_key=weight_key(cfg.name, pid, "body", stack=(lo, hi)),
        operands=operands, layers=L, run=run,
        reads=("x", "cache"), writes=("x", "new_layers")))
    _emit_final_logits(cfg, params, stages, m_rows=B)

    def finish(env):
        cache = env["cache"]
        env["cache"] = {
            "pos": cache["pos"] + 1,
            "layers": {
                "conv": jnp.concatenate(env["new_layers"]["conv"], axis=0),
                "h": jnp.concatenate(env["new_layers"]["h"], axis=0),
            },
        }

    stages.append(GlueStage(finish, reads=("cache", "new_layers"),
                            writes=("cache",)))
    return ProgramTemplate(stages=stages, batch=B, model_name=cfg.name)


def build_ssm_decode_template(model, params, batch: int, *,
                              stacked: bool = True) -> ProgramTemplate:
    """Compile the decode step of an attention-free SSM (Mamba-2/SSD) model
    into a ProgramTemplate. Equivalent to ``Model.decode_step`` for
    arch_type 'ssm': per layer, the in projection ([B, d] → z/xBC/dt) and
    the out projection are declared ``GemmStage``s — coalescible across
    tenants — while the selective-scan recurrence between them runs as glue
    via ``ssm_lib.decode_core`` (the SAME function ``ssd_decode_step``
    calls, so the recurrence math has exactly one copy). The epilogue
    stacks the per-layer conv windows + SSD states back into the tenant's
    recurrent cache.
    """
    cfg: ModelConfig = model.cfg
    assert cfg.arch_type == "ssm" and cfg.has_ssm, cfg.arch_type
    if stacked:
        return _build_stacked_ssm_decode_template(model, params, batch)
    from repro.models import ssm as ssm_lib
    scfg = cfg.ssm
    B, d = batch, cfg.d_model
    d_inner = scfg.expand * d
    n_in = 2 * d_inner + 2 * scfg.d_state + scfg.num_heads(d)
    blocks = params["blocks"]
    pid = id(params)
    stages: List[Stage] = []

    def glue(fn, reads=None, writes=None):
        stages.append(GlueStage(fn, reads=reads, writes=writes))

    _emit_decode_embed(cfg, params, stages)

    def reset_layers(env):
        env["new_layers"] = {"conv": [], "h": []}

    glue(reset_layers, reads=(), writes=("new_layers",))
    for l in range(cfg.num_layers):
        lp = jax.tree_util.tree_map(lambda a, l=l: a[l], blocks)

        def pre(env, lp=lp):
            env["h"] = rmsnorm(env["x"], lp["ln1"], cfg.norm_eps)

        glue(pre, reads=("x",), writes=("h",))
        stages.append(GemmStage(
            "ssm_in_proj", weight_key(cfg.name, pid, "in_proj", layer=l),
            lambda lp=lp: lp["mamba"]["in_proj"],
            lambda env: env["h"],
            lambda env, out: env.__setitem__("zxbcdt", out),
            shape=GemmShape(m=B, n=n_in, k=d),
            reads=("h",), writes=("zxbcdt",)))

        def scan(env, lp=lp, l=l):
            layers = env["cache"]["layers"]
            y, new_c = _jitted_ssm_core(cfg)(
                lp["mamba"], env.pop("zxbcdt"),
                layers["conv"][l], layers["h"][l])
            env["new_layers"]["conv"].append(new_c["conv"])
            env["new_layers"]["h"].append(new_c["h"])
            env["ssm_y"] = y

        glue(scan, reads=("cache", "zxbcdt"),
             writes=("new_layers", "ssm_y"))
        stages.append(GemmStage(
            "ssm_out_proj", weight_key(cfg.name, pid, "out_proj", layer=l),
            lambda lp=lp: lp["mamba"]["out_proj"],
            lambda env: env["ssm_y"],
            lambda env, out: env.__setitem__("x", env["x"] + out),
            shape=GemmShape(m=B, n=d, k=d_inner),
            reads=("ssm_y", "x"), writes=("x",)))

    _emit_final_logits(cfg, params, stages, m_rows=B)

    def finish(env):
        cache = env["cache"]
        env["cache"] = {
            "pos": cache["pos"] + 1,
            "layers": {
                "conv": jnp.stack(env["new_layers"]["conv"]),
                "h": jnp.stack(env["new_layers"]["h"]),
            },
        }

    glue(finish, reads=("cache", "new_layers"), writes=("cache",))
    return ProgramTemplate(stages=stages, batch=B, model_name=cfg.name)


# ---------------------------------------------------------------------------
# prefill programs — the prompt pass as first-class declared ops
# ---------------------------------------------------------------------------

def prefill_bucket(prompt_len: int, minimum: int = 8) -> int:
    """Power-of-two padding bucket for a prompt length.

    Prefill templates are compiled per bucket, not per exact length, so the
    plan-cache key space stays finite over arbitrary prompt distributions.
    Padded tail rows are computed and discarded — causal masking keeps them
    out of every real row's softmax, and the epilogue copies only the real
    positions into the KV cache — so any bucket ≥ prompt_len is correct.
    """
    assert prompt_len >= 1, prompt_len
    return max(minimum, 1 << (prompt_len - 1).bit_length())


def _causal_prefill_attend(cfg: ModelConfig, Sp: int, q_flat, k_flat,
                           v_flat, positions, is_global: bool, out_dtype
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer of causal prompt attention: the PURE math shared verbatim
    by the per-layer prefill glue and the stacked scan body. Returns
    (attn_out [Sp, H·hd], k [1, Hkv, Sp, hd] rope'd, v [1, Hkv, Sp, hd]
    raw) — the k/v pair in decode-cache layout, exactly what
    transformer._project_kv emits for the analytic path."""
    hd = cfg.resolved_head_dim
    q = q_flat.reshape(1, Sp, cfg.num_heads, hd)
    k = k_flat.reshape(1, Sp, cfg.num_kv_heads, hd)
    v = v_flat.reshape(1, Sp, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    G = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(1, Sp, cfg.num_kv_heads, G, hd)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    idx = jnp.arange(Sp)
    ok = idx[None, :] <= idx[:, None]
    if cfg.window_size > 0 and not is_global:
        ok = ok & (idx[None, :] > (idx[:, None] - cfg.window_size))
    scores = jnp.where(ok[None, None, None], scores, -2.0e38)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgst,bthd->bshgd", p, v.astype(jnp.float32))
    return (o.reshape(Sp, cfg.num_heads * hd).astype(out_dtype),
            k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))


def _stacked_prefill_body_stage(model, params, Sp: int, lo: int, hi: int
                                ) -> StackedGemmStage:
    """ONE scanned prefill body covering layers [lo, hi): the stacked
    replacement for the per-layer prompt-pass stages. The scan body replays
    ``_causal_prefill_attend`` verbatim and stacks each layer's [1, Hkv,
    Sp, hd] KV pair into a [Lsub, Hkv, Sp, hd] ys chunk — the layout the
    shared prefill epilogue already concatenates."""
    cfg: ModelConfig = model.cfg
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    eps = cfg.norm_eps
    blocks = params["blocks"]
    pid = id(params)
    Lsub = hi - lo
    is_global = bool(cfg.layer_is_global(lo))

    def sop(tag, name, arr, n, k):
        return StackedOperand(
            tag, weight_key(cfg.name, pid, name, stack=(lo, hi)),
            GemmShape(m=Sp, n=n, k=k, layers=Lsub),
            lambda a=arr: _stack_slice(a, lo, hi), (arr,))

    attn = blocks["attn"]
    mlp = blocks["mlp"]
    operands = [
        sop("attn_wq", "wq", attn["wq"], cfg.num_heads * hd, d),
        sop("attn_wk", "wk", attn["wk"], cfg.num_kv_heads * hd, d),
        sop("attn_wv", "wv", attn["wv"], cfg.num_kv_heads * hd, d),
        sop("attn_wo", "wo", attn["wo"], d, cfg.num_heads * hd),
        sop("ffn_gate", "w_gate", mlp["w_gate"], cfg.d_ff, d),
        sop("ffn_up", "w_up", mlp["w_up"], cfg.d_ff, d),
        sop("ffn_down", "w_down", mlp["w_down"], d, cfg.d_ff),
    ]
    ln1s = _stack_slice(blocks["ln1"], lo, hi)
    ln2s = _stack_slice(blocks["ln2"], lo, hi)
    jits: Dict[Tuple, Callable] = {}

    def make_scan(bm: int, bn: int, bk: int, interpret: bool):
        def gemm(a, w, n):
            return _scan_gemm(a, w, n, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)

        # per-layer norms enter as jit arguments (see the decode body note)
        def scan_fn(x, positions, w, aux):
            def body(carry, per):
                wl = per["w"]
                h = rmsnorm(carry, per["ln1"], eps)
                q = gemm(h, wl["attn_wq"], cfg.num_heads * hd)
                k = gemm(h, wl["attn_wk"], cfg.num_kv_heads * hd)
                v = gemm(h, wl["attn_wv"], cfg.num_kv_heads * hd)
                attn_out, k_t, v_t = _causal_prefill_attend(
                    cfg, Sp, q, k, v, positions, is_global, h.dtype)
                x2 = carry + gemm(attn_out, wl["attn_wo"], d)
                h2 = rmsnorm(x2, per["ln2"], eps)
                gate = gemm(h2, wl["ffn_gate"], cfg.d_ff)
                up = gemm(h2, wl["ffn_up"], cfg.d_ff)
                x3 = x2 + gemm(jax.nn.silu(gate) * up, wl["ffn_down"], d)
                return x3, (k_t[0], v_t[0])

            xs = dict(aux, w=w)
            return jax.lax.scan(body, x, xs)

        return scan_fn

    aux = {"ln1": ln1s, "ln2": ln2s}

    def run(env, padded, ex, block=None):
        # live-tuned tile override (JitSession._run_stacked): keyed beside
        # the executor defaults, so each distinct tuned config compiles
        # its scan body once and stable configs never retrace
        key = (ex.bm, ex.bn, ex.bk, ex.interpret) if block is None else \
            (block.bm, block.bn, block.bk, ex.interpret)
        fn = jits.get(key)
        if fn is None:
            fn = jits[key] = jax.jit(make_scan(*key))
        x, (k_ys, v_ys) = fn(env["x"], env["positions"], padded, aux)
        env["x"] = x
        env["new_layers"]["k"].append(k_ys)
        env["new_layers"]["v"].append(v_ys)

    return StackedGemmStage(
        tag=f"body_{lo}_{hi}",
        weight_key=weight_key(cfg.name, pid, "body", stack=(lo, hi)),
        operands=operands, layers=Lsub, run=run,
        reads=("x", "positions"), writes=("x", "new_layers"))


def prefill_program_cache_key(model, params, seq_len: int, cache, *,
                              stacked: bool = True) -> Tuple:
    """Plan-cache key for a dense prefill template: (model identity, padded
    prompt bucket, dtype, cache geometry). Same guard discipline as
    ``dense_program_cache_key`` — params identity is caught by the lookup
    site's ``guard=(model, params)``, never baked into the key."""
    kc = cache["layers"]["k"]
    return ("dense-prefill", model.cfg.name, id(model), seq_len,
            str(params["embed"].dtype), str(kc.dtype), tuple(kc.shape),
            ("stacked", bool(stacked), model.cfg.num_layers))


def build_dense_prefill_template(model, params, seq_len: int, *,
                                 stacked: bool = True) -> ProgramTemplate:
    """Compile the PROMPT pass of a dense GQA model into a ProgramTemplate.

    Every projection GEMM is declared to the JIT with m = ``seq_len`` (the
    padded prefill bucket) — tall problems that enter the live op pool and
    coalesce with decode GEMVs (and other tenants' prefill GEMMs) sharing
    their (n, k) weight dims. Equivalent to ``Model.prefill`` for arch_type
    'dense', last-position logits only.

    Per-request env entries (bound via ``ProgramTemplate.bind``'s
    ``env_extra``):

      * ``tokens``   — the prompt zero-padded to [1, seq_len];
      * ``real_len`` — the true prompt length S (≤ seq_len);
      * ``slot``     — the reserved decode-slot index the epilogue writes
        the request's KV rows + pos into, or None for a single-token
        request that never decodes (the cache is left untouched);
      * ``cache``    — the tenant's slotted decode cache.

    The epilogue writes exactly the rows the engine's analytic admission
    writes (zero-padded to cache_len past S), so a declared prefill is
    bit-compatible with ``ServingEngine._admit``'s cache state.
    """
    cfg: ModelConfig = model.cfg
    assert cfg.arch_type == "dense", cfg.arch_type
    hd = cfg.resolved_head_dim
    Sp = seq_len
    stages: List[Stage] = []

    def glue(fn, reads=None, writes=None):
        stages.append(GlueStage(fn, reads=reads, writes=writes))

    def embed(env):
        x = params["embed"][env["tokens"]]            # [1, Sp, d]
        env["x"] = (x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype))[0]
        env["positions"] = jnp.arange(Sp)[None, :]    # rope positions

    glue(embed, reads=("tokens",), writes=("x", "positions"))

    if stacked:
        for lo, hi in partition_layers(cfg.global_layer_flags()):
            stages.append(_stacked_prefill_body_stage(model, params, Sp,
                                                      lo, hi))
    else:
        def attend_for(l, lp, is_global):
            # causal self-attention over the whole (padded) prompt
            def attend(env, is_global=is_global):
                attn_out, k_t, v_t = _jitted_prefill_attend(
                    cfg, Sp, is_global, env["h"].dtype)(
                    env["wq"], env["wk"], env["wv"], env["positions"])
                env["new_layers"]["k"].append(k_t)
                env["new_layers"]["v"].append(v_t)
                env["attn_out"] = attn_out

            return attend

        # prefill attention never touches the live cache: k/v come from
        # the projections and rope by env positions, landing in new_layers
        _emit_dense_body(cfg, params, stages, m_rows=Sp,
                         attend_for=attend_for,
                         attend_reads=("wq", "wk", "wv", "positions"))

    def final_norm(env):
        # only the last REAL position is unembedded (Model.prefill returns
        # logits for y[:, -1:]); padded tail rows are dropped here
        last = env["x"][env["real_len"] - 1:env["real_len"]]
        env["hf"] = rmsnorm(last, params["final_norm"], cfg.norm_eps)

    glue(final_norm, reads=("x", "real_len"), writes=("hf",))
    _emit_unembed(cfg, params, stages, m_rows=1)

    def finish(env):
        """Epilogue: write the request's KV rows into its reserved slot.

        Mirrors the engine's analytic admission write: the slot row holds
        the S real positions (k rope'd, v raw), zero-padded to cache_len,
        and pos[slot] = S. A single-token request (slot None) leaves the
        cache untouched — it retires at completion without decoding."""
        slot = env["slot"]
        if slot is None:
            return
        S = env["real_len"]
        cache = env["cache"]
        layers = cache["layers"]
        kc, vc = layers["k"], layers["v"]
        cache_len = int(kc.shape[3])
        k_new = jnp.concatenate(env["new_layers"]["k"], axis=0)[:, :, :S]
        v_new = jnp.concatenate(env["new_layers"]["v"], axis=0)[:, :, :S]
        pad = ((0, 0), (0, 0), (0, cache_len - S), (0, 0))
        new_layers = dict(layers)
        new_layers["k"] = kc.at[:, slot].set(
            jnp.pad(k_new, pad).astype(kc.dtype))
        new_layers["v"] = vc.at[:, slot].set(
            jnp.pad(v_new, pad).astype(vc.dtype))
        env["cache"] = {"pos": cache["pos"].at[slot].set(S),
                        "layers": new_layers}

    glue(finish, reads=("cache", "new_layers", "real_len", "slot"),
         writes=("cache",))
    return ProgramTemplate(stages=stages, batch=Sp, model_name=cfg.name,
                           kind="prefill")


def build_dense_decode_program(model, params, tokens: jax.Array, cache,
                               stream_id: int, *, slo_s: float = float("inf"),
                               arrival_t: float = 0.0,
                               deadline_t: float = float("inf"),
                               req_deadlines: Tuple = ()) -> KernelProgram:
    """One-shot compile + bind (the uncached path; kept for callers that
    build a single step). The serving engine instead caches the template
    (``VLIWJit.plan_cache``) and calls ``bind`` per step."""
    template = build_dense_decode_template(model, params,
                                           int(tokens.shape[0]))
    return template.bind(stream_id=stream_id, tokens=tokens, cache=cache,
                         slo_s=slo_s, arrival_t=arrival_t,
                         deadline_t=deadline_t, req_deadlines=req_deadlines)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamStat:
    """Streaming aggregate (count/sum/min/max) over one per-superkernel
    observable. Replaces the unbounded per-dispatch lists ``JitStats``
    used to keep — memory grew linearly over long serving sessions —
    while preserving ``mean_group`` and ``merge`` semantics (``+`` folds
    two aggregates)."""

    count: int = 0
    total: float = 0.0
    min: float = math.inf
    max: float = -math.inf

    def add(self, x: float) -> None:
        self.count += 1
        self.total += x
        self.min = min(self.min, x)
        self.max = max(self.max, x)

    @classmethod
    def of(cls, xs) -> "StreamStat":
        s = cls()
        for x in xs:
            s.add(x)
        return s

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def __add__(self, other: "StreamStat") -> "StreamStat":
        if not self.count:
            return dataclasses.replace(other)
        if not other.count:
            return dataclasses.replace(self)
        return StreamStat(self.count + other.count, self.total + other.total,
                          min(self.min, other.min), max(self.max, other.max))


@dataclasses.dataclass
class JitStats:
    superkernels: int = 0
    ops_executed: int = 0
    groups: StreamStat = dataclasses.field(default_factory=StreamStat)
    padding_waste: StreamStat = dataclasses.field(default_factory=StreamStat)
    modeled_time_s: float = 0.0
    modeled_serial_time_s: float = 0.0
    shared_dispatches: int = 0
    # event-loop counters
    waits: int = 0                 # stagger (WAIT) decisions taken
    # missed stragglers demoted from EDF anchoring. When request ids are
    # plumbed through the program (serving path), this counts exactly once
    # per missed *request* across all of its steps — even a straggler
    # hidden behind a healthy batchmate's anchor deadline; for raw op
    # streams without ids it falls back to once per (stream, deadline)
    evictions: int = 0
    mid_flight_admissions: int = 0  # programs joining live ops post-start
    # dispatched superkernel groups that packed a prefill op together with
    # at least one other stream's op — the §5.2 spatial-sharing win applied
    # to prompt GEMMs (serving acceptance: must be > 0 on long-prompt
    # multi-tenant traces)
    prefill_coalesced: int = 0
    # non-dense (MoE / SSM) tenant steps compiled+bound as KernelPrograms
    # instead of taking the monolithic batched fallback — the serving
    # engine counts one per decode program it admits for such a tenant
    nondense_programs: int = 0
    # dispatched superkernel groups that packed an MoE per-expert FFN GEMM
    # (tag "expert_*", clustering.is_expert_op) together with at least one
    # other stream's op — the heterogeneous-tenant spatial-sharing win the
    # MoE coalescing benchmark gates on
    expert_coalesced: int = 0
    # plan-cache deltas accrued during this run (core/plancache.py):
    # program templates (ServingEngine._build_program / VLIWJit.plan_cache)
    # and superkernel block plans (Coalescer memo). PlanCacheStats supports
    # ``+`` so merge() folds these like every other counter.
    plan_cache: PlanCacheStats = dataclasses.field(
        default_factory=PlanCacheStats)
    block_plans: PlanCacheStats = dataclasses.field(
        default_factory=PlanCacheStats)
    # live-tuner cache deltas (core/autotuner.LiveTuner / VLIWJit.
    # tune_cache): one access per planned dispatch when live tuning is on
    # (zeros otherwise), a miss only on a never-seen group signature — the
    # compiled-autotune bench gates hit rate ≥ (steps-1)/steps on these.
    tune_cache: PlanCacheStats = dataclasses.field(
        default_factory=PlanCacheStats)
    # jitted dispatch fast-path deltas (core/dispatch.py): packed-weight
    # cache hits/misses/invalidations, retraces of the jitted
    # pack+kernel+unpack, and weight bytes NOT re-staged thanks to the
    # cache. DispatchStats supports ``+`` so merge() folds it like every
    # other counter.
    dispatch: DispatchStats = dataclasses.field(default_factory=DispatchStats)
    # schedule-certifier counters (repro.analysis.certify, wired by
    # ServingEngine(certify=True)): per-op/per-group legality checks run
    # and violations observed. A gating bench asserts violations == 0
    # while checks > 0 — certification that silently checked nothing
    # would otherwise read as a clean pass.
    hazard_checks: int = 0
    hazard_violations: int = 0
    # multi-device mesh counters: modeled cross-device collective seconds
    # charged (MoE expert dispatch/combine for device-spanning tenants —
    # nonzero iff some tenant's expert span > 1), and dispatched groups
    # that actually coalesced (>1 op) — per-session this is a per-DEVICE
    # count, which the multi-device bench requires to be nonzero on every
    # device (a mesh where one device never coalesces is misplaced).
    collective_time_s: float = 0.0
    coalesced_groups: int = 0

    @property
    def mean_group(self) -> float:
        return self.groups.mean

    @property
    def modeled_speedup(self) -> float:
        return self.modeled_serial_time_s / self.modeled_time_s \
            if self.modeled_time_s else 1.0

    def merge(self, other: "JitStats") -> "JitStats":
        """Fold another run's counters into this one (in place). Every
        field accumulates by ``+`` (ints, floats and lists alike), so new
        counters are merged automatically."""
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))
        return self


@dataclasses.dataclass
class TickEvent:
    """Outcome of one scheduler decision on the session's virtual clock."""
    kind: str                      # "dispatch" | "wait" | "idle"
    t: float                       # virtual time after the event
    dt: float = 0.0                # modeled device seconds consumed
    completed: List[KernelProgram] = dataclasses.field(default_factory=list)


# a timed admission: (virtual arrival time, program or zero-arg factory)
Arrival = Tuple[float, Union[KernelProgram, Callable[[], KernelProgram]]]


class JitSession:
    """A live, admission-open run of the VLIW JIT.

    Unlike the closed-world ``VLIWJit.run`` wrapper, a session keeps its
    scheduler, live-op pool and stats across calls: the serving engine admits
    new tenant programs *between superkernel dispatches* and advances the
    shared virtual clock one scheduler decision (``tick``) at a time.
    """

    def __init__(self, jit: "VLIWJit", record_trace: bool = False, *,
                 device: int = 0, cost: Optional[CostModel] = None,
                 trace: Optional[ScheduleTrace] = None):
        self.jit = jit
        self.stats = JitStats()
        # mesh placement: one session drives ONE device's virtual timeline.
        # The scheduler and coalescer are per-device views over the shared
        # JIT state — the coalescer plans with this device's cost model and
        # keys the SHARED block-plan memo with the device id, and the
        # scheduler owns this device's ready pool / EDF anchor set. The
        # default (device 0, jit.cost) is exactly the single-device setup.
        self.device = device
        self.cost = cost if cost is not None else jit.cost
        if device == 0 and cost is None:
            coalescer = jit.coalescer
        else:
            # non-default device: a per-device tuner over THIS device's
            # cost model, sharing the JIT-owned tune cache (device id in
            # every key) — mirrors the per-device coalescer/memo pattern
            tuner = None if jit.tuner is None else \
                LiveTuner(self.cost, jit.tune_cache,
                          objective=jit.tune_objective, device_id=device)
            coalescer = Coalescer(self.cost, max_group=jit.max_group,
                                  memo=jit.block_plans, device_id=device,
                                  tuner=tuner)
        self.sched = OoOScheduler(self.cost, coalescer, jit.sched_cfg,
                                  device=device)
        # expert-parallel span per stream (tenant): streams whose MoE
        # expert weights span >1 devices pay the all-to-all collective
        # charge on every expert GEMM (set by the engine from the
        # placement policy; default 1 = local, no charge).
        self.stream_span: Dict[int, int] = {}
        # dispatch trace for the schedule certifier (repro.analysis):
        # admissions, waits and per-op dispatch records, appended BEFORE
        # each superkernel executes so a crash mid-dispatch still leaves
        # the offending group on the trace. None (default) records
        # nothing — zero steady-state overhead unless certification is on.
        # An explicit ``trace`` shares one audit log across the per-device
        # sessions of a mesh run (the certifier sees the whole fleet).
        self.trace: Optional[ScheduleTrace] = trace if trace is not None \
            else (ScheduleTrace() if record_trace else None)
        # pending GEMM per program: op_id -> (program, stage)
        self.live: Dict[int, Tuple[KernelProgram, GemmStage]] = {}
        self._done: List[KernelProgram] = []
        self._started = False          # True once the first tick has run
        # plan caches and the dispatch executor outlive sessions (that is
        # the point); snapshot their counters so this session's stats
        # report only its own delta
        self._plan_base = jit.plan_cache.stats.copy()
        self._block_base = jit.block_plans.stats.copy()
        self._tune_base = jit.tune_cache.stats.copy()
        self._dispatch_base = jit.executor.stats.copy()

    def _sync_cache_stats(self) -> None:
        self.stats.plan_cache = self.jit.plan_cache.stats - self._plan_base
        self.stats.block_plans = self.jit.block_plans.stats - self._block_base
        self.stats.tune_cache = self.jit.tune_cache.stats - self._tune_base
        self.stats.dispatch = self.jit.executor.stats - self._dispatch_base

    @property
    def pending(self) -> int:
        return len(self.live)

    def set_next_arrival(self, t: float) -> None:
        """Tell the scheduler when the next admission is coming, enabling
        the stagger/WAIT branch on the real path."""
        self.sched.next_arrival_t = t

    def set_stream_span(self, stream_id: int, span: int) -> None:
        """Declare a stream's expert-parallel device span (placement
        policy's ``TenantPlacement.expert_span``). Spans > 1 charge the
        MoE expert dispatch/combine all-to-all on every expert GEMM the
        stream declares from now on."""
        self.stream_span[stream_id] = span

    def admit(self, prog: KernelProgram) -> None:
        """Add a program to the live pool (legal at any point in time)."""
        # mid-flight = joining other streams' live ops after execution has
        # begun; the initial batch of admissions before the first tick is
        # just the starting pool
        if self.live and self._started:
            self.stats.mid_flight_admissions += 1
        prog.device = self.device     # placement binds at admission
        if self.trace is not None:
            self.trace.prog_admits.append(ProgramAdmit(
                prog_uid=prog.uid, stream=prog.stream_id, kind=prog.kind,
                req_ids=tuple(r for r, _ in prog.req_deadlines),
                kv_writes=tuple(prog.kv_writes), device=self.device))
        st = prog.advance_glue()
        if st is None:            # pure-glue program: completes immediately
            self._done.append(prog)
            return
        self._push_op(prog, st)

    def _expert_collective_s(self, stream_id: int, m: int, k: int,
                             layers: int = 1, dtype_bytes: int = 2) -> float:
        """All-to-all charge for one expert-FFN trio of a device-spanning
        MoE stream: dispatch scatters the [m, k] expert activations to the
        shards, combine gathers the outputs back — 2·m·k bytes round trip
        per scanned layer. Charged ONCE per trio (on the gate GEMM) so a
        gate/up/down triple is not triple-billed. Local streams
        (span <= 1) pay nothing."""
        span = self.stream_span.get(stream_id, 1)
        if span <= 1:
            return 0.0
        return self.cost.all_to_all_time(
            2.0 * layers * m * k * dtype_bytes, span)

    def _push_op(self, prog: KernelProgram, st: Stage) -> None:
        if isinstance(st, StackedGemmStage):
            self._push_stacked_op(prog, st)
            return
        a = st.input_fn(prog.env)
        w = st.weight_fn()
        # aspect boundary derived from the JIT's m-tile (kernelspec owns
        # the classification) — a problem within one bm tile is a gemv
        op = make_op(prog.stream_id, op_aspect(int(a.shape[0]), self.jit.bm),
                     GemmShape(m=int(a.shape[0]), n=int(w.shape[1]),
                               k=int(w.shape[0])),
                     arrival_t=prog.arrival_t,
                     deadline_t=prog.effective_deadline,
                     seq_index=prog.pc, tag=st.tag,
                     model_id=st.weight_key[0] if st.weight_key else "",
                     op_kind=prog.kind)
        # carry operand bindings on the op (declarative dispatch payload)
        op.payload = (a, w, st.weight_key)
        op.prog_uid = prog.uid
        op.device = self.device
        if st.tag == "expert_gate":
            op.collective_s = self._expert_collective_s(
                prog.stream_id, op.shape.m, op.shape.k)
        # per-request identity: the scheduler accounts SLO demotions per
        # request id, not per (stream, deadline) of the batch anchor
        op.req_deadlines = prog.req_deadlines
        if math.isfinite(op.deadline_t):
            # EDF anchor = deadline minus the program's remaining critical
            # path (plus any collective charge), so upstream stages inherit
            # the urgency of the whole step
            op.latest_start_t = op.deadline_t \
                - prog.remaining_gemm_time(self.cost, prog.pc) \
                - op.collective_s
        self.live[op.op_id] = (prog, st)
        self.sched.push([op])

    def _push_stacked_op(self, prog: KernelProgram,
                         st: StackedGemmStage) -> None:
        """Declare one layer-stacked body stage as a single KernelOp.

        ``op.shape`` carries the DOMINANT operand (largest total weight
        volume) for EDF/aspect bookkeeping; the full per-operand signature
        rides on ``op.stack`` and drives coalescing (clustering.
        coalesce_key) and the cost charge (L sequential tile-waves per
        operand)."""
        dom = max((od.shape for od in st.operands),
                  key=lambda s: s.layers * s.n * s.k)
        op = make_op(prog.stream_id, op_aspect(dom.m, self.jit.bm), dom,
                     arrival_t=prog.arrival_t,
                     deadline_t=prog.effective_deadline,
                     seq_index=prog.pc, tag=st.tag,
                     model_id=st.weight_key[0],
                     op_kind=prog.kind)
        op.stack = tuple((od.tag, od.shape) for od in st.operands)
        # no eager activation binding — the stacked operands are
        # materialized at dispatch time (_run_stacked); the key slot keeps
        # shared-operand detection uniform with plain ops. The weight slot
        # carries the operand GUARD arrays (the original stacked params,
        # stable across ticks) so op_weight_identity resolves a stacked
        # op's operand identity for the certifier's shared-operand check.
        op.payload = (None,
                      tuple(a for od in st.operands for a in od.guard),
                      st.weight_key)
        op.prog_uid = prog.uid
        op.device = self.device
        # expert-parallel collective: charge the first expert_gate operand
        # of the scanned body (one dispatch+combine per layer of the trio)
        for od in st.operands:
            if od.tag == "expert_gate":
                op.collective_s = self._expert_collective_s(
                    prog.stream_id, od.shape.m, od.shape.k,
                    layers=od.shape.layers,
                    dtype_bytes=od.shape.dtype_bytes)
                break
        op.req_deadlines = prog.req_deadlines
        if math.isfinite(op.deadline_t):
            op.latest_start_t = op.deadline_t \
                - prog.remaining_gemm_time(self.cost, prog.pc) \
                - op.collective_s
        self.live[op.op_id] = (prog, st)
        self.sched.push([op])

    def _op_record(self, op: KernelOp) -> OpRecord:
        """Snapshot one live op for the dispatch trace. Env writes come
        from the stage's declared ``writes`` set — an undeclared stage
        conservatively aliases everything (``("*",)``), qualified by the
        program env's identity so two tenants' private envs never read as
        conflicting resources."""
        prog, st = self.live[op.op_id]
        writes = getattr(st, "writes", None)
        return OpRecord(
            op_id=op.op_id, stream=op.stream_id, prog_uid=op.prog_uid,
            tag=op.tag, seq=op.seq_index, op_kind=op.op_kind,
            deadline_t=op.deadline_t, latest_start_t=op.latest_start_t,
            weight_key=op_weight_key(op), weight_id=op_weight_identity(op),
            kv_writes=tuple(prog.kv_writes),
            env_writes=tuple(writes) if writes is not None else ("*",),
            env_id=id(prog.env), device=op.device)

    def _run_stacked(self, ops, completed,
                     block: Optional[BlockConfig] = None) -> None:
        """Dispatch a coalesced group of layer-stacked body ops: pack each
        op's stacked weight operands through the executor's persistent
        cache, then run the scanned bodies back-to-back. ``block``
        overrides the executor's default tile for the scanned GEMMs (the
        live-tuned config of the plan) — each distinct config compiles its
        own scan body once, keyed beside the executor defaults."""
        ex = self.jit.executor
        for op in ops:
            prog, st = self.live.pop(op.op_id)
            padded = {}
            if not ex.enabled:
                # eager ablation (executor.enabled=False): pad each stacked
                # operand fresh — same envelope, same bits — but through
                # neither the persistent cache nor DispatchStats
                for od in st.operands:
                    w = od.weight_fn()
                    K = envelope_bucket(int(od.shape.k))
                    N = envelope_bucket(int(od.shape.n))
                    pad = [(0, 0)] * (w.ndim - 2) + \
                        [(0, K - int(w.shape[-2])), (0, N - int(w.shape[-1]))]
                    padded[od.tag] = jnp.pad(w, pad)
            else:
                h0, m0 = ex.stats.weight_hits, ex.stats.weight_misses
                for od in st.operands:
                    # params-free group identity: a hot-swap (new params id
                    # in the weight key) changes the key within the same
                    # group, so the cache drops the superseded entry
                    group = (op.stream_id, od.weight_key[0]) \
                        + od.weight_key[2:]
                    padded[od.tag] = ex.stacked_operand(
                        od.weight_key, od.shape.k, od.shape.n,
                        od.shape.layers, od.weight_fn, od.guard,
                        group=group, device=op.device)
                # collapse the per-operand cache accesses into ONE hit/miss
                # event per dispatch (miss iff any operand had to repack)
                # so the DispatchStats invariant hits + misses == dispatches
                # holds across plain and stacked dispatch alike
                missed = ex.stats.weight_misses - m0
                ex.stats.weight_hits, ex.stats.weight_misses = h0, m0
                if missed:
                    ex.stats.weight_misses += 1
                else:
                    ex.stats.weight_hits += 1
                ex.stats.dispatches += 1
            st.run(prog.env, padded, ex, block)
            prog.pc += 1
            nxt = prog.advance_glue()
            if nxt is None:
                completed.append(prog)
            else:
                self._push_op(prog, nxt)

    def tick(self, now: float) -> TickEvent:
        """Execute one scheduler decision at virtual time ``now``."""
        self._sync_cache_stats()
        completed, self._done = self._done, []
        if not self.live:
            return TickEvent("idle", now, completed=completed)
        self._started = True
        decision = self.sched.decide(now)
        self.stats.evictions = self.sched.evictions
        self._sync_cache_stats()
        if decision.kind == "wait":
            self.stats.waits += 1
            if self.trace is not None:
                self.trace.waits.append(decision.wait_until)
            return TickEvent("wait", decision.wait_until, completed=completed)
        assert decision.kind == "dispatch" and decision.plan
        plan = decision.plan
        # operand identity lives with the clustering layer: a group whose
        # ops all carry ONE weight key loads the weights once
        shared = shared_weight_key(plan.ops) is not None
        stacked = plan.ops[0].stack is not None
        if self.trace is not None:
            # record BEFORE execution: a dispatch that crashes (e.g. the
            # executor's shared-operand identity guard) still leaves the
            # offending group on the trace for the certifier's post-mortem
            self.trace.dispatches.append(DispatchRecord(
                t=now, shared_operand=shared, device=self.device,
                ops=tuple(self._op_record(op) for op in plan.ops)))
        # cross-device collective charge of the group (expert-parallel MoE
        # dispatch/combine): one all-to-all covers the group — it is a
        # per-layer exchange, not per-member — so charge the max, exactly
        # as Coalescer.plan does for est_time_s
        coll = max((op.collective_s for op in plan.ops), default=0.0)
        # live tuning: the plan's block IS the tuned config for this
        # group's signature — flow it into the executor so the dispatched
        # kernels actually run the tile the cost model chose. Off (the
        # default), the executor keeps its fixed defaults and nothing about
        # the pre-existing trace-cache population changes.
        tuned_block = plan.block if self.jit.live_tune else None
        if stacked:
            # coalesce_key keeps stacked and plain ops in disjoint buckets
            assert all(op.stack is not None for op in plan.ops)
            serial_shapes = [s for op in plan.ops for _, s in op.stack]
            outs = None
            t = plan.est_time_s          # already includes the collective
        else:
            # the jitted dispatch fast path (core/dispatch.py): persistent
            # packed weights + bucketed envelopes + compiled
            # pack/kernel/unpack
            outs = self.jit.executor.execute(plan.ops,
                                             shared_operand=shared,
                                             device=self.device,
                                             block=tuned_block)
            serial_shapes = [o.shape for o in plan.ops]
            t = self.cost.coalesced_time(serial_shapes, plan.block,
                                         shared_operand=shared) + coll
        stats = self.stats
        stats.superkernels += 1
        stats.ops_executed += len(plan.ops)
        stats.groups.add(len(plan.ops))
        stats.padding_waste.add(plan.padding_waste)
        stats.shared_dispatches += int(shared)
        stats.collective_time_s += coll
        stats.coalesced_groups += int(len(plan.ops) > 1)
        if len({op.stream_id for op in plan.ops}) > 1:
            if any(op.op_kind == "prefill" for op in plan.ops):
                stats.prefill_coalesced += 1
            if any(is_expert_op(op) for op in plan.ops):
                stats.expert_coalesced += 1
        stats.modeled_time_s += t
        stats.modeled_serial_time_s += self.cost.time_multiplexed(
            serial_shapes, plan.block) + coll
        if stacked:
            self._run_stacked(plan.ops, completed, block=tuned_block)
        else:
            for op, out in zip(plan.ops, outs):
                prog, st = self.live.pop(op.op_id)
                st.output_fn(prog.env, out)
                prog.pc += 1
                nxt = prog.advance_glue()
                if nxt is None:
                    completed.append(prog)
                else:
                    self._push_op(prog, nxt)
        # re-sync after the dispatch so a session that ends on this tick
        # still reports the executor/plan-cache work it just did
        self._sync_cache_stats()
        return TickEvent("dispatch", now + t, dt=t, completed=completed)


class VLIWJit:
    """Run tenant KernelPrograms to completion with OoO coalescing."""

    def __init__(self, cost: Optional[CostModel] = None,
                 sched_cfg: SchedulerConfig = SchedulerConfig(),
                 max_group: int = 16, bm: int = 8,
                 plan_capacity: int = 128,
                 weight_capacity: Optional[int] = None,
                 weight_budget_bytes: Optional[int] = None,
                 live_tune: bool = False,
                 tune_objective: str = "collaborative"):
        self.cost = cost or CostModel(attached_device())
        # persistent plan caches (core/plancache.py): program templates for
        # the serving hot path and superkernel block plans per coalesced
        # group signature. They live on the JIT — across sessions — so
        # steady-state ticks only rebind per-step state.
        # plan_capacity=0 disables both (the rebuild-per-step baseline).
        self.plan_cache = PlanCache(plan_capacity)
        self.block_plans = PlanCache(plan_capacity * 4)
        self.max_group = max_group
        # live collaborative autotuning (core/autotuner.LiveTuner): when
        # on, every coalescer consults the tuner per plan and the tuned
        # (bm, bn, bk) flows into the dispatched superkernels. TuneResults
        # live in their own device-keyed PlanCache BESIDE the block plans
        # — same lifetime (the JIT's), separately accounted
        # (JitStats.tune_cache) because the hit rate is a gated serving
        # acceptance criterion. The cache exists even with live_tune=False
        # so session stat plumbing is unconditional (its stats stay zero).
        self.tune_cache = PlanCache(plan_capacity * 4)
        self.live_tune = live_tune
        self.tune_objective = tune_objective
        self.tuner = LiveTuner(self.cost, self.tune_cache,
                               objective=tune_objective) if live_tune \
            else None
        self.coalescer = Coalescer(self.cost, max_group=max_group,
                                   memo=self.block_plans, tuner=self.tuner)
        self.sched_cfg = sched_cfg
        self.bm = bm
        # the jitted dispatch fast path (core/dispatch.py): packed weight
        # operands cached across sessions, bucketed envelopes, compiled
        # pack+kernel+unpack. Entries are full padded weight copies, so
        # the entry-count bound (weight_capacity, default tracks
        # plan_capacity; 0 = repack per dispatch, still jitted) does NOT
        # bound memory at real model sizes — weight_budget_bytes does (LRU
        # evicts past the byte budget; default None = half the memory of
        # the attached device, dispatch.device_weight_budget).
        wcap = 2 * plan_capacity if weight_capacity is None else \
            weight_capacity
        self.weight_cache = PlanCache(
            wcap, byte_capacity=device_weight_budget()
            if weight_budget_bytes is None else weight_budget_bytes)
        self.executor = SuperkernelExecutor(self.weight_cache, bm=bm)

    def session(self, record_trace: bool = False, *, device: int = 0,
                cost: Optional[CostModel] = None,
                trace: Optional[ScheduleTrace] = None) -> JitSession:
        """Open an admission-open event-loop session (engine entry point).

        ``record_trace=True`` makes the session keep a ``ScheduleTrace``
        (admissions, waits, per-op dispatch records) for the schedule
        certifier — the engine's ``certify=True`` path. Multi-device
        serving opens one session PER mesh device (``device``/``cost``
        from the ``DeviceSet``) sharing this JIT's caches — keyed with the
        device id — and optionally one shared ``trace``."""
        return JitSession(self, record_trace=record_trace, device=device,
                          cost=cost, trace=trace)

    def run(self, programs: Sequence[KernelProgram],
            arrivals: Optional[Sequence[Arrival]] = None,
            start_t: float = 0.0) -> JitStats:
        """Drive a session to completion on a virtual clock.

        ``programs`` are admitted at ``start_t``; each ``(t, program)`` in
        ``arrivals`` is admitted mid-flight once the clock reaches ``t``
        (a zero-arg factory is called at admission time, letting callers
        defer program construction until its inputs exist).
        """
        session = self.session()
        for prog in programs:
            session.admit(prog)
        queue = sorted(arrivals or (), key=lambda e: e[0])
        qi = 0
        now = start_t
        while True:
            while qi < len(queue) and queue[qi][0] <= now:
                entry = queue[qi][1]
                session.admit(entry() if callable(entry) else entry)
                qi += 1
            session.set_next_arrival(queue[qi][0] if qi < len(queue)
                                     else math.inf)
            ev = session.tick(now)
            if ev.kind == "idle":
                if qi < len(queue):
                    now = queue[qi][0]
                    continue
                break
            now = max(now, ev.t)
        return session.stats
