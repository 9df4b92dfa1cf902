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

Non-dense tenants are first-class streams too: MoE decode steps declare
their expert packs as stacked operands and run the router, capacity
dispatch and combine inside the scanned body
(``build_moe_decode_template``, ``JitStats.expert_coalesced``), SSM
(Mamba-2/SSD) decode steps declare the in/out projections and run the
selective-scan recurrence inside the body (``build_ssm_decode_template``),
and hybrid steps run both side by side in every layer
(``build_hybrid_decode_template``) — the paper's heterogeneous-tenant
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

**Layer-stacked templates (scan-over-layers).** Every builder emits ONE
scanned layer body per homogeneous sub-stack of layers: the params tree
already stores weights stacked along a leading layer axis, so a
``StackedGemmStage`` declares the whole sub-stack as one schedulable op
whose operands are the stacked ``blocks`` arrays ([L, k, n] per
projection, [L, E, k, n] for MoE expert packs) and whose execution is a
jitted ``jax.lax.scan`` over the layer axis — template build, trace size
and plan/weight-cache entries are O(1) in depth. Design points:

  * weight-key schema (``clustering.weight_key`` is the single
    constructor): stacked operands drop the layer index —
    ``(model, pid, "stack", lo, hi, name)`` names ONE stacked
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
    in its [L, ...] layout;
  * the scan body's GEMMs (``_scan_gemm``) run the coalesced-GEMM kernel
    at the body's own tile (``decode_tile`` / ``prefill_tile``) over one
    xs slice of a cached stacked operand, whose envelope is aligned to
    512 (``dispatch.stacked_envelope``);
  * cost/coalescing granularity: a stacked op is charged as L sequential
    tile-waves (``GemmShape.layers``), clusters on its full stack
    signature (``clustering.coalesce_key``) so only same-depth-and-dims
    tenants coalesce entire stacks, and carries the dominant operand's
    shape for EDF/aspect bookkeeping.

Correctness: a program's logits and cache match the plain reference,
``Model.decode_step`` / ``Model.prefill``, within the float32 tolerance
the kernel's blocked K accumulation allows (tests/test_stacked_templates.py,
tests/test_nondense_programs.py), regardless of admission timing
(tests/test_event_loop.py).
"""
from __future__ import annotations

import collections
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
                                 pad_stacked, stacked_envelope)
from repro.core.kernelspec import make_op, op_aspect
from repro.core.plancache import PlanCache, PlanCacheStats
from repro.core.scheduler import OoOScheduler, SchedulerConfig
from repro.core.schedtrace import (DispatchRecord, OpRecord, ProgramAdmit,
                                   ScheduleTrace)
from repro.core.spans import span, spanned
from repro.kernels.coalesced_gemm import coalesced_gemm
from repro.kernels.ops import check_vmem
from repro.configs.base import Multipliers
from repro.models.layers import apply_rope, rmsnorm, scale, scale_embedding


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

    tag: str                       # operand tag, e.g. "ffn_gate"
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
    a single schedulable op. The session fetches each operand's padded
    stack from the executor's persistent cache (``SuperkernelExecutor.
    stacked_operand``) and calls ``run`` — a jitted ``jax.lax.scan`` over
    the layers whose GEMMs are ``_scan_gemm`` calls."""

    tag: str                       # body identity, e.g. "body_0_12"
    weight_key: Tuple              # clustering.weight_key("body", stack=...)
    operands: List[StackedOperand]
    layers: int                    # hi - lo
    # run(env, {operand tag -> padded stacked array}, executor): executes
    # the scan, writes results (residual stream, cache updates) to env and
    # returns its rows and GEMM tile (m, bm, bn, bk), which the session
    # counts in JitStats.prefill_bodies or decode_bodies
    run: Callable[[Dict[str, Any], Dict[str, jax.Array],
                   SuperkernelExecutor], Tuple[int, int, int, int]]
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
    """One GEMM inside a scanned layer body at the tile it is given: ``a``
    is padded to whole m-tiles and to the envelope's K, ``w_pad`` is one
    xs slice of a cached ``stacked_operand``, and the blocks along N and K
    shrink to divisors of the envelope (``_panel``). Returns the real
    ``[m, n_real]`` rows and columns."""
    m = int(a.shape[0])
    K, N = int(w_pad.shape[-2]), int(w_pad.shape[-1])
    m_tiles = _tile_bucket([m], bm)
    ap = jnp.pad(a, ((0, m_tiles * bm - m), (0, K - int(a.shape[1]))))
    out = coalesced_gemm(ap, w_pad[None], jnp.zeros((m_tiles,), jnp.int32),
                         bm=bm, bn=_panel(bn, N), bk=_panel(bk, K),
                         interpret=interpret)
    return out[:m, :n_real]


def _panel(b: int, extent: int) -> int:
    """A tile's block ``b`` along one weight extent, shrunk to a divisor of
    it: an aligned envelope (dispatch.stacked_envelope) is a multiple of
    512, not a power of two, and a power-of-two block wider than 512
    takes the largest power of two that divides the envelope."""
    return math.gcd(min(b, extent), extent)


def _body_jit(jits: Dict[Tuple, Callable], jit: Callable[[Tuple], Callable],
              tile: Tuple[int, int, int], padded: Dict[str, jax.Array],
              ex: SuperkernelExecutor, block: Optional[BlockConfig]
              ) -> Tuple[Callable, Tuple[int, int, int]]:
    """The jitted scan of one stacked body and the GEMM tile it runs at:
    the body's own ``tile``, or the live-tuned ``block``
    (JitSession._run_stacked) where one is given. Each distinct tile is
    jitted once by ``jit`` (bm, bn, bk, interpret), memoized in ``jits``
    for the template's lifetime, after every operand is checked against
    VMEM at it. Callers pass ``jax.jit`` of their maker in a lambda, the
    form in which ``analysis/lint.py`` sees the body as jit-rooted."""
    if block is not None:
        tile = (block.bm, block.bn, block.bk)
    key = tuple(tile) + (ex.interpret,)
    fn = jits.get(key)
    if fn is None:
        bm, bn, bk = tile
        for w in padded.values():
            K, N = int(w.shape[-2]), int(w.shape[-1])
            check_vmem(bm, _panel(bn, N), _panel(bk, K),
                       dtype_bytes=w.dtype.itemsize, interpret=ex.interpret)
        fn = jits[key] = jit(key)
    return fn, tuple(tile)


def _stack_slice(arr: jax.Array, lo: int, hi: int) -> jax.Array:
    return arr if lo == 0 and hi == int(arr.shape[0]) else arr[lo:hi]


def _rename(fn: Callable, name: str) -> None:
    """Give ``fn`` the name ``name``: ``jax.jit`` names its executable
    ``jit_<name>``, which is what the device trace's module line shows."""
    fn.__name__ = fn.__qualname__ = name


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


def dense_program_cache_key(model, params, batch: int, cache) -> Tuple:
    """Plan-cache key for a dense decode template: (model identity, active
    batch m, dtype, cache geometry). Params identity is deliberately NOT in
    the key — a weight hot-swap lands on the same slot and is caught by the
    cache's identity guard (``guard=(model, params)`` at the lookup site),
    which invalidates (and counts) instead of silently serving stale
    closures. The guard also pins both objects, so ``id(model)`` here can
    never be a recycled address aliasing a dead model.

    The depth is part of the key: the sub-stack spans are a function of
    num_layers."""
    kc = cache["layers"]["k"]
    return ("dense-decode", model.cfg.name, id(model), batch,
            str(params["embed"].dtype), str(kc.dtype), tuple(kc.shape),
            model.cfg.num_layers)


# ---------------------------------------------------------------------------
# program builders for dense GQA (the real-execution demo family)
# ---------------------------------------------------------------------------

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
    """Token-embedding prologue shared by every DECODE builder: scaled
    embed of the step's [B, 1] tokens squeezed to [B, d], plus the
    cache-position snapshot."""

    def embed(env):
        x = params["embed"][env["tokens"]]
        env["x"] = scale_embedding(x, cfg.d_model, cfg.mult.embedding)[:, 0]
        env["pos"] = env["cache"]["pos"]

    stages.append(GlueStage(embed, reads=("tokens", "cache"),
                            writes=("x", "pos")))


def _emit_final_logits(cfg: ModelConfig, params, stages: List[Stage], *,
                       m_rows: int) -> None:
    """Final-norm + unembed tail of the GQA and SSM decode builders."""

    def final_norm(env):
        env["hf"] = rmsnorm(env["x"], params["final_norm"], cfg.norm_eps)

    stages.append(GlueStage(final_norm, reads=("x",), writes=("hf",)))
    _emit_unembed(cfg, params, stages, m_rows=m_rows)


def _emit_unembed(cfg: ModelConfig, params, stages: List[Stage], *,
                  m_rows: int) -> None:
    """Emit the unembedding GEMM over ``env['hf']`` into ``env['logits']``
    (decode and prefill; ``m_rows`` = the normed rows to unembed)."""
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
        lambda env, out: env.__setitem__("logits",
                                         scale(out, cfg.mult.lm_head)),
        shape=GemmShape(m=m_rows, n=n, k=cfg.d_model),
        reads=("hf",), writes=("logits",)))


def _gqa_decode_attend(cfg: ModelConfig, B: int, q_flat, k_flat, v_flat,
                       kc, vc, pos, is_global: bool, out_dtype
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer of single-token slotted-cache GQA attention, the pure
    math the dense, MoE and hybrid decode bodies share. ``kc``/``vc`` are
    the layer's cache slices [B, Hkv, S, hd]; returns (attn_out [B, H·hd],
    new kc, new vc)."""
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


def _stacked_dense_body_stage(model, params, B: int, lo: int, hi: int, *,
                              moe: bool = False) -> StackedGemmStage:
    """ONE scanned decode body covering layers [lo, hi) of a GQA model:
    ``_scan_gemm`` for every projection, ``_gqa_decode_attend`` for
    attention, and for MoE the literal ``moe_lib`` route/dispatch/combine
    calls ``moe_ffn`` makes (so capacity and drop semantics cannot drift)
    around E per-expert GEMMs over the [C, d] expert buffer."""
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
    # one jitted scan per GEMM tile, memoized for the template's lifetime
    # (templates live in the JIT's plan cache, so the steady state reuses
    # one compiled executable)
    jits: Dict[Tuple, Callable] = {}

    def make_scan(bm: int, bn: int, bk: int, interpret: bool):
        def gemm(a, w, n):
            return _scan_gemm(a, w, n, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)

        # every per-layer param enters as a jit ARGUMENT (via xs), never a
        # closure: XLA would bake a closed-over array in as a constant,
        # pinning its buffer and codegenning it differently in the last
        # ulp (analysis/lint.py TH001)
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

        # one body serves dense and MoE decode; each keeps its own name
        _rename(scan_fn, "moe_decode_layers" if moe
                else "dense_decode_layers")
        return scan_fn

    aux = {"ln1": ln1s, "ln2": ln2s}
    if moe:
        aux["router"] = routers

    # one m-tile covers the expert GEMMs' capacity rows as well
    rows = max(B, C) if moe else B

    def run(env, padded, ex, block=None):
        fn, tile = _body_jit(
            jits, lambda k: jax.jit(make_scan(*k)),
            decode_tile(rows, ex.bk), padded, ex, block)
        cache = env["cache"]
        x, (kc_new, vc_new) = fn(env["x"], jnp.asarray(cache["pos"]),
                                 cache["layers"]["k"], cache["layers"]["v"],
                                 padded, aux)
        env["x"] = x
        env["new_layers"]["k"].append(kc_new)
        env["new_layers"]["v"].append(vc_new)
        return (B,) + tile

    return StackedGemmStage(
        tag=f"body_{lo}_{hi}",
        weight_key=weight_key(cfg.name, pid, "body", stack=(lo, hi)),
        operands=operands, layers=Lsub, run=run,
        reads=("x", "cache"), writes=("x", "new_layers"))


def _build_gqa_decode_template(model, params, batch: int, *,
                               moe: bool = False) -> ProgramTemplate:
    """Decode template of every GQA-attention family (dense, and MoE with
    ``moe``): embed glue, one scanned body stage per homogeneous sub-stack,
    final norm and unembed, and an epilogue that concatenates the bodies'
    [Lsub, ...] cache updates into the tenant's [L, ...] KV cache."""
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


def _plain_blocks(cfg: ModelConfig) -> None:
    """The GQA + FFN templates serve the embedding and head multipliers
    only: a config whose blocks scale their own paths (keys, attention's
    input and output, the FFN) runs the hybrid template."""
    assert dataclasses.replace(cfg.mult, embedding=None, lm_head=1.0) \
        == Multipliers(), f"{cfg.name}: block multipliers unsupported here"


def build_dense_decode_template(model, params, batch: int
                                ) -> ProgramTemplate:
    """Compile the decode step of a dense GQA model into a ProgramTemplate.

    Equivalent to ``Model.decode_step`` but with every projection GEMM
    declared to the JIT, in one scanned body per homogeneous layer
    sub-stack. Supported: arch_type 'dense' (and the text path of 'vlm').
    Per-step inputs (tokens [B, 1], KV cache) are read from the bound
    program's env, so one template serves every steady-state step.
    """
    assert model.cfg.arch_type in ("dense", "vlm"), model.cfg.arch_type
    _plain_blocks(model.cfg)
    return _build_gqa_decode_template(model, params, batch)


# ---------------------------------------------------------------------------
# non-dense decode programs: MoE and SSM tenants as first-class streams
# ---------------------------------------------------------------------------

def moe_program_cache_key(model, params, batch: int, cache) -> Tuple:
    """Plan-cache key for an MoE decode template. Same discipline as
    ``dense_program_cache_key`` (params identity lives in the lookup-site
    guard, not the key); the expert capacity C is a pure function of
    (batch, cfg.moe), both captured here via batch + model identity."""
    kc = cache["layers"]["k"]
    return ("moe-decode", model.cfg.name, id(model), batch,
            str(params["embed"].dtype), str(kc.dtype), tuple(kc.shape),
            model.cfg.num_layers)


def build_moe_decode_template(model, params, batch: int
                              ) -> ProgramTemplate:
    """Compile the decode step of an MoE model into a ProgramTemplate.

    Equivalent to ``Model.decode_step`` for arch_type 'moe'. The attention
    is the dense body's (``_stacked_dense_body_stage``), so MoE attention
    operands carry the same tags and shapes as a dense tenant's. Each
    layer's FFN runs inside the scanned body: the router and sort-based
    capacity dispatch, E per-expert gate/up/down GEMMs over the [C, d]
    expert buffer, and the weighted combine. The three expert packs are
    [Lsub, E, k, n] stacked operands tagged ``expert_*``, so a body holding
    them counts in ``JitStats.expert_coalesced`` when it coalesces.
    """
    cfg: ModelConfig = model.cfg
    assert cfg.arch_type == "moe" and cfg.has_moe, cfg.arch_type
    _plain_blocks(cfg)
    return _build_gqa_decode_template(model, params, batch, moe=True)


def ssm_program_cache_key(model, params, batch: int, cache) -> Tuple:
    """Plan-cache key for an SSM decode template: (model identity, batch,
    dtype, recurrent-cache geometry). Guard discipline as for dense."""
    cc = cache["layers"]["conv"]
    return ("ssm-decode", model.cfg.name, id(model), batch,
            str(params["embed"].dtype), str(cc.dtype), tuple(cc.shape),
            tuple(cache["layers"]["h"].shape), model.cfg.num_layers)


def build_ssm_decode_template(model, params, batch: int) -> ProgramTemplate:
    """Compile the decode step of an attention-free SSM (Mamba-2/SSD) model
    into a ProgramTemplate. Equivalent to ``Model.decode_step`` for
    arch_type 'ssm'. The whole stack is ONE homogeneous sub-stack, so a
    single scanned body stage declares the stacked in/out projections and
    runs the selective-scan recurrence between them (``ssm_lib.
    decode_core``, the function ``ssd_decode_step`` calls) inside the scan
    body. The epilogue writes the conv windows and SSD states back into
    the tenant's recurrent cache.
    """
    cfg: ModelConfig = model.cfg
    assert cfg.arch_type == "ssm" and cfg.has_ssm, cfg.arch_type
    from repro.models import ssm as ssm_lib
    scfg = cfg.ssm
    B, d = batch, cfg.d_model
    d_inner = scfg.inner(d)
    n_in = scfg.in_proj_dim(d)
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

        # per-layer params enter as jit ARGUMENTS (xs), never closures
        # (see _stacked_dense_body_stage)
        def ssm_decode_layers(x, conv_full, h_full, w, aux):
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

        return ssm_decode_layers

    aux = {"ln1": ln1s, "mamba": mamba_rest}

    def run(env, padded, ex, block=None):
        fn, tile = _body_jit(
            jits, lambda k: jax.jit(make_scan(*k)),
            decode_tile(B, ex.bk), padded, ex, block)
        cache = env["cache"]
        x, (conv_new, h_new) = fn(env["x"], cache["layers"]["conv"],
                                  cache["layers"]["h"], padded, aux)
        env["x"] = x
        env["new_layers"]["conv"].append(conv_new)
        env["new_layers"]["h"].append(h_new)
        return (B,) + tile

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


# ---------------------------------------------------------------------------
# hybrid decode programs: attention and SSM heads side by side in each layer
# ---------------------------------------------------------------------------

def hybrid_program_cache_key(model, params, batch: int, cache) -> Tuple:
    """Plan-cache key for a hybrid decode template: (model identity, batch,
    dtype, the KV rows' and the recurrent state's geometry, depth). Guard
    discipline as for dense."""
    lay = cache["layers"]
    return ("hybrid-decode", model.cfg.name, id(model), batch,
            str(params["embed"].dtype), str(lay["k"].dtype),
            tuple(lay["k"].shape), str(lay["conv"].dtype),
            tuple(lay["conv"].shape), tuple(lay["h"].shape),
            model.cfg.num_layers)


def _stacked_hybrid_body_stage(model, params, B: int, lo: int, hi: int
                               ) -> StackedGemmStage:
    """ONE scanned decode body covering layers [lo, hi) of a hybrid model.
    Per layer, on the ln1 output: the GQA stages of the dense body (wq/wk/
    wv, ``_gqa_decode_attend``, wo) and the SSM stages of the SSM body (in
    projection, ``ssm_lib.decode_core``, out projection) side by side; the
    two outputs join the residual by ``combine_mixers``, the model's one
    combine rule; then the SwiGLU FFN. The µP multipliers apply where
    ``models/transformer.py`` applies them."""
    from repro.models import ssm as ssm_lib
    from repro.models.transformer import combine_mixers
    cfg: ModelConfig = model.cfg
    scfg, mu = cfg.ssm, cfg.mult
    hd, d = cfg.resolved_head_dim, cfg.d_model
    n_in = scfg.in_proj_dim(d)
    eps = cfg.norm_eps
    blocks = params["blocks"]
    pid = id(params)
    Lsub = hi - lo
    is_global = bool(cfg.layer_is_global(lo))

    def sop(tag, name, arr, n, k):
        return StackedOperand(
            tag, weight_key(cfg.name, pid, name, stack=(lo, hi)),
            GemmShape(m=B, n=n, k=k, layers=Lsub),
            lambda a=arr: _stack_slice(a, lo, hi), (arr,))

    attn, mamba, mlp = blocks["attn"], blocks["mamba"], blocks["mlp"]
    operands = [
        sop("attn_wq", "wq", attn["wq"], cfg.num_heads * hd, d),
        sop("attn_wk", "wk", attn["wk"], cfg.num_kv_heads * hd, d),
        sop("attn_wv", "wv", attn["wv"], cfg.num_kv_heads * hd, d),
        sop("attn_wo", "wo", attn["wo"], d, cfg.num_heads * hd),
        sop("ssm_in_proj", "in_proj", mamba["in_proj"], n_in, d),
        sop("ssm_out_proj", "out_proj", mamba["out_proj"], d,
            scfg.inner(d)),
        sop("ffn_gate", "w_gate", mlp["w_gate"], cfg.d_ff, d),
        sop("ffn_up", "w_up", mlp["w_up"], cfg.d_ff, d),
        sop("ffn_down", "w_down", mlp["w_down"], d, cfg.d_ff),
    ]
    # decode_core reads only the conv/dt/A/D/norm leaves; the projections
    # are the declared stacked operands above
    aux = {"ln1": _stack_slice(blocks["ln1"], lo, hi),
           "ln2": _stack_slice(blocks["ln2"], lo, hi),
           "mamba": {k: _stack_slice(v, lo, hi) for k, v in mamba.items()
                     if k not in ("in_proj", "out_proj")}}
    jits: Dict[Tuple, Callable] = {}

    def make_scan(bm: int, bn: int, bk: int, interpret: bool):
        def gemm(a, w, n):
            return _scan_gemm(a, w, n, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)

        # per-layer params enter as jit ARGUMENTS (xs), never closures
        # (see _stacked_dense_body_stage)
        def hybrid_decode_layers(x, pos_in, kc_full, vc_full, conv_full,
                                 h_full, w, aux):
            pos = jnp.broadcast_to(pos_in, (B,))

            def body(carry, per):
                wl = per["w"]
                h = rmsnorm(carry, per["ln1"], eps)
                ha = scale(h, mu.attention_in)
                q = gemm(ha, wl["attn_wq"], cfg.num_heads * hd)
                k = scale(gemm(ha, wl["attn_wk"], cfg.num_kv_heads * hd),
                          mu.key)
                v = gemm(ha, wl["attn_wv"], cfg.num_kv_heads * hd)
                attn_out, kc_new, vc_new = _gqa_decode_attend(
                    cfg, B, q, k, v, per["kc"], per["vc"], pos, is_global,
                    h.dtype)
                a = gemm(attn_out, wl["attn_wo"], d)
                y, st = ssm_lib.decode_core(
                    per["mamba"], gemm(h, wl["ssm_in_proj"], n_in),
                    {"conv": per["conv"], "h": per["h"]}, scfg, d)
                x2 = carry + combine_mixers(
                    cfg, a, gemm(y, wl["ssm_out_proj"], d))
                h2 = rmsnorm(x2, per["ln2"], eps)
                gate = scale(gemm(h2, wl["ffn_gate"], cfg.d_ff), mu.mlp_gate)
                up = gemm(h2, wl["ffn_up"], cfg.d_ff)
                down = gemm(jax.nn.silu(gate) * up, wl["ffn_down"], d)
                return x2 + scale(down, mu.mlp_down), \
                    (kc_new, vc_new, st["conv"], st["h"])

            xs = dict(aux, kc=kc_full[lo:hi], vc=vc_full[lo:hi],
                      conv=conv_full[lo:hi], h=h_full[lo:hi], w=w)
            return jax.lax.scan(body, x, xs)

        return hybrid_decode_layers

    def run(env, padded, ex, block=None):
        fn, tile = _body_jit(
            jits, lambda k: jax.jit(make_scan(*k)),
            decode_tile(B, ex.bk), padded, ex, block)
        lay = env["cache"]["layers"]
        x, new = fn(env["x"], jnp.asarray(env["cache"]["pos"]), lay["k"],
                    lay["v"], lay["conv"], lay["h"], padded, aux)
        env["x"] = x
        for name, arr in zip(("k", "v", "conv", "h"), new):
            env["new_layers"][name].append(arr)
        return (B,) + tile

    return StackedGemmStage(
        tag=f"body_{lo}_{hi}",
        weight_key=weight_key(cfg.name, pid, "body", stack=(lo, hi)),
        operands=operands, layers=Lsub, run=run,
        reads=("x", "cache"), writes=("x", "new_layers"))


def _hybrid_head_stage(model, params, B: int) -> StackedGemmStage:
    """The final norm and the output head as one declared op whose operand
    is served at the stacked operands' aligned envelope, without a copy:
    the unembedding GemmStage would pad a 5120 x 261120 head to a
    power-of-two copy of 4.3 GB."""
    cfg: ModelConfig = model.cfg
    d, eps, lm_head = cfg.d_model, cfg.norm_eps, cfg.mult.lm_head
    pid = id(params)
    w = _tied_unembed(params) if cfg.tie_embeddings else params["unembed"]
    V = cfg.padded_vocab
    operand = StackedOperand(
        "unembed", weight_key(cfg.name, pid, "unembed", stack=(0, 1)),
        GemmShape(m=B, n=V, k=d, layers=1), lambda: w, (w,))
    jits: Dict[Tuple, Callable] = {}

    def make_head(bm: int, bn: int, bk: int, interpret: bool):
        def hybrid_decode_head(x, w, final_norm):
            hf = rmsnorm(x, final_norm, eps)
            return scale(_scan_gemm(hf, w["unembed"], V, bm=bm, bn=bn,
                                    bk=bk, interpret=interpret), lm_head)

        return hybrid_decode_head

    def run(env, padded, ex, block=None):
        fn, tile = _body_jit(
            jits, lambda k: jax.jit(make_head(*k)),
            decode_tile(B, ex.bk), padded, ex, block)
        env["logits"] = fn(env["x"], padded, params["final_norm"])
        return (B,) + tile

    return StackedGemmStage(
        tag="head", weight_key=weight_key(cfg.name, pid, "head"),
        operands=[operand], layers=1, run=run,
        reads=("x",), writes=("logits",))


def build_hybrid_decode_template(model, params, batch: int
                                 ) -> ProgramTemplate:
    """Compile the decode step of a hybrid model (Falcon-H1, Hymba) into a
    ProgramTemplate: embed glue, one scanned body per homogeneous sub-stack
    (``partition_layers`` over the sliding-window flags, as the dense path
    does), the head, and an epilogue that writes the KV rows and the
    recurrent state back into the tenant's cache side by side. Equivalent
    to ``Model.decode_step`` for arch_type 'hybrid'."""
    cfg: ModelConfig = model.cfg
    assert cfg.arch_type == "hybrid" and cfg.has_ssm, cfg.arch_type
    stages: List[Stage] = []
    _emit_decode_embed(cfg, params, stages)

    def reset_layers(env):
        env["new_layers"] = {"k": [], "v": [], "conv": [], "h": []}

    stages.append(GlueStage(reset_layers, reads=(), writes=("new_layers",)))
    for lo, hi in partition_layers(cfg.global_layer_flags()):
        stages.append(_stacked_hybrid_body_stage(model, params, batch,
                                                 lo, hi))
    stages.append(_hybrid_head_stage(model, params, batch))

    def finish(env):
        cache = env["cache"]
        env["cache"] = {
            "pos": cache["pos"] + 1,
            "layers": {k: jnp.concatenate(v, axis=0)
                       for k, v in env["new_layers"].items()},
        }

    stages.append(GlueStage(finish, reads=("cache", "new_layers"),
                            writes=("cache",)))
    return ProgramTemplate(stages=stages, batch=batch, model_name=cfg.name)


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


# The stacked prefill body's GEMM tile. At the executor's decode-sized tile
# (bm 8, bn 128) a prompt GEMM runs one Pallas grid step per 1 MFLOP and
# re-streams each weight panel once per 8 rows; tiled by its own rows it
# streams each panel Sp / bm times. bk stays the executor's, so the K
# blocks are summed in the same order. (512, 512) timed fastest of bm and
# bn in {256, 512} on a TPU v5e at buckets 512-2048 (PERF.md §6).
PREFILL_BM_MAX = 512
PREFILL_BN = 512


def prefill_tile(m: int, bk: int) -> Tuple[int, int, int]:
    """GEMM tile (bm, bn, bk) of a prefill body over ``m`` rows (its
    bucket, a power of two): min(m, PREFILL_BM_MAX) rows and PREFILL_BN
    columns a tile, at the executor's ``bk``."""
    return min(m, PREFILL_BM_MAX), PREFILL_BN, bk


# The stacked decode bodies' GEMM tile. At the executor's tile (8, 128,
# 512) a body over 16 slots runs two m-tiles, so it streams every weight
# panel twice a step, in one Pallas grid step per 128 KB panel. One m-tile
# of the batch's own rows (up to 128, a v5e MXU's height) streams each
# panel once, and a wide panel cuts the grid steps. ``_scan_gemm`` clamps
# bn to a divisor of each aligned envelope (10752 and 2560 take 512;
# 11264, 21504 and 261120 at most 1024), so 1024 is the widest panel the
# largest operands can take: 2048 gives them the same tile. bk stays the
# executor's, so the K blocks are summed in the same order.
DECODE_BM_MAX = 128
DECODE_BN = 1024


def decode_tile(m: int, bk: int) -> Tuple[int, int, int]:
    """GEMM tile (bm, bn, bk) of a stacked decode body over ``m`` rows (the
    slotted batch, or an MoE expert's capacity where larger): ``m`` rounded
    up to a multiple of 8, at most DECODE_BM_MAX, so that one m-tile
    covers the batch; DECODE_BN columns; the executor's ``bk``."""
    return min(-(-m // 8) * 8, DECODE_BM_MAX), DECODE_BN, bk


def _causal_prefill_attend(cfg: ModelConfig, Sp: int, q_flat, k_flat,
                           v_flat, positions, is_global: bool, out_dtype
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer of causal prompt attention, the pure math of the prefill
    body. Returns
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
    """ONE scanned prefill body covering layers [lo, hi): the prompt pass
    of the sub-stack, with ``_causal_prefill_attend`` for attention. Each
    layer's [1, Hkv, Sp, hd] KV pair stacks into a [Lsub, Hkv, Sp, hd] ys
    chunk, which the prefill epilogue concatenates."""
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
        def dense_prefill_layers(x, positions, w, aux):
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

        return dense_prefill_layers

    aux = {"ln1": ln1s, "ln2": ln2s}

    def run(env, padded, ex, block=None):
        fn, tile = _body_jit(
            jits, lambda k: jax.jit(make_scan(*k)),
            prefill_tile(Sp, ex.bk), padded, ex, block)
        x, (k_ys, v_ys) = fn(env["x"], env["positions"], padded, aux)
        env["x"] = x
        env["new_layers"]["k"].append(k_ys)
        env["new_layers"]["v"].append(v_ys)
        return (Sp,) + tile

    return StackedGemmStage(
        tag=f"body_{lo}_{hi}",
        weight_key=weight_key(cfg.name, pid, "body", stack=(lo, hi)),
        operands=operands, layers=Lsub, run=run,
        reads=("x", "positions"), writes=("x", "new_layers"))


def prefill_program_cache_key(model, params, seq_len: int, cache) -> Tuple:
    """Plan-cache key for a dense prefill template: (model identity, padded
    prompt bucket, dtype, cache geometry). Same guard discipline as
    ``dense_program_cache_key`` — params identity is caught by the lookup
    site's ``guard=(model, params)``, never baked into the key."""
    kc = cache["layers"]["k"]
    return ("dense-prefill", model.cfg.name, id(model), seq_len,
            str(params["embed"].dtype), str(kc.dtype), tuple(kc.shape),
            model.cfg.num_layers)


def build_dense_prefill_template(model, params, seq_len: int
                                 ) -> ProgramTemplate:
    """Compile the PROMPT pass of a dense GQA model into a ProgramTemplate.

    Every projection GEMM is declared to the JIT with m = ``seq_len`` (the
    padded prefill bucket) — tall problems that enter the live op pool and
    coalesce with decode GEMVs (and other tenants' prefill GEMMs) sharing
    their (n, k) weight dims, in one scanned body per homogeneous layer
    sub-stack. Equivalent to ``Model.prefill`` for arch_type 'dense',
    last-position logits only.

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
    Sp = seq_len
    stages: List[Stage] = []

    def glue(fn, reads=None, writes=None):
        stages.append(GlueStage(fn, reads=reads, writes=writes))

    def embed(env):
        x = params["embed"][env["tokens"]]            # [1, Sp, d]
        env["x"] = (x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype))[0]
        env["positions"] = jnp.arange(Sp)[None, :]    # rope positions

    glue(embed, reads=("tokens",), writes=("x", "positions"))

    for lo, hi in partition_layers(cfg.global_layer_flags()):
        stages.append(_stacked_prefill_body_stage(model, params, Sp, lo, hi))

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
    # non-dense (MoE / SSM / hybrid) tenant steps compiled+bound as
    # KernelPrograms
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
    # decode programs the serving engine admitted, their active rows, and
    # the real seconds (time.monotonic) from each one's admission to the
    # end of the token sync that lands its tokens on the host
    decode_programs: int = 0
    decode_rows: int = 0
    decode_step_s: StreamStat = dataclasses.field(default_factory=StreamStat)
    # stacked prefill bodies dispatched, by (Sp, bm, bn, bk): the prompt
    # bucket and the GEMM tile its body ran at (``prefill_tile``). A
    # Counter: ``+`` merges, ``-`` gives an epoch's delta
    prefill_bodies: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    # stacked decode bodies dispatched (the head of a hybrid step among
    # them), by (B, bm, bn, bk): the slotted batch and the GEMM tile the
    # body ran at (``decode_tile``); merged like ``prefill_bodies``
    decode_bodies: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)

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
                             layers: int, dtype_bytes: int) -> float:
        """All-to-all charge for the expert-FFN trios of a device-spanning
        MoE stream's scanned body: dispatch scatters the [m, k] expert
        activations to the shards, combine gathers the outputs back — 2·m·k
        bytes round trip per scanned layer. Charged ONCE per trio (on the
        gate operand) so a gate/up/down triple is not triple-billed. Local
        streams (span <= 1) pay nothing."""
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
        cache, then run the scanned bodies back-to-back and count each in
        ``prefill_bodies`` or ``decode_bodies`` at the tile it ran.
        ``block`` overrides each body's own tile for the scanned GEMMs (the
        live-tuned config of the plan) — each distinct config compiles its
        own scan body once, keyed beside the body's tile."""
        ex = self.jit.executor
        for op in ops:
            prog, st = self.live.pop(op.op_id)
            padded = {}
            if not ex.enabled:
                # eager ablation (executor.enabled=False): pad each stacked
                # operand fresh — same envelope, same bits — but through
                # neither the persistent cache nor DispatchStats
                for od in st.operands:
                    padded[od.tag] = pad_stacked(
                        od.weight_fn(), stacked_envelope(int(od.shape.k)),
                        stacked_envelope(int(od.shape.n)))
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
            tile = st.run(prog.env, padded, ex, block)
            bodies = self.stats.prefill_bodies if prog.kind == "prefill" \
                else self.stats.decode_bodies
            bodies[tile] += 1
            prog.pc += 1
            nxt = prog.advance_glue()
            if nxt is None:
                completed.append(prog)
            else:
                self._push_op(prog, nxt)

    @spanned("jit.tick")
    def tick(self, now: float) -> TickEvent:
        """Execute one scheduler decision at virtual time ``now``."""
        self._sync_cache_stats()
        completed, self._done = self._done, []
        if not self.live:
            return TickEvent("idle", now, completed=completed)
        self._started = True
        with span(self.jit.spans, "sched.decide"):
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
            with span(self.jit.spans, "dispatch.execute"):
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
        # host spans in the profiler's trace around the tick, the
        # scheduler's decision and the dispatch, and around the serving
        # engine's calls (core/spans.py); off, no span is constructed
        self.spans = False

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
