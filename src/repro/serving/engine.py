"""Multi-tenant serving engine: event-driven OoO serving with live admission.

Three execution modes, mirroring the paper's comparison end-to-end:

  * "time"    — each request decodes alone, requests strictly serialized
                (GPU time-multiplexing, §4.1);
  * "batched" — continuous batching *within* each tenant, tenants serialized
                (ModelBatch / TensorRT-style, §4.2's strongest baseline);
  * "vliw"    — OUR engine: a single virtual-time **event loop** over an
                admission-open ``JitSession`` (core/jit.py). Tenants'
                decode steps AND dense prompt prefills are compiled to
                KernelPrograms and coalesced ACROSS tenants: admission
                *declares* a prefill program (prompt GEMMs enter the live
                op pool, KV write-back is the program epilogue, and the
                tenant's decode joins only after the completion event)
                instead of charging the prompt analytically on the shared
                clock — so a long prompt no longer head-of-line-blocks
                other tenants, it coalesces with them. A request arriving
                mid-flight joins *between superkernel dispatches*, not at
                a round boundary.
                The trace's future arrival times are fed to the OoO
                scheduler, so its stagger/WAIT branch executes for real; the
                tightest per-request deadline of each tenant's batch flows
                into per-op ``latest_start_t`` for EDF anchoring and
                eviction of already-missed stragglers.

In vliw mode the engine can drive an N-device modeled mesh
(``num_devices`` / an explicit ``DeviceSet``): each tenant is bound to a
home device at its FIRST admission (``distributed/placement.py`` — greedy
least-loaded bin-packing over modeled steady-state load) and every op it
ever declares runs on that device's own virtual timeline — one
``JitSession`` (scheduler + coalescer + free instant + EDF anchor set)
per device, all sharing one ``VLIWJit``'s plan/weight caches (keyed with
the device id) and one ``ScheduleTrace``. Ops never coalesce across
devices. Expert-parallel MoE tenants additionally SPAN the mesh with
their expert weights when the mesh size divides the expert count; their
ops stay on the home timeline but carry an all-to-all dispatch/combine
charge in EDF slack and plan estimates. When at least N JAX devices are
attached, mesh slot d executes on ``jax.devices()[d]``: placement commits
the tenant's params, KV cache and token slots there, so its packed weights
and every dispatch follow. A wider mesh than the attached devices is an
error on a TPU and only modeled (with a warning) on the CPU backend.

Arch-support matrix (which path each tenant takes in vliw mode):

  ==========  =====================  ==========================  ===============
  arch_type   decode step            prompt prefill              mesh placement
  ==========  =====================  ==========================  ===============
  dense       KernelProgram          declared prefill program    home device
                                     (>= prefill_declare_min;
                                     analytic below it)
  vlm         KernelProgram          analytic (patch projector)  home device
  moe         KernelProgram          analytic                    home device;
              (router glue +                                     experts span
              per-expert GEMMs)                                  mesh when
                                                                 N | n_experts
                                                                 (+ all-to-all)
  ssm         KernelProgram          analytic                    home device
              (scan recurrence glue)
  hybrid      monolithic batched     analytic                    home device
  audio       monolithic batched     analytic                    home device
  int8-KV     monolithic batched     analytic                    home device
  (any arch)
  ==========  =====================  ==========================  ===============

KernelProgram rows flow through admission → EDF scheduling → clustering →
coalesced dispatch (``JitStats.nondense_programs`` counts the MoE/SSM
ones); "monolithic batched" rows run ``Model.decode_step`` inside the same
event loop, serialized on the virtual clock. Baseline modes ("time",
"batched") always run monolithic steps — that asymmetry IS the experiment.

The baseline modes keep their defining round-synchronous semantics
(``_run_rounds``); greedy tokens are asserted identical across all three
modes because batch rows are independent, so scheduling order cannot change
any request's token stream.

Token generation is REAL (greedy argmax through the actual models); time is
attributed with the calibrated device cost model, since wall-clock on a CPU
host says nothing about TPU latency. Both are reported.

Continuous batching mechanics: each tenant owns a slotted decode cache
(``max_batch`` rows, per-row positions). Admission prefills a request
(real ``Model.prefill``) and writes its KV rows into a free slot; completed
requests free their slot mid-flight — per-row ``pos`` makes mixed-depth
batches correct (models/attention.py).

The front door (daemon mode + admission control)
------------------------------------------------

``engine.run`` replays a finite trace in virtual time and terminates when
it is exhausted. ``engine.serve_forever(door)`` is the production front
door: a long-lived loop over the SAME per-device event-loop machinery
that accepts continuous admission from a ``FrontDoor`` on a real clock
(``serving/frontdoor.py``), streams each request's tokens out as they
retire (``token_sink`` / per-request ``Ticket``), IDLES while the door is
open and empty (the replay stall guard becomes a wait), and flushes
in-flight work then terminates cleanly once the door closes.

Real-clock vs virtual-time semantics: with an authoritative clock
(``MonotonicClock``, the default) the per-device virtual timelines are
floored at real elapsed time each iteration, so arrival stamps, SLO
deadlines and modeled service charges share one axis. With a follower
``VirtualClock`` (tests / the sustained-load bench) the clock tracks the
modeled timelines instead and a pre-scheduled door replays exactly like
``run`` — bit-identical tokens on the admitted set.

Admission control (``admission_control=True`` or an explicit
``AdmissionController``): every request carries a priority/SLO ``tier``
(serving/admission.py's ``TierSpec`` ladder), and when it becomes due the
door makes an explicit decision from the analytic cost model — forecast
completion = now + committed device backlog + modeled request cost + an
overload margin from the ``ArrivalPredictor`` load forecast. A request
whose tier deadline is infeasible is DEGRADED down the ladder (relaxed
deadline it can actually keep, ``degraded_from`` records the original
tier) or SHED at the door — so under overload accepted requests keep
their deadlines instead of every request degrading together. Shed
requests never occupy a slot; they count as SLO misses in
``ServeReport.slo_attainment`` and per-tier attainment (never silently
vanishing into ``unfinished``). The same admission path runs under
``run`` for deterministic open-loop replay benches
(benchmarks/e2e_slo_attainment.py gates admission-on vs admit-everything).
"""
from __future__ import annotations

import dataclasses
import math
import time as _time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.certify import ScheduleCertifier, check_conservation
from repro.configs.base import ModelConfig
from repro.core.costmodel import CostModel, attached_device
from repro.core.dispatch import device_weight_budget
from repro.core.jit import (JitStats, KernelProgram, VLIWJit,
                            build_dense_decode_template,
                            build_dense_prefill_template,
                            build_moe_decode_template,
                            build_ssm_decode_template,
                            dense_program_cache_key, moe_program_cache_key,
                            prefill_bucket, prefill_program_cache_key,
                            ssm_program_cache_key)
from repro.core.kernelspec import gemm_population
from repro.core.scheduler import SchedulerConfig
from repro.core.schedtrace import ScheduleTrace
from repro.distributed.placement import DeviceSet, PlacementPolicy
from repro.models.model import Model
from repro.serving.admission import AdmissionController, DEFAULT_TIERS
from repro.serving.frontdoor import FrontDoor, MonotonicClock
from repro.serving.workload import ServeRequest


def _slot_devices(n: int) -> Optional[List[Any]]:
    """The JAX devices an ``n``-slot mesh executes on: the first ``n``
    attached devices, or None for one slot (the default device). A mesh
    wider than the attached devices is an error on a TPU; on another
    backend (the CPU tests) it is only modeled — every slot executes on the
    default device — and a warning says so."""
    if n == 1:
        return None
    avail = jax.devices()
    if n <= len(avail):
        return avail[:n]
    if avail[0].platform == "tpu":
        raise ValueError(f"a {n}-device mesh needs {n} chips; "
                         f"{len(avail)} attached")
    warnings.warn(f"{n}-device mesh is modeled only: all {n} slots execute "
                  f"on {avail[0]}", stacklevel=3)
    return None


@dataclasses.dataclass
class Tenant:
    name: str
    model: Model
    params: Any
    cache_len: int = 64
    max_batch: int = 4
    # runtime state
    cache: Any = None
    slot_req: List[Optional[ServeRequest]] = dataclasses.field(
        default_factory=list)
    slot_tok: Any = None
    slot_remaining: List[int] = dataclasses.field(default_factory=list)

    @property
    def cfg(self) -> ModelConfig:
        return self.model.cfg

    def active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]


@dataclasses.dataclass
class ServeReport:
    mode: str
    requests: List[ServeRequest]
    modeled_time_s: float
    wall_time_s: float
    jit: Optional[JitStats] = None
    # multi-device vliw runs only (None otherwise): index d = mesh slot d
    device_time_s: Optional[List[float]] = None   # final per-device clock
    device_busy_s: Optional[List[float]] = None   # modeled busy time charged

    @property
    def num_devices(self) -> int:
        return len(self.device_time_s) if self.device_time_s else 1

    @property
    def device_util(self) -> List[float]:
        """Per-device busy fraction of the fleet makespan — the utilization
        skew the placement policy is judged on."""
        if not self.device_busy_s or not self.modeled_time_s:
            return []
        return [b / self.modeled_time_s for b in self.device_busy_s]

    @property
    def device_skew(self) -> float:
        """max/mean per-device busy time; 1.0 = perfectly balanced."""
        if not self.device_busy_s:
            return 1.0
        mean = sum(self.device_busy_s) / len(self.device_busy_s)
        return max(self.device_busy_s) / mean if mean > 0 else 1.0

    @property
    def finished(self) -> List[ServeRequest]:
        return [r for r in self.requests if not np.isnan(r.finish_t)]

    @property
    def unfinished(self) -> int:
        """Requests that never finished (shed / dropped / stalled /
        unadmittable). Exposed so latency stats restricted to finished
        requests cannot silently hide drops."""
        return len(self.requests) - len(self.finished)

    @property
    def shed(self) -> int:
        """Requests the front door refused at admission (a subset of
        ``unfinished``; they count as SLO misses, see below)."""
        return sum(1 for r in self.requests if r.shed)

    @property
    def slo_attainment(self) -> float:
        """Fraction of ALL requests that finished within their SLO.

        The denominator is every request — shed and unfinished requests
        count as misses (``met_slo`` is False on a NaN finish). They used
        to be excluded entirely, which silently inflated attainment the
        moment the front door shed or dropped anything. NOTE the
        deliberate asymmetry with ``mean_latency``: attainment is a
        promise-keeping ratio (a drop is a broken promise), while a mean
        over latencies that include NaN/inf drops would be meaningless —
        so the mean stays finished-only, with ``unfinished``/``shed``
        published alongside it."""
        n = len(self.requests)
        return sum(r.met_slo for r in self.requests) / max(n, 1)

    def tier_attainment(self, original: bool = True) -> Dict[int, float]:
        """Per-tier SLO attainment (shed/unfinished count as misses).
        ``original=True`` groups a degraded request under the tier it
        ARRIVED with (the door's promise ledger); ``original=False``
        groups by the tier it was served at."""
        def tier_of(r: ServeRequest) -> int:
            if original and r.degraded_from is not None:
                return r.degraded_from
            return r.tier
        out: Dict[int, List[ServeRequest]] = {}
        for r in self.requests:
            out.setdefault(tier_of(r), []).append(r)
        return {tier: sum(r.met_slo for r in grp) / len(grp)
                for tier, grp in sorted(out.items())}

    @property
    def goodput_rps(self) -> float:
        """SLO-met completions per modeled second — the front-door
        acceptance metric: past the saturation knee an admit-everything
        policy keeps its throughput but loses its goodput."""
        met = sum(r.met_slo for r in self.requests)
        return met / self.modeled_time_s if self.modeled_time_s else 0.0

    @property
    def mean_latency(self) -> float:
        """Mean latency over FINISHED requests only — an unfinished request
        has finish_t = NaN, which used to poison the whole mean. Check
        ``unfinished`` / ``shed`` to see how many were excluded (attainment
        and ``p_latency`` DO count them; see ``slo_attainment``)."""
        done = self.finished
        return float(np.mean([r.latency for r in done])) if done \
            else float("nan")

    def p_latency(self, q: float) -> float:
        """Latency quantile over ALL requests: an unfinished or shed
        request contributes +inf (it never completed), so tail percentiles
        reflect drops instead of silently excluding them. Computed by
        explicit linear-interpolation rank (np.quantile's interpolation
        through inf produces NaN); matches np.quantile when every request
        finished. NaN when the report is empty."""
        n = len(self.requests)
        if n == 0:
            return float("nan")
        lats = sorted(r.latency for r in self.finished)
        k = len(lats)
        pos = q * (n - 1)
        lo, hi = int(math.floor(pos)), int(math.ceil(pos))
        if lo >= k:
            return math.inf
        if hi >= k:
            return math.inf if pos > lo else float(lats[lo])
        return float(lats[lo] + (pos - lo) * (lats[hi] - lats[lo]))

    @property
    def tokens_per_s(self) -> float:
        """Throughput over tokens actually emitted — counting
        ``max_new_tokens`` overstated it whenever a request was unfinished
        or retired early (e.g. at admission for single-token requests)."""
        toks = sum(len(r.tokens_out or ()) for r in self.requests)
        return toks / self.modeled_time_s if self.modeled_time_s else 0.0


@dataclasses.dataclass
class ArrivalPredictor:
    """Per-tenant inter-arrival EWMA (ROADMAP "Arrival prediction").

    The scheduler's stagger/WAIT branch needs ``next_arrival_t`` — on a
    replayed trace the engine simply peeks at the trace, but live traffic
    has no oracle. This estimator observes each tenant's admissions and
    predicts the earliest next arrival across tenants:

      * ``observe(tenant, t)`` folds the new inter-arrival gap into the
        tenant's EWMA (``alpha`` weights the newest gap). Observations
        need NOT be globally monotone: with N per-device admission queues
        and a real clock, a pair of arrivals is routinely observed out of
        order — the ABSOLUTE gap |t - last| is folded either way (it is
        the same inter-arrival sample, seen from the other side), and
        ``last`` tracks the max observed time. Dropping out-of-order
        samples (the old behavior) silently starved the EWMA stale;
      * ``predict(now)`` returns min over tenants of the expected next
        arrival — ``last + gap`` while that is still in the future, else
        ``now + gap`` (restart the clock: for a memoryless/Poisson flow
        the expected residual wait is one mean gap regardless of how
        overdue the arrival is). ``inf`` until at least one gap has been
        seen, which leaves the scheduler's never-wait behavior untouched.
    """

    alpha: float = 0.2
    _last: Dict[str, float] = dataclasses.field(default_factory=dict)
    _gap: Dict[str, float] = dataclasses.field(default_factory=dict)

    def observe(self, tenant: str, t: float) -> None:
        last = self._last.get(tenant)
        if last is not None:
            # |t - last| folds out-of-order observations too (normal with
            # per-device queues + a real clock): the reordered pair's gap
            # is the same inter-arrival sample either way round — the old
            # ``t >= last`` guard dropped it and let the EWMA go stale
            gap = abs(t - last)
            prev = self._gap.get(tenant)
            self._gap[tenant] = gap if prev is None else \
                self.alpha * gap + (1.0 - self.alpha) * prev
        self._last[tenant] = max(t, last) if last is not None else t

    def reset(self) -> None:
        """Forget all state. The engine calls this when a run's virtual
        clock restarts at 0 — otherwise a reused engine's stored last-
        arrival times (from the previous trace's end) sit AHEAD of every
        new arrival, ``observe`` drops every gap, and the scheduler is fed
        stagger hints from a dead workload forever."""
        self._last.clear()
        self._gap.clear()

    def gap(self, tenant: str) -> float:
        """The tenant's current EWMA inter-arrival gap (inf if unseen)."""
        return self._gap.get(tenant, math.inf)

    def predict(self, now: float) -> float:
        est = math.inf
        for tenant, gap in self._gap.items():
            t_hat = self._last[tenant] + gap
            if t_hat <= now:
                t_hat = now + gap
            est = min(est, t_hat)
        return est


@dataclasses.dataclass
class _LoopState:
    """Mutable state of one event-loop epoch — a ``run`` replay or an open
    ``serve_forever`` door session. Everything the per-device pass touches
    is factored here so both loops drive the IDENTICAL machinery; only the
    outer termination policy differs (replay terminates on exhaustion, the
    daemon idle-waits while the door is open and flushes on close)."""
    rng: Any
    sessions: List[Any]
    trace: Optional[ScheduleTrace]
    cert: Optional[ScheduleCertifier]
    stream_ids: Dict[str, int]
    id2name: Dict[int, str]
    tenant_dev: Dict[str, int]
    queues: List[List[ServeRequest]]     # per-device admission queues
    pis: List[int]
    waiting: List[List[ServeRequest]]
    inflight: Dict[str, Any]
    now: List[float]                     # per-device virtual clocks
    busy: List[float]                    # analytic charges per device
    committed: List[float]               # admission-committed horizon
    certified: int = 0                   # dispatch records already certified
    n_done: int = 0
    total: int = 0
    oracle: bool = True        # replay: trace lookahead feeds next-arrival
    next_hint: Optional[Any] = None      # daemon: door's scheduled lookahead


class ServingEngine:
    def __init__(self, tenants: Sequence[Tenant], mode: str = "vliw",
                 cost: Optional[CostModel] = None, max_group: int = 16,
                 sched_cfg: SchedulerConfig = SchedulerConfig(),
                 plan_capacity: int = 128, declared_prefill: bool = True,
                 prefill_declare_min: int = 16,
                 predict_arrivals: bool = False,
                 arrival_alpha: float = 0.2,
                 weight_budget_bytes: Optional[int] = None,
                 stacked_layers: bool = True,
                 certify: bool = False,
                 num_devices: int = 1,
                 devices: Optional[DeviceSet] = None,
                 live_tune: bool = False,
                 tune_objective: str = "collaborative",
                 admission_control: bool = False,
                 admission: Optional[AdmissionController] = None,
                 token_sink: Optional[Any] = None):
        assert mode in ("time", "batched", "vliw")
        self.tenants = {t.name: t for t in tenants}
        self.mode = mode
        # certify=True records a ScheduleTrace on the vliw session and runs
        # the incremental hazard certifier (repro.analysis.certify) on every
        # tick's dispatches plus whole-run conservation — a HazardViolation
        # raises at the offending dispatch. Off by default: tracing every
        # op record is pure overhead when nobody is checking. The last run's
        # trace stays on ``last_trace`` (mutation tests re-certify it).
        self.certify = certify
        self.last_trace = None
        # stacked_layers=True (default) compiles tenants to layer-stacked
        # templates (one scanned body per homogeneous sub-stack; build and
        # trace size O(1) in depth). False keeps per-layer emission — the
        # bit-identity oracle. The analytic charges below (_ops_time etc.)
        # are regime-independent: the stacked cost model charges a stacked
        # op as L sequential tile-waves, the same total the per-layer path
        # accumulates stage by stage.
        self.stacked_layers = stacked_layers
        # vliw mode compiles dense tenants' prompt passes to KernelPrograms
        # (prefill GEMMs enter the live op pool and coalesce across
        # tenants); declared_prefill=False keeps the analytic serialized
        # charge instead — the ablation baseline the prefill benchmark
        # measures against. Baseline modes always charge analytically:
        # that asymmetry IS the experiment.
        self.declared_prefill = declared_prefill
        # prompts shorter than this stay on the analytic charge even in
        # vliw mode: their GEMMs sit in the same GEMV regime as a decode
        # step (nothing tall to overlap) while a declared program still
        # pays a per-stage dispatch on every layer — measurably worse on
        # staggered short-prompt traces. 16 = the first prefill bucket
        # above the m<=8 GEMV boundary.
        self.prefill_declare_min = prefill_declare_min
        # predict_arrivals=True blinds the scheduler's stagger lookahead to
        # the replay trace and feeds it the per-tenant inter-arrival EWMA
        # instead — the non-replayed-traffic mode. Default (False) keeps
        # the trace-driven oracle. The replay mechanics (when requests
        # BECOME due) always follow the trace; only the scheduler's
        # next-arrival hint changes.
        self.predict_arrivals = predict_arrivals
        self._arrival_pred = ArrivalPredictor(alpha=arrival_alpha)
        # the front door's admit/degrade/shed policy (serving/admission.py):
        # consulted once per request, when it becomes due in the event loop
        # — both under serve_forever (the daemon) and under run (open-loop
        # replay benches). None = admit everything (exact legacy behavior,
        # and the bench's ablation baseline).
        self.admission = admission if admission is not None else (
            AdmissionController() if admission_control else None)
        assert self.admission is None or mode == "vliw", \
            "admission control lives in the vliw event loop"
        # token streaming: called as token_sink(req, token, t) for every
        # token the moment it retires on the modeled clock — the daemon
        # wires the FrontDoor's per-request Ticket delivery here
        self.token_sink = token_sink
        self.cost = cost or CostModel(attached_device())
        # the modeled mesh: N virtual device timelines, each with its own
        # scheduler/coalescer (ops never coalesce across devices) sharing
        # one VLIWJit's plan + weight caches (keyed with the device id).
        # Tenants bind to a home device at FIRST admission (placement.py).
        if devices is not None:
            self.devices = devices
            if cost is not None and cost.device is devices.devices[0]:
                devices.bind_cost(0, cost)
            self.cost = devices.cost(0)
        else:
            self.devices = DeviceSet.homogeneous(self.cost.device,
                                                 max(1, int(num_devices)))
            # mesh slot 0 IS the engine's cost model: downstream memos
            # (the template GEMM-suffix table) key on cost identity
            self.devices.bind_cost(0, self.cost)
        assert len(self.devices) == 1 or mode == "vliw", \
            "multi-device serving requires mode='vliw' (baseline modes " \
            "define single-device round semantics)"
        self.placement = PlacementPolicy(self.devices)
        # the JAX device each mesh slot executes on (None: the default
        # device runs everything — one slot, or a modeled mesh). A tenant's
        # params, KV cache and token slots are committed to its home device
        # when placement binds it, so its packed weights and dispatches
        # follow them there.
        self.slot_devices = _slot_devices(len(self.devices))
        # (id(params), slot) -> (source tree, committed tree): tenants that
        # share one params tree on one device keep sharing one copy
        # (operand sharing keys on the tree's identity); holding the source
        # keeps its id from being recycled
        self._committed: Dict[Tuple[int, int], Tuple[Any, Any]] = {}
        # per-device timeline/busy vectors of the last vliw run (ServeReport
        # device_time_s / device_busy_s)
        self._last_device_time: Optional[List[float]] = None
        self._last_device_busy: Optional[List[float]] = None
        # plan_capacity bounds the JIT's persistent plan caches (program
        # templates + block plans); 0 = rebuild per step (baseline).
        # weight_budget_bytes bounds the dispatch executor's packed-weight
        # cache in BYTES — entries are full padded operand copies, and the
        # stacked per-expert packs of MoE tenants are the big ones. None
        # sizes it from the memory of the devices the mesh runs on
        # (dispatch.device_weight_budget)
        # live_tune=True puts the collaborative autotuner on the dispatch
        # hot path (core/autotuner.LiveTuner): every coalesced group's
        # (bm, bn, bk) is tuned for the group's actual co-resident shapes
        # and flows into the dispatched superkernels, cached per signature
        # in the JIT's tune cache. tune_objective="greedy" is the Table 1
        # ablation (isolated-latency tiles imposed on the shared device).
        if weight_budget_bytes is None:
            weight_budget_bytes = device_weight_budget(len(self.devices))
        self.jit = VLIWJit(self.cost, sched_cfg=sched_cfg,
                           max_group=max_group, plan_capacity=plan_capacity,
                           weight_budget_bytes=weight_budget_bytes,
                           live_tune=live_tune,
                           tune_objective=tune_objective)
        self.jit_stats = JitStats()
        for t in tenants:
            t.cache = t.model.init_cache(t.max_batch, t.cache_len)
            t.slot_req = [None] * t.max_batch
            t.slot_tok = jnp.zeros((t.max_batch, 1), jnp.int32)
            t.slot_remaining = [0] * t.max_batch

    # ------------------------------------------------------------------
    # modeled step times
    # ------------------------------------------------------------------
    def _ops_time(self, cfg: ModelConfig, m: int) -> float:
        """Serial modeled time for one full decode step at batch m."""
        t = 0.0
        for tag, shape in gemm_population(cfg, m):
            reps = 1 if tag == "unembed" else cfg.num_layers
            t += reps * self.cost.gemm_time(shape)
        return t + self._attn_time(cfg, m)

    def _attn_time(self, cfg: ModelConfig, m: int) -> float:
        """KV-cache streaming time (memory-bound), same for every mode."""
        if cfg.is_attention_free:
            return 0.0
        hd = cfg.resolved_head_dim
        # mean filled length ~ half the cache
        mean_len = 0.5 * max(t.cache_len for t in self.tenants.values()
                             if t.cfg is cfg) if any(
            t.cfg is cfg for t in self.tenants.values()) else 64
        bytes_ = 2 * cfg.num_layers * cfg.num_kv_heads * mean_len * hd * 2 * m
        return bytes_ / self.cost.device.hbm_bw

    def _prefill_attn_time(self, cfg: ModelConfig, prompt_len: int) -> float:
        """KV write-back + causal attention streaming for one prompt
        (memory-bound, the same accounting family as ``_attn_time``): the S
        new K/V entries are written once and each query position streams
        the prefix behind it (~S(S+1)/2 entries). Charged at prefill
        completion on the declared path and folded into ``_prefill_time``
        for the analytic one, so both paths model the same traffic."""
        if cfg.is_attention_free:
            return 0.0
        hd = cfg.resolved_head_dim
        s = prompt_len
        per_entry = 2 * cfg.num_layers * cfg.num_kv_heads * hd * 2
        return per_entry * (s + s * (s + 1) / 2.0) / self.cost.device.hbm_bw

    def _prefill_time(self, cfg: ModelConfig, prompt_len: int) -> float:
        """Analytic serialized prompt cost: GEMMs + KV/attention traffic
        (the latter used to be dropped, making prefill inconsistently
        cheaper than ``_attn_time``-style decode accounting)."""
        t = 0.0
        for tag, shape in gemm_population(cfg, prompt_len):
            reps = 1 if tag == "unembed" else cfg.num_layers
            t += reps * self.cost.gemm_time(shape)
        return t + self._prefill_attn_time(cfg, prompt_len)

    def _request_cost_s(self, t: Tenant, req: ServeRequest) -> float:
        """Modeled end-to-end service cost of one request — the front
        door's admission currency: full prefill plus the remaining decode
        steps at the tenant's batch width (amortized: a decode step is
        shared by up to ``max_batch`` requests, so the marginal per-token
        cost is the batched step divided by the batch)."""
        m = max(t.max_batch, 1)
        per_tok = self._ops_time(t.cfg, m) / m
        return self._prefill_time(t.cfg, req.prompt_len) \
            + max(req.max_new_tokens - 1, 0) * per_tok

    def _emit_token(self, req: ServeRequest, tok: int, t: float) -> None:
        if self.token_sink is not None:
            self.token_sink(req, tok, t)

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def make_prompt(self, tenant: Tenant, req: ServeRequest,
                    rng: jax.Array) -> jax.Array:
        """The request's synthetic prompt [1, prompt_len] — derived from
        (rng, req_id) only, so every mode and prefill path sees the exact
        same tokens."""
        return jax.random.randint(jax.random.fold_in(rng, req.req_id),
                                  (1, req.prompt_len), 0,
                                  tenant.cfg.vocab_size)

    def _admit(self, tenant: Tenant, req: ServeRequest, rng: jax.Array,
               now: float) -> float:
        """Prefill ``req`` into the tenant. Returns the modeled prefill time
        (0.0 with ``tokens_out`` still None means: no free slot, retry).

        A request whose prefill already produced its only token
        (``max_new_tokens <= 1``) is retired here, at admission, in every
        mode: it never occupies a decode slot, so it cannot join a decode
        step it does not need (which used to inflate its latency by one
        step and emit an extra token). ``finish_t`` is set for the caller
        to count it as done."""
        needs_slot = req.max_new_tokens > 1
        slots = [i for i, r in enumerate(tenant.slot_req) if r is None]
        if needs_slot and not slots:
            return 0.0  # caller retries later
        m = tenant.model
        pbatch = {"tokens": self.make_prompt(tenant, req, rng)}
        if m.cfg.arch_type == "vlm":
            pbatch["patch_embeds"] = jnp.zeros(
                (1, m.cfg.num_patch_tokens, m.cfg.d_model), m.dtype)
        if m.cfg.is_encdec:
            pbatch["frames"] = jnp.zeros(
                (1, m.cfg.encoder_seq_len, m.cfg.d_model), m.dtype)
        logits, pc = m.prefill(tenant.params, pbatch,
                               cache_len=tenant.cache_len)
        tok = jnp.argmax(logits[0, -1]).astype(jnp.int32)
        req.tokens_out = [int(tok)]
        dt = self._prefill_time(m.cfg, req.prompt_len)
        self._emit_token(req, int(tok), now + dt)
        if not needs_slot:
            req.finish_t = now + dt    # done at admission: no decode steps
            return dt
        # write row into the tenant's slotted cache
        slot = slots[0]
        new_layers = {}
        for key, arr in tenant.cache["layers"].items():
            new_layers[key] = arr.at[:, slot].set(pc["layers"][key][:, 0])
        tenant.cache = {
            "pos": tenant.cache["pos"].at[slot].set(pc["pos"][0]),
            "layers": new_layers,
        }
        tenant.slot_tok = tenant.slot_tok.at[slot, 0].set(tok)
        tenant.slot_req[slot] = req
        tenant.slot_remaining[slot] = req.max_new_tokens - 1
        return dt

    # ------------------------------------------------------------------
    # one decode round (baseline modes only)
    # ------------------------------------------------------------------
    def _decode_round(self, now: float = 0.0) -> float:
        live = [t for t in self.tenants.values() if t.active_slots()]
        dt = 0.0
        if self.mode == "batched":
            for t in live:
                dt += self._tenant_batched_step(t, now + dt)
        else:  # time: every active request decodes alone, serialized
            for t in live:
                n_active = len(t.active_slots())
                logits, t.cache = t.model.decode_step(t.params, t.slot_tok,
                                                      t.cache)
                self._consume(t, logits, now + dt)
                dt += n_active * self._ops_time(t.cfg, 1)
        return dt

    def _tenant_batched_step(self, t: Tenant, now: float = 0.0) -> float:
        logits, t.cache = t.model.decode_step(t.params, t.slot_tok, t.cache)
        dt = self._ops_time(t.cfg, len(t.active_slots()))
        self._consume(t, logits, now + dt)
        return dt

    def _consume(self, t: Tenant, logits: jax.Array, now: float = 0.0
                 ) -> None:
        toks = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        t.slot_tok = toks[:, None]
        for slot in t.active_slots():
            req = t.slot_req[slot]
            req.tokens_out.append(int(toks[slot]))
            self._emit_token(req, int(toks[slot]), now)
            t.slot_remaining[slot] -= 1

    def _retire(self, t: Tenant, now: float) -> List[ServeRequest]:
        """Free slots of finished requests; returns the retired requests
        (the vliw trace records their ids, everyone else just counts)."""
        done: List[ServeRequest] = []
        for slot in t.active_slots():
            if t.slot_remaining[slot] <= 0:
                req = t.slot_req[slot]
                req.finish_t = now
                t.slot_req[slot] = None
                done.append(req)
        return done

    # ------------------------------------------------------------------
    # the event loop (vliw mode)
    # ------------------------------------------------------------------
    def _jit_capable(self, t: Tenant) -> bool:
        # layerwise kernel programs cover dense/vlm GQA decode, MoE decode
        # (router glue + per-expert GemmStages) and SSM decode (selective-
        # scan glue) over bf16/f32 caches; int8-KV tenants (and hybrid /
        # encdec archs) take the monolithic batched step — see the
        # arch-support matrix in the module docstring
        return t.cfg.arch_type in ("dense", "vlm", "moe", "ssm") \
            and not getattr(t.model, "kv_quant", False)

    def _prefill_capable(self, t: Tenant) -> bool:
        # declared prefill covers pure-dense tenants (a vlm prompt needs
        # the patch-embed projector; it keeps the analytic charge)
        return self.declared_prefill and t.cfg.arch_type == "dense" \
            and self._jit_capable(t)

    def _declare_prefill(self, t: Tenant, req: ServeRequest, rng: jax.Array,
                         stream_id: int, now: float
                         ) -> Optional[KernelProgram]:
        """Compile+bind ``req``'s prompt pass as a prefill KernelProgram.

        Returns None when the tenant has no free decode slot (the caller
        keeps the request waiting). The slot is RESERVED here — legal
        because the tenant admits nothing else and builds no decode program
        while this program is inflight — but its token/cache state lands at
        the completion event (``_on_prefill_complete``), not now: the
        device hasn't executed anything yet on the virtual clock.

        The program's deadline discounts the decode steps still to come
        (mirroring ``_build_program``) so a long prompt inherits its
        request's end-to-end urgency for EDF anchoring and the stagger
        budget."""
        needs_slot = req.max_new_tokens > 1
        slots = [i for i, r in enumerate(t.slot_req) if r is None]
        if needs_slot and not slots:
            return None
        s = req.prompt_len
        assert s <= t.cache_len, (s, t.cache_len)
        bucket = prefill_bucket(s)
        prompt = self.make_prompt(t, req, rng)
        padded = jnp.pad(prompt, ((0, 0), (0, bucket - s)))
        template = self.jit.plan_cache.get_or_build(
            prefill_program_cache_key(t.model, t.params, bucket, t.cache,
                                      stacked=self.stacked_layers),
            lambda: build_dense_prefill_template(
                t.model, t.params, bucket, stacked=self.stacked_layers),
            guard=(t.model, t.params),
            group=("tenant-prefill", t.name, bucket))
        final = req.arrival_t + req.slo_s
        n_active = len(t.active_slots()) + (1 if needs_slot else 0)
        step_t = self._ops_time(t.cfg, max(n_active, 1))
        deadline = final - max(req.max_new_tokens - 1, 0) * step_t
        if deadline <= now:
            deadline = final
        slot = slots[0] if needs_slot else None
        prog = template.bind(
            stream_id=stream_id, tokens=padded, cache=t.cache,
            arrival_t=now, deadline_t=deadline,
            req_deadlines=((req.req_id, final),),
            # the prefill epilogue writes exactly its reserved slot's rows
            kv_writes=(("kv", t.name, slot),) if slot is not None else (),
            env_extra={"real_len": s, "slot": slot, "req": req})
        if needs_slot:
            t.slot_req[slot] = req
            t.slot_remaining[slot] = req.max_new_tokens - 1
        return prog

    def _on_prefill_complete(self, t: Tenant, prog: KernelProgram,
                             now: float) -> Tuple[float, int]:
        """Land a completed prefill: first token, KV slot state, traffic
        charge. Returns (now, requests retired here)."""
        req: ServeRequest = prog.env["req"]
        tok = jnp.argmax(prog.env["logits"][0]).astype(jnp.int32)
        req.tokens_out = [int(tok)]
        now += self._prefill_attn_time(t.cfg, prog.env["real_len"])
        self._emit_token(req, int(tok), now)
        slot = prog.env["slot"]
        if slot is None:
            req.finish_t = now     # single token: done at prefill, no slot
            return now, 1
        t.cache = prog.env["cache"]
        t.slot_tok = t.slot_tok.at[slot, 0].set(tok)
        return now, 0

    def _build_program(self, t: Tenant, stream_id: int, now: float
                       ) -> KernelProgram:
        """Bind the tenant's next decode step, carrying the tightest
        *this-step* deadline of its batch into the program.

        Steady-state hot path: the compiled ``ProgramTemplate`` (stage
        list + glue closures + weight keys) comes from the JIT's persistent
        plan cache keyed by (model identity, batch m, dtype, cache
        geometry) and identity-guarded on ``(t.model, t.params)`` — only the per-step
        env (tokens, KV cache refs, deadlines) is rebuilt per tick, so the
        cache misses only on the first step, a batch-size change, or a
        weight hot-swap.

        A request's final deadline is discounted by the modeled time of its
        decode steps still to come AFTER this one, so the scheduler's slack
        (and therefore its WAIT budget) reflects whole-request progress,
        not just the current step's GEMM suffix — otherwise a request with
        zero end-to-end slack would look staggerable at every step.

        Already-missed requests are ignored while a healthy batchmate
        exists — one hopeless straggler must not demote the whole tenant's
        programs from EDF anchoring and cascade misses onto requests that
        still have slack. Only when every batched request has missed does
        the program carry the raw (past) final deadline; that value is
        step-invariant, which the scheduler's per-(stream, deadline)
        eviction dedup relies on."""
        reqs = [(t.slot_req[s], t.slot_remaining[s])
                for s in t.active_slots()]
        # one full decode step (GEMMs + KV streaming; _ops_time includes
        # _attn_time already) at the ACTIVE batch size — charging max_batch
        # over-discounted partially-filled tenants' remaining-step
        # deadlines, artificially shrinking their WAIT slack
        step_t = self._ops_time(t.cfg, max(len(reqs), 1))
        finals = [r.arrival_t + r.slo_s for r, _ in reqs]
        step_deadlines = [f - max(rem - 1, 0) * step_t
                          for f, (_, rem) in zip(finals, reqs)]
        future = [d for d in step_deadlines if d > now]
        deadline = min(future) if future else \
            min(finals) if finals else math.inf
        batch = int(t.slot_tok.shape[0])
        arch = t.cfg.arch_type
        stacked = self.stacked_layers
        if arch == "moe":
            key = moe_program_cache_key(t.model, t.params, batch, t.cache,
                                        stacked=stacked)
            build = lambda: build_moe_decode_template(  # noqa: E731
                t.model, t.params, batch, stacked=stacked)
        elif arch == "ssm":
            key = ssm_program_cache_key(t.model, t.params, batch, t.cache,
                                        stacked=stacked)
            build = lambda: build_ssm_decode_template(  # noqa: E731
                t.model, t.params, batch, stacked=stacked)
        else:
            key = dense_program_cache_key(t.model, t.params, batch, t.cache,
                                          stacked=stacked)
            build = lambda: build_dense_decode_template(  # noqa: E731
                t.model, t.params, batch, stacked=stacked)
        template = self.jit.plan_cache.get_or_build(
            key, build, guard=(t.model, t.params), group=("tenant", t.name))
        return template.bind(
            stream_id=stream_id, tokens=t.slot_tok, cache=t.cache,
            arrival_t=now, deadline_t=deadline,
            # a decode step appends one position to every batch row of the
            # tenant's slotted cache (idle rows advance too)
            kv_writes=tuple(("kv", t.name, s) for s in range(batch)),
            req_deadlines=tuple((r.req_id, f)
                                for (r, _), f in zip(reqs, finals)))

    def _open_loop(self, rng: jax.Array, *, oracle: bool = True,
                   next_hint: Optional[Any] = None) -> _LoopState:
        # each epoch is a fresh virtual-clock epoch: arrival history from a
        # previous trace describes a different workload (and would poison
        # observe(), whose last-arrival times now sit past every new t)
        self._arrival_pred.reset()
        n_dev = len(self.devices)
        # one JitSession PER DEVICE — each owns its scheduler, coalescer,
        # virtual free instant and EDF anchor set — all sharing one
        # VLIWJit's plan/block/weight caches (device-id-keyed) and ONE
        # ScheduleTrace, so the certifier sees the whole mesh. Device 0
        # reuses the jit's own coalescer (exact single-device behavior).
        trace = ScheduleTrace() if self.certify else None
        sessions = [self.jit.session(
            device=d, cost=None if d == 0 else self.devices.cost(d),
            trace=trace) for d in range(n_dev)]
        stream_ids = {name: i for i, name in enumerate(self.tenants)}
        return _LoopState(
            rng=rng, sessions=sessions, trace=trace,
            cert=ScheduleCertifier() if trace is not None else None,
            stream_ids=stream_ids,
            id2name={i: name for name, i in stream_ids.items()},
            tenant_dev={n: p.device
                        for n, p in self.placement.assignments.items()},
            queues=[[] for _ in range(n_dev)], pis=[0] * n_dev,
            waiting=[[] for _ in range(n_dev)], inflight={},
            now=[0.0] * n_dev, busy=[0.0] * n_dev,
            committed=[0.0] * n_dev, oracle=oracle, next_hint=next_hint)

    def _dev_of(self, st: _LoopState, name: str) -> int:
        # placement binds ONCE, at the tenant's first admission; an
        # expert-parallel MoE tenant spanning the mesh registers its
        # span with its home session, which prices the all-to-all
        # into every expert GEMM's slack and plan estimate
        d = st.tenant_dev.get(name)
        if d is None:
            t = self.tenants[name]
            pl = self.placement.place(name, t.cfg, batch=t.max_batch)
            d = st.tenant_dev[name] = pl.device
            if self.slot_devices is not None:
                self._commit(t, d)
            if pl.expert_span > 1:
                st.sessions[d].set_stream_span(st.stream_ids[name],
                                               pl.expert_span)
        return d

    def _commit(self, t: Tenant, d: int) -> None:
        """Commit tenant ``t``'s params, KV cache and token slots to mesh
        slot ``d``'s JAX device (placement time): every array derived from
        them afterwards — packed weights, activations, logits — lives and
        computes there."""
        dev = self.slot_devices[d]
        key = (id(t.params), d)
        if key not in self._committed:
            self._committed[key] = (t.params, jax.device_put(t.params, dev))
        t.params = self._committed[key][1]
        t.cache = jax.device_put(t.cache, dev)
        t.slot_tok = jax.device_put(t.slot_tok, dev)

    def _route(self, st: _LoopState, req: ServeRequest) -> int:
        """Append ``req`` to its home device's admission queue."""
        d = self._dev_of(st, req.tenant)
        st.queues[d].append(req)
        st.total += 1
        return d

    def _door_decision(self, st: _LoopState, req: ServeRequest, d: int
                       ) -> bool:
        """Consult the admission controller for one due request (fires
        exactly once, when the request first becomes due on its device's
        clock). Returns False when the request was shed at the door — it
        never occupies a slot and stays out of the schedule trace, like a
        refused admission, but counts as an SLO miss in the report."""
        t = self.tenants[req.tenant]
        cost_s = self._request_cost_s(t, req)
        backlog = max(0.0, st.committed[d] - st.now[d])
        dec = self.admission.decide(req, st.now[d], backlog, cost_s,
                                    self._arrival_pred.gap(req.tenant))
        if dec.action == "shed":
            req.shed = True
            st.n_done += 1
            return False
        if dec.action == "degrade":
            req.degraded_from = req.tier
            req.tier = dec.tier
            req.slo_s = dec.slo_s
        # commit the modeled cost to the device's completion horizon —
        # the backlog meter later decisions are judged against
        st.committed[d] = max(st.committed[d], st.now[d]) + cost_s
        return True

    def _device_pass(self, st: _LoopState, d: int) -> bool:
        """One pass over device ``d``'s timeline: drain due arrivals
        (through the admission controller when the front door is on),
        admit waiting requests, keep JIT-capable tenants' programs in the
        pool, take one scheduler decision, land completions, and step
        non-JIT tenants. Returns True if anything progressed."""
        progressed = False
        session, q, wq = st.sessions[d], st.queues[d], st.waiting[d]
        trace, cert, rng = st.trace, st.cert, st.rng
        now, busy = st.now, st.busy
        # 1. live admission on device d's timeline. Dense tenants
        #    DECLARE the prompt pass as a prefill KernelProgram —
        #    its GEMMs join the device's live op pool and coalesce
        #    with decode (and other tenants' prefill) traffic; the
        #    tenant's decode joins only after its completion event.
        #    Non-dense tenants keep the analytic serialized charge.
        #    A tenant with a program inflight (or full slots)
        #    admits at its next step boundary, but other tenants'
        #    due requests are admitted past it, not blocked.
        while st.pis[d] < len(q) and q[st.pis[d]].arrival_t <= now[d]:
            req = q[st.pis[d]]
            st.pis[d] += 1
            if self.predict_arrivals or self.admission is not None:
                self._arrival_pred.observe(req.tenant, req.arrival_t)
            if self.admission is not None \
                    and not self._door_decision(st, req, d):
                progressed = True   # shed at the door: resolved right here
                continue
            wq.append(req)
        still: List[ServeRequest] = []
        for req in wq:
            t = self.tenants[req.tenant]
            if req.tenant in st.inflight:
                still.append(req)
                continue
            if self._prefill_capable(t) \
                    and req.prompt_len >= self.prefill_declare_min:
                prog = self._declare_prefill(
                    t, req, rng, st.stream_ids[req.tenant], now[d])
                if prog is None:
                    still.append(req)  # slots full; retry later
                    continue
                st.inflight[req.tenant] = prog
                session.admit(prog)
                if trace is not None:
                    trace.req_admits.append((req.req_id, now[d]))
                    trace.req_devices[req.req_id] = d
                progressed = True
                continue
            dt = self._admit(t, req, rng, now[d])
            if dt == 0.0 and req.tokens_out is None:
                still.append(req)  # tenant slots full; retry later
                continue
            now[d] += dt
            busy[d] += dt
            if trace is not None:
                trace.req_admits.append((req.req_id, now[d]))
                trace.req_devices[req.req_id] = d
            if not math.isnan(req.finish_t):
                st.n_done += 1     # retired at admission (single token)
                if trace is not None:
                    trace.req_retires.append((req.req_id, now[d]))
                    trace.retire_devices[req.req_id] = d
            progressed = True
        st.waiting[d] = still
        if self.predict_arrivals:
            hint = self._arrival_pred.predict(now[d])
        else:
            # replay: oracle lookahead into the routed trace; the daemon
            # additionally consults the door's scheduled submissions
            hint = q[st.pis[d]].arrival_t if st.pis[d] < len(q) \
                else math.inf
            if not st.oracle and st.next_hint is not None:
                nxt = st.next_hint(now[d])
                if nxt is not None:
                    hint = min(hint, nxt)
        session.set_next_arrival(hint)

        # 2. every JIT-capable tenant homed here with live requests
        #    keeps a program in this device's pool — admitted
        #    between dispatches, not per round
        for name, t in self.tenants.items():
            if st.tenant_dev.get(name) != d:
                continue
            if self._jit_capable(t) and name not in st.inflight \
                    and t.active_slots():
                prog = self._build_program(t, st.stream_ids[name],
                                           now[d])
                if t.cfg.arch_type in ("moe", "ssm"):
                    session.stats.nondense_programs += 1
                st.inflight[name] = prog
                session.admit(prog)
                progressed = True

        # 3. one scheduler decision on device d's virtual clock
        ev = session.tick(now[d])
        if cert is not None:
            # certify this tick's new dispatches at the tick they
            # happened — a HazardViolation raises right here, with
            # the offending group as the last trace record. The
            # trace is shared, so records from every device flow
            # through the same certifier (placement checks included)
            for dr in trace.dispatches[st.certified:]:
                cert.observe(dr)
            st.certified = len(trace.dispatches)
        progressed |= ev.kind != "idle"
        now[d] = max(now[d], ev.t)
        for prog in ev.completed:
            t = self.tenants[st.id2name[prog.stream_id]]
            del st.inflight[st.id2name[prog.stream_id]]
            if prog.kind == "prefill":
                t0 = now[d]
                now[d], done = self._on_prefill_complete(
                    t, prog, now[d])
                busy[d] += now[d] - t0
                st.n_done += done
                if done and trace is not None:
                    trace.req_retires.append(
                        (prog.env["req"].req_id, now[d]))
                    trace.retire_devices[prog.env["req"].req_id] = d
                continue
            t.cache = prog.env["cache"]
            # KV streaming charged at the ACTIVE batch size: idle
            # slots have no cache rows to read, so charging
            # max_batch over-billed partially-filled tenants
            attn = self._attn_time(t.cfg,
                                   max(len(t.active_slots()), 1))
            self._consume(t, prog.env["logits"][:, None, :],
                          now[d] + attn)
            now[d] += attn
            busy[d] += attn
            retired = self._retire(t, now[d])
            st.n_done += len(retired)
            if trace is not None:
                trace.req_retires.extend(
                    (r.req_id, now[d]) for r in retired)
                for r in retired:
                    trace.retire_devices[r.req_id] = d

        # 4. non-JIT tenants homed here interleave monolithic
        #    batched steps on this device's clock
        for name, t in self.tenants.items():
            if st.tenant_dev.get(name) != d:
                continue
            if not self._jit_capable(t) and t.active_slots():
                dt = self._tenant_batched_step(t, now[d])
                now[d] += dt
                busy[d] += dt
                retired = self._retire(t, now[d])
                st.n_done += len(retired)
                if trace is not None:
                    trace.req_retires.extend(
                        (r.req_id, now[d]) for r in retired)
                    for r in retired:
                        trace.retire_devices[r.req_id] = d
                progressed = True
        return progressed

    def _close_loop(self, st: _LoopState,
                    requests: Sequence[ServeRequest]) -> None:
        trace, cert, sessions = st.trace, st.cert, st.sessions
        if trace is not None:
            # close the request lifecycle, then balance it: SLO-demoted
            # requests from every device's scheduler, plus admitted
            # requests that never finished (refused-admission and
            # door-shed requests were never admitted, so they stay out
            # of the trace entirely)
            trace.evicted = set()
            for s in sessions:
                trace.evicted |= set(s.sched.demoted_requests())
            by_id = {r.req_id: r for r in requests}
            admitted = {rid for rid, _ in trace.req_admits}
            trace.unfinished = {rid for rid in admitted
                                if math.isnan(by_id[rid].finish_t)}
            cert.checks += 1
            cert.violations.extend(check_conservation(trace))
            sessions[0].stats.hazard_checks += cert.checks
            sessions[0].stats.hazard_violations += len(cert.violations)
        self.last_trace = trace
        # per-device dispatch time lives in each session's stats; analytic
        # charges (prefill/attention/batched steps) were accumulated above
        self._last_device_time = list(st.now)
        self._last_device_busy = [
            st.busy[d] + sessions[d].stats.modeled_time_s
            for d in range(len(sessions))]
        for s in sessions:
            self.jit_stats.merge(s.stats)

    def _run_event_loop(self, pending: List[ServeRequest], rng: jax.Array
                        ) -> float:
        st = self._open_loop(rng)
        # route the arrival-sorted trace onto per-device admission queues;
        # _dev_of fires in arrival order of each tenant's FIRST request —
        # the same binding a lazy per-admission call would make, but the
        # queues keep one slow device's backlog from head-of-line-blocking
        # another device's due requests
        for req in pending:
            self._route(st, req)
        n_dev = len(self.devices)
        while True:
            progressed = False
            for d in range(n_dev):
                progressed |= self._device_pass(st, d)
            if st.n_done >= st.total \
                    and not any(s.live for s in st.sessions) \
                    and all(st.pis[d] >= len(st.queues[d])
                            for d in range(n_dev)) \
                    and not any(st.waiting):
                break
            if not progressed:
                advanced = False
                for d in range(n_dev):
                    # idle device: its clock jumps to its next arrival
                    if st.pis[d] < len(st.queues[d]) \
                            and st.now[d] < st.queues[d][st.pis[d]].arrival_t:
                        st.now[d] = st.queues[d][st.pis[d]].arrival_t
                        advanced = True
                if advanced:
                    continue
                if not any(st.waiting):
                    break
                # stall guard: every queue is exhausted, every waiting
                # request was refused admission, and there is nothing
                # inflight or decoding anywhere whose completion could
                # change that — another iteration would see the identical
                # state, so the loop must terminate (the requests stay
                # unfinished and surface in ServeReport.unfinished)
                if not any(s.live for s in st.sessions) \
                        and not st.inflight \
                        and not any(t.active_slots()
                                    for t in self.tenants.values()):
                    break
        self._close_loop(st, pending)
        return max(st.now)

    # ------------------------------------------------------------------
    # the front door (daemon mode)
    # ------------------------------------------------------------------
    def _live_stats(self, st: _LoopState, served: List[ServeRequest],
                    t: float) -> Dict[str, Any]:
        return {
            "t": t,
            "submitted": len(served),
            "finished": sum(1 for r in served
                            if not math.isnan(r.finish_t)),
            "shed": sum(1 for r in served if r.shed),
            "inflight": len(st.inflight),
            "waiting": sum(len(w) for w in st.waiting),
            "device_time_s": list(st.now),
        }

    def serve_forever(self, door: FrontDoor, *,
                      clock: Optional[Any] = None,
                      rng: Optional[jax.Array] = None,
                      idle_poll_s: float = 0.005,
                      on_stats: Optional[Any] = None,
                      stats_interval_s: float = 1.0) -> ServeReport:
        """Serve continuously from ``door`` until it closes (daemon mode).

        The same per-device event-loop machinery as ``run``, driven by a
        clock instead of a finite trace: requests stream in through the
        thread-safe ``FrontDoor`` (arrival-stamped on the clock), the
        admission controller (when configured) admits / degrades / sheds
        each one as it becomes due, tokens stream out per request the
        moment they retire (``FrontDoor.deliver`` -> per-request
        ``Ticket``), and the engine IDLES while the door is open and
        empty — the replay stall guard becomes an idle-wait. Closing the
        door flushes all in-flight work, then the loop terminates and
        returns the epoch's ``ServeReport`` (shed requests included, as
        SLO misses).

        ``clock`` is a ``MonotonicClock`` by default — the real wall
        clock; per-device modeled timelines are floored at real elapsed
        time every iteration so arrivals, deadlines and modeled charges
        share one axis. Pass a follower ``VirtualClock`` for
        deterministic tests/benches: it only tracks the modeled
        timelines, so a door pre-loaded with scheduled submissions
        replays with exactly the per-device clock semantics of ``run``.
        ``on_stats`` (optional) is called at most every
        ``stats_interval_s`` clock seconds with a live-stats dict — the
        daemon's heartbeat."""
        assert self.mode == "vliw", \
            "daemon serving is a vliw-engine feature (baseline modes " \
            "define closed-trace round semantics)"
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        clock = clock if clock is not None else MonotonicClock()
        wall0 = _time.perf_counter()
        st = self._open_loop(rng, oracle=False,
                             next_hint=door.next_arrival)
        served: List[ServeRequest] = []
        seen_ids: Dict[int, int] = {}
        n_dev = len(self.devices)
        prev_sink = self.token_sink
        if prev_sink is None:
            self.token_sink = door.deliver
        last_stats = clock.now()
        try:
            while True:
                now_r = clock.now()
                if clock.authoritative:
                    # real clock: a device cannot serve in the past — its
                    # modeled timeline is floored at real elapsed time
                    for d in range(n_dev):
                        st.now[d] = max(st.now[d], now_r)
                for req in door.poll(now_r):
                    if req.req_id in seen_ids:
                        raise ValueError(
                            f"duplicate req_id {req.req_id} through the "
                            f"door — request ids key prompt synthesis "
                            f"and retirement accounting")
                    seen_ids[req.req_id] = 1
                    served.append(req)
                    self._route(st, req)
                progressed = False
                for d in range(n_dev):
                    progressed |= self._device_pass(st, d)
                # a follower clock tracks the modeled timelines; the real
                # clock ignores this (time advances itself)
                clock.advance_to(max(st.now))
                if on_stats is not None \
                        and clock.now() - last_stats >= stats_interval_s:
                    last_stats = clock.now()
                    on_stats(self._live_stats(st, served, last_stats))
                if progressed:
                    continue
                # idle devices jump to their next released-but-not-yet-due
                # arrival (the replay idle-jump, on routed requests)
                advanced = False
                for d in range(n_dev):
                    if st.pis[d] < len(st.queues[d]) \
                            and st.now[d] < st.queues[d][st.pis[d]].arrival_t:
                        st.now[d] = st.queues[d][st.pis[d]].arrival_t
                        advanced = True
                if advanced:
                    continue
                # nothing live anywhere. With the door closed and drained
                # the flush is complete — terminate (waiting requests that
                # can never admit surface in ServeReport.unfinished, the
                # replay stall guard's behavior). With the door OPEN,
                # idle-wait instead of terminating: a new submission or
                # the closing of the door are the only remaining sources
                # of progress.
                if not any(s.live for s in st.sessions) \
                        and not st.inflight \
                        and not any(t.active_slots()
                                    for t in self.tenants.values()):
                    if door.finished(now_r) \
                            and all(st.pis[d] >= len(st.queues[d])
                                    for d in range(n_dev)):
                        break
                    targets = []
                    nxt = door.next_arrival(now_r)
                    if nxt is not None:
                        targets.append(max(nxt, now_r))
                    if door.close_at is not None \
                            and door.close_at > now_r:
                        targets.append(door.close_at)
                    clock.sleep_until(min(targets) if targets
                                      else now_r + idle_poll_s)
        finally:
            self.token_sink = prev_sink
        self._close_loop(st, served)
        makespan = max(st.now) if st.now else 0.0
        wall = _time.perf_counter() - wall0
        return ServeReport("vliw", served, makespan, wall,
                           jit=self.jit_stats,
                           device_time_s=self._last_device_time,
                           device_busy_s=self._last_device_busy)

    # ------------------------------------------------------------------
    # round loop (baseline modes: rounds ARE their semantics)
    # ------------------------------------------------------------------
    def _run_rounds(self, pending: List[ServeRequest], rng: jax.Array
                    ) -> float:
        now, pi, n_done = 0.0, 0, 0
        while n_done < len(pending):
            progressed = False
            while pi < len(pending) and pending[pi].arrival_t <= now:
                req = pending[pi]
                t = self.tenants[req.tenant]
                dt = self._admit(t, req, rng, now)
                if dt == 0.0 and req.tokens_out is None:
                    break  # tenant full; retry after this round
                now += dt
                if not math.isnan(req.finish_t):
                    n_done += 1        # retired at admission (single token)
                pi += 1
                progressed = True
            dt = self._decode_round(now)
            if dt == 0.0 and not progressed:
                if pi < len(pending):
                    now = max(now, pending[pi].arrival_t)
                    continue
                break
            now += dt
            for t in self.tenants.values():
                n_done += len(self._retire(t, now))
        return now

    # ------------------------------------------------------------------
    def run(self, trace: Sequence[ServeRequest],
            rng: Optional[jax.Array] = None) -> ServeReport:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        # request identity keys everything downstream — prompt synthesis
        # (make_prompt folds req_id into the rng), the scheduler's
        # per-request eviction dedup, and the certifier's conservation
        # check — so a trace with colliding ids must be rejected up front
        # instead of silently double-counting one identity
        ids: Dict[int, int] = {}
        for r in trace:
            ids[r.req_id] = ids.get(r.req_id, 0) + 1
        dupes = sorted(i for i, n in ids.items() if n > 1)
        if dupes:
            raise ValueError(
                f"duplicate req_id(s) in trace: {dupes} — request ids must "
                f"be unique per run (they key prompt synthesis, eviction "
                f"dedup and retirement accounting)")
        # run() serves private COPIES of the requests: results (tokens_out,
        # finish_t, shed, tier degradation) land on the copies in the
        # returned report, and the caller's trace objects are NEVER
        # mutated — a trace can be replayed across engines and modes
        # without the defensive deepcopy every call site used to need
        requests = [dataclasses.replace(
            r, finish_t=float("nan"), tokens_out=None, shed=False,
            degraded_from=None) for r in trace]
        pending = sorted(requests, key=lambda r: r.arrival_t)
        wall0 = _time.perf_counter()
        if self.mode == "vliw":
            makespan = self._run_event_loop(pending, rng)
            dev_t, dev_b = self._last_device_time, self._last_device_busy
        else:
            makespan = self._run_rounds(pending, rng)
            dev_t = dev_b = None
        wall = _time.perf_counter() - wall0
        return ServeReport(self.mode, requests, makespan, wall,
                           jit=self.jit_stats if self.mode == "vliw" else None,
                           device_time_s=dev_t, device_busy_s=dev_b)
