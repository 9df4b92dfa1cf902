"""Jitted superkernel dispatch fast path — the steady-state execution layer.

The paper's thesis is that late-binding JIT dispatch recovers the spatial-
coalescing opportunity — but late binding only wins if the *dispatch* itself
stays off the critical path. The eager path (kernels/ops.py
``execute_superkernel``) pays an early-binding tax on every tick:

  * it re-pads and ``jnp.stack``s the **full weight matrices** of the group
    on every dispatch — O(model-weights) host traffic per tick, the
    dominant per-invocation overhead of fine-grained GPU multiplexing
    (D-STACK; the multi-tenant GPU inference surveys);
  * it runs pack → kernel → unpack as separate eager ops with exact
    max-(K, N) envelopes, so any group-shape churn retraces the
    ``coalesced_gemm`` ``pallas_call``.

``SuperkernelExecutor`` (owned by ``VLIWJit``, surviving sessions like the
plan caches) retires both:

  * **persistent packed-weight cache** — the padded/stacked weight operand
    of a group is cached in a ``PlanCache`` keyed by the group's ordered
    weight-key tuple + bucketed envelope, identity-guarded on the weight
    arrays themselves (the same discipline as the PR-2 program-template
    guard): a weight hot-swap produces new arrays, trips the guard, and is
    rebuilt — never served stale. Steady-state ticks re-send ZERO weight
    bytes (``DispatchStats.bytes_not_copied`` counts the traffic avoided).
  * **shape-bucketed superkernels** — every envelope extent is bucketed:
    per-problem rows to ``bm`` multiples with the total m-tile count a
    power of two, K and N to 128-floored powers of two
    (``kernels/ops.envelope_bucket``), and the problem/stacked-weight
    count G to an UNfloored power of two (``_pow2`` — flooring G at 128
    would stack 128 full weight copies per group). The jitted
    pack+kernel+unpack therefore hits JAX's compile cache instead of
    retracing per unique group shape.
  * **retrace-free steady state** — the whole dispatch (activation pack →
    ``coalesced_gemm``/``coalesced_gemv`` → per-problem unpack) is one
    ``jax.jit`` with a static group signature, including the
    ``shared_operand`` fast path and the ``coalesced_matvec`` regime. A
    module-level trace counter (incremented when a traced body actually
    runs) surfaces retraces in ``DispatchStats.retraces``; on a stable
    trace it stops moving after warmup (tests/test_dispatch.py).

Correctness contract: bucket padding is zeros, and adding ``+0.0`` terms to
an fp32 accumulator is exact — so whenever the bucketed K keeps the same
``bk`` contraction split as the eager exact envelope (all power-of-two
weight dims, e.g. every smoke config), the fast path is BIT-identical to
the eager reference (asserted in tests/test_dispatch.py, and end-to-end as
greedy-token identity in benchmarks/dispatch_bench.py). When bucketing
changes the contraction split (a non-power-of-two K like 300: eager
384/bk=384 vs bucketed 512/bk=512), fp32 reduction regrouping shifts the
last ulps — numerically equivalent (see the ragged-dims test's 1e-4
tolerance), but a greedy argmax at an exact logit tie could differ, so
token identity for such models is an empirical property, not a guarantee.

Memory note: cached packed weights are full padded copies — on a real
deployment this is the point (the packed operand lives in HBM across ticks
instead of being re-staged), but the footprint must be bounded in BYTES,
not entries (one entry can be hundreds of MB at real model sizes):
``VLIWJit(weight_budget_bytes=...)`` sets the LRU byte budget (default:
``device_weight_budget``, half the memory of the devices served),
``weight_capacity`` the entry count, and ``capacity=0`` disables the cache
entirely (the repack-per-tick baseline, still jitted).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.clustering import matvec_weight_key
from repro.core.costmodel import BlockConfig
from repro.core.kernelspec import KernelOp
from repro.core.schedtrace import OperandIdentityHazard
from repro.core.plancache import PlanCache
from repro.kernels.backend import interpret_default
from repro.kernels.coalesced_gemm import coalesced_gemm
from repro.kernels.coalesced_gemv import coalesced_gemv
from repro.kernels.ops import (_round_up, check_vmem, coalesced_matvec,
                               envelope_bucket, execute_superkernel)


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DispatchStats:
    """Counters for the jitted dispatch fast path. Supports ``+``/``-`` so
    per-session deltas fold through ``JitStats.merge`` like every other
    counter (the executor outlives sessions; each ``JitSession`` snapshots
    the executor's stats and reports only its own delta)."""

    dispatches: int = 0
    weight_hits: int = 0           # packed-weight operand served from cache
    weight_misses: int = 0         # packed/stacked + staged this dispatch
    weight_invalidations: int = 0  # identity-guard trips (weight hot-swap)
    retraces: int = 0              # jitted dispatch bodies actually traced
    bytes_not_copied: int = 0      # packed-weight bytes NOT re-staged (hits)

    @property
    def weight_hit_rate(self) -> float:
        n = self.weight_hits + self.weight_misses
        return self.weight_hits / n if n else 0.0

    def copy(self) -> "DispatchStats":
        return dataclasses.replace(self)

    def _combine(self, other: "DispatchStats", sign: int) -> "DispatchStats":
        return DispatchStats(
            *(getattr(self, f.name) + sign * getattr(other, f.name)
              for f in dataclasses.fields(self)))

    def __add__(self, other: "DispatchStats") -> "DispatchStats":
        return self._combine(other, +1)

    def __sub__(self, other: "DispatchStats") -> "DispatchStats":
        return self._combine(other, -1)


# ---------------------------------------------------------------------------
# the jitted dispatch bodies (module-level: one process-wide compile cache)
# ---------------------------------------------------------------------------

_TRACE_COUNT = 0


def trace_count() -> int:
    """Process-wide count of jitted-dispatch traces (compiles). The body of
    a ``jax.jit`` function runs exactly once per (shape, static-arg) cache
    entry, so the delta across a call window counts retraces."""
    return _TRACE_COUNT


def _mark_trace() -> None:
    global _TRACE_COUNT
    _TRACE_COUNT += 1


@functools.partial(jax.jit, static_argnames=("n_real", "m_tiles", "bm", "bn",
                                             "bk", "interpret"))
def _dispatch_grouped(activations, b_stacked, group_ids, *, n_real, m_tiles,
                      bm, bn, bk, interpret):
    """pack → grouped GEMM → unpack, one compiled executable.

    activations: tuple of [m_i, k_i] (k_i ≤ K); b_stacked: [G_pad, K, N];
    group_ids: [m_tiles] int32 (pad tiles point at group 0 — their zero
    activation rows produce zero output rows, sliced off below)."""
    _mark_trace()
    K = b_stacked.shape[1]
    parts = [jnp.pad(a, ((0, _round_up(a.shape[0], bm) - a.shape[0]),
                         (0, K - a.shape[1]))) for a in activations]
    a_packed = jnp.concatenate(parts, axis=0)
    a_packed = jnp.pad(a_packed,
                       ((0, m_tiles * bm - a_packed.shape[0]), (0, 0)))
    out = coalesced_gemm(a_packed, b_stacked, group_ids, bm=bm, bn=bn, bk=bk,
                         interpret=interpret)
    outs, s = [], 0
    for a, n in zip(activations, n_real):
        outs.append(out[s:s + a.shape[0], :n])
        s += _round_up(a.shape[0], bm)
    return tuple(outs)


@functools.partial(jax.jit, static_argnames=("n_real", "m_tiles", "bm", "bn",
                                             "bk", "interpret"))
def _dispatch_shared(activations, b_padded, *, n_real, m_tiles, bm, bn, bk,
                     interpret):
    """Shared-operand fast path: all problems use ONE weight matrix (the
    RNN/decode lockstep case) — activations concatenate into a single GEMM
    so the weight panel streams through VMEM once."""
    _mark_trace()
    K = b_padded.shape[0]
    x = jnp.concatenate(activations, axis=0)
    xp = jnp.pad(x, ((0, m_tiles * bm - x.shape[0]), (0, K - x.shape[1])))
    out = coalesced_gemm(xp, b_padded[None],
                         jnp.zeros((m_tiles,), jnp.int32),
                         bm=bm, bn=bn, bk=bk, interpret=interpret)
    outs, s = [], 0
    for a in activations:
        outs.append(out[s:s + a.shape[0], :n_real])
        s += a.shape[0]
    return tuple(outs)


@functools.partial(jax.jit, static_argnames=("n_real", "bn", "bk",
                                             "interpret"))
def _dispatch_matvec(xs, w_stacked, *, n_real, bn, bk, interpret):
    """Distinct-weights matvec regime: G_pad vectors against G_pad stacked
    weight panels via ``coalesced_gemv``. The CALLER owns G-bucket padding
    (``matvec`` extends ``xs``/``n_real`` with zero vectors to match
    ``w_stacked``'s leading dim) so exactly one layer decides the bucket."""
    _mark_trace()
    assert len(xs) == w_stacked.shape[0], (len(xs), w_stacked.shape)
    K = w_stacked.shape[1]
    xp = jnp.stack([jnp.pad(x, (0, K - x.shape[0])) for x in xs])
    out = coalesced_gemv(xp, w_stacked, bn=bn, bk=bk, interpret=interpret)
    return tuple(out[i, :n] for i, n in enumerate(n_real))


def device_weight_budget(n_devices: int = 1) -> int:
    """Default byte budget of the packed-weight cache serving the first
    ``n_devices`` JAX devices: half of each device's memory
    (``memory_stats()["bytes_limit"]``), leaving the other half to the
    params, KV caches and activations. Packed operands are padded copies of
    the weights, so at published widths the cache must be sized like the
    model itself — a fixed cap smaller than one tenant's operands would
    bypass or evict them and repack gigabytes every tick. A device that
    reports no memory (the CPU backend) counts 1 GiB."""
    total = 0
    for dev in jax.devices()[:max(1, n_devices)]:
        stats = dev.memory_stats()
        total += stats["bytes_limit"] // 2 if stats else 1 << 30
    # a modeled mesh wider than the attached devices counts 1 GiB a slot
    return total + (1 << 30) * max(0, n_devices - len(jax.devices()))


def _pow2(n: int) -> int:
    """Smallest power of two ≥ n (n ≥ 1)."""
    return 1 << max(n - 1, 0).bit_length()


def _tile_bucket(rows: Sequence[int], bm: int) -> int:
    """Power-of-two m-tile count covering per-problem rows padded to ``bm``
    multiples (``rows`` already concatenated tightly for the shared path is
    handled by passing the single total)."""
    return _pow2(sum(_round_up(m, bm) // bm for m in rows))


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------

class SuperkernelExecutor:
    """Zero-copy, zero-retrace steady-state superkernel execution.

    Owned by ``VLIWJit`` (persistent across sessions, like the plan
    caches); ``JitSession.tick`` hands it the planned op group and gets the
    per-problem outputs back. ``enabled=False`` falls back to the eager
    reference path (``execute_superkernel``) — the ablation baseline the
    dispatch benchmark and the bit-identity tests measure against.
    """

    def __init__(self, weight_cache: Optional[PlanCache] = None, *,
                 bm: int = 8, bn: int = 128, bk: int = 512,
                 enabled: bool = True, interpret: Optional[bool] = None):
        assert bm & (bm - 1) == 0, f"bm must be a power of two, got {bm}"
        # the fallback cache is byte-budgeted too — packed-weight entries
        # are full padded copies, so an entry-count bound alone does not
        # bound memory (see the module docstring's memory note)
        self.weight_cache = weight_cache if weight_cache is not None \
            else PlanCache(256, byte_capacity=device_weight_budget())
        self.bm, self.bn, self.bk = bm, bn, bk
        self.enabled = enabled
        # compiled on a TPU, interpreted only on a CPU backend
        self.interpret = interpret_default() if interpret is None \
            else interpret
        self.stats = DispatchStats()

    # ------------------------------------------------------------------
    def _packed_weights(self, weights: Sequence[jax.Array],
                        wkeys: Sequence[Tuple], K: int, N: int, G_pad: int,
                        *, shared: bool, group=None,
                        device: int = 0) -> jax.Array:
        """The group's padded weight operand — [K, N] (shared) or
        [G_pad, K, N] (stacked) — from the persistent cache.

        Keyed by the ordered weight-key tuple + bucketed envelope and
        identity-guarded on the weight arrays themselves: a hot-swap that
        lands on the SAME key (same params object mutated in place) trips
        the guard and rebuilds, so the cache can never serve stale
        weights. A hot-swap that CHANGES the key (the serving path:
        replacing the params tree embeds a new ``id(params)`` in every
        weight key) is caught by ``group`` — a params-free identity of the
        logical dispatch slot (the ops' (stream, tag, seq) tuple) whose
        key change eagerly drops the superseded entry, instead of letting
        generations of full packed-weight copies (each pinning its old
        arrays via the guard) linger until LRU pressure. Both paths count
        in ``weight_invalidations``. On a hit, the bytes of the packed
        operand are counted as traffic NOT re-staged this tick.

        ``device`` is part of the key: per-device op pools share one
        executor (one VLIWJit-owned weight cache), and a packed operand
        modeled as resident on device 0's HBM must not satisfy a device-1
        dispatch — each device stages (and then retains) its own copy."""
        key = ("wpack", device, "shared" if shared else "stacked",
               tuple(wkeys), K, N, G_pad, str(weights[0].dtype))

        def build() -> jax.Array:
            parts = [jnp.pad(w, ((0, K - w.shape[0]), (0, N - w.shape[1])))
                     for w in weights]
            if shared:
                return parts[0]
            if G_pad > len(parts):
                pad = jnp.zeros((K, N), parts[0].dtype)
                parts.extend([pad] * (G_pad - len(parts)))
            return jnp.stack(parts, axis=0)

        inval0 = self.weight_cache.stats.invalidations
        value, hit = self.weight_cache.get_or_build_flagged(
            key, build, guard=tuple(weights), group=group)
        # accrued outside the hit/miss branch: a group-key change can drop
        # a superseded entry even on a call that then HITS (another slot
        # already rebuilt the new key), and that drop must still be counted
        self.stats.weight_invalidations += \
            self.weight_cache.stats.invalidations - inval0
        if hit:
            self.stats.weight_hits += 1
            self.stats.bytes_not_copied += int(value.nbytes)
        else:
            self.stats.weight_misses += 1
        return value

    # ------------------------------------------------------------------
    def stacked_operand(self, wkey: Tuple, k: int, n: int, layers: int,
                        weight_fn, guard: Sequence[jax.Array], *,
                        group=None, device: int = 0) -> jax.Array:
        """One LAYER-STACKED weight operand — [L, ..., K, N] padded to the
        bucketed (K, N) envelope — from the persistent cache.

        This is the stacked-template analogue of ``_packed_weights``: one
        cache entry per stacked operand per params generation (entry count
        per tenant O(#operands), not O(#operands × layers)), m-free so the
        same entry serves decode, prefill and every batch size.

        ``weight_fn`` builds the raw stacked array lazily (typically a
        [lo:hi) slice of the params tree's stacked blocks) — it only runs
        on a miss. ``guard`` must be the ORIGINAL stacked params arrays
        (stable across ticks), never per-build slices: a fresh slice every
        tick would read as a phantom hot-swap and repack the whole stack.
        A real hot-swap replaces the params tree → new ``id(params)`` in
        ``wkey`` → new cache key; ``group`` (params-free slot identity)
        eagerly drops the superseded entry, exactly like
        ``_packed_weights``."""
        K = envelope_bucket(int(k))
        N = envelope_bucket(int(n))
        # device id keyed for the same reason as _packed_weights: the
        # shared cache holds one resident stack PER DEVICE
        key = ("wstack", device, wkey, int(layers), K, N,
               str(guard[0].dtype) if guard else "")

        def build() -> jax.Array:
            w = weight_fn()
            pad = [(0, 0)] * (w.ndim - 2) + [(0, K - int(w.shape[-2])),
                                             (0, N - int(w.shape[-1]))]
            return jnp.pad(w, pad)

        inval0 = self.weight_cache.stats.invalidations
        value, hit = self.weight_cache.get_or_build_flagged(
            key, build, guard=tuple(guard), group=group)
        self.stats.weight_invalidations += \
            self.weight_cache.stats.invalidations - inval0
        if hit:
            self.stats.weight_hits += 1
            self.stats.bytes_not_copied += int(value.nbytes)
        else:
            self.stats.weight_misses += 1
        return value

    # ------------------------------------------------------------------
    def execute(self, ops: Sequence[KernelOp], *,
                shared_operand: bool = False,
                interpret: Optional[bool] = None,
                device: int = 0,
                block: Optional[BlockConfig] = None) -> List[jax.Array]:
        """Execute a planned group; returns per-problem outputs in op order.

        Each op carries its operand binding (``op.payload`` =
        (activation, weight, weight_key), attached by
        ``JitSession._push_op``). ``block`` overrides the executor's
        default (bm, bn, bk) for THIS dispatch — the live-tuned config of
        the planned group (``SuperkernelPlan.block`` when
        ``VLIWJit(live_tune=True)``). The override enters the jitted
        bodies as static args, so each DISTINCT tuned config compiles
        once (a warmup trace, like any first-seen envelope bucket) and a
        group whose signature — and therefore tuned config — is stable
        never retraces; config churn that lands back on an already-seen
        config is a pure compile-cache hit, never a spurious retrace."""
        # pack in CANONICAL op order: the scheduler sorts a group by
        # urgency, so the same set of ops can arrive in different orders
        # tick to tick — an order-sensitive key would fork duplicate
        # packed-weight entries (and orphan some from the group tag's
        # eager hot-swap drop). Outputs are restored to call order below.
        order = sorted(range(len(ops)),
                       key=lambda i: (ops[i].stream_id, ops[i].tag,
                                      ops[i].seq_index))
        problems = [ops[i].payload[:2] for i in order]
        wkeys = [ops[i].payload[2] for i in order]
        if shared_operand:
            # the shared regime loads ops[0]'s weight ONCE for the whole
            # group, so equal weight keys must mean the identical array —
            # a key aliasing two distinct arrays (e.g. a weight_fn that
            # rebuilds a transpose per template) would silently serve one
            # tenant another's weights. Fail loudly instead; the schedule
            # certifier (repro.analysis.certify) makes the same check on
            # the recorded trace.
            w0 = problems[0][1]
            bad = next((i for i, (_, w) in enumerate(problems)
                        if w is not w0), None)
            if bad is not None:
                raise OperandIdentityHazard(
                    "shared-operand dispatch over non-identical weight "
                    f"arrays: key {wkeys[0]} vs {wkeys[bad]}",
                    detail={"keys": (wkeys[0], wkeys[bad])})
        # params-free identity of this dispatch slot, so a hot-swap that
        # renames every weight key (new id(params)) still eagerly drops
        # the superseded packed-weight entry (see _packed_weights)
        group = (tuple((ops[i].stream_id, ops[i].tag, ops[i].seq_index)
                       for i in order), shared_operand, device)
        canon = self.execute_problems(problems, wkeys,
                                      shared_operand=shared_operand,
                                      interpret=interpret, group=group,
                                      device=device, block=block)
        outs: List[Optional[jax.Array]] = [None] * len(ops)
        for pos, i in enumerate(order):
            outs[i] = canon[pos]
        return outs

    def execute_problems(self, problems, wkeys, *,
                         shared_operand: bool = False,
                         interpret: Optional[bool] = None,
                         group=None, device: int = 0,
                         block: Optional[BlockConfig] = None
                         ) -> List[jax.Array]:
        interpret = self.interpret if interpret is None else interpret
        # per-dispatch tile override (live tuning); tuner candidates are
        # power-of-two, which the m-tile bucketing below relies on
        bm, bn, bk = (self.bm, self.bn, self.bk) if block is None else \
            (block.bm, block.bn, block.bk)
        assert bm & (bm - 1) == 0, f"bm must be a power of two, got {bm}"
        if not self.enabled:
            return execute_superkernel(problems, bm=bm, bn=bn, bk=bk,
                                       shared_operand=shared_operand,
                                       interpret=interpret)
        acts = tuple(a for a, _ in problems)
        ws = [w for _, w in problems]
        G = len(acts)
        self.stats.dispatches += 1
        trace0 = trace_count()
        # bucket the problem COUNT too: the activation tuple's arity is
        # part of the jit trace key, so a group shrinking from 8 to 7
        # same-shape problems would otherwise retrace. Pad entries are
        # zero activations (cheapest member's shape) whose outputs are
        # dropped — for homogeneous groups, any G in one bucket shares
        # one traced signature.
        G_pad = _pow2(G)
        if G_pad > G:
            pad = jnp.zeros_like(min(acts, key=lambda a: int(a.shape[0])))
            acts = acts + (pad,) * (G_pad - G)
        if shared_operand:
            w = ws[0]
            K = envelope_bucket(int(w.shape[0]))
            N = envelope_bucket(int(w.shape[1]))
            m_tiles = _tile_bucket([sum(int(a.shape[0]) for a in acts)],
                                   bm)
            b = self._packed_weights([w], [wkeys[0]], K, N, 1, shared=True,
                                     group=group, device=device)
            check_vmem(bm, min(bn, N), min(bk, K),
                       dtype_bytes=b.dtype.itemsize, interpret=interpret)
            outs = _dispatch_shared(
                acts, b, n_real=int(w.shape[1]), m_tiles=m_tiles,
                bm=bm, bn=min(bn, N), bk=min(bk, K),
                interpret=interpret)
        else:
            K = envelope_bucket(max(int(w.shape[0]) for w in ws))
            N = envelope_bucket(max(int(w.shape[1]) for w in ws))
            b = self._packed_weights(ws, wkeys, K, N, G_pad, shared=False,
                                     group=group, device=device)
            n_real = [int(w.shape[1]) for w in ws]
            n_real += [n_real[0]] * (G_pad - G)
            m_tiles = _tile_bucket([int(a.shape[0]) for a in acts], bm)
            gids = []
            for g, a in enumerate(acts):
                # pad problems read group 0's weights: their activations
                # are zero, so the product is zero and never read back
                gids.extend([g if g < G else 0]
                            * (_round_up(int(a.shape[0]), bm)
                               // bm))
            gids.extend([0] * (m_tiles - len(gids)))  # pad tiles: group 0
            check_vmem(bm, min(bn, N), min(bk, K),
                       dtype_bytes=b.dtype.itemsize, interpret=interpret)
            outs = _dispatch_grouped(
                acts, b, jnp.asarray(gids, jnp.int32),
                n_real=tuple(n_real),
                m_tiles=m_tiles, bm=bm, bn=min(bn, N),
                bk=min(bk, K), interpret=interpret)
        self.stats.retraces += trace_count() - trace0
        return list(outs[:G])

    # ------------------------------------------------------------------
    def matvec(self, xs: Sequence[jax.Array], ws: Sequence[jax.Array], *,
               interpret: Optional[bool] = None,
               group=None) -> List[jax.Array]:
        """Jitted ``coalesced_matvec``: G matvecs (x [k], w [k, n]) with the
        stacked weight operand cached persistently (keyed on the weight
        arrays' identity). Dispatches the shared-weight GEMM regime when
        every problem uses the same weight array, exactly like the eager
        ``kernels.ops.coalesced_matvec``.

        A caller that hot-swaps its weights should pass a stable ``group``
        (any hashable identity of ITS dispatch slot): the ``id(w)``-based
        keys change with every swap, and without a group tag the
        superseded packed stacks — each pinning its dead weight arrays via
        the guard — are only reclaimed by the cache's LRU/byte bounds."""
        interpret = self.interpret if interpret is None else interpret
        if not self.enabled:
            return coalesced_matvec(xs, ws, interpret=interpret)
        if all(w is ws[0] for w in ws):
            outs = self.execute_problems(
                [(x[None, :], ws[0]) for x in xs],
                [matvec_weight_key(ws[0], shared=True)] * len(xs),
                shared_operand=True, interpret=interpret, group=group)
            return [o[0] for o in outs]
        self.stats.dispatches += 1
        trace0 = trace_count()
        G = len(xs)
        G_pad = _pow2(G)
        K = envelope_bucket(max(int(w.shape[0]) for w in ws))
        N = envelope_bucket(max(int(w.shape[1]) for w in ws))
        wkeys = [matvec_weight_key(w) for w in ws]
        w_stacked = self._packed_weights(ws, wkeys, K, N, G_pad,
                                         shared=False, group=group)
        xs = tuple(xs)
        n_real = [int(w.shape[1]) for w in ws]
        if G_pad > G:
            xs = xs + (jnp.zeros_like(xs[0]),) * (G_pad - G)
            n_real += [n_real[0]] * (G_pad - G)
        outs = _dispatch_matvec(
            xs, w_stacked, n_real=tuple(n_real),
            bn=min(self.bn, N), bk=min(self.bk, K), interpret=interpret)
        self.stats.retraces += trace_count() - trace0
        return list(outs[:G])
