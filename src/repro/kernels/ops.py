"""jit'd wrappers + host-side packing for the Pallas superkernels.

This is the layer the JIT engine (core/jit.py, serving/engine.py) calls:
``execute_superkernel`` takes a planned group of (activation, weight)
problems, pads them to the cluster envelope, packs, dispatches the right
Pallas kernel, and unpacks per-problem results. The functions here are the
**eager reference path**: every dispatch re-pads and re-stacks its weight
operands and pays exact max-(K, N) envelopes. The serving hot path goes
through ``core/dispatch.py``'s ``SuperkernelExecutor`` instead, which caches
packed weights persistently and buckets envelopes so steady-state ticks hit
JAX's compile cache; this module stays the bit-compatibility oracle those
fast paths are tested against.

Interpret mode and the compiled lane
------------------------------------
Every Pallas call in this package takes ``interpret=None`` by default and
resolves it from the attached backend when it is called
(``kernels.backend.interpret_default``): interpret mode on a CPU backend,
where Pallas cannot compile a TPU kernel (the test suite runs there), and
Mosaic-compiled kernels everywhere else. There is no switch: on a TPU every
kernel on the serving path is compiled, and a kernel the compiler refuses
fails the run. A caller may still pass ``interpret`` explicitly — the
compile-only tests (tests/test_tpu_compile.py) pass ``interpret=False`` to
compile for a described TPU from a CPU host.

Interpret mode pays a ~2 ms/grid-step host floor, so interpret-mode
wall-clock numbers measure only dispatch-layer overheads (packing,
retraces, cache traffic); there it gates correctness (bit-identity, cache
hit rates, retrace counts) and nothing else. Tile geometry and VMEM
residency show only in compiled runs on the chip.

Compiled tiles must also fit VMEM: ``check_vmem`` raises a clear error
before dispatching a compiled kernel whose per-tile working set
(bm·bk + bk·bn input panels + fp32 bm·bn accumulator) exceeds the budget —
Mosaic would otherwise fail deep inside lowering. Interpret mode skips the
check (tiles are host arrays; nothing is resident).

Envelope bucketing policy (used by core/dispatch.py)
----------------------------------------------------
``envelope_bucket`` rounds a packed-dimension extent up to the next power of
two, floored at the 128-lane MXU tile — the same idea as ``prefill_bucket``
(core/jit.py) applied to the superkernel envelope. The jitted dispatch path
buckets every envelope extent — per-problem padded rows (multiples of
``bm``, total m-tiles a power of two) and the shared K and N via this
function; the problem/stacked-weight count G uses an UNfloored power-of-two
bucket (``dispatch._pow2`` — a 128 floor there would stack 128 full weight
copies per group) — so the number of distinct traced shapes stays finite
under group-shape churn and a steady-state tick never retraces. Bucket
padding is zeros: zero activation rows produce zero output rows (sliced
off), zero K columns/rows contribute exact ``+0.0`` terms to the fp32
accumulator, zero N columns and zero-padded weight slots are never read
back — so any bucket ≥ the exact envelope is correct. Note that bucketing
K beyond the eager path's exact 128-multiple envelope changes the fp32
contraction split (last-ulp reassociation); see the correctness contract
in core/dispatch.py.

The same zero-problem padding is what makes RAGGED groups safe — including
MoE expert-GEMM groups, whose per-problem row counts (the per-expert token
buffers, m = capacity C) vary with each tenant's batch and routing: every
problem's rows pad independently to ``bm`` multiples, the G bucket pads
with whole zero problems (outputs dropped), and a group mixing a tall
prefill GEMM, a 4-row decode GEMV and a C-row expert buffer shares one
traced signature per bucketed envelope. No kernel changes were needed for
non-dense tenants; only this padding contract.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_default
from repro.kernels.coalesced_gemm import coalesced_gemm
from repro.kernels.coalesced_gemv import coalesced_gemv
from repro.kernels.flash_attention import flash_attention
from repro.kernels import ref

# VMEM budget the compiled-lane guard checks tiles against (TPU v5e:
# ~16 MiB/core). Overridable for smaller parts / headroom experiments.
VMEM_BYTES = int(os.environ.get("REPRO_VMEM_BYTES", 16 * 1024 * 1024))


def vmem_tile_bytes(bm: int, bn: int, bk: int, dtype_bytes: int = 4) -> int:
    """Per-tile working set of the coalesced GEMM kernels: the A and B
    input panels at the serving dtype plus the fp32 accumulator scratch."""
    return dtype_bytes * (bm * bk + bk * bn) + 4 * bm * bn


def check_vmem(bm: int, bn: int, bk: int, *, dtype_bytes: int = 4,
               interpret: bool, budget: int | None = None) -> None:
    """Compiled-lane VMEM guard (see the module docstring). No-op in
    interpret mode; raises ``ValueError`` before launching a compiled
    kernel whose tile cannot be resident."""
    if interpret:
        return
    budget = VMEM_BYTES if budget is None else budget
    need = vmem_tile_bytes(bm, bn, bk, dtype_bytes)
    if need > budget:
        raise ValueError(
            f"block (bm={bm}, bn={bn}, bk={bk}) needs {need} bytes of VMEM "
            f"> budget {budget}; tune under the budget (the autotuner's "
            f"candidate filter does) or raise REPRO_VMEM_BYTES")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def envelope_bucket(x: int, minimum: int = 128) -> int:
    """Power-of-two bucket for one packed-envelope extent (≥ ``minimum``).

    See "Envelope bucketing policy" in the module docstring; the jitted
    dispatch path (core/dispatch.py) applies this to K, N and G so the
    traced shape space stays finite over arbitrary group-shape churn.
    """
    assert x >= 1, x
    return max(minimum, 1 << (x - 1).bit_length())


@dataclasses.dataclass
class PackedGroup:
    """Host-side packing metadata for one superkernel dispatch."""
    a_packed: jax.Array           # [M_pad, K_pad]
    b_stacked: jax.Array          # [G, K_pad, N_pad]
    group_ids: jax.Array          # [M_pad // bm]
    row_slices: List[Tuple[int, int]]   # (start, real_m) per problem
    n_real: List[int]
    bm: int


def pack_problems(problems: Sequence[Tuple[jax.Array, jax.Array]], *,
                  bm: int = 128) -> PackedGroup:
    """Pad G (a [m,k], b [k,n]) problems to a common (K, N) envelope and
    concatenate the a's along m (per-problem m padded to a ``bm`` multiple)."""
    K = max(int(a.shape[1]) for a, _ in problems)
    N = max(int(b.shape[1]) for _, b in problems)
    K = _round_up(K, 128)
    N = _round_up(N, 128)
    a_parts, b_parts, gids, rows, n_real = [], [], [], [], []
    start = 0
    for g, (a, b) in enumerate(problems):
        m, k = a.shape
        m_pad = _round_up(m, bm)
        a_parts.append(jnp.pad(a, ((0, m_pad - m), (0, K - k))))
        b_parts.append(jnp.pad(b, ((0, K - b.shape[0]), (0, N - b.shape[1]))))
        gids.extend([g] * (m_pad // bm))
        rows.append((start, m))
        n_real.append(int(b.shape[1]))
        start += m_pad
    return PackedGroup(
        a_packed=jnp.concatenate(a_parts, axis=0),
        b_stacked=jnp.stack(b_parts, axis=0),
        group_ids=jnp.asarray(gids, jnp.int32),
        row_slices=rows, n_real=n_real, bm=bm)


def execute_superkernel(problems: Sequence[Tuple[jax.Array, jax.Array]], *,
                        bm: int = 128, bn: int = 128, bk: int = 512,
                        shared_operand: bool = False,
                        interpret: bool | None = None) -> List[jax.Array]:
    """Coalesce and execute G GEMM problems; returns per-problem outputs.

    shared_operand=True (all problems share one weight matrix — the RNN/
    decode lockstep case) concatenates activations into a single GEMM so the
    weights stream through VMEM once.
    """
    interpret = interpret_default() if interpret is None else interpret
    if shared_operand:
        b = problems[0][1]
        ms = [int(a.shape[0]) for a, _ in problems]
        x = jnp.concatenate([a for a, _ in problems], axis=0)
        m_pad = _round_up(x.shape[0], bm)
        k_pad = _round_up(b.shape[0], 128)
        n_pad = _round_up(b.shape[1], 128)
        xp = jnp.pad(x, ((0, m_pad - x.shape[0]), (0, k_pad - x.shape[1])))
        bp = jnp.pad(b, ((0, k_pad - b.shape[0]), (0, n_pad - b.shape[1])))
        check_vmem(bm, min(bn, n_pad), min(bk, k_pad),
                   dtype_bytes=xp.dtype.itemsize, interpret=interpret)
        out = coalesced_gemm(
            xp, bp[None], jnp.zeros((m_pad // bm,), jnp.int32),
            bm=bm, bn=min(bn, n_pad), bk=min(bk, k_pad), interpret=interpret)
        outs, s = [], 0
        for m in ms:
            outs.append(out[s:s + m, :b.shape[1]])
            s += m
        return outs
    packed = pack_problems(problems, bm=bm)
    check_vmem(bm, min(bn, packed.b_stacked.shape[-1]),
               min(bk, packed.b_stacked.shape[1]),
               dtype_bytes=packed.a_packed.dtype.itemsize,
               interpret=interpret)
    out = coalesced_gemm(packed.a_packed, packed.b_stacked, packed.group_ids,
                         bm=bm, bn=min(bn, packed.b_stacked.shape[-1]),
                         bk=min(bk, packed.b_stacked.shape[1]),
                         interpret=interpret)
    return [out[s:s + m, :n] for (s, m), n in
            zip(packed.row_slices, packed.n_real)]


def coalesced_matvec(xs: Sequence[jax.Array], ws: Sequence[jax.Array], *,
                     interpret: bool | None = None) -> List[jax.Array]:
    """G matvecs (x [k], w [k, n]). Dispatches the shared-weight GEMM path
    when every problem uses the same weight array."""
    shared = all(w is ws[0] for w in ws)
    if shared:
        outs = execute_superkernel(
            [(x[None, :], ws[0]) for x in xs], bm=8,
            shared_operand=True, interpret=interpret)
        return [o[0] for o in outs]
    K = _round_up(max(int(w.shape[0]) for w in ws), 128)
    N = _round_up(max(int(w.shape[1]) for w in ws), 128)
    xp = jnp.stack([jnp.pad(x, (0, K - x.shape[0])) for x in xs])
    wp = jnp.stack([jnp.pad(w, ((0, K - w.shape[0]), (0, N - w.shape[1])))
                    for w in ws])
    out = coalesced_gemv(xp, wp, bn=128, bk=min(512, K), interpret=interpret)
    return [out[i, :int(w.shape[1])] for i, w in enumerate(ws)]


def windowed_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: int = 0,
                       interpret: bool | None = None) -> jax.Array:
    """[B, H, S, D] flash attention via the Pallas kernel (flattens B×H)."""
    B, H, S, D = q.shape
    out = flash_attention(q.reshape(B * H, S, D), k.reshape(B * H, S, D),
                          v.reshape(B * H, S, D), causal=causal,
                          window=window, interpret=interpret)
    return out.reshape(B, H, S, D)
