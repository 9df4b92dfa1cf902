"""The paper's superkernel, TPU-native: a grouped GEMM Pallas kernel.

One ``pallas_call`` executes G heterogeneous GEMM problems that the JIT
coalesced (paper §5.3 / Fig. 6). Problems are padded to a common (K, N)
envelope and concatenated along m; a scalar-prefetched ``group_ids`` vector
maps each m-tile to its weight matrix, so the B BlockSpec index_map selects
the right problem's operand per grid step — the TPU analogue of
``cublasSgemmBatched`` with *ragged* problem sizes.

VMEM tiling: (bm × bk) A panels, (bk × bn) B panels, one (bm × bn) fp32
accumulator scratch; the k grid dimension is innermost ("arbitrary"
semantics) and accumulates into scratch, so VMEM footprint is
bm·bk + bk·bn + bm·bn regardless of problem size — exactly the working-set
knob the co-tenancy autotuner (core/autotuner.py) tunes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_default


def _kernel(gid_ref, a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def coalesced_gemm(a_packed: jax.Array, b_stacked: jax.Array,
                   group_ids: jax.Array, *, bm: int = 128, bn: int = 128,
                   bk: int = 512, interpret: bool | None = None) -> jax.Array:
    """Run the grouped superkernel.

    a_packed:  [M_pad, K]    problems concatenated along m (rows padded per
                             problem to multiples of ``bm``; pad rows zero);
    b_stacked: [G, K, N]     per-problem weight envelopes (padded to common
                             K, N by the packer);
    group_ids: [M_pad // bm] int32 problem id per m-tile (scalar-prefetched).
    Returns [M_pad, N]; pad rows come back zero.
    """
    M, K = a_packed.shape
    G, K2, N = b_stacked.shape
    assert K == K2, (K, K2)
    assert M % bm == 0 and group_ids.shape == (M // bm,)
    bn = min(bn, N)
    bk = min(bk, K)
    assert N % bn == 0 and K % bk == 0, (N, bn, K, bk)
    nk = K // bk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, gid: (i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k, gid: (gid[i], k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, gid: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), a_packed.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default() if interpret is None else interpret,
    )(group_ids, a_packed, b_stacked)
