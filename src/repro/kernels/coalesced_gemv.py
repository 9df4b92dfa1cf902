"""Coalesced matrix-vector superkernel (paper §5.3: RNN/LSTM inference).

Packs G decode-time matvecs — one per stream — into a single Pallas kernel.
Two regimes:

  * distinct weights (different tenants / different layers): batched GEMV,
    grid over (problem, n-tile), each step streams one (K × bn) weight panel;
  * shared weights (G streams of the SAME model+layer — the paper's RNN
    claim): the packer concatenates vectors into one [G, K] matrix and calls
    the plain GEMM path instead, loading the weight panel ONCE (see
    ops.coalesced_matvec which makes this dispatch decision).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_default


# rows per vector block: the TPU tiles the last two dims of a block in
# (8, 128) units, so each vector rides in row 0 of an 8-row slab whose
# other rows are zero (their products are never read back)
_SUBLANES = 8


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # [8, bk] @ [bk, bn] -> [8, bn]; row 0 is the real vector
    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def coalesced_gemv(x: jax.Array, w: jax.Array, *, bn: int = 128,
                   bk: int = 512, interpret: bool | None = None) -> jax.Array:
    """x: [G, K] packed vectors; w: [G, K, N] per-problem weights -> [G, N]."""
    G, K = x.shape
    G2, K2, N = w.shape
    assert (G, K) == (G2, K2)
    bn = min(bn, N)
    bk = min(bk, K)
    assert N % bn == 0 and K % bk == 0, (N, bn, K, bk)
    nk = K // bk
    xs = jnp.pad(x[:, None, :], ((0, 0), (0, _SUBLANES - 1), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(G, N // bn, nk),
        in_specs=[
            pl.BlockSpec((1, _SUBLANES, bk), lambda g, j, k: (g, 0, k)),
            pl.BlockSpec((1, bk, bn), lambda g, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, _SUBLANES, bn), lambda g, j, k: (g, 0, j)),
        scratch_shapes=[pltpu.VMEM((_SUBLANES, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((G, _SUBLANES, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="coalesced_gemv",
        interpret=interpret_default() if interpret is None else interpret,
    )(xs, w)
    return out[:, 0, :]
