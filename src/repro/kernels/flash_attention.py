"""Flash attention (causal, optional sliding window) as a Pallas TPU kernel.

Used by the long-context serving path: gemma3 / llama4 / hymba local layers
attend within a window, which bounds the per-token working set; the kernel
keeps a running (m, l, acc) online-softmax state in VMEM scratch and streams
K/V tiles through the innermost grid dimension.

Layout: q/k/v are [BH, S, D] (batch×heads flattened by ops.py). Grid is
(BH, S/bq, S/bkv) with the kv dimension 'arbitrary' (sequential) so the
scratch accumulator carries across kv tiles of one q tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_default

_NEG = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nkv: int, bq: int, bkv: int, causal: bool, window: int,
            scale: float):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # [bq, D]
    k = k_ref[0].astype(jnp.float32)              # [bkv, D]
    v = v_ref[0].astype(jnp.float32)              # [bkv, D]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    rows = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0)
    cols = kv_idx * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    ok = jnp.ones((bq, bkv), bool)
    if causal:
        ok &= cols <= rows
    if window > 0:
        ok &= cols > rows - window
    s = jnp.where(ok, s, _NEG)

    m_prev = m_ref[...]                           # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)               # [bq, 1]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_idx == nkv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "causal", "window",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 128, bkv: int = 128, causal: bool = True,
                    window: int = 0, interpret: bool | None = None) -> jax.Array:
    """q, k, v: [BH, S, D] -> [BH, S, D]."""
    BH, S, D = q.shape
    bq = min(bq, S)
    bkv = min(bkv, S)
    assert S % bq == 0 and S % bkv == 0, (S, bq, bkv)
    nkv = S // bkv
    scale = 1.0 / (D ** 0.5)

    return pl.pallas_call(
        functools.partial(_kernel, nkv=nkv, bq=bq, bkv=bkv, causal=causal,
                          window=window, scale=scale),
        grid=(BH, S // bq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default() if interpret is None else interpret,
    )(q, k, v)
