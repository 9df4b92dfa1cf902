"""Shared neural-net building blocks (pure JAX, functional).

Conventions used throughout the model zoo:
  * params are plain dict pytrees of jnp arrays;
  * per-layer params are STACKED on a leading layer axis and consumed with
    ``jax.lax.scan`` so HLO size / compile time is O(1) in depth;
  * matmuls run in the param dtype (bf16 by default), reductions
    (norms, softmax, losses) in fp32.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float = 1.0):
    """Truncated-normal fan-in init (stddev = scale / sqrt(fan_in))."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((xf * scale) * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def scale(x: jax.Array, m: float) -> jax.Array:
    """x · m computed in fp32 and returned in x's dtype (a bf16 constant
    such as 0.0375 would be off by 0.3%); no op where m is 1, the default
    of every multiplier (configs/base.py ``Multipliers``)."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def scale_embedding(x: jax.Array, d_model: int, multiplier=None):
    """The token embedding's scale: the config's multiplier, or the
    repository's sqrt(d_model) where it gives none."""
    if multiplier is None:
        return x * jnp.asarray(jnp.sqrt(d_model), x.dtype)
    return scale(x, multiplier)


def init_rmsnorm(d: int, dtype) -> jax.Array:
    # stored as the deviation from 1.0 (gemma convention); init to zeros.
    return jnp.zeros((d,), dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    # computed on HOST (numpy) so the table is one literal constant: a
    # device-side ``theta ** x`` evaluates through the runtime pow kernel
    # eagerly but through XLA's constant folder under jit, and the two
    # disagree in the last ulp between eager calls and jitted
    # scan-over-layers bodies
    half = head_dim // 2
    return jnp.asarray(
        (1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
         ).astype(np.float32))


# Host-precomputed rope cos/sin tables, one per (head_dim, theta). The trig
# itself must NOT be evaluated on device: XLA's standalone cos/sin kernels
# and its fused-loop vectorized versions disagree in the last ulp, so the
# same ``cos(pos * freq)`` computes different bits inside a jitted
# scan-over-layers body than in an eager call. A host table + device
# gather is bit-exact in every execution regime. 8192 positions bounds every
# cache/prefill geometry this repo serves (gather clips beyond it).
_ROPE_TABLE_POSITIONS = 8192
_ROPE_TRIG: Dict[Any, Any] = {}


def _rope_trig_tables(head_dim: int, theta: float):
    # cache NUMPY arrays only — materializing device arrays here would leak
    # tracers when the first call happens inside a jit/scan trace; the
    # use-site jnp.asarray embeds them as constants under trace and
    # transfers on the eager path
    key = (head_dim, float(theta))
    tab = _ROPE_TRIG.get(key)
    if tab is None:
        half = head_dim // 2
        freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
        ang = np.arange(_ROPE_TABLE_POSITIONS,
                        dtype=np.float64)[:, None] * freqs
        tab = (np.cos(ang).astype(np.float32),
               np.sin(ang).astype(np.float32))
        _ROPE_TRIG[key] = tab
    return tab


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq].

    cos/sin come from a host-precomputed per-position table, so the trig is
    a bit-exact gather in every execution regime, eager or jitted."""
    head_dim = x.shape[-1]
    cos_t, sin_t = _rope_trig_tables(head_dim, theta)
    idx = positions.astype(jnp.int32)
    cos = jnp.asarray(cos_t)[idx][..., None, :]  # [..., seq, 1, half]
    sin = jnp.asarray(sin_t)[idx][..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int) -> jax.Array:
    """Whisper-style fixed sinusoidal position embeddings [seq, d_model]."""
    half = d_model // 2
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(half, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10000.0) * dim / max(half - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def mlp(params: Params, x: jax.Array, gate_scale: float = 1.0,
        down_scale: float = 1.0) -> jax.Array:
    gate = jax.nn.silu(scale(x @ params["w_gate"], gate_scale))
    up = x @ params["w_up"]
    return scale((gate * up) @ params["w_down"], down_scale)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits: jax.Array, labels: jax.Array, mask=None) -> jax.Array:
    """Mean next-token CE in fp32. logits [..., V], labels int [...]."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
