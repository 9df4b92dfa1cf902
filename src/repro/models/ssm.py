"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) block.

Two execution paths, matching the paper's duality:

* ``ssd_chunked`` — training / prefill: the quadratic *intra-chunk* part is
  computed attention-like with matmuls (MXU-friendly), the *inter-chunk*
  part is a linear recurrence over chunk states via ``jax.lax.scan``.
* ``ssd_decode_step`` — single-token recurrent update h = a·h + dt·B⊗x,
  y = C·h + D·x (O(1) per token; this is what makes long_500k decodable).

Shapes: d_inner = ``SSMConfig.inner(d_model)`` (expand·d_model unless the
config gives it), H heads of size P = head_dim, state size N = d_state, and
B/C in G = n_groups groups: head h reads group h // (H / G). The gated
RMSNorm normalizes each group's d_inner / G channels on its own, after the
gate (``_gated_norm``). Falcon-H1's µP constants scale the in-projection's
output (``_proj_scale``); Mamba2 and Hymba have none.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SSMConfig
from repro.models.layers import Params, dense_init, rmsnorm


def init_mamba(key, d_model: int, cfg: SSMConfig, dtype) -> Params:
    d_inner = cfg.inner(d_model)
    H = cfg.num_heads(d_model)
    conv_dim = cfg.conv_dim(d_model)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    # dt bias initialised so softplus(dt_bias) spans [1e-3, 1e-1]
    dt_init = jnp.exp(
        jax.random.uniform(k3, (H,), jnp.float32)
        * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001)
    )
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))
    return {
        "in_proj": dense_init(k1, (d_model, cfg.in_proj_dim(d_model)), dtype),
        "conv_w": (jax.random.normal(k2, (cfg.d_conv, conv_dim), jnp.float32)
                   * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": dt_bias,
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "norm": jnp.zeros((d_inner,), dtype),
        "out_proj": dense_init(k4, (d_inner, d_model), dtype),
    }


def _split_zxbcdt(zxbcdt: jax.Array, cfg: SSMConfig, d_model: int):
    """THE in_proj packing layout: [z (d_inner) | xBC (d_inner + 2GN) |
    dt (H)]. Single source of truth — both the full-sequence path
    (``_split_in_proj``) and the decode path (``decode_core``, which the
    JIT's SSM and hybrid templates feed from a declared GEMM) split through
    here, after the in-projection's multipliers (``_proj_scale``)."""
    d_inner = cfg.inner(d_model)
    mup = _proj_scale(cfg, d_model)
    if mup is not None:
        zxbcdt = zxbcdt.astype(jnp.float32) * mup
    return jnp.split(zxbcdt, [d_inner, d_inner + cfg.conv_dim(d_model)],
                     axis=-1)


def _proj_scale(cfg: SSMConfig, d_model: int):
    """Per-output scale of the in-projection: the input multiplier (moved
    past the linear map) times the five-part µP vector over [z | x | B |
    C | dt]; None when every factor is 1."""
    parts = tuple(cfg.in_multiplier * m for m in cfg.proj_multipliers)
    if all(p == 1.0 for p in parts):
        return None
    di, gn = cfg.inner(d_model), cfg.n_groups * cfg.d_state
    sizes = (di, di, gn, gn, cfg.num_heads(d_model))
    return jnp.asarray(np.repeat(np.asarray(parts, np.float32), sizes))


def _split_xbc(xBC: jax.Array, cfg: SSMConfig, d_model: int):
    """[x | B | C] after the conv: x [..., d_inner], B and C [..., G, N]."""
    d_inner, G, N = cfg.inner(d_model), cfg.n_groups, cfg.d_state
    x, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + G * N], axis=-1)
    return (x, Bm.reshape(Bm.shape[:-1] + (G, N)),
            Cm.reshape(Cm.shape[:-1] + (G, N)))


def _gated_norm(y: jax.Array, z: jax.Array, gain: jax.Array,
                n_groups: int, dtype) -> jax.Array:
    """y · silu(z), then RMSNorm over each of the ``n_groups`` groups of
    channels (the gain stored as the deviation from 1); y, z float32."""
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    if n_groups == 1:
        return rmsnorm(y, gain)
    g = y.reshape(y.shape[:-1] + (n_groups, -1))
    g = rmsnorm(g, gain.reshape(n_groups, -1))
    return g.reshape(y.shape)


def _split_in_proj(params: Params, u: jax.Array, cfg: SSMConfig, d_model: int):
    z, xBC, dt = _split_zxbcdt(u @ params["in_proj"], cfg, d_model)
    return z, xBC, dt, cfg.inner(d_model), cfg.num_heads(d_model), \
        cfg.d_state


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over time. xBC: [B, S, Cdim]; w: [K, Cdim]."""
    K = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC, dtype=jnp.float32)
    S = xBC.shape[1]
    for i in range(K):  # K is tiny (4); unrolled taps keep HLO simple
        out = out + pad[:, i:i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    return jax.nn.silu(out + b.astype(jnp.float32))


def ssd_chunked(params: Params, u: jax.Array, cfg: SSMConfig,
                return_state: bool = False):
    """Full-sequence SSD. u: [B, S, d_model] -> [B, S, d_model].

    With ``return_state=True`` also returns the recurrent cache
    {"conv", "h"} after the last position (used by serving prefill).
    """
    Bsz, S0, d_model = u.shape
    Q = cfg.chunk_size
    # right-pad the sequence to a chunk multiple; padded steps have dt ->
    # softplus(large negative) ~ 0 so they do not perturb the final state.
    S = ((S0 + Q - 1) // Q) * Q
    if S != S0:
        u = jnp.pad(u, ((0, 0), (0, S - S0), (0, 0)))
    nc = S // Q
    z, xBC, dt, d_inner, H, N = _split_in_proj(params, u, cfg, d_model)
    if S != S0:
        dt = dt.at[:, S0:, :].set(-30.0)  # freeze state on padded steps
    P = cfg.head_dim
    G = cfg.n_groups
    Hg = H // G

    conv_tail = xBC[:, S0 - (cfg.d_conv - 1):S0, :]  # pre-conv inputs for decode
    xBC = _causal_conv(xBC, params["conv_w"], params["conv_b"]).astype(u.dtype)
    x, Bm, Cm = _split_xbc(xBC, cfg, d_model)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # [B,S,H]
    a = -jnp.exp(params["A_log"])                                    # [H] < 0

    # Precision policy (TPU-native; §Perf iteration M1): the scalar decay
    # chain (alpha/cum/decay/state scan) stays fp32 for stability, but the
    # four big einsums and the stacked per-chunk states run in the model
    # compute dtype (bf16 in production) with fp32 MXU accumulation —
    # profiling showed fp32 SSD intermediates dominated the memory roofline
    # term (chunk states alone: 1.2 TB/step/chip at prefill_32k).
    cdt = u.dtype

    # chunked views; heads split [G, Hg]: head (g, j) reads B/C group g
    xc = x.reshape(Bsz, nc, Q, G, Hg, P).astype(cdt)
    Bc = Bm.reshape(Bsz, nc, Q, G, N).astype(cdt)
    Cc = Cm.reshape(Bsz, nc, Q, G, N).astype(cdt)
    dtc = dt.reshape(Bsz, nc, Q, G, Hg)

    alpha = a.reshape(G, Hg) * dtc                         # [B,nc,Q,G,Hg] (<=0)
    cum = jnp.cumsum(alpha, axis=2)                        # [B,nc,Q,G,Hg]
    total = cum[:, :, -1]                                  # [B,nc,G,Hg]

    # ---- intra-chunk (quadratic, matmul form) --------------------------------
    # L[i,j] = exp(cum_i - cum_j) for j <= i
    diff = cum[:, :, :, None] - cum[:, :, None, :]         # [B,nc,Q(i),Q(j),G,Hg]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(causal[None, None, :, :, None, None], jnp.exp(diff), 0.0)
    CB = jnp.einsum("bcign,bcjgn->bcijg", Cc, Bc,
                    preferred_element_type=jnp.float32)    # [B,nc,Q,Q,G]
    scores = (CB[..., None] * L).astype(cdt)               # [B,nc,Q,Q,G,Hg]
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(cdt)
    y_intra = jnp.einsum("bcijgh,bcjghp->bcighp", scores, xdt,
                         preferred_element_type=jnp.float32)

    # ---- chunk states + inter-chunk recurrence -------------------------------
    # §Perf iteration M2: the inter-chunk contribution is computed INSIDE the
    # recurrence scan, so the [nc, B, H, P, N] chunk-state stack is never
    # materialized (it was the single largest HBM consumer: 1.2 TB/step at
    # prefill_32k), and ``states`` is emitted directly in scan-major layout
    # (saves a full-buffer transpose pass).
    decay_end = jnp.exp(total[:, :, None] - cum).astype(cdt)
    states = jnp.einsum("bcjgh,bcjgn,bcjghp->cbghpn", decay_end, Bc, xdt,
                        preferred_element_type=jnp.float32).astype(cdt)
    expcum = jnp.exp(cum).astype(cdt)                      # [B,nc,Q,G,Hg]

    def step(h, inputs):
        st, tot, c_c, ec_c = inputs  # [B,G,Hg,P,N] [B,G,Hg] [B,Q,G,N] [B,Q,G,Hg]
        # ys stay fp32: mixed dtypes at the scan's stacking
        # dynamic-update-slice make XLA round-trip the WHOLE [nc,...] buffer
        # through convert every iteration (measured 44 TB of phantom
        # traffic); uniform-dtype ys are written slice-by-slice in place.
        y_c = jnp.einsum("bign,bghpn,bigh->bighp", c_c, h.astype(cdt), ec_c,
                         preferred_element_type=jnp.float32)
        h = jnp.exp(tot)[..., None, None] * h + st.astype(jnp.float32)
        return h, y_c

    h0 = jnp.zeros((Bsz, G, Hg, P, N), jnp.float32)
    h_final, y_inter = jax.lax.scan(
        step, h0,
        (states, total.transpose(1, 0, 2, 3),
         Cc.transpose(1, 0, 2, 3, 4), expcum.transpose(1, 0, 2, 3, 4)))
    y_inter = y_inter.transpose(1, 0, 2, 3, 4, 5)          # [B,nc,Q,G,Hg,P]

    y = (y_intra + y_inter.astype(jnp.float32)).reshape(Bsz, S, H, P)
    y = y + params["D"][None, None, :, None] \
        * x.reshape(Bsz, S, H, P).astype(jnp.float32)
    y = y.reshape(Bsz, S, d_inner)

    # gate + norm in one fp32 pass, then back to the compute dtype
    y = _gated_norm(y, z, params["norm"], G, u.dtype)
    out = y @ params["out_proj"]
    if S != S0:
        out = out[:, :S0]
    if return_state:
        return out, {"conv": conv_tail.astype(u.dtype),
                     "h": h_final.reshape(Bsz, H, P, N)}
    return out


def init_ssm_cache(batch: int, d_model: int, cfg: SSMConfig, dtype
                   ) -> Dict[str, jax.Array]:
    H = cfg.num_heads(d_model)
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.conv_dim(d_model)),
                          dtype),
        "h": jnp.zeros((batch, H, cfg.head_dim, cfg.d_state), jnp.float32),
    }


def decode_core(params: Params, zxbcdt: jax.Array,
                cache: Dict[str, jax.Array], cfg: SSMConfig, d_model: int
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Everything between the two decode-step projections.

    Takes the in-projection output ``zxbcdt`` [B, 2·d_inner + 2GN + H] and
    the per-layer recurrent cache; returns the gated/normed ``y``
    [B, d_inner] *ready for the out projection* plus the updated cache.
    This is the per-stage seam the JIT's SSM and hybrid decode templates
    (core/jit.py) build on: the in/out projections become declared
    ``GemmStage``s (coalescible across tenants) while this selective-scan
    recurrence runs as glue — keeping exactly ONE copy of the recurrence
    math shared with ``ssd_decode_step``."""
    Bsz = zxbcdt.shape[0]
    dtype = zxbcdt.dtype
    d_inner = cfg.inner(d_model)
    H, G, P = cfg.num_heads(d_model), cfg.n_groups, cfg.head_dim
    z, xBC, dt = _split_zxbcdt(zxbcdt, cfg, d_model)

    # causal conv over the cached window + the new input
    window = jnp.concatenate([cache["conv"],
                              xBC[:, None].astype(cache["conv"].dtype)], axis=1)
    conv = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                      params["conv_w"].astype(jnp.float32))
    xBC_t = jax.nn.silu(conv + params["conv_b"].astype(jnp.float32))
    new_conv = window[:, 1:]

    x, Bm, Cm = _split_xbc(xBC_t, cfg, d_model)
    x = x.reshape(Bsz, G, H // G, P).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # [B,H]
    dt = dt.reshape(Bsz, G, H // G)
    a = -jnp.exp(params["A_log"]).reshape(G, H // G)

    decay = jnp.exp(a[None] * dt)                          # [B,G,Hg]
    dBx = jnp.einsum("bgh,bgn,bghp->bghpn", dt, Bm.astype(jnp.float32), x)
    h = decay[..., None, None] * cache["h"].reshape(dBx.shape) + dBx
    y = jnp.einsum("bgn,bghpn->bghp", Cm.astype(jnp.float32), h)
    y = y + params["D"].reshape(G, H // G)[None, :, :, None] * x
    y = y.reshape(Bsz, d_inner)

    y = _gated_norm(y, z, params["norm"], G, dtype)
    return y, {"conv": new_conv, "h": h.reshape(Bsz, H, P, -1)}


def ssd_decode_step(params: Params, u: jax.Array, cache: Dict[str, jax.Array],
                    cfg: SSMConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token recurrent update. u: [B, 1, d_model]."""
    Bsz, _, d_model = u.shape
    y, new_cache = decode_core(params, u[:, 0] @ params["in_proj"],
                               cache, cfg, d_model)
    out = (y @ params["out_proj"])[:, None]
    return out, new_cache


def ssd_reference(params: Params, u: jax.Array, cfg: SSMConfig) -> jax.Array:
    """Naive step-by-step recurrence oracle (for tests)."""
    Bsz, S, d_model = u.shape
    cache = init_ssm_cache(Bsz, d_model, cfg, u.dtype)
    outs = []
    for t in range(S):
        y, cache = ssd_decode_step(params, u[:, t:t + 1], cache, cfg)
        outs.append(y)
    return jnp.concatenate(outs, axis=1)
