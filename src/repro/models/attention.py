"""Grouped-query attention (GQA/MQA) with optional sliding-window locality.

Supports three execution modes:
  * full  — training / prefill self-attention over the whole sequence
    (causal or bidirectional), optional sliding window;
  * decode — one new token against a pre-filled KV cache, updating the cache
    in place (functionally);
  * cross — encoder-decoder cross attention (whisper), bidirectional over a
    fixed memory.

Layer locality (``is_global``) is a *traced* per-layer boolean so that
heterogeneous local/global stacks (gemma3 5:1, llama4 3:1, hymba) stay
homogeneous under ``jax.lax.scan``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Params, apply_rope, dense_init, scale

NEG_INF = -2.0e38


def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, dtype) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d_model, num_heads * head_dim), dtype),
        "wk": dense_init(kk, (d_model, num_kv_heads * head_dim), dtype),
        "wv": dense_init(kv, (d_model, num_kv_heads * head_dim), dtype),
        "wo": dense_init(ko, (num_heads * head_dim, d_model), dtype),
    }


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, hd))


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: [B,S,Hkv,G,hd]; k: [B,T,Hkv,hd] -> scores [B,Hkv,G,S,T] (fp32)."""
    return jnp.einsum("bshgd,bthd->bhgst", q, k, preferred_element_type=jnp.float32)


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: [B,Hkv,G,S,T]; v: [B,T,Hkv,hd] -> [B,S,Hkv,G,hd]."""
    return jnp.einsum("bhgst,bthd->bshgd", p, v.astype(jnp.float32))


def _locality_mask(rows: jax.Array, cols: jax.Array, is_global, window: int,
                   causal: bool) -> jax.Array:
    """Boolean mask [S, T]: True = attendable."""
    rows = rows[:, None]
    cols = cols[None, :]
    ok = cols <= rows if causal else jnp.ones((rows.shape[0], cols.shape[1]), bool)
    if window > 0:
        local_ok = ok & (cols > rows - window)
        ok = jnp.where(jnp.asarray(is_global), ok, local_ok)
    return ok


# sequences at or above this length use the chunked (memory-efficient)
# attention path: never materialize [B, H, S, S]
CHUNKED_THRESHOLD = 2048
Q_CHUNK = 512


def _attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       is_global, window: int, causal: bool,
                       head_dim: int) -> jax.Array:
    """Flash-style chunked attention: scan over q chunks, full-row scores per
    chunk only ([B, Hkv, G, bq, S] lives transiently). The chunk body is
    rematerialized in the backward pass, so training memory stays
    O(S·bq) instead of O(S²). q: [B,S,Hkv,G,hd]; k,v: [B,S,Hkv,hd].

    BANDED local layers (§Perf W1): when ``window > 0`` and the window band
    fits well under S, local layers take a lax.cond branch that slices only
    the [bq + window] K/V band per q chunk instead of masking full-S scores
    — a S/(bq+window)× cut in attention compute AND score traffic for the
    5:1 / 3:1 local:global stacks (gemma3, llama4, hymba). ``is_global`` is
    a traced per-layer scalar, so one homogeneous scan body serves both
    layer kinds.
    """
    B, S, Hkv, G, hd = q.shape
    bq = Q_CHUNK
    assert S % bq == 0, (S, bq)
    nq = S // bq
    qc = q.reshape(B, nq, bq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    cols = jnp.arange(S)
    Wlen = bq + window                      # band length per q chunk

    def scores_to_out(s, ok, vv):
        s = s / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgst,bthd->bshgd", p, vv.astype(jnp.float32))

    def full_branch(qi, rows, idx):
        s = jnp.einsum("bshgd,bthd->bhgst", qi, k,
                       preferred_element_type=jnp.float32)
        ok = cols[None, :] <= rows[:, None] if causal else \
            jnp.ones((bq, S), bool)
        return scores_to_out(s, ok, v)

    def banded_branch(qi, rows, idx):
        start = jnp.clip(idx * bq - window, 0, S - Wlen)
        kb = jax.lax.dynamic_slice_in_dim(k, start, Wlen, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, start, Wlen, axis=1)
        bcols = start + jnp.arange(Wlen)
        s = jnp.einsum("bshgd,bthd->bhgst", qi, kb,
                       preferred_element_type=jnp.float32)
        ok = (bcols[None, :] <= rows[:, None]) \
            & (bcols[None, :] > rows[:, None] - window)
        return scores_to_out(s, ok, vb)

    def masked_fallback(qi, rows, idx):
        """Old semantics for window bands too wide to slice: full scores
        with the locality mask selected by the traced flag."""
        s = jnp.einsum("bshgd,bthd->bhgst", qi, k,
                       preferred_element_type=jnp.float32)
        ok = cols[None, :] <= rows[:, None] if causal else \
            jnp.ones((bq, S), bool)
        if window > 0:
            local = ok & (cols[None, :] > rows[:, None] - window)
            ok = jnp.where(jnp.asarray(is_global), ok, local)
        return scores_to_out(s, ok, v)

    def chunk(carry, inp):
        qi, idx = inp                                   # [B,bq,Hkv,G,hd]
        rows = idx * bq + jnp.arange(bq)
        if window > 0 and causal and Wlen < S:
            o = jax.lax.cond(jnp.asarray(is_global), full_branch,
                             banded_branch, qi, rows, idx)
        else:
            o = masked_fallback(qi, rows, idx)
        return carry, o.astype(q.dtype)

    _, outs = jax.lax.scan(jax.checkpoint(chunk, prevent_cse=False),
                           None, (qc, jnp.arange(nq)))
    # outs: [nq, B, bq, Hkv, G, hd]
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hkv * G * hd)


def attention_full(params: Params, x: jax.Array, *, num_heads: int,
                   num_kv_heads: int, head_dim: int, rope_theta: float,
                   is_global=True, window: int = 0, causal: bool = True,
                   use_rope: bool = True,
                   positions: Optional[jax.Array] = None,
                   key_scale: float = 1.0) -> jax.Array:
    """Self-attention over the full sequence. x: [B, S, d] -> [B, S, d].
    ``key_scale`` multiplies the keys (Falcon-H1's ``key_multiplier``)."""
    B, S, _ = x.shape
    G = num_heads // num_kv_heads
    q = _split_heads(x @ params["wq"], num_heads, head_dim)
    k = _split_heads(scale(x @ params["wk"], key_scale), num_kv_heads,
                     head_dim)
    v = _split_heads(x @ params["wv"], num_kv_heads, head_dim)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    q = q.reshape(B, S, num_kv_heads, G, head_dim)
    if S >= CHUNKED_THRESHOLD and S % Q_CHUNK == 0:
        out = _attention_chunked(q, k, v, is_global=is_global, window=window,
                                 causal=causal, head_dim=head_dim)
        return out.astype(x.dtype) @ params["wo"]
    scores = _gqa_scores(q, k) / jnp.sqrt(jnp.float32(head_dim))
    mask = _locality_mask(jnp.arange(S), jnp.arange(S), is_global, window, causal)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(p, v).reshape(B, S, num_heads * head_dim).astype(x.dtype)
    return out @ params["wo"]


def attention_decode(params: Params, x: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, pos: jax.Array, *, num_heads: int,
                     num_kv_heads: int, head_dim: int, rope_theta: float,
                     is_global=True, window: int = 0,
                     use_rope: bool = True,
                     k_scale=None, v_scale=None, key_scale: float = 1.0):
    """One-token decode against a KV cache.

    x: [B, 1, d]; k_cache/v_cache: [B, Hkv, S, hd]; pos: int32 [B] — the
    per-row index the new token is written at (tokens 0..pos[b] attendable).
    Per-row positions are what makes continuous batching possible: requests
    at different depths share one decode batch (serving/engine.py).

    int8 KV mode (§Perf K1): when ``k_scale/v_scale`` [B,Hkv,S,1] are given,
    the caches are int8; the new token is quantized on write and the (banded)
    read is dequantized into the compute dtype — halving decode's dominant
    roofline term (cache bandwidth). Returns
    (y, kc, vc) or (y, kc, vc, k_scale, v_scale) accordingly.
    """
    from repro.models.kvquant import dequantize, quantize
    quant = k_scale is not None
    B, _, _ = x.shape
    S = k_cache.shape[2]
    G = num_heads // num_kv_heads
    pos = jnp.broadcast_to(jnp.asarray(pos), (B,))
    q = _split_heads(x @ params["wq"], num_heads, head_dim)     # [B,1,H,hd]
    k = _split_heads(scale(x @ params["wk"], key_scale), num_kv_heads,
                     head_dim)                                  # [B,1,Hkv,hd]
    v = _split_heads(x @ params["wv"], num_kv_heads, head_dim)
    posb = pos[:, None]
    if use_rope:
        q = apply_rope(q, posb, rope_theta)
        k = apply_rope(k, posb, rope_theta)
    # write each row's new K/V at its own index ``pos[b]``. Mask-select
    # instead of vmap(dynamic_update_slice): the latter lowers to a scatter
    # that XLA round-trips through fp32 (whole-cache convert per layer —
    # §Perf L2); the where-form stays in the cache dtype and fuses with the
    # attention read.
    write = (jnp.arange(S)[None, :] == pos[:, None])      # [B, S]
    wmask = write[:, None, :, None]
    k_t = k.transpose(0, 2, 1, 3)
    v_t = v.transpose(0, 2, 1, 3)
    if quant:
        kq, ks_new = quantize(k_t, scale_dtype=k_scale.dtype)
        vq, vs_new = quantize(v_t, scale_dtype=v_scale.dtype)
        k_cache = jnp.where(wmask, kq, k_cache)
        v_cache = jnp.where(wmask, vq, v_cache)
        k_scale = jnp.where(wmask, ks_new, k_scale)
        v_scale = jnp.where(wmask, vs_new, v_scale)
    else:
        k_cache = jnp.where(wmask, k_t.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(wmask, v_t.astype(v_cache.dtype), v_cache)
    q = q.reshape(B, 1, num_kv_heads, G, head_dim)
    cdt = x.dtype

    # Serving precision policy (§Perf L1): the QK and PV dots run in the
    # CACHE dtype (MXU accumulates fp32 internally); only the softmax is
    # fp32. Requesting f32 dot outputs (or upcasting V) makes XLA
    # materialize an fp32 COPY of the whole KV cache per layer — measured
    # 327 GB/step of phantom cache traffic on llama4 decode_32k.
    def _attend(kc, vc, ks, vs, col_idx, plimit):
        """col_idx: absolute positions of kc's entries [B or 1, T]."""
        if quant:
            kc = dequantize(kc, ks, dtype=cdt)
            vc = dequantize(vc, vs, dtype=cdt)
        scores = jnp.einsum("bshgd,bhtd->bhgst", q.astype(kc.dtype), kc)
        scores = scores.astype(jnp.float32) / jnp.sqrt(
            jnp.float32(head_dim))
        ok = (col_idx <= pos[:, None]) & (col_idx > plimit[:, None])
        scores = jnp.where(ok[:, None, None, None, :], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhgst,bhtd->bshgd", p.astype(vc.dtype), vc)

    idx = jnp.arange(S)
    neg = jnp.full((B,), -1)
    dummy = jnp.zeros((B, num_kv_heads, S, 1), cdt)
    ks_in = k_scale if quant else dummy
    vs_in = v_scale if quant else dummy

    def full_attend(kc, vc, ks, vs):
        limit = jnp.where(jnp.asarray(is_global) | (window <= 0),
                          neg, pos - window)
        return _attend(kc, vc, ks, vs, idx[None, :], limit)

    if 0 < window < S:
        # banded decode (§Perf W1): local layers read only the last
        # ``window`` cache entries — an S/window cut in cache traffic for
        # sliding-window layers (gemma3 32× at decode_32k).
        def banded(kc, vc, ks, vs):
            # per-row band (rows decode at different depths under
            # continuous batching)
            start = jnp.clip(pos - window + 1, 0, S - window)   # [B]
            slc = jax.vmap(lambda c, s: jax.lax.dynamic_slice_in_dim(
                c, s, window, axis=1))
            kb, vb = slc(kc, start), slc(vc, start)
            ksb, vsb = slc(ks, start), slc(vs, start)
            bcols = start[:, None] + jnp.arange(window)[None, :]
            return _attend(kb, vb, ksb, vsb, bcols, pos - window)

        out = jax.lax.cond(jnp.asarray(is_global), full_attend, banded,
                           k_cache, v_cache, ks_in, vs_in)
    else:
        out = full_attend(k_cache, v_cache, ks_in, vs_in)
    out = out.reshape(B, 1, num_heads * head_dim).astype(x.dtype)
    y = out @ params["wo"]
    if quant:
        return y, k_cache, v_cache, k_scale, v_scale
    return y, k_cache, v_cache


def attention_cross(params: Params, x: jax.Array, k_mem: jax.Array,
                    v_mem: jax.Array, *, num_heads: int, num_kv_heads: int,
                    head_dim: int) -> jax.Array:
    """Cross attention against precomputed memory K/V [B, Hkv, T, hd]."""
    B, S, _ = x.shape
    G = num_heads // num_kv_heads
    q = _split_heads(x @ params["wq"], num_heads, head_dim)
    q = q.reshape(B, S, num_kv_heads, G, head_dim)
    scores = jnp.einsum("bshgd,bhtd->bhgst", q, k_mem,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(head_dim))
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bshgd", p, v_mem.astype(jnp.float32))
    out = out.reshape(B, S, num_heads * head_dim).astype(x.dtype)
    return out @ params["wo"]


def project_memory_kv(params: Params, mem: jax.Array, *, num_kv_heads: int,
                      head_dim: int) -> Tuple[jax.Array, jax.Array]:
    """Project encoder output into cross-attention K/V [B, Hkv, T, hd]."""
    k = _split_heads(mem @ params["wk"], num_kv_heads, head_dim)
    v = _split_heads(mem @ params["wv"], num_kv_heads, head_dim)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
