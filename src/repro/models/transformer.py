"""Layer blocks and scan-over-layers stacks for every assigned family.

All stacks scan over STACKED per-layer params (leading axis = layer) so HLO
size and compile time are independent of depth. Heterogeneous local/global
attention patterns ride along as a scanned boolean ``is_global`` vector, so
the scan body stays homogeneous (DESIGN.md §7).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.hints import constrain
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (Params, init_mlp, init_rmsnorm, mlp, rmsnorm,
                                 scale)

Cache = Dict[str, Any]


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype) -> Params:
    """One decoder layer's params for any family."""
    keys = jax.random.split(key, 6)
    p: Params = {"ln1": init_rmsnorm(cfg.d_model, dtype),
                 "ln2": init_rmsnorm(cfg.d_model, dtype)}
    hd = cfg.resolved_head_dim
    if cfg.arch_type != "ssm":
        p["attn"] = attn.init_attention(
            keys[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd, dtype)
    if cfg.arch_type == "ssm":
        p["mamba"] = ssm_lib.init_mamba(keys[1], cfg.d_model, cfg.ssm, dtype)
    elif cfg.arch_type == "hybrid":
        p["mamba"] = ssm_lib.init_mamba(keys[1], cfg.d_model, cfg.ssm, dtype)
        p["mlp"] = init_mlp(keys[2], cfg.d_model, cfg.d_ff, dtype)
    elif cfg.has_moe:
        p["moe"] = moe_lib.init_moe(keys[2], cfg.d_model, cfg.d_ff, cfg.moe, dtype)
    else:
        p["mlp"] = init_mlp(keys[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def init_stacked_blocks(key, cfg: ModelConfig, dtype, num_layers=None) -> Params:
    L = num_layers if num_layers is not None else cfg.num_layers
    keys = jax.random.split(key, L)
    return jax.vmap(lambda k: init_block(k, cfg, dtype))(keys)


# ---------------------------------------------------------------------------
# per-layer forward (full-sequence)
# ---------------------------------------------------------------------------

def combine_mixers(cfg: ModelConfig, a: jax.Array, s: jax.Array
                   ) -> jax.Array:
    """THE hybrid combine rule: attention_out · attention + ssm_out · SSM
    (``cfg.mult``; Falcon-H1's µP multipliers, Hymba's mean as 0.5 and
    0.5). The decode template (core/jit.py) calls it too."""
    mu = cfg.mult
    return scale(a, mu.attention_out) + scale(s, mu.ssm_out)


def ffn(p: Params, h2: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The dense SwiGLU FFN with the config's gate and down multipliers."""
    return mlp(p["mlp"], h2, cfg.mult.mlp_gate, cfg.mult.mlp_down)


def _attention_kw(cfg: ModelConfig, is_global) -> Dict[str, Any]:
    return dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                is_global=is_global, window=cfg.window_size,
                use_rope=(cfg.arch_type != "audio"), key_scale=cfg.mult.key)


def _mixer_full(p: Params, h: jax.Array, cfg: ModelConfig, is_global) -> jax.Array:
    """Token mixer (attention and/or SSM) on the normed input, full sequence."""
    if cfg.arch_type == "ssm":
        return ssm_lib.ssd_chunked(p["mamba"], h, cfg.ssm)
    a = attn.attention_full(p["attn"], scale(h, cfg.mult.attention_in),
                            causal=True, **_attention_kw(cfg, is_global))
    if cfg.arch_type == "hybrid":
        return combine_mixers(cfg, a, ssm_lib.ssd_chunked(p["mamba"], h,
                                                          cfg.ssm))
    return a


def block_full(p: Params, x: jax.Array, cfg: ModelConfig, is_global
               ) -> Tuple[jax.Array, jax.Array]:
    """Full-seq layer: returns (y, moe_aux_loss)."""
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    x = x + _mixer_full(p, h, cfg, is_global)
    aux = jnp.zeros((), jnp.float32)
    if cfg.arch_type == "ssm":
        return x, aux
    h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if cfg.has_moe and cfg.arch_type != "hybrid":
        B, S, d = h2.shape
        y, aux = moe_lib.moe_ffn(p["moe"], h2.reshape(B * S, d), cfg.moe)
        y = y.reshape(B, S, d)
    else:
        y = ffn(p, h2, cfg)
    return x + y, aux


# ---------------------------------------------------------------------------
# decoder stack (scan over layers), full-sequence mode
# ---------------------------------------------------------------------------

def stack_full(stacked: Params, x: jax.Array, cfg: ModelConfig,
               flags: jax.Array, remat: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """Run all layers. flags: [L] bool (is_global). Returns (y, aux_sum)."""

    def body(carry, layer):
        x, aux = carry
        p, flag = layer
        y, a = block_full(p, constrain(x, "btd"), cfg, flag)
        return (constrain(y, "btd"), aux + a), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (y, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (stacked, flags))
    return y, aux


# ---------------------------------------------------------------------------
# decoder stack, prefill mode: full-seq forward that also emits the cache
# ---------------------------------------------------------------------------

def _project_kv(p: Params, h: jax.Array, cfg: ModelConfig,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    hd = cfg.resolved_head_dim
    k = scale(h @ p["attn"]["wk"], cfg.mult.key).reshape(
        h.shape[0], h.shape[1], cfg.num_kv_heads, hd)
    v = (h @ p["attn"]["wv"]).reshape(h.shape[0], h.shape[1], cfg.num_kv_heads, hd)
    if cfg.arch_type != "audio":
        from repro.models.layers import apply_rope
        k = apply_rope(k, positions, cfg.rope_theta)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # [B,Hkv,S,hd]


def stack_prefill(stacked: Params, x: jax.Array, cfg: ModelConfig,
                  flags: jax.Array) -> Tuple[jax.Array, Cache]:
    """Full forward emitting the per-layer decode cache as scan outputs."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]

    def body(carry, layer):
        x, aux = carry
        x = constrain(x, "btd")
        p, flag = layer
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        out: Dict[str, jax.Array] = {}
        if cfg.arch_type == "ssm":
            y, st = ssm_lib.ssd_chunked(p["mamba"], h, cfg.ssm, return_state=True)
            out["conv"], out["h"] = st["conv"], st["h"]
            x = x + y
        else:
            ha = scale(h, cfg.mult.attention_in)
            out["k"], out["v"] = _project_kv(p, ha, cfg, positions)
            a = attn.attention_full(p["attn"], ha, causal=True,
                                    **_attention_kw(cfg, flag))
            if cfg.arch_type == "hybrid":
                y, st = ssm_lib.ssd_chunked(p["mamba"], h, cfg.ssm,
                                            return_state=True)
                out["conv"], out["h"] = st["conv"], st["h"]
                a = combine_mixers(cfg, a, y)
            x = x + a
            h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
            if cfg.has_moe and cfg.arch_type != "hybrid":
                y2, a2 = moe_lib.moe_ffn(p["moe"], h2.reshape(B * S, -1), cfg.moe)
                x = x + y2.reshape(h2.shape)
                aux = aux + a2
            else:
                x = x + ffn(p, h2, cfg)
            return (x, aux), out
        return (x, aux), out

    (y, _aux), cache = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (stacked, flags))
    return y, cache


# ---------------------------------------------------------------------------
# decoder stack, single-token decode mode
# ---------------------------------------------------------------------------

def stack_decode(stacked: Params, x: jax.Array, cache: Cache, pos: jax.Array,
                 cfg: ModelConfig, flags: jax.Array
                 ) -> Tuple[jax.Array, Cache]:
    """One-token decode through all layers, updating the cache.

    The stacked cache rides in the scan CARRY (not xs→ys): while-loop state
    aliases in place, so each layer's update is one dynamic-update-slice
    into the donated buffer. Stacking updated caches as scan outputs makes
    XLA rebuild the full [L, ...] buffer every iteration (§Perf L3:
    327 GB/step of stacked-cache copies measured on llama4 decode_32k).
    """
    L = cfg.num_layers

    def body(carry, layer):
        x, cstack = carry
        x = constrain(x, "btd")
        p, flag, li = layer
        c = {k: jax.lax.dynamic_index_in_dim(v, li, 0, keepdims=False)
             for k, v in cstack.items()}
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        new_c: Dict[str, jax.Array] = {}
        if cfg.arch_type == "ssm":
            y, st = ssm_lib.ssd_decode_step(
                p["mamba"], h, {"conv": c["conv"], "h": c["h"]}, cfg.ssm)
            new_c.update(st)
            x = x + y
        else:
            kw = _attention_kw(cfg, flag)
            ha = scale(h, cfg.mult.attention_in)
            if "k_scale" in c:   # int8 KV cache (§Perf K1)
                a, nk, nv, nks, nvs = attn.attention_decode(
                    p["attn"], ha, c["k"], c["v"], pos,
                    k_scale=c["k_scale"], v_scale=c["v_scale"], **kw)
                new_c["k_scale"], new_c["v_scale"] = nks, nvs
            else:
                a, nk, nv = attn.attention_decode(
                    p["attn"], ha, c["k"], c["v"], pos, **kw)
            new_c["k"], new_c["v"] = nk, nv
            if cfg.arch_type == "hybrid":
                y, st = ssm_lib.ssd_decode_step(
                    p["mamba"], h, {"conv": c["conv"], "h": c["h"]}, cfg.ssm)
                new_c.update(st)
                a = combine_mixers(cfg, a, y)
            x = x + a
            h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
            if cfg.has_moe and cfg.arch_type != "hybrid":
                B = h2.shape[0]
                y2, _ = moe_lib.moe_ffn(p["moe"], h2.reshape(B, -1), cfg.moe)
                x = x + y2.reshape(h2.shape)
            else:
                x = x + ffn(p, h2, cfg)
        cstack = {k: jax.lax.dynamic_update_index_in_dim(
            cstack[k], new_c[k].astype(cstack[k].dtype), li, 0)
            for k in cstack}
        return (x, cstack), None

    (y, new_cache), _ = jax.lax.scan(
        body, (x, cache), (stacked, flags, jnp.arange(L)))
    return y, new_cache


# ---------------------------------------------------------------------------
# encoder stack (whisper) — bidirectional, no cache
# ---------------------------------------------------------------------------

def init_encoder_block(key, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "attn": attn.init_attention(k1, cfg.d_model, cfg.num_heads,
                                    cfg.num_kv_heads, cfg.resolved_head_dim,
                                    dtype),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def encoder_stack(stacked: Params, x: jax.Array, cfg: ModelConfig,
                  remat: bool = False) -> jax.Array:
    def body(x, p):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        x = x + attn.attention_full(
            p["attn"], h, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta, causal=False, use_rope=False)
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        return x + mlp(p["mlp"], h2), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    y, _ = jax.lax.scan(body, x, stacked)
    return y


# ---------------------------------------------------------------------------
# whisper decoder stack: self-attn + cross-attn + mlp
# ---------------------------------------------------------------------------

def init_decoder_block_encdec(key, cfg: ModelConfig, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "ln_cross": init_rmsnorm(cfg.d_model, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "attn": attn.init_attention(k1, cfg.d_model, cfg.num_heads,
                                    cfg.num_kv_heads, cfg.resolved_head_dim,
                                    dtype),
        "cross": attn.init_attention(k2, cfg.d_model, cfg.num_heads,
                                     cfg.num_kv_heads, cfg.resolved_head_dim,
                                     dtype),
        "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, dtype),
    }


def encdec_decoder_full(stacked: Params, x: jax.Array, mem: jax.Array,
                        cfg: ModelConfig, with_cache: bool = False,
                        remat: bool = False):
    """Whisper decoder full-seq forward; optionally emits the decode cache
    (self K/V from the prompt + cross K/V from the encoder memory)."""
    hd = cfg.resolved_head_dim

    def body(x, p):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        out: Dict[str, jax.Array] = {}
        if with_cache:
            k = (h @ p["attn"]["wk"]).reshape(
                h.shape[0], h.shape[1], cfg.num_kv_heads, hd)
            v = (h @ p["attn"]["wv"]).reshape(
                h.shape[0], h.shape[1], cfg.num_kv_heads, hd)
            out["k"] = k.transpose(0, 2, 1, 3)
            out["v"] = v.transpose(0, 2, 1, 3)
        x = x + attn.attention_full(
            p["attn"], h, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=hd,
            rope_theta=cfg.rope_theta, causal=True, use_rope=False)
        hc = rmsnorm(x, p["ln_cross"], cfg.norm_eps)
        km, vm = attn.project_memory_kv(p["cross"], mem,
                                        num_kv_heads=cfg.num_kv_heads,
                                        head_dim=hd)
        if with_cache:
            out["cross_k"], out["cross_v"] = km, vm
        x = x + attn.attention_cross(p["cross"], hc, km, vm,
                                     num_heads=cfg.num_heads,
                                     num_kv_heads=cfg.num_kv_heads, head_dim=hd)
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        return x + mlp(p["mlp"], h2), out

    if remat and not with_cache:
        body = jax.checkpoint(body, prevent_cse=False)
    y, cache = jax.lax.scan(body, x, stacked)
    if with_cache:
        return y, cache
    return y


def encdec_decoder_decode(stacked: Params, x: jax.Array, cache: Cache,
                          pos: jax.Array, cfg: ModelConfig
                          ) -> Tuple[jax.Array, Cache]:
    """One-token whisper decode; cache: k/v (self) + cross_k/cross_v (fixed)."""
    hd = cfg.resolved_head_dim

    def body(x, layer):
        p, c = layer
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        a, nk, nv = attn.attention_decode(
            p["attn"], h, c["k"], c["v"], pos, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=hd,
            rope_theta=cfg.rope_theta, use_rope=False)
        x = x + a
        hc = rmsnorm(x, p["ln_cross"], cfg.norm_eps)
        x = x + attn.attention_cross(p["cross"], hc, c["cross_k"], c["cross_v"],
                                     num_heads=cfg.num_heads,
                                     num_kv_heads=cfg.num_kv_heads, head_dim=hd)
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp(p["mlp"], h2)
        return x, {"k": nk, "v": nv, "cross_k": c["cross_k"],
                   "cross_v": c["cross_v"]}

    y, new_cache = jax.lax.scan(body, x, (stacked, cache))
    return y, new_cache
