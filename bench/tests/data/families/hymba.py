"""A test-only family: Hymba-style blocks, GQA attention and SSD heads side
by side on the same normed input, their outputs averaged, then a SwiGLU
FFN (the program's ``hybrid`` arch). It lives only in the test data, with
its configuration and mix, to show that a family joins the benchmark as
new files: this module, its configuration file (with its rate) and a mix.

Its reference is written here in plain ``jax.numpy`` at the highest matmul
precision: causal attention over the whole sequence and the SSD recurrence
token by token, one request at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import check

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def model_config(name: str, spec: dict):
    from repro.configs.base import ModelConfig, SSMConfig
    s = spec["ssm"]
    return ModelConfig(
        name=name, arch_type="hybrid",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["head_dim"], d_ff=spec["intermediate_size"],
        vocab_size=spec["vocab_size"], rope_theta=spec["rope_theta"],
        norm_eps=spec["rms_norm_eps"],
        tie_embeddings=spec["tie_word_embeddings"],
        ssm=SSMConfig(d_state=s["d_state"], head_dim=s["headdim"],
                      expand=s["expand"], chunk_size=s["chunk_size"],
                      d_conv=s["d_conv"]))


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, gain, eps):
    # gain stored as the deviation from 1 (the program's convention)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain.astype(F32))


def _identity(w):
    return w.astype(F32)


def _attention(a, h, spec, wfn):
    S = h.shape[0]
    H, Hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    half = hd // 2
    inv = 1.0 / (spec["rope_theta"] ** (np.arange(half) / half))
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[:, None, :]

    def rope(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    q = rope(_mm(h, wfn(a["wq"])).reshape(S, H, hd))
    k = rope(_mm(h, wfn(a["wk"])).reshape(S, Hkv, hd))
    v = _mm(h, wfn(a["wv"])).reshape(S, Hkv, hd)
    q = q.reshape(S, Hkv, H // Hkv, hd)
    s = jnp.einsum("shgd,thd->hgst", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hgst,thd->shgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return _mm(o.reshape(S, H * hd), wfn(a["wo"]))


def _ssd(m, h, spec, wfn):
    s = spec["ssm"]
    d, N, P = spec["hidden_size"], s["d_state"], s["headdim"]
    di = s["expand"] * d
    H = di // P
    S = h.shape[0]
    z, xbc, dt = jnp.split(_mm(h, wfn(m["in_proj"])), [di, 2 * di + 2 * N],
                           axis=-1)
    w = m["conv_w"].astype(F32)
    K = w.shape[0]
    pad = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[i:i + S] * w[i] for i in range(K))
                      + m["conv_b"].astype(F32))
    xs, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(F32))
    A = -jnp.exp(m["A_log"].astype(F32))
    D = m["D"].astype(F32)

    def step(st, inp):
        x_t, b_t, c_t, dt_t = inp
        st = jnp.exp(A * dt_t)[:, None, None] * st \
            + (dt_t[:, None] * x_t)[..., None] * b_t[None, None, :]
        y = jnp.einsum("hpn,n->hp", st, c_t, precision=HI)
        return st, y + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32),
                        (xs.reshape(S, H, P), Bm, Cm, dt))
    y = _rms(y.reshape(S, di) * jax.nn.silu(z), m["norm"], 1e-5)
    return _mm(y, wfn(m["out_proj"]))


@functools.partial(jax.jit, static_argnames=("spec_key", "wfn"))
def _forward(params, tokens, read, *, spec_key, wfn):
    spec = dict(spec_key)
    spec["ssm"] = dict(spec["ssm"])
    d, eps = spec["hidden_size"], spec["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32) * math.sqrt(d)

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        x = x + 0.5 * (_attention(p["attn"], h, spec, wfn)
                       + _ssd(p["mamba"], h, spec, wfn))
        m = p["mlp"]
        h = _rms(x, p["ln2"], eps)
        g = jax.nn.silu(_mm(h, wfn(m["w_gate"]))) * _mm(h, wfn(m["w_up"]))
        return x + _mm(g, wfn(m["w_down"])), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    y = _rms(x[read], params["final_norm"], eps)
    head = params["embed"].T if spec["tie_word_embeddings"] \
        else params["unembed"]
    return _mm(y, wfn(head))


def reference_logits(spec: dict, params, reqs, seq: int, reads: int,
                     wfn=None):
    key = tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                        else v) for k, v in spec.items()))
    out = []
    for s in reqs:
        toks, read = check.reference_inputs(s, seq, reads)
        lg = _forward(params, jnp.asarray(toks), jnp.asarray(read),
                      spec_key=key, wfn=wfn or _identity)
        out.append(np.asarray(lg[: len(s.tokens)]))
    return out


def _dims(spec: dict):
    s = spec["ssm"]
    d = spec["hidden_size"]
    di = s["expand"] * d
    return (d, spec["num_attention_heads"], spec["num_key_value_heads"],
            spec["head_dim"], spec["intermediate_size"], di,
            di // s["headdim"], s["headdim"], s["d_state"], s["d_conv"],
            spec["num_hidden_layers"], spec["vocab_size"])


def _layer_weights(spec: dict) -> int:
    d, H, Hkv, hd, ff, di, Hs, P, N, K, L, V = _dims(spec)
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff \
        + d * (2 * di + 2 * N + Hs) + di * d + K * (di + 2 * N)


def token_flops(spec: dict, visible: int, logits: bool) -> float:
    d, H, Hkv, hd, ff, di, Hs, P, N, K, L, V = _dims(spec)
    per = 2 * _layer_weights(spec) + 4 * H * hd * visible + 6 * Hs * P * N
    return L * per + (2 * d * V if logits else 0)


def prefill_flops(spec: dict, prompt_len: int) -> float:
    d, H, Hkv, hd, ff, di, Hs, P, N, K, L, V = _dims(spec)
    return sum(token_flops(spec, c, False) for c in range(1, prompt_len + 1)) \
        + 2 * d * V


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    d, H, Hkv, hd, ff, di, Hs, P, N, K, L, V = _dims(spec)
    layer = _layer_weights(spec) + (di + 2 * N) + di + 2 * d
    return itemsize * (L * layer + d + d * V) + L * 3 * Hs * 4


def decode_state_bytes(spec: dict, visible: int, itemsize: int = 2) -> int:
    d, H, Hkv, hd, ff, di, Hs, P, N, K, L, V = _dims(spec)
    return L * (2 * Hkv * hd * visible * itemsize
                + 2 * (Hs * P * N * 4 + (K - 1) * (di + 2 * N) * itemsize))


def prefill_state_bytes(spec: dict, prompt_len: int,
                        itemsize: int = 2) -> int:
    d, H, Hkv, hd, ff, di, Hs, P, N, K, L, V = _dims(spec)
    return L * (2 * Hkv * hd * prompt_len * itemsize
                + Hs * P * N * 4 + (K - 1) * (di + 2 * N) * itemsize)
