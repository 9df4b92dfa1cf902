"""Plain float32 reference of a Llama-family decoder (Yi-9B): the
published equations in straightforward ``jax.numpy``, with no kernels,
cache or batching, at the highest matmul precision.

It reads the benchmark's weight arrays in the program's tree layout
(per-layer weights stacked on a leading axis). Two conventions follow the
program, not the published model, and are noted where they are applied:
norm gains are stored as the deviation from 1, and the token embedding is
multiplied by sqrt(hidden_size).

``logits(params, spec, tokens, read)`` runs the whole sequence once and
returns the logits at the positions ``read``; ``wfn`` is applied to every
weight matrix as it is read (the control passes a lower precision there).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, gain, eps):
    # gain stored as the deviation from 1 (the program's convention)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain.astype(F32))


def _rope(x, cos, sin):
    """x [S, heads, hd]; rotate-half form with frequencies
    theta^(-i/half), i < half."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _identity(w):
    return w.astype(F32)


@functools.partial(jax.jit, static_argnames=("spec_key", "wfn"))
def _forward(params, tokens, read, *, spec_key, wfn):
    spec = dict(spec_key)
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    Hkv, hd = spec["num_key_value_heads"], spec["head_dim"]
    eps, G = spec["rms_norm_eps"], H // Hkv
    S = tokens.shape[0]
    half = hd // 2
    inv = 1.0 / (spec["rope_theta"] ** (np.arange(half) / half))
    ang = np.arange(S)[:, None] * inv[None, :]
    cos, sin = jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    # the program scales the embedding by sqrt(hidden_size); the gather
    # itself is not a matmul and keeps its stored values under every wfn
    x = params["embed"][tokens].astype(F32) * math.sqrt(d)

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["ln1"], eps)
        q = _rope(_mm(h, wfn(a["wq"])).reshape(S, H, hd), cos, sin)
        k = _rope(_mm(h, wfn(a["wk"])).reshape(S, Hkv, hd), cos, sin)
        v = _mm(h, wfn(a["wv"])).reshape(S, Hkv, hd)
        q = q.reshape(S, Hkv, G, hd)
        s = jnp.einsum("shgd,thd->hgst", q, k, precision=HI) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("hgst,thd->shgd", jax.nn.softmax(s, axis=-1), v,
                       precision=HI)
        x = x + _mm(o.reshape(S, H * hd), wfn(a["wo"]))
        m = p["mlp"]
        h = _rms(x, p["ln2"], eps)
        g = jax.nn.silu(_mm(h, wfn(m["w_gate"]))) * _mm(h, wfn(m["w_up"]))
        return x + _mm(g, wfn(m["w_down"])), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    y = _rms(x[read], params["final_norm"], eps)
    head = params["embed"].T if spec["tie_word_embeddings"] \
        else params["unembed"]
    return _mm(y, wfn(head))


def logits(params, spec: dict, tokens, read, wfn=_identity):
    """Logits [len(read), vocab] (float32) of ``tokens`` [S] at ``read``."""
    key = tuple(sorted((k, v) for k, v in spec.items()
                       if not isinstance(v, dict)))
    return _forward(params, jnp.asarray(tokens), jnp.asarray(read),
                    spec_key=key, wfn=wfn)
