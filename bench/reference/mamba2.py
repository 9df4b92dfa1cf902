"""Plain float32 reference of a Mamba2 (SSD) language model: the published
recurrence, token by token, in straightforward ``jax.numpy`` at the
highest matmul precision, with no chunking, kernels or cache.

Per layer, on the pre-normed input h:
  [z | xBC | dt] = h W_in;  xBC = silu(causal depthwise conv(xBC) + b);
  [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log);
  s_t = exp(A dt_t) s_{t-1} + dt_t x_t B_t^T;  y_t = s_t C_t + D x_t;
  out = rmsnorm(y * silu(z)) W_out,  added to the residual stream.
One B/C group (ngroups 1). The logits use the tied embedding.

It reads the benchmark's weight arrays in the program's tree layout.
Two conventions follow the program and are noted where applied: norm gains
are stored as the deviation from 1, and the token embedding is multiplied
by sqrt(d_model). ``wfn`` is applied to every weight matrix as it is read.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, gain, eps):
    # gain stored as the deviation from 1 (the program's convention)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain.astype(F32))


def _identity(w):
    return w.astype(F32)


@functools.partial(jax.jit, static_argnames=("spec_key", "wfn"))
def _forward(params, tokens, read, *, spec_key, wfn):
    spec = dict(spec_key)
    d, eps = spec["d_model"], spec["rms_norm_eps"]
    N, P = spec["d_state"], spec["headdim"]
    di = spec["expand"] * d
    H = di // P
    Bsz, S = tokens.shape

    # the program scales the embedding by sqrt(d_model); the gather itself
    # is not a matmul and keeps its stored values under every wfn
    x = params["embed"][tokens].astype(F32) * math.sqrt(d)   # [B, S, d]

    def layer(x, p):
        m = p["mamba"]
        h = _rms(x, p["ln1"], eps)
        zxbcdt = _mm(h, wfn(m["in_proj"]))
        z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
        w = m["conv_w"].astype(F32)                             # [K, C]
        K = w.shape[0]
        pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * w[i] for i in range(K))
        xbc = jax.nn.silu(conv + m["conv_b"].astype(F32))
        xs, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
        xs = xs.reshape(Bsz, S, H, P)
        dt = jax.nn.softplus(dt + m["dt_bias"].astype(F32))    # [B, S, H]
        A = -jnp.exp(m["A_log"].astype(F32))                    # [H]
        D = m["D"].astype(F32)

        def step(s, inp):
            x_t, b_t, c_t, dt_t = inp        # [B,H,P] [B,N] [B,N] [B,H]
            s = jnp.exp(A * dt_t)[:, :, None, None] * s \
                + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
            y = jnp.einsum("bhpn,bn->bhp", s, c_t, precision=HI)
            return s, y + D[None, :, None] * x_t

        s0 = jnp.zeros((Bsz, H, P, N), F32)
        _, y = jax.lax.scan(step, s0, (xs.swapaxes(0, 1), Bm.swapaxes(0, 1),
                                       Cm.swapaxes(0, 1), dt.swapaxes(0, 1)))
        y = y.swapaxes(0, 1).reshape(Bsz, S, di) * jax.nn.silu(z)
        y = _rms(y, m["norm"], eps)
        return x + _mm(y, wfn(m["out_proj"])), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    y = jnp.take_along_axis(x, read[:, :, None], axis=1)       # [B, R, d]
    y = _rms(y, params["final_norm"], eps)
    return _mm(y, wfn(params["embed"].T))


def logits(params, spec: dict, tokens, read, wfn=_identity):
    """Logits [B, R, vocab] (float32) of ``tokens`` [B, S] at ``read``
    [B, R]."""
    flat = dict(spec, **spec["ssm_cfg"])
    key = tuple(sorted((k, v) for k, v in flat.items()
                       if not isinstance(v, dict)))
    return _forward(params, jnp.asarray(tokens), jnp.asarray(read),
                    spec_key=key, wfn=wfn)
