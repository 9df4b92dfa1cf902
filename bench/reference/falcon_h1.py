"""Plain float32 reference of a Falcon-H1 language model, written from the
layer equations of ``transformers``' ``modeling_falcon_h1.py``
(``FalconH1DecoderLayer``, ``FalconH1Attention``, ``FalconH1Mixer``'s
``torch_forward``, ``FalconH1RMSNormGated``, ``FalconH1MLP``), in
straightforward ``jax.numpy`` at the highest matmul precision, with no
kernels, cache or chunking.

Per layer, on h = RMSNorm(x):
  attention: a = Attn(h · attention_in) with K scaled by key_multiplier,
    RoPE, causal softmax over the whole sequence;
  mixer: [z | xBC | dt] = (h · ssm_in_multiplier) W_in ⊙ mup, where mup
    scales the five parts [z | x | B | C | dt] by ssm_multipliers;
    xBC = silu(causal depthwise conv(xBC) + b); [x | B | C] = xBC with B
    and C in mamba_n_groups groups (head h reads group h // (H / G));
    dt = softplus(dt + dt_bias); A = -exp(A_log);
    s_t = exp(A dt_t) s_{t-1} + dt_t x_t B_t^T;  y_t = s_t C_t + D x_t,
    token by token; y = RMSNorm_per_group(y ⊙ silu(z)) (the norm after
    the gate); m = y W_out;
  x = x + attention_out · a + ssm_out · m;
  x = x + (up(h2) ⊙ silu(gate(h2) · mlp_gate)) W_down · mlp_down, h2 =
    RMSNorm(x).
The embedding is scaled by embedding_multiplier, the logits by
lm_head_multiplier.

Departures from ``modeling_falcon_h1.py``: none in what is computed. It
reads the benchmark's weight arrays in the program's tree layout, where a
norm's gain is stored as its deviation from 1 (the published weight is
1 + gain), and it supports the published switches only
(``mamba_rms_norm`` on, ``mamba_norm_before_gate`` off, no projection
biases, a conv bias). ``wfn`` is applied to every weight matrix as it is
read (the control passes a lower precision there).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

# the published switches the reference implements
SWITCHES = {"mamba_rms_norm": True, "mamba_norm_before_gate": False,
            "mamba_conv_bias": True, "mamba_proj_bias": False,
            "attention_bias": False, "mlp_bias": False,
            "projectors_bias": False}


def check_switches(spec: dict) -> None:
    for k, v in SWITCHES.items():
        if spec.get(k, v) != v:
            raise ValueError(f"Falcon-H1 with {k}={spec[k]!r} is not "
                             f"implemented (only {k}={v!r})")


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

    h = h * spec["attention_in_multiplier"]
    q = rope(_mm(h, wfn(a["wq"])).reshape(S, H, hd))
    k = rope((_mm(h, wfn(a["wk"])) * spec["key_multiplier"])
             .reshape(S, Hkv, hd))
    v = _mm(h, wfn(a["wv"])).reshape(S, Hkv, hd)
    q = q.reshape(S, Hkv, H // Hkv, hd)
    s = jnp.einsum("shgd,thd->hgst", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hgst,thd->shgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return _mm(o.reshape(S, H * hd), wfn(a["wo"]))


def _mixer(m, h, spec, wfn):
    S = h.shape[0]
    di, H = spec["mamba_d_ssm"], spec["mamba_n_heads"]
    G, N, P = spec["mamba_n_groups"], spec["mamba_d_state"], \
        spec["mamba_d_head"]
    eps = spec["rms_norm_eps"]
    mult = spec["ssm_multipliers"]
    sizes = (di, di, G * N, G * N, H)
    mup = jnp.asarray(np.repeat(np.asarray(mult, np.float32), sizes))
    zxbcdt = _mm(h * spec["ssm_in_multiplier"], wfn(m["in_proj"])) * mup
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    w = m["conv_w"].astype(F32)                                  # [K, C]
    K = w.shape[0]
    pad = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[i:i + S] * w[i] for i in range(K))
                      + m["conv_b"].astype(F32))
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    # each head reads its group's B and C (repeat_interleave over heads)
    Bh = jnp.repeat(Bm.reshape(S, G, N), H // G, axis=1)        # [S, H, N]
    Ch = jnp.repeat(Cm.reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(F32))         # [S, H]
    A = -jnp.exp(m["A_log"].astype(F32))
    D = m["D"].astype(F32)

    def step(st, inp):
        x_t, b_t, c_t, dt_t = inp           # [H,P] [H,N] [H,N] [H]
        st = jnp.exp(A * dt_t)[:, None, None] * st \
            + (dt_t[:, None] * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", st, c_t, precision=HI)
        return st, y + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32),
                        (xs.reshape(S, H, P), Bh, Ch, dt))
    # gated RMSNorm, norm after the gate, over each group's channels
    y = (y.reshape(S, di) * jax.nn.silu(z)).reshape(S, G, di // G)
    y = _rms(y, m["norm"].reshape(G, di // G), eps).reshape(S, di)
    return _mm(y, wfn(m["out_proj"]))


def _hidden(params, tokens, spec, wfn):
    """The final hidden states [S, d] of one sequence."""
    eps = spec["rms_norm_eps"]
    gm, dm = spec["mlp_multipliers"]
    x = params["embed"][tokens].astype(F32) * spec["embedding_multiplier"]

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        x = x + spec["attention_out_multiplier"] \
            * _attention(p["attn"], h, spec, wfn) \
            + spec["ssm_out_multiplier"] * _mixer(p["mamba"], h, spec, wfn)
        m = p["mlp"]
        h = _rms(x, p["ln2"], eps)
        g = jax.nn.silu(_mm(h, wfn(m["w_gate"])) * gm) \
            * _mm(h, wfn(m["w_up"]))
        return x + _mm(g, wfn(m["w_down"])) * dm, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return x


def _blocks(V: int) -> int:
    """Vocabulary blocks of the head: a float32 copy of a 261120 x 5120
    head (5.3 GB) would not fit beside the bf16 fleet, an eighth does."""
    return next(n for n in (8, 4, 2, 1) if V % n == 0)


@functools.partial(jax.jit, static_argnames=("spec_key", "wfn"))
def _forward(params, tokens, read, *, spec_key, wfn):
    spec = dict(spec_key)
    y = _rms(_hidden(params, tokens, spec, wfn)[read],
             params["final_norm"], spec["rms_norm_eps"])
    head = params["embed"].T if spec["tie_word_embeddings"] \
        else params["unembed"]
    V = head.shape[1]
    blk = V // _blocks(V)

    def block(_, i):
        w = jax.lax.dynamic_slice_in_dim(head, i * blk, blk, axis=1)
        return None, _mm(y, wfn(w))

    _, out = jax.lax.scan(block, None, jnp.arange(V // blk))
    logits = out.transpose(1, 0, 2).reshape(y.shape[0], V)
    return logits * spec["lm_head_multiplier"]


def spec_key(spec: dict):
    """The hashable view of a model entry the jitted forward is keyed on:
    scalars as they are, lists as tuples, nested groups left out."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in spec.items() if not isinstance(v, dict)))


def logits(params, spec: dict, tokens, read, wfn=_identity):
    """Logits [len(read), vocab] (float32) of ``tokens`` [S] at ``read``."""
    check_switches(spec)
    return _forward(params, jnp.asarray(tokens), jnp.asarray(read),
                    spec_key=spec_key(spec), wfn=wfn)
