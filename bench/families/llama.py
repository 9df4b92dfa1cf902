"""The Llama family (Yi-9B): GQA attention with RoPE and a SwiGLU FFN.

Model FLOPs of one token (a multiply-add counts 2): per layer
2 (d H hd + 2 d Hkv hd + H hd d + 3 d ff) for the projections and the
SwiGLU FFN, plus 4 H hd c for the scores and the weighted sum over its c
visible positions; plus 2 d V for the logits where the token produces them.

Bytes: a decode step reads each active row's keys and values over its
visible positions; a prefill writes its prompt's keys and values.
"""
from __future__ import annotations

import numpy as np

import check
from reference import llama as reference


def model_config(name: str, spec: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=name, arch_type="dense",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["head_dim"], d_ff=spec["intermediate_size"],
        vocab_size=spec["vocab_size"], rope_theta=spec["rope_theta"],
        norm_eps=spec["rms_norm_eps"],
        tie_embeddings=spec["tie_word_embeddings"])


def reference_logits(spec: dict, params, reqs, seq: int, reads: int,
                     wfn=None):
    """One request at a time: a whole sequence's attention fits the chip
    for one row."""
    kw = {} if wfn is None else {"wfn": wfn}
    out = []
    for s in reqs:
        toks, read = check.reference_inputs(s, seq, reads)
        lg = reference.logits(params, spec, toks, read, **kw)
        out.append(np.asarray(lg[: len(s.tokens)]))
    return out


def _dims(spec: dict):
    return (spec["hidden_size"], spec["num_attention_heads"],
            spec["num_key_value_heads"], spec["head_dim"],
            spec["intermediate_size"], spec["num_hidden_layers"],
            spec["vocab_size"])


def token_flops(spec: dict, visible: int, logits: bool) -> float:
    d, H, Hkv, hd, ff, L, V = _dims(spec)
    proj = 2 * (d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff)
    return L * (proj + 4 * H * hd * visible) + (2 * d * V if logits else 0)


def prefill_flops(spec: dict, prompt_len: int) -> float:
    d, H, Hkv, hd, ff, L, V = _dims(spec)
    base = token_flops(spec, 0, False) * prompt_len
    attn = L * 4 * H * hd * prompt_len * (prompt_len + 1) / 2
    return base + attn + 2 * d * V


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    d, H, Hkv, hd, ff, L, V = _dims(spec)
    layer = d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff + 2 * d
    return itemsize * (L * layer + d + d * V)


def decode_state_bytes(spec: dict, visible: int, itemsize: int = 2) -> int:
    d, H, Hkv, hd, ff, L, V = _dims(spec)
    return L * 2 * Hkv * hd * visible * itemsize


def prefill_state_bytes(spec: dict, prompt_len: int,
                        itemsize: int = 2) -> int:
    d, H, Hkv, hd, ff, L, V = _dims(spec)
    return L * 2 * Hkv * hd * prompt_len * itemsize
