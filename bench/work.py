"""Operations and bytes of served work, counted from the configuration's
shapes (not from what the program pads or recomputes).

Model FLOPs of one token (a multiply-add counts 2):
  * llama: per layer 2 (d H hd + 2 d Hkv hd + H hd d + 3 d ff) for the
    projections and the SwiGLU FFN, plus 4 H hd c for the scores and the
    weighted sum over its c visible positions; plus 2 d V for the logits
    where the token produces them.
  * mamba2: per layer 2 d (2 di + 2 N + H) + 2 di d for the projections,
    2 K (di + 2 N) for the depthwise conv, and 6 H P N for the recurrence
    (decay and input terms, and the read-out); plus 2 d V for the logits.
    A prompt is counted token by token, the recurrent form: the least work.

Bytes, a lower bound of what the device must move:
  * every program of a tenant (a decode step or a prefill) reads that
    tenant's weight matrices, unpadded, once: layers, final norm and the
    output head (the embedding gather reads rows only and is left out);
  * a llama decode step reads each active row's keys and values over its
    visible positions; a prefill writes its prompt's keys and values;
  * a mamba2 decode step reads and writes each active row's recurrent and
    conv state; a prefill writes them once.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def _dims(spec: dict):
    if spec["family"] == "llama":
        return (spec["hidden_size"], spec["num_attention_heads"],
                spec["num_key_value_heads"], spec["head_dim"],
                spec["intermediate_size"], spec["num_hidden_layers"],
                spec["vocab_size"])
    s = spec["ssm_cfg"]
    d = spec["d_model"]
    di = s["expand"] * d
    return (d, di, di // s["headdim"], s["headdim"], s["d_state"],
            s["d_conv"], spec["n_layer"], spec["vocab_size"])


def token_flops(spec: dict, visible: int, logits: bool) -> float:
    """Model FLOPs of one token that sees ``visible`` positions (itself
    included), with or without its logits."""
    if spec["family"] == "llama":
        d, H, Hkv, hd, ff, L, V = _dims(spec)
        proj = 2 * (d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff)
        return L * (proj + 4 * H * hd * visible) + (2 * d * V if logits
                                                     else 0)
    if spec["family"] == "mamba2":
        d, di, H, P, N, K, L, V = _dims(spec)
        per = 2 * d * (2 * di + 2 * N + H) + 2 * di * d \
            + 2 * K * (di + 2 * N) + 6 * H * P * N
        return L * per + (2 * d * V if logits else 0)
    raise ValueError(spec["family"])


def prefill_flops(spec: dict, prompt_len: int) -> float:
    """A prompt's FLOPs, with the logits of its last position only."""
    if spec["family"] == "llama":
        d, H, Hkv, hd, ff, L, V = _dims(spec)
        base = token_flops(spec, 0, False) * prompt_len
        attn = L * 4 * H * hd * prompt_len * (prompt_len + 1) / 2
        return base + attn + 2 * d * V
    return token_flops(spec, 0, False) * prompt_len \
        + token_flops(spec, 0, True) - token_flops(spec, 0, False)


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    """Weight matrices one program must read: layers, final norm, head."""
    if spec["family"] == "llama":
        d, H, Hkv, hd, ff, L, V = _dims(spec)
        layer = d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff \
            + 2 * d
        return itemsize * (L * layer + d + d * V)
    d, di, H, P, N, K, L, V = _dims(spec)
    layer = d * (2 * di + 2 * N + H) + di * d + K * (di + 2 * N) \
        + (di + 2 * N) + di + d
    # dt_bias, A_log and D are float32 (4 bytes each, per head)
    return itemsize * (L * layer + d + d * V) + L * 3 * H * 4


def decode_state_bytes(spec: dict, visible: int, itemsize: int = 2) -> int:
    """Cache bytes one active row's decode step must move."""
    if spec["family"] == "llama":
        d, H, Hkv, hd, ff, L, V = _dims(spec)
        return L * 2 * Hkv * hd * visible * itemsize
    d, di, H, P, N, K, L, V = _dims(spec)
    # the f32 state is read and written; the conv window likewise
    return L * 2 * (H * P * N * 4 + (K - 1) * (di + 2 * N) * itemsize)


def prefill_state_bytes(spec: dict, prompt_len: int,
                        itemsize: int = 2) -> int:
    """Cache bytes a prefill must write."""
    if spec["family"] == "llama":
        d, H, Hkv, hd, ff, L, V = _dims(spec)
        return L * 2 * Hkv * hd * prompt_len * itemsize
    d, di, H, P, N, K, L, V = _dims(spec)
    return L * (H * P * N * 4 + (K - 1) * (di + 2 * N) * itemsize)


def served_work(spec: dict, prompts: Sequence[int],
                decode_steps: Sequence[Sequence[int]]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one tenant's work: the prefills of ``prompts``
    (prompt lengths) and ``decode_steps``, each a list of the visible
    positions of its active rows."""
    flops = sum(prefill_flops(spec, p) for p in prompts)
    nbytes = sum(weight_bytes(spec) + prefill_state_bytes(spec, p)
                 for p in prompts)
    for rows in decode_steps:
        flops += sum(token_flops(spec, c, True) for c in rows)
        nbytes += weight_bytes(spec) + sum(decode_state_bytes(spec, c)
                                           for c in rows)
    return flops, nbytes
