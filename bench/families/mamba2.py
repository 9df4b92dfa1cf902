"""The Mamba2 family (Mamba2-2.7B): an attention-free SSD stack.

Model FLOPs of one token (a multiply-add counts 2): per layer
2 d (2 di + 2 N + H) + 2 di d for the projections, 2 K (di + 2 N) for the
depthwise conv, and 6 H P N for the recurrence (decay and input terms, and
the read-out); plus 2 d V for the logits. A prompt is counted token by
token, the recurrent form: the least work.

Bytes: a decode step reads and writes each active row's recurrent and conv
state; a prefill writes them once.
"""
from __future__ import annotations

import numpy as np

import check
from fleet import padded_vocab
from reference import mamba2 as reference


def model_config(name: str, spec: dict):
    from repro.configs.base import ModelConfig, SSMConfig
    s = spec["ssm_cfg"]
    if s["ngroups"] != 1:
        raise ValueError("the program's SSD block has one B/C group")
    return ModelConfig(
        name=name, arch_type="ssm", num_layers=spec["n_layer"],
        d_model=spec["d_model"], num_heads=0, num_kv_heads=0, d_ff=0,
        vocab_size=padded_vocab(spec), norm_eps=spec["rms_norm_eps"],
        tie_embeddings=spec["tie_embeddings"],
        ssm=SSMConfig(d_state=s["d_state"], head_dim=s["headdim"],
                      expand=s["expand"], chunk_size=s["chunk_size"],
                      d_conv=s["d_conv"]))


def reference_logits(spec: dict, params, reqs, seq: int, reads: int,
                     wfn=None):
    """A fixed batch of ``check.MAX_PER_TENANT`` requests at a time: the
    token-by-token scan carries only a state per row."""
    kw = {} if wfn is None else {"wfn": wfn}
    n = check.MAX_PER_TENANT
    out = []
    for i in range(0, len(reqs), n):
        block = list(reqs[i:i + n])
        toks = np.zeros((n, seq), np.int32)
        read = np.zeros((n, reads), np.int32)
        for j, s in enumerate(block):
            toks[j], read[j] = check.reference_inputs(s, seq, reads)
        lg = np.asarray(reference.logits(params, spec, toks, read, **kw))
        out += [lg[j, : len(s.tokens)] for j, s in enumerate(block)]
    return out


def _dims(spec: dict):
    s = spec["ssm_cfg"]
    d = spec["d_model"]
    di = s["expand"] * d
    return (d, di, di // s["headdim"], s["headdim"], s["d_state"],
            s["d_conv"], spec["n_layer"], spec["vocab_size"])


def token_flops(spec: dict, visible: int, logits: bool) -> float:
    d, di, H, P, N, K, L, V = _dims(spec)
    per = 2 * d * (2 * di + 2 * N + H) + 2 * di * d \
        + 2 * K * (di + 2 * N) + 6 * H * P * N
    return L * per + (2 * d * V if logits else 0)


def prefill_flops(spec: dict, prompt_len: int) -> float:
    return token_flops(spec, 0, False) * prompt_len \
        + token_flops(spec, 0, True) - token_flops(spec, 0, False)


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    d, di, H, P, N, K, L, V = _dims(spec)
    layer = d * (2 * di + 2 * N + H) + di * d + K * (di + 2 * N) \
        + (di + 2 * N) + di + d
    # dt_bias, A_log and D are float32 (4 bytes each, per head)
    return itemsize * (L * layer + d + d * V) + L * 3 * H * 4


def decode_state_bytes(spec: dict, visible: int, itemsize: int = 2) -> int:
    d, di, H, P, N, K, L, V = _dims(spec)
    # the f32 state is read and written; the conv window likewise
    return L * 2 * (H * P * N * 4 + (K - 1) * (di + 2 * N) * itemsize)


def prefill_state_bytes(spec: dict, prompt_len: int,
                        itemsize: int = 2) -> int:
    d, di, H, P, N, K, L, V = _dims(spec)
    return L * (H * P * N * 4 + (K - 1) * (di + 2 * N) * itemsize)
