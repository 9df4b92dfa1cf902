"""The Falcon-H1 family (Falcon-H1-34B-Instruct): GQA attention and a
Mamba-2 mixer side by side on one normed input in every layer, each scaled
by its µP multiplier, then a SwiGLU FFN (the program's ``hybrid`` arch).

Model FLOPs of one token (a multiply-add counts 2): per layer 2 × the
layer's matrix weights (attention's four projections, the mixer's in and
out projections, the FFN's three), 2 K (di + 2 G N) for the depthwise
conv, 6 Hs P N for the recurrence (decay and input terms, and the
read-out) and 4 H hd c for the scores and the weighted sum over its c
visible positions; plus 2 d V for the logits where the token produces
them. A prompt is counted token by token: the least work.

Bytes: a decode step reads each active row's keys and values over its
visible positions and reads and writes its f32 state and its conv window;
a prefill writes them once. Every weight name is one ``fleet._leaf``
knows, so the family has no ``leaf`` rule.
"""
from __future__ import annotations

import numpy as np

import check
from reference import falcon_h1 as reference


def model_config(name: str, spec: dict):
    from repro.configs.base import ModelConfig, Multipliers, SSMConfig
    reference.check_switches(spec)
    gate, down = spec["mlp_multipliers"]
    return ModelConfig(
        name=name, arch_type="hybrid",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["head_dim"], d_ff=spec["intermediate_size"],
        vocab_size=spec["vocab_size"], rope_theta=spec["rope_theta"],
        norm_eps=spec["rms_norm_eps"],
        tie_embeddings=spec["tie_word_embeddings"],
        ssm=SSMConfig(
            d_state=spec["mamba_d_state"], head_dim=spec["mamba_d_head"],
            expand=spec["mamba_expand"],
            chunk_size=spec["mamba_chunk_size"], d_conv=spec["mamba_d_conv"],
            n_groups=spec["mamba_n_groups"], d_inner=spec["mamba_d_ssm"],
            in_multiplier=spec["ssm_in_multiplier"],
            proj_multipliers=tuple(spec["ssm_multipliers"])),
        multipliers=Multipliers(
            embedding=spec["embedding_multiplier"],
            lm_head=spec["lm_head_multiplier"], key=spec["key_multiplier"],
            attention_in=spec["attention_in_multiplier"],
            attention_out=spec["attention_out_multiplier"],
            ssm_out=spec["ssm_out_multiplier"], mlp_gate=gate,
            mlp_down=down))


def reference_logits(spec: dict, params, reqs, seq: int, reads: int,
                     wfn=None):
    """One request at a time: a whole sequence's attention fits the chip
    for one row, and the head is read in vocabulary blocks."""
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
            spec["intermediate_size"], spec["mamba_d_ssm"],
            spec["mamba_n_heads"], spec["mamba_d_head"],
            spec["mamba_n_groups"] * spec["mamba_d_state"],
            spec["mamba_d_state"], spec["mamba_d_conv"],
            spec["num_hidden_layers"], spec["vocab_size"])


def _layer_matrices(spec: dict) -> int:
    d, H, Hkv, hd, ff, di, Hs, P, GN, N, K, L, V = _dims(spec)
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d \
        + d * (2 * di + 2 * GN + Hs) + di * d + 3 * d * ff


def token_flops(spec: dict, visible: int, logits: bool) -> float:
    d, H, Hkv, hd, ff, di, Hs, P, GN, N, K, L, V = _dims(spec)
    per = 2 * _layer_matrices(spec) + 2 * K * (di + 2 * GN) \
        + 6 * Hs * P * N + 4 * H * hd * visible
    return L * per + (2 * d * V if logits else 0)


def prefill_flops(spec: dict, prompt_len: int) -> float:
    d, H, Hkv, hd, ff, di, Hs, P, GN, N, K, L, V = _dims(spec)
    base = token_flops(spec, 0, False) * prompt_len
    attn = L * 4 * H * hd * prompt_len * (prompt_len + 1) / 2
    return base + attn + 2 * d * V


def layer_weight_bytes(spec: dict, itemsize: int = 2) -> int:
    """The layers' weights alone: what one decode step's layer bodies must
    read (the head and the final norm left out)."""
    d, H, Hkv, hd, ff, di, Hs, P, GN, N, K, L, V = _dims(spec)
    # matrices, the conv's taps and bias, the gated norm's gain, ln1, ln2
    layer = _layer_matrices(spec) + (K + 1) * (di + 2 * GN) + di + 2 * d
    # dt_bias, A_log and D are float32 (4 bytes each, per head)
    return itemsize * L * layer + L * 3 * Hs * 4


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    d = spec["hidden_size"]
    return layer_weight_bytes(spec, itemsize) \
        + itemsize * (d + d * spec["vocab_size"])


def decode_state_bytes(spec: dict, visible: int, itemsize: int = 2) -> int:
    d, H, Hkv, hd, ff, di, Hs, P, GN, N, K, L, V = _dims(spec)
    # keys and values over the visible positions; the f32 state and the
    # conv window are read and written
    return L * (2 * Hkv * hd * visible * itemsize
                + 2 * (Hs * P * N * 4 + (K - 1) * (di + 2 * GN) * itemsize))


def prefill_state_bytes(spec: dict, prompt_len: int,
                        itemsize: int = 2) -> int:
    d, H, Hkv, hd, ff, di, Hs, P, GN, N, K, L, V = _dims(spec)
    return L * (2 * Hkv * hd * prompt_len * itemsize
                + Hs * P * N * 4 + (K - 1) * (di + 2 * GN) * itemsize)
