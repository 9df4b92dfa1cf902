"""Operations and bytes of served work, counted from the configuration's
shapes (not from what the program pads or recomputes). Each count is the
model family's (``bench/families/<family>.py``, see ``family.py``); this
module dispatches to it and sums one tenant's work.

Model FLOPs count a multiply-add as 2. Bytes are a lower bound of what the
device must move: every program of a tenant (a decode step or a prefill)
reads that tenant's weight matrices, unpadded, once: layers, final norm and
the output head (the embedding gather reads rows only and is left out);
besides, a decode step moves each active row's cache and a prefill writes
its prompt's, as the family counts them.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import family


def token_flops(spec: dict, visible: int, logits: bool) -> float:
    """Model FLOPs of one token that sees ``visible`` positions (itself
    included), with or without its logits."""
    return family.of(spec).token_flops(spec, visible, logits)


def prefill_flops(spec: dict, prompt_len: int) -> float:
    """A prompt's FLOPs, with the logits of its last position only."""
    return family.of(spec).prefill_flops(spec, prompt_len)


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    """Weight matrices one program must read: layers, final norm, head."""
    return family.of(spec).weight_bytes(spec, itemsize)


def decode_state_bytes(spec: dict, visible: int, itemsize: int = 2) -> int:
    """Cache bytes one active row's decode step must move."""
    return family.of(spec).decode_state_bytes(spec, visible, itemsize)


def prefill_state_bytes(spec: dict, prompt_len: int,
                        itemsize: int = 2) -> int:
    """Cache bytes a prefill must write."""
    return family.of(spec).prefill_state_bytes(spec, prompt_len, itemsize)


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
