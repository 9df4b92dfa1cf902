"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed with each tenant's longest
request in it, is run through its model family's plain float32 reference
(``reference/``, called by ``families/<family>.py``) over its prompt and its
served tokens. The number compared, per model, is
the widest gap by which a served (greedy) token's reference logit lies
below the reference's best logit at that position, and the mean of that
shortfall over the sampled positions:

    gap_j = max(ref_j) - ref_j[served_j],
    logit_gap = max_j gap_j,    mean_logit_gap = mean_j gap_j.

A number with no limit in the configuration file is not compared (it is
printed on standard error only). A run is correct when every model of the
fleet has its compared numbers and each is within its limit (``correct``).

The control (``compare(..., controls=...)``) is the same reference with
its weight matrices rounded to a lower precision (``WEIGHT_FORMATS``),
read at the same positions: the gaps of the tokens it puts first. Its
numbers go through the same ``readings`` and ``correct`` as the program's.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

import family
from traffic import rng_for

TOKENS_PER_TENANT = 256      # served tokens sampled per tenant, at least
MAX_PER_TENANT = 8           # requests sampled per tenant, at most


@dataclasses.dataclass
class Served:
    rid: int
    tenant: int
    prompt: np.ndarray       # [prompt_len] int32
    tokens: List[int]        # served tokens, first one from the prefill


def fp8_weights(w):
    """float8 e4m3 with a per-output-channel scale to its range."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16_weights(w):
    """bfloat16, the control of a float32 configuration."""
    return w.astype(jnp.bfloat16).astype(jnp.float32)


WEIGHT_FORMATS: Dict[str, Callable] = {"fp8": fp8_weights,
                                       "bf16": bf16_weights}


def sample(served: Sequence[Served], n_tenants: int, seed: int
           ) -> Dict[int, List[Served]]:
    """Per tenant: its longest finished request, then others in an order
    drawn from the seed, until TOKENS_PER_TENANT served tokens or
    MAX_PER_TENANT requests."""
    rng = rng_for(seed, 4)
    out: Dict[int, List[Served]] = {}
    for t in range(n_tenants):
        mine = [s for s in served if s.tenant == t]
        if not mine:
            continue
        longest = max(mine, key=lambda s: (len(s.prompt) + len(s.tokens),
                                           -s.rid))
        rest = [mine[i] for i in rng.permutation(len(mine))
                if mine[i] is not longest]
        pick, n = [longest], len(longest.tokens)
        for s in rest:
            if n >= TOKENS_PER_TENANT or len(pick) >= MAX_PER_TENANT:
                break
            pick.append(s)
            n += len(s.tokens)
        out[t] = pick
    return out


def reference_inputs(s: Served, seq: int, reads: int):
    """The reference's input (prompt + served tokens but the last, padded
    to ``seq``) and the positions whose logits chose each served token."""
    toks = np.zeros(seq, np.int32)
    full = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
    toks[: len(full)] = full
    read = np.zeros(reads, np.int32)
    n = len(s.tokens)
    read[:n] = len(s.prompt) - 1 + np.arange(n)
    return toks, read


def reference_logits(spec: dict, params, reqs: Sequence[Served], seq: int,
                     reads: int, wfn=None) -> List[np.ndarray]:
    """Reference logits [len(tokens), vocab] of each request, from the
    model family's reference, in blocks of the family's choosing that fit
    the chip."""
    return family.of(spec).reference_logits(spec, params, reqs, seq, reads,
                                            wfn=wfn)


def gaps(ref: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """max(ref_j) - ref_j[chosen_j] at every position j."""
    ref = np.asarray(ref, np.float64)
    idx = np.asarray(chosen, np.int64)
    return ref.max(axis=-1) - ref[np.arange(len(idx)), idx]


STATS = ("logit_gap", "mean_logit_gap")


def _stats(per_position: List[np.ndarray]) -> Dict[str, float]:
    g = np.concatenate(per_position)
    return {"logit_gap": float(g.max()), "mean_logit_gap": float(g.mean())}


@dataclasses.dataclass
class Reading:
    name: str                # "<stat>.<model>"
    value: float
    limit: Optional[float]
    tokens: int              # served tokens compared
    requests: int

    @property
    def ok(self) -> bool:
        return self.limit is not None and self.value <= self.limit


def compare(fleet, served: Sequence[Served], seed: int, seq: int,
            reads: int, controls: Sequence[str] = ()) -> Dict[str, dict]:
    """The program's gaps per model over the seed's sample, and with
    ``controls`` the same under each lower-precision weight format (the
    gaps of the tokens the control puts first). Returns {model: {"program":
    {stat: value}, "<format>": {stat: value}, "tokens": n, "requests": k}}.
    """
    picked = sample(served, len(fleet.tenant_names), seed)
    per: Dict[str, Dict[str, list]] = {}
    out: Dict[str, dict] = {}
    for t, reqs in sorted(picked.items()):
        model = fleet.tenant_model[t]
        spec = fleet.cfg["models"][model]
        ref = reference_logits(spec, fleet.params[t], reqs, seq, reads)
        acc = per.setdefault(model, {})
        row = out.setdefault(model, {"tokens": 0, "requests": 0})
        for s, r in zip(reqs, ref):
            acc.setdefault("program", []).append(gaps(r, s.tokens))
            row["tokens"] += len(s.tokens)
            row["requests"] += 1
        for fmt in controls:
            ctl = reference_logits(spec, fleet.params[t], reqs, seq, reads,
                                   wfn=WEIGHT_FORMATS[fmt])
            for r, c in zip(ref, ctl):
                acc.setdefault(fmt, []).append(gaps(r, np.argmax(c, -1)))
    for model, acc in per.items():
        out[model].update({k: _stats(v) for k, v in acc.items()})
    return out


def readings(fleet, compared: Dict[str, dict], source: str = "program"
             ) -> List[Reading]:
    """Every (stat, model) number of ``source`` (the program, or a control's
    weight format) with its limit (None: not compared)."""
    return [Reading(f"{stat}.{m}", g[source][stat], fleet.limit(m, stat),
                    g["tokens"], g["requests"])
            for m, g in sorted(compared.items()) for stat in STATS]


def correct(fleet, read: Sequence[Reading]) -> bool:
    """Every model of the fleet was compared, and every compared number is
    within its limit."""
    compared = [r for r in read if r.limit is not None]
    return {r.name.split(".", 1)[1] for r in compared} \
        == set(fleet.tenant_model) and all(r.ok for r in compared)
