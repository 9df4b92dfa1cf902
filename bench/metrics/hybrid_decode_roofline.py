"""The stacked hybrid decode body's share of its roofline, in percent:
the least time the layers' work of the hybrid tenants' decode steps
needs, max(FLOPs / peak, least bytes / HBM bandwidth), over the device
seconds of ``jit_hybrid_decode_layers`` in the traced interval.

The work is that of the decode steps whose tokens land in the interval
(``runctx.decode_steps``), counted by the family (``layer_weight_bytes``,
``token_flops`` without the logits, ``decode_state_bytes``) from the
configuration's shapes, with each row at its visible positions: the layers
only, whatever implements them. It cannot exceed 100; a reading above is
a counting fault. None where no hybrid body ran in the interval or the
family gives no layer counts."""
from __future__ import annotations

import family
from runctx import decode_steps

BODY = "jit_hybrid_decode_layers"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name == BODY)
    if seconds <= 0:
        return None
    fleet = ctx.fleet
    hybrid = [t for t, m in enumerate(fleet.tenant_model)
              if fleet.model_cfgs[m].arch_type == "hybrid"]
    recs = ctx.by_rid()
    events = [e for e in ctx.events if tr["t_lo"] <= e[0] < tr["t_hi"]]
    flops = nbytes = 0.0
    for t in hybrid:
        spec = ctx.spec(t)
        fam = family.of(spec)
        if not hasattr(fam, "layer_weight_bytes"):
            return None
        for tenant, step in decode_steps(events):
            if tenant != t:
                continue
            visible = [recs[e[1]].prompt_len + e[3] for e in step]
            flops += sum(fam.token_flops(spec, c, False) for c in visible)
            nbytes += fam.layer_weight_bytes(spec) \
                + sum(fam.decode_state_bytes(spec, c) for c in visible)
    least = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds if least > 0 else None
