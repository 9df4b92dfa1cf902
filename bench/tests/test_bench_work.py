"""FLOPs and bytes of served work (bench/work.py) against hand counts at
the cells' cuts, and the roofline share against a synthetic trace."""
import dataclasses
import json

import pytest

import trace_reduce
import work
from fleet import load_config
from runctx import RunContext

YI = load_config("yi9b-pair")["models"]["yi-9b"]
MAMBA = load_config("yi9b-mamba2")["models"]["mamba2-2.7b"]

# Yi-9B, 4 layers: q 4096x4096, k/v 4096x512 each, o 4096x4096 and the
# SwiGLU FFN 3x4096x11008 are 173,015,040 weights a layer
YI_LAYER_FLOPS = 2 * 173_015_040
YI_LOGITS = 2 * 4096 * 64000
YI_ATTN = 4 * 32 * 128 * 4                 # per visible position, 4 layers
# Mamba2-2.7B, 16 layers: in_proj 2560x10576, out_proj 5120x2560, conv
# 4x5376, recurrence 6 x 80 heads x 64 x 128
MAMBA_LAYER_FLOPS = 2 * 2560 * 10576 + 2 * 5120 * 2560 + 2 * 4 * 5376 \
    + 6 * 80 * 64 * 128
MAMBA_LOGITS = 2 * 2560 * 50277


def test_yi_token_and_prefill_flops():
    assert work.token_flops(YI, 1000, True) \
        == 4 * YI_LAYER_FLOPS + YI_ATTN * 1000 + YI_LOGITS
    assert work.token_flops(YI, 1, False) == 4 * YI_LAYER_FLOPS + YI_ATTN
    p = 512
    assert work.prefill_flops(YI, p) \
        == 4 * YI_LAYER_FLOPS * p + YI_ATTN * p * (p + 1) / 2 + YI_LOGITS


def test_mamba2_token_and_prefill_flops():
    assert MAMBA_LAYER_FLOPS == 84_338_688
    assert work.token_flops(MAMBA, 999, True) \
        == 16 * MAMBA_LAYER_FLOPS + MAMBA_LOGITS
    assert work.prefill_flops(MAMBA, 256) \
        == 16 * MAMBA_LAYER_FLOPS * 256 + MAMBA_LOGITS


def test_weight_and_state_bytes():
    # layers (projections + two norm gains), final norm, unembedding
    assert work.weight_bytes(YI) \
        == 2 * (4 * (173_015_040 + 2 * 4096) + 4096 + 4096 * 64000)
    # in/out proj, conv w+b, gated norm, ln1; final norm; tied head;
    # three float32 scalars per head and layer
    layer = 2560 * 10576 + 5120 * 2560 + 4 * 5376 + 5376 + 5120 + 2560
    assert work.weight_bytes(MAMBA) \
        == 2 * (16 * layer + 2560 + 2560 * 50277) + 16 * 3 * 80 * 4
    assert work.decode_state_bytes(YI, 1000) == 4 * 2 * 4 * 128 * 1000 * 2
    assert work.decode_state_bytes(MAMBA, 1000) \
        == 16 * 2 * (80 * 64 * 128 * 4 + 3 * 5376 * 2)
    assert work.prefill_state_bytes(YI, 300) == 4 * 2 * 4 * 128 * 300 * 2


@dataclasses.dataclass
class _Fleet:
    cfg: dict
    tenant_model: list
    tenant_names: list
    tree_of: list


def _ctx(busy_scale: float):
    """Two Yi tenants' prefills and decode steps, and a synthetic device
    trace in which each program takes ``busy_scale`` times its own least
    time, one after the other."""
    from runctx import Rec
    cfg = load_config("yi9b-pair")
    fleet = _Fleet(cfg, ["yi-9b", "yi-9b"], ["t0", "t1"], [0, 1])
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    recs, events, ops, t = [], [], [], 1.0
    for tenant in (0, 1):
        for i in range(3):
            recs.append(Rec(len(recs), tenant, 0.0, 512 + 256 * i, 20))
    for step in range(20):
        for tenant in (0, 1):
            rows = [r for r in recs if r.tenant == tenant]
            if step == 0:
                for r in rows:
                    f, b = work.served_work(YI, [r.prompt_len], [])
                    dt = busy_scale * max(f / 197e12, b / 819e9)
                    ops.append(("prefill", t * 1e9, (t + dt) * 1e9))
                    t += dt + 1e-3
                    events.append((t, r.rid, tenant, 0))
                continue
            vis = [r.prompt_len + step for r in rows]
            f, b = work.served_work(YI, [], [vis])
            dt = busy_scale * max(f / 197e12, b / 819e9)
            ops.append(("decode", t * 1e9, (t + dt) * 1e9))
            t += dt + 1e-3
            events += [(t, r.rid, tenant, step) for r in rows]
    host = [(trace_reduce.WINDOW_SPAN, 0.5e9, (t + 0.1) * 1e9)]
    tr = trace_reduce.Trace([trace_reduce.Device(ops, [])], host)
    red = trace_reduce.reduce(tr, trace_reduce.window_of(tr))
    red.update(t_lo=0.5, t_hi=t + 0.1)
    return RunContext(fleet, 10.0, 0.0, t, 1.0, recs, events, {}, [],
                      {"peak": 1, "limit": 2}, peaks, red)


@pytest.mark.parametrize("scale", [1.0, 1.5, 4.0])
def test_busy_roofline_bounded_by_100(scale):
    from run import reader
    roof = reader("busy_roofline")(_ctx(scale))
    assert 0 < roof <= 100.0 + 1e-6
    assert roof == pytest.approx(100.0 / scale, rel=0.35)
    mfu = reader("step_mfu")(_ctx(scale))
    idle = reader("device_idle_share")(_ctx(scale))
    assert 0 < mfu < roof and 0 <= idle < 100


def test_benchmark_metrics_have_readers():
    from run import ROOT, reader
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(reader(m["name"]))


def test_padded_vocab_follows_the_source():
    from fleet import padded_vocab
    assert padded_vocab(MAMBA) == 50288
    assert padded_vocab({"vocab_size": 64000}) == 64000


def _latency_ctx():
    """Three requests due at 0, 1 and 9 s of a 10 s window starting at 100:
    the first answered at gaps of 0.1 s, the second with one 2 s gap, the
    third never answered; a gap whose later token falls past the window
    is left out."""
    from runctx import Rec
    a = Rec(0, 0, 100.0, 64, 4, times=[100.5, 100.6, 100.7, 100.8])
    b = Rec(1, 1, 101.0, 64, 3, times=[101.2, 103.2, 110.5])
    c = Rec(2, 0, 109.0, 64, 2)
    return RunContext(None, 10.0, 100.0, 112.0, 1.0, [a, b, c], [], {}, [],
                      {}, {})


def test_ttft_median_counts_every_due_request():
    from run import reader
    # 0.5, 0.2, and 3.0 (the unanswered one waits until the drain ends)
    assert reader("ttft_p50_s")(_latency_ctx()) == pytest.approx(0.5)


def test_itl_p95_over_every_gap_in_the_window():
    import numpy as np
    from run import reader
    want = float(np.percentile([0.1, 0.1, 0.1, 2.0], 95))
    assert reader("itl_p95_s")(_latency_ctx()) == pytest.approx(want)
