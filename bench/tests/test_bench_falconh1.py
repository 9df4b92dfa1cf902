"""The Falcon-H1 family (``families/falconh1.py``): the cell's file, its
work counts at the cell's cut against hand counts, the two hybrid readers on a synthetic
trace, and a tiny Falcon-H1 fleet served through ``run.run`` to a correct
last line."""
import dataclasses
import json
from pathlib import Path

import pytest

import fleet as fleet_mod
import trace_reduce
import traffic
import work
from runctx import Rec, RunContext

DATA = Path(__file__).resolve().parent / "data"
CELL = fleet_mod.load_config("falconh1-yi9b")
FALCON = CELL["models"]["falcon-h1-34b"]

# Falcon-H1-34B, one layer: attention q 5120x2560, k/v 5120x512, o
# 2560x5120 (31,457,280); mixer in_proj 5120x9248 (z 4096, x 4096, B and C
# 2x256 each, dt 32), out_proj 4096x5120, conv taps and bias 5x5120, the
# gated norm 4096, dt_bias/A_log/D 3x32 (68,351,072); FFN 3x5120x21504
# (330,301,440); ln1 and ln2 10,240: 430,120,032 parameters
LAYER_PARAMS = 31_457_280 + 68_351_072 + 330_301_440 + 10_240
LAYER_BYTES = 2 * (LAYER_PARAMS - 96) + 4 * 96      # 96 float32 scalars
HEAD_BYTES = 2 * 261_120 * 5120
MATRICES = 31_457_280 + 5120 * 9248 + 4096 * 5120 + 330_301_440


def test_the_published_config_sits_at_the_top_of_the_file():
    # the file holds Falcon-H1-34B's published config.json at its top
    # level; the harness reads models["falcon-h1-34b"], which must repeat it
    published = {k: v for k, v in FALCON.items() if k != "family"}
    assert len(published) == 42
    assert {k: CELL[k] for k in published} == published
    assert CELL["num_hidden_layers"] == 4
    assert CELL["published"]["falcon-h1-34b"] == {"num_hidden_layers": 72}
    assert CELL["reduced"] == ["num_hidden_layers"]


def test_falconh1_work_counts_by_hand():
    assert LAYER_PARAMS == 430_120_032
    assert LAYER_BYTES == 860_240_256              # 0.86 GB a layer
    assert HEAD_BYTES == 2_673_868_800             # 2.67 GB
    assert work.weight_bytes(FALCON) \
        == 4 * LAYER_BYTES + HEAD_BYTES + 2 * 5120
    fam = __import__("family").of(FALCON)
    assert fam.layer_weight_bytes(FALCON) == 4 * LAYER_BYTES
    # per token and layer: the matrices, the conv (4 taps over 5120
    # channels), the recurrence (6 x 32 heads x 128 x 256) and 4 x 20 heads
    # x 128 per visible position; the head 2 x 5120 x 261120
    per = 2 * MATRICES + 2 * 4 * 5120 + 6 * 32 * 128 * 256
    assert work.token_flops(FALCON, 1000, True) \
        == 4 * (per + 4 * 20 * 128 * 1000) + 2 * 5120 * 261_120
    assert work.token_flops(FALCON, 1, False) == 4 * (per + 4 * 20 * 128)
    assert work.prefill_flops(FALCON, 512) \
        == 4 * per * 512 + 4 * 4 * 20 * 128 * 512 * 513 / 2 \
        + 2 * 5120 * 261_120
    # KV rows over the visible positions; the f32 state (32 x 128 x 256)
    # and the conv window (3 x 5120) read and written
    assert work.decode_state_bytes(FALCON, 1000) \
        == 4 * (2 * 4 * 128 * 1000 * 2 + 2 * (32 * 128 * 256 * 4
                                              + 3 * 5120 * 2))
    assert work.prefill_state_bytes(FALCON, 300) \
        == 4 * (2 * 4 * 128 * 300 * 2 + 32 * 128 * 256 * 4 + 3 * 5120 * 2)


@dataclasses.dataclass
class _Fleet:
    cfg: dict
    tenant_model: list
    tenant_names: list
    tree_of: list
    model_cfgs: dict


def _ctx(scale: float, body: str = "jit_hybrid_decode_layers"):
    """Falcon-H1's decode steps (three rows) and a synthetic trace in which
    each step's hybrid body takes ``scale`` times its layers' least time,
    beside a head op of a fixed length."""
    cfg = fleet_mod.load_config("falconh1-yi9b")
    fam = __import__("family").of(FALCON)
    mcfg = fleet_mod.model_config("falcon-h1-34b", FALCON)
    fleet = _Fleet(cfg, ["falcon-h1-34b"], ["t0"], [0],
                   {"falcon-h1-34b": mcfg})
    recs = [Rec(i, 0, 0.0, 600 + 100 * i, 40) for i in range(3)]
    events, ops, t = [], [], 1.0
    for step in range(1, 30):
        vis = [r.prompt_len + step for r in recs]
        f = sum(fam.token_flops(FALCON, c, False) for c in vis)
        b = fam.layer_weight_bytes(FALCON) \
            + sum(fam.decode_state_bytes(FALCON, c) for c in vis)
        dt = scale * max(f / 197e12, b / 819e9)
        ops.append((body, t * 1e9, (t + dt) * 1e9))
        ops.append(("jit_hybrid_decode_head", (t + dt) * 1e9,
                    (t + dt + 0.003) * 1e9))
        t += dt + 0.004
        events += [(t, r.rid, 0, step) for r in recs]
    host = [(trace_reduce.WINDOW_SPAN, 0.5e9, (t + 0.1) * 1e9)]
    tr = trace_reduce.Trace([trace_reduce.Device(ops, ops)], host)
    red = trace_reduce.reduce(tr, trace_reduce.window_of(tr))
    red.update(t_lo=0.5, t_hi=t + 0.1)
    return RunContext(fleet, 10.0, 0.0, t, 1.0, recs, events, {}, [],
                      {"peak": 1, "limit": 2},
                      {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, red)


@pytest.mark.parametrize("scale", [1.0, 1.6, 3.0])
def test_hybrid_readers_on_a_synthetic_trace(scale):
    from run import reader
    ctx = _ctx(scale)
    roof = reader("hybrid_decode_roofline")(ctx)
    assert roof == pytest.approx(100.0 / scale, rel=1e-6)
    body = dict(ctx.trace["device_ops"])["jit_hybrid_decode_layers"]
    share = reader("hybrid_decode_share")(ctx)
    assert share == pytest.approx(100.0 * body / ctx.trace["busy_s"])
    assert 0 < share < 100
    # a program without the hybrid body: both readers read nothing
    other = _ctx(scale, body="jit_dense_decode_layers")
    assert reader("hybrid_decode_roofline")(other) is None
    assert reader("hybrid_decode_share")(other) is None


def test_a_tiny_falcon_h1_fleet_runs(monkeypatch, capsys):
    import run
    monkeypatch.setattr(fleet_mod, "CONFIG_DIR", DATA / "configs")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", DATA / "traffic")
    bench = json.loads((DATA / "bench.json").read_text())
    bench["workloads"] = [{"name": "tiny-falconh1.solo",
                           "config": "tiny-falconh1",
                           "traffic": "tiny-solo", "chips": 1}]
    rc = run.run(["--workload", "tiny-falconh1.solo", "--seed",
                  str(2**31 + 1717), "--seconds", "2", "--trace", "0"],
                 need_chip=False, bench=bench)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 4
    assert set(res["check"]) == {"logit_gap.tiny-falconh1",
                                 "logit_gap.tiny-llama"}
