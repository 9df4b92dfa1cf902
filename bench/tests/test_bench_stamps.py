"""The program's request stamps and decode-step counters at a test size on
the CPU: the stamps' order, the split of each request's time to first token
summing to the client's reading, the decode counters against the steps the
token stream shows, and the readers of ``bench/metrics`` that read them."""
import math
from pathlib import Path

import pytest

import fleet as fleet_mod
import lifecycle
import traffic
from runctx import Rec, RunContext, decode_steps
from stamps import StampedContext

DATA = Path(__file__).resolve().parent / "data"
NEW = lifecycle.NEW_METRICS


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(fleet_mod, "CONFIG_DIR", DATA / "configs")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", DATA / "traffic")


@pytest.mark.parametrize("cell", ["tiny-pair.chat", "tiny-mix.chat"])
def test_split_sums_to_the_client_ttft(tiny, cell):
    import json
    bench = json.loads((DATA / "bench.json").read_text())
    spec = {w["name"]: w for w in bench["workloads"]}[cell]
    out = lifecycle.measure(spec, 3000000123, 2.0, spans=False, trace=False,
                            need_chip=False, bench=bench)
    rows = out["requests"]
    assert len(rows) == round(6.0 * 2)
    for r in rows:
        released, admitted, first = r["stamps"]
        assert all(math.isfinite(x) for x in r["stamps"])
        assert released <= admitted <= first
        assert r["ttft_s"] is not None
        split = r["door_lag_s"] + r["queue_wait_s"] + r["prefill_s"]
        assert abs(split - r["ttft_s"]) <= 2e-3
    assert set(NEW) <= set(out["metrics"])
    assert out["counters"]["decode_step_count"] \
        == out["counters"]["decode_programs"] > 0


@pytest.mark.parametrize("config", ["tiny-pair", "tiny-mix"])
def test_decode_counters_match_the_token_stream(tiny, config):
    """The engine's decode-program counters against the steps
    ``runctx.decode_steps`` finds in the token stream, on a replayed
    schedule; the decode programs are also counted where they are built."""
    import jax
    from repro.serving import (FrontDoor, ServeRequest, ServingEngine,
                               VirtualClock)
    fl = fleet_mod.Fleet(config, seed=5)
    reqs = traffic.schedule(traffic.load_mix("tiny-chat"), config, 3.0)
    prompts = traffic.prompts(reqs, fl.vocab(), 5)
    eng = ServingEngine(fl.tenants(), mode="vliw")
    eng.make_prompt = lambda t, r, rng: jax.numpy.asarray(prompts[r.req_id])
    programs = []
    build = eng._build_program

    def counted(t, stream_id, now):
        programs.append(t.name)
        return build(t, stream_id, now)
    eng._build_program = counted
    events, seen = [], {}

    def sink_for(r):
        def sink(tok, t):
            seen[r.rid] = seen.get(r.rid, -1) + 1
            events.append((len(events), r.rid, r.tenant, seen[r.rid]))
        return sink
    door = FrontDoor()
    for r in reqs:
        door.submit(ServeRequest(r.rid, fl.tenant_names[r.tenant], 0.0,
                                 r.prompt_len, r.output_len, r.slo_s),
                    at=r.due_s, on_token=sink_for(r))
    door.close(at=3.0)
    rep = eng.serve_forever(door, clock=VirtualClock())
    assert rep.unfinished == 0
    js = eng.jit_stats
    assert js.decode_programs == len(decode_steps(events)) == len(programs)
    assert js.decode_rows == sum(r.output_len - 1 for r in reqs)
    assert js.decode_step_s.count == js.decode_programs
    assert 0 < js.decode_step_s.min <= js.decode_step_s.max


def _ctx(cls=StampedContext, **extra):
    """A 10 s window from t=100: requests due at 101, 102 and 103 and one
    at 115, past the window; the third never gets its first token before
    the drain ends at 120."""
    reqs = [Rec(0, 0, 101.0, 64, 4, times=[101.5]),
            Rec(1, 1, 102.0, 64, 4, times=[102.9]),
            Rec(2, 0, 103.0, 64, 4),
            Rec(3, 1, 115.0, 64, 4, times=[116.0])]
    jit = {"decode_programs": 8, "decode_rows": 20, "decode_step_count": 8,
           "decode_step_total_s": 0.4}
    return cls(None, 10.0, 100.0, 120.0, 1.0, reqs, [], jit, [], {}, {},
               **extra)


STAMPS = {0: (101.1, 101.2, 101.5),            # 0.1, 0.1, 0.3
          1: (102.05, 102.45, 102.9),          # 0.05, 0.4, 0.45
          2: (103.2, math.nan, math.nan),      # 0.2, 16.8 to the end, 0
          3: (115.0, 115.5, 116.0)}            # not due in the window


@pytest.mark.parametrize("name,want", [
    ("door_lag_p50_s", 0.1), ("queue_wait_p50_s", 0.4),
    ("prefill_p50_s", 0.3), ("decode_step_mean_ms", 50.0),
    ("decode_rows_mean", 2.5)])
def test_reader_on_a_hand_built_context(name, want):
    from run import reader
    assert reader(name)(_ctx(stamps=STAMPS)) == pytest.approx(want)


def test_split_of_an_unanswered_request_runs_to_the_drain_s_end():
    from stamps import ttft_split
    split = {r.rid: parts for r, *parts in ttft_split(_ctx(stamps=STAMPS))}
    assert sorted(split) == [0, 1, 2]
    assert split[2] == pytest.approx([0.2, 16.8, 0.0])
    assert sum(split[2]) == pytest.approx(120.0 - 103.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_where_the_program_kept_nothing(name):
    """``bench/run.py``'s context: no stamps, none of the decode keys."""
    from run import reader
    ctx = _ctx(cls=RunContext)
    ctx.jit = {}
    assert reader(name)(ctx) is None


def test_trace_details_on_a_synthetic_trace():
    """Stamps mapped through the window span onto the trace's clock: one
    inside its span, one 2 ms after it, one outside the recorded device
    passes (not counted); op time summed by instruction name."""
    import trace_reduce as tr
    ms = 1e6
    host = [(tr.WINDOW_SPAN, 0, 100 * ms),
            ("engine.device_pass", 10 * ms, 50 * ms),
            ("engine.prefill_land", 20 * ms, 30 * ms),
            ("engine.declare_prefill", 40 * ms, 41 * ms),
            ("door.poll", 5 * ms, 6 * ms), ("door.poll", 60 * ms, 61 * ms)]
    ops = [("%coalesced_gemm.46 = bf16[16,64] custom-call(...)", 0, 10 * ms),
           ("%coalesced_gemm.7 = bf16[8,64] custom-call(...)", 20 * ms,
            25 * ms), ("%while.2 = (s32[]) while(...)", 30 * ms, 40 * ms)]
    trace = tr.Trace([tr.Device(ops, [])], host)
    t_lo = 500.0                          # the window span opens here
    stamps = {1: (500.0055, 500.0405, 500.025),  # all inside their spans
              2: (500.0605, 500.043, 500.090)}   # 2 ms late; outside
    out = lifecycle.trace_details(trace, t_lo, stamps)
    d = out["stamp_to_span"]
    assert d["released_s"]["n"] == 2
    assert d["released_s"]["max_ms"] == pytest.approx(0.0)
    assert d["admitted_s"]["n"] == 2
    assert d["admitted_s"]["max_ms"] == pytest.approx(2.0)
    assert d["first_token_s"]["n"] == 1          # 90 ms: no device pass
    assert d["first_token_s"]["max_ms"] == pytest.approx(0.0)
    assert dict(out["op_names"]) == pytest.approx(
        {"coalesced_gemm": 0.015, "while": 0.010})
    assert out["spans_seen"] == ["engine.device_pass",
                                 "engine.declare_prefill", "door.poll",
                                 "engine.prefill_land"]
