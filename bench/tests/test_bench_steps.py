"""The token stream's decode-step grouping (runctx.decode_steps) against
the engine's own count of decode programs, at a test size on the CPU."""
from pathlib import Path

import pytest

import fleet as fleet_mod
import traffic
from runctx import decode_steps

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(fleet_mod, "CONFIG_DIR", DATA / "configs")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", DATA / "traffic")


@pytest.mark.parametrize("config", ["tiny-pair", "tiny-mix"])
def test_steps_match_decode_programs(tiny, config):
    import jax
    from repro.serving import (FrontDoor, ServeRequest, ServingEngine,
                               VirtualClock)
    fl = fleet_mod.Fleet(config, seed=5)
    mix = traffic.load_mix("tiny-chat")
    reqs = traffic.schedule(mix, config, 3.0)
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
    steps = decode_steps(events)
    assert len(steps) == len(programs)
    assert [fl.tenant_names[t] for t, _ in steps] == programs
    assert sum(len(rows) for _, rows in steps) \
        == sum(r.output_len - 1 for r in reqs)
    assert all(len({e[1] for e in rows}) == len(rows) for _, rows in steps)


def test_grouping_rules():
    ev = [(0, 1, 0, 0), (1, 1, 0, 1), (2, 2, 0, 1), (3, 1, 0, 2),
          (4, 3, 1, 0), (5, 3, 1, 1), (6, 2, 0, 2), (7, 1, 0, 3)]
    steps = decode_steps(ev)
    assert [(t, [e[1] for e in rows]) for t, rows in steps] \
        == [(0, [1, 2]), (0, [1]), (1, [3]), (0, [2, 1])]
