"""The traffic generator: the mix fixes the schedule, the seed the prompt
tokens, and the warm-up covers every shape the window sends."""
import json

import numpy as np
import pytest

import traffic

MIX = json.loads((traffic.TRAFFIC_DIR / "chat.json").read_text())


def _key(reqs):
    return [(r.tenant, r.due_s, r.prompt_len, r.output_len) for r in reqs]


def test_schedule_is_the_mix_s_and_prompts_the_seed_s():
    a = traffic.schedule(MIX, "yi9b-pair", 30.0)
    b = traffic.schedule(MIX, "yi9b-pair", 30.0)
    assert _key(a) == _key(b)
    vocab = {0: 64000, 1: 64000}
    pa = traffic.prompts(a, vocab, 2**31 + 5)
    pb = traffic.prompts(b, vocab, 2**31 + 5)
    pc = traffic.prompts(b, vocab, 2**31 + 6)
    assert all(np.array_equal(pa[k], pb[k]) for k in pa)
    assert not all(np.array_equal(pa[k], pc[k]) for k in pa)
    assert all(pa[r.rid].shape == (1, r.prompt_len) for r in a)


def test_schedule_order_follows_the_schedule_seed():
    other = dict(MIX, schedule_seed=MIX["schedule_seed"] + 1)
    a = traffic.schedule(MIX, "yi9b-pair", 30.0)
    b = traffic.schedule(other, "yi9b-pair", 30.0)
    assert _key(a) != _key(b)
    for f in ("prompt_len", "output_len", "tenant"):
        assert sorted(getattr(r, f) for r in a) \
            == sorted(getattr(r, f) for r in b)
    assert abs(a[-1].due_s - b[-1].due_s) < 1e-9
    assert len(a) == round(MIX["rate_per_s"]["yi9b-pair"] * 30)
    assert all(0 <= r.due_s < 30.0 for r in a)


def test_lengths_follow_the_mix():
    reqs = traffic.schedule(MIX, "yi9b-pair", 50.0)
    p = np.array([r.prompt_len for r in reqs])
    o = np.array([r.output_len for r in reqs])
    spec = MIX["prompt_len"]
    assert p.min() >= spec["min"] and p.max() <= spec["max"]
    assert abs(np.median(p) - spec["median"]) <= 0.1 * spec["median"]
    # whole tokens, not a grid: nearly every request has a length of its own
    assert len(set(p)) >= 0.8 * len(p)
    out = MIX["output_len"]
    assert o.min() >= out["min"] and o.max() <= out["max"]
    assert p.mean() > o.mean()


@pytest.mark.parametrize("mix", ["chat"])
def test_warmup_covers_every_length_and_slot(mix):
    m = traffic.load_mix(mix)
    reqs = traffic.schedule(m, "yi9b-pair", 40.0)
    warm = traffic.warmup_schedule(m, reqs, 16, rid0=10**7)
    for t in {r.tenant for r in reqs}:
        mine = [r for r in warm if r.tenant == t]
        assert {r.prompt_len for r in mine} \
            == {r.prompt_len for r in reqs if r.tenant == t}
        assert len(mine) >= 16
    assert {r.tenant for r in warm} == {r.tenant for r in reqs}
    assert all(r.due_s == 0.0 and r.rid >= 10**7 for r in warm)


def test_poisson_gaps_keep_the_mean_rate():
    g = traffic.gap_set({"process": "poisson"}, 4.0, 1000)
    assert len(g) == 1000 and g.min() > 0
    assert abs(g.mean() - 0.25) < 0.01
    with pytest.raises(ValueError):
        traffic.gap_set({"process": "mmpp"}, 4.0, 10)
