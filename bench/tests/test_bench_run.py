"""The harness end to end at a test size on the CPU: the chip check, the
last line's schema, and ``correct`` coming out false for each fault a
served cell can have, planted underneath the timed path."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

import fleet as fleet_mod
import run
import traffic

DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((DATA / "bench.json").read_text())


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(fleet_mod, "CONFIG_DIR", DATA / "configs")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", DATA / "traffic")


def _run(capsys, on_engine=None, seed=3000000123):
    rc = run.run(["--workload", "tiny-mix.chat", "--seed", str(seed),
                  "--seconds", "2", "--trace", "0"], need_chip=False,
                 bench=BENCH, on_engine=on_engine)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_refuses_a_machine_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "yi9b-pair.chat", "--seed", str(2**31 + 9),
                        "--seconds", "1", "--trace", "0"],
                       cwd=run.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_last_line_schema(tiny, capsys):
    res = _run(capsys)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == round(6.0 * 2)
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert set(res["metrics"]) == names
    for v in res["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(res["check"]) == {"logit_gap.tiny-llama",
                                 "logit_gap.tiny-mamba2"}
    for c in res["check"].values():
        assert c["value"] <= c["limit"]


def _state_unchanged(eng):
    """A decode step that returns its state unchanged: the cache its
    program wrote is dropped for the one it read."""
    saved, build, consume = {}, eng._build_program, eng._consume

    def built(t, sid, now):
        saved[t.name] = t.cache
        return build(t, sid, now)

    def consumed(t, logits, now):
        t.cache = saved.pop(t.name, t.cache)
        return consume(t, logits, now)
    eng._build_program, eng._consume = built, consumed


def _half_batch(eng):
    """Half of the decode batch left out: the second half of the rows get
    the first half's results."""
    consume = eng._consume

    def consumed(t, logits, now):
        h = logits.shape[0] // 2
        return consume(t, jnp.concatenate([logits[:h], logits[:h]]), now)
    eng._consume = consumed


def _token_altered(eng):
    """A token altered where it is produced: row 0's logits rolled by one,
    so its greedy token is the neighbour of the right one."""
    consume = eng._consume

    def consumed(t, logits, now):
        return consume(t, logits.at[0].set(jnp.roll(logits[0], 1, -1)), now)
    eng._consume = consumed


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_fault_makes_the_run_incorrect(tiny, capsys, fault):
    res = _run(capsys, on_engine=fault)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["check"].values())
