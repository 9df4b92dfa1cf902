"""Model families as plug-ins (``bench/family.py``): the two families of the
benchmark give, through their modules, exactly the values the harness gave
before they were modules; a family, configuration and mix that live only in
the test data run through ``run.run`` to a correct last line; and an unknown
family names the file it looked for."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import family
import fleet as fleet_mod
import run
import traffic
import work

DATA = Path(__file__).resolve().parent / "data"
TINY = json.loads((DATA / "configs" / "tiny-mix.json").read_text())["models"]
REAL = {**fleet_mod.load_config("yi9b-pair")["models"],
        **fleet_mod.load_config("yi9b-mamba2")["models"]}

# the values the harness gave before the families were modules, at the
# visible positions 1, 17, 1000 (token_flops, without and with logits),
# prompts 1, 24, 512 (prefill_flops), item sizes 2, 4 (weight_bytes) and
# 1, 300 positions (decode_state_bytes, prefill_state_bytes)
COUNTS = {
    "tiny-llama": ([147968, 180736, 156160, 188928, 659456, 692224],
                   [180736, 3725312, 142770176], [180864, 361728],
                   [256, 76800], [256, 76800]),
    "tiny-mamba2": ([135680, 168448] * 3, [168448, 3289088, 69500928],
                    [145600, 291008], [36608, 36608], [18304, 18304]),
    "yi-9b": ([1384185856, 1908473856, 1385234432, 1909522432, 1449656320,
               1973944320], [1908473856, 33762836480, 717800603648],
              [1908482048, 3816964096], [8192, 2457600], [8192, 2457600]),
    "mamba2-2.7b": ([1349419008, 1606837248] * 3,
                    [1606837248, 32643474432, 691159950336],
                    [1544360960, 3088706560], [84918272, 84918272],
                    [42459136, 42459136]),
}
# sha256 of the drawn weight tree (seed 2**31 + 77, stream 1) and of the
# reference logits of three fixed requests, plain and under the fp8 control
PINNED = {
    "tiny-llama": (
        "3e6b6148683090f9bc8968d87482fd962c530dff2c9fe7395c3015965f6a2756",
        "b45e9513b074b9174804450031daa91fb3abbd2160776b9c8f72b05234bb9216",
        "d4eaa15c5776427b66fdccd297c56f443c84850a3a44c4ff90fb982526eaea7a"),
    "tiny-mamba2": (
        "9294c37959a29237981b5320d54b51d477571dd679bbd6ed93b9da52ce47860d",
        "1a302a8796f02443708b1b337352a613c8997aa6da65d534e25b03e572327f71",
        "98d38a325bd9f46fb881d11383615cb8af2ea8f4a894627ec06bd0cdd8969a39"),
}


@pytest.mark.parametrize("model", sorted(COUNTS))
def test_work_counts_are_the_pinned_ones(model):
    spec = {**TINY, **REAL}[model]
    flops, prefill, weights, decode, state = COUNTS[model]
    assert [work.token_flops(spec, v, lg) for v in (1, 17, 1000)
            for lg in (False, True)] == flops
    assert [work.prefill_flops(spec, p) for p in (1, 24, 512)] == prefill
    assert [work.weight_bytes(spec), work.weight_bytes(spec, 4)] == weights
    assert [work.decode_state_bytes(spec, v) for v in (1, 300)] == decode
    assert [work.prefill_state_bytes(spec, p) for p in (1, 300)] == state


def _sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("model", sorted(PINNED))
def test_weights_and_reference_logits_are_the_pinned_ones(model):
    from repro.models import Model
    spec = TINY[model]
    mo = Model(fleet_mod.model_config(model, spec), param_dtype=jnp.float32)
    params = fleet_mod.make_weights(mo, 2**31 + 77, 1)
    h = hashlib.sha256()
    for p, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(p).encode())
        h.update(np.asarray(a).tobytes())
    rng = np.random.default_rng(5)
    reqs = [check.Served(i, 0, rng.integers(0, 256, n).astype(np.int32),
                         [int(t) for t in rng.integers(0, 256, m)])
            for i, (n, m) in enumerate([(24, 8), (61, 40), (16, 12)])]
    plain = check.reference_logits(spec, params, reqs, 104, 40)
    fp8 = check.reference_logits(spec, params, reqs, 104, 40,
                                 wfn=check.WEIGHT_FORMATS["fp8"])
    assert [len(r) for r in plain] == [8, 40, 12]
    assert (h.hexdigest(), _sha(plain), _sha(fp8)) == PINNED[model]


def test_an_unknown_family_names_its_file():
    with pytest.raises(ValueError, match=r"no .*families/falcon_h1\.py"):
        fleet_mod.model_config("x", {"family": "falcon_h1"})
    with pytest.raises(ValueError, match="falcon_h1.py"):
        work.weight_bytes({"family": "falcon_h1"})


class _Model:
    """A param tree with a leaf name no rule of ``fleet._leaf`` knows."""

    def init(self, key):
        return {"embed": jnp.zeros((16, 8)), "unembed": jnp.zeros((8, 16)),
                "blocks": {"router": jnp.zeros((2, 8, 4))}}


def test_a_family_leaf_rule_draws_only_the_leaves_fleet_lacks():
    with pytest.raises(KeyError, match="blocks/router"):
        fleet_mod.make_weights(_Model(), 7, 0)
    seen = []

    def rule(path, key, shape, dtype, tied):
        seen.append(path)
        return jnp.full(shape, 3.0, dtype) if path[-1] == "router" else None
    w = fleet_mod.make_weights(_Model(), 7, 0, leaf=rule)
    # consulted for the one leaf fleet has no rule for, and for no other
    assert seen == [("blocks", "router")]
    assert np.all(np.asarray(w["blocks"]["router"]) == 3.0)
    assert np.std(np.asarray(w["unembed"])) > 0.1


@pytest.fixture
def plugin(monkeypatch):
    """The harness pointed at the test data's families, configurations and
    mixes only."""
    monkeypatch.setattr(family, "FAMILY_DIR", DATA / "families")
    monkeypatch.setattr(fleet_mod, "CONFIG_DIR", DATA / "configs")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", DATA / "traffic")


def test_the_rate_of_a_mix_that_names_no_configuration(plugin):
    mix = traffic.load_mix("tiny-solo")
    assert "rate_per_s" not in mix
    assert traffic.offered(mix, "tiny-hymba") == (2.0, [0.75, 0.25])
    reqs = traffic.schedule(mix, "tiny-hymba", 6.0)
    assert len(reqs) == 12
    assert sorted(r.tenant for r in reqs) == [0] * 9 + [1] * 3
    # a mix that names the configuration keeps its own rate and shares
    chat = traffic.load_mix("tiny-chat")
    assert traffic.offered(chat, "tiny-pair") == (6.0, [0.5, 0.5])
    with pytest.raises(ValueError, match="no rate for 'tiny-pair'"):
        traffic.offered(mix, "tiny-pair")


def test_a_family_that_lives_only_in_the_test_data_runs(plugin, capsys):
    bench = json.loads((DATA / "bench.json").read_text())
    bench["workloads"] = [{"name": "tiny-hymba.solo", "config": "tiny-hymba",
                           "traffic": "tiny-solo", "chips": 1}]
    rc = run.run(["--workload", "tiny-hymba.solo", "--seed",
                  str(2**31 + 4242), "--seconds", "2", "--trace", "0"],
                 need_chip=False, bench=bench)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 4
    assert set(res["check"]) == {"logit_gap.tiny-hymba"}
    # itl_p95_s reads only gaps whose later token lands inside the 2 s
    # window, which a loaded CPU may not reach
    assert {"ttft_p50_s", "setup_s"} <= set(res["metrics"]) \
        <= {m["name"] for m in bench["end_to_end"]}
    # the family's work counts, through work.py: one more visible position
    # costs its attention scores and weighted sum in each of 2 layers
    spec = fleet_mod.load_config("tiny-hymba")["models"]["tiny-hymba"]
    assert work.token_flops(spec, 11, False) \
        - work.token_flops(spec, 10, False) == 2 * 4 * 4 * 16
