"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them, with a seed, into a fixed
schedule of requests.

The schedule (arrival gaps, prompt and output lengths, tenants) is drawn
once per mix from its own ``schedule_seed``: stratified quantiles of the
mix's distributions, in an order drawn from that seed, as a load generator
such as MLPerf's LoadGen fixes its schedule seeds per round. The run's seed
draws the prompt tokens (and, in ``fleet.py``, the weights). So every run
offers the same work: at this system's speed a window holds a few dozen
requests, and a seed that reordered them moved the tails by 40% from seed
to seed against 1% between two runs of one seed (``PERF.md``). A mix with
another order is another mix file with another ``schedule_seed``.

A mix file holds:

* ``arrivals``: ``{"process": "poisson"}``;
* ``rate_per_s``: the offered rate for each configuration that runs the
  mix, by configuration name (set once from a sweep on the chip);
* ``tenant_shares``: the popularity of the fleet's tenants, in the
  configuration's tenant order, for the configurations ``rate_per_s``
  names;
* ``prompt_len`` and ``output_len``: lognormal ``median`` and ``sigma``,
  clipped to ``min``..``max``, in whole tokens;
* ``slo_s``: the deadline each request carries into the scheduler;
* ``schedule_seed``: the seed of the schedule's order;
* ``warmup``: the ``output_len`` of the warm-up requests.

A configuration that the mix's ``rate_per_s`` does not name gives its rate
and shares in its own file, ``bench/configs/<config>.json``, under
``"traffic": {"<mix>": {"rate_per_s": r, "tenant_shares": [...]}}``: a new
configuration joins a mix without an edit to the mix's file.
"""
from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Request:
    rid: int
    tenant: int              # index into the configuration's tenants
    due_s: float             # scheduled arrival, seconds after window start
    prompt_len: int
    output_len: int          # tokens the request asks for (first included)
    slo_s: float


def load_mix(name: str) -> dict:
    """The mix's parameters, with its ``name``."""
    return dict(json.loads((TRAFFIC_DIR / f"{name}.json").read_text()),
                name=name)


def offered(mix: dict, config_name: str):
    """(rate_per_s, tenant_shares) of the mix for one configuration: the
    mix's own where its ``rate_per_s`` names the configuration, else those
    the configuration file gives for the mix."""
    if config_name in mix.get("rate_per_s", {}):
        return mix["rate_per_s"][config_name], mix["tenant_shares"]
    from fleet import load_config
    own = load_config(config_name).get("traffic", {}).get(mix["name"])
    if own is None:
        raise ValueError(f"no rate for {config_name!r} in mix "
                         f"{mix['name']!r} nor under \"traffic\" in its "
                         f"configuration file")
    return own["rate_per_s"], own["tenant_shares"]


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of a run's seed (any integer)."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % 2**64, *stream]))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a clipped lognormal, in whole tokens."""
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(int)


def gap_set(arrivals: dict, rate: float, n: int) -> np.ndarray:
    """``n`` stratified inter-arrival gaps at mean rate ``rate``."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return -np.log1p(-_quantiles(n)) / rate


def _split(shares: Sequence[float], n: int) -> List[int]:
    """Tenant index per request, counts proportional to ``shares``."""
    w = np.asarray(shares, float) / sum(shares)
    counts = np.floor(w * n).astype(int)
    for i in np.argsort(-(w * n - counts))[: n - counts.sum()]:
        counts[i] += 1
    return [t for t, c in enumerate(counts) for _ in range(c)]


def schedule(mix: dict, config_name: str, seconds: float) -> List[Request]:
    """The window's requests, due in [0, seconds): the same for every run
    of the mix."""
    rate, shares = offered(mix, config_name)
    n = max(2, int(round(rate * seconds)))
    rng = rng_for(mix["schedule_seed"], 1)
    gaps = rng.permutation(gap_set(mix["arrivals"], rate, n))
    due = np.concatenate([[0.0], np.cumsum(gaps[: n - 1])])
    due *= seconds * (n - 1) / n / due[-1]
    plens = rng.permutation(lognormal_set(mix["prompt_len"], n))
    olens = rng.permutation(lognormal_set(mix["output_len"], n))
    tenants = rng.permutation(_split(shares, n))
    return [Request(i, int(tenants[i]), float(due[i]), int(plens[i]),
                    int(olens[i]), float(mix["slo_s"])) for i in range(n)]


def warmup_schedule(mix: dict, reqs: Sequence[Request], max_batch: int,
                    rid0: int) -> List[Request]:
    """The warm-up burst, all due at once: for every tenant the window
    sends to, one request at each prompt length the window sends it (every
    distinct length is a shape the program compiles), and more at those
    lengths until every decode slot is filled, interleaved over tenants."""
    lens: Dict[int, List[int]] = {}
    for r in reqs:
        lens.setdefault(r.tenant, [])
        if r.prompt_len not in lens[r.tenant]:
            lens[r.tenant].append(r.prompt_len)
    per = max([max_batch] + [len(v) for v in lens.values()])
    out: List[Request] = []
    for k in range(per):
        for t in sorted(lens):
            out.append(Request(rid0 + len(out), t, 0.0,
                               lens[t][k % len(lens[t])],
                               mix["warmup"]["output_len"],
                               float(mix["slo_s"])))
    return out


def prompts(reqs: Sequence[Request], vocab: Dict[int, int], seed: int,
            stream: int = 2) -> Dict[int, np.ndarray]:
    """Prompt token ids [1, prompt_len] per request id, drawn from one
    stream of the seed."""
    rng = rng_for(seed, stream)
    return {r.rid: rng.integers(0, vocab[r.tenant], (1, r.prompt_len),
                                dtype=np.int32) for r in reqs}
