"""A fleet from its configuration file: the program's model objects at the
file's sizes, and weights drawn by the benchmark from the run's seed.

The weights are the benchmark's, not the program's initializer's: the
param tree's layout (leaf names, shapes, dtypes) is read from the program
with ``jax.eval_shape``, and every leaf is then drawn here, by the rules in
``_leaf``, in one jitted call on the device. The plain references in
``bench/reference`` read the same arrays.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import family
from traffic import rng_for

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load_config(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def padded_vocab(spec: dict) -> int:
    """The embedding's rows: the vocabulary padded up to the source's
    ``pad_vocab_size_multiple``, where it gives one."""
    m = spec.get("pad_vocab_size_multiple", 1)
    return -(-spec["vocab_size"] // m) * m


def model_config(name: str, spec: dict):
    """The program's ``ModelConfig`` for one model entry of a fleet file,
    from the entry's family module."""
    return family.of(spec).model_config(name, spec)


def _path(keypath) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", k)) for k in keypath)


def _leaf(path: Tuple[str, ...], key, shape, dtype, tied: bool,
          extra: Optional[Callable] = None):
    """One weight leaf. Matrices: normal with std 1/sqrt(fan_in); norm
    gains: stored as the deviation from 1 (the program's convention), drawn
    N(0, 0.1); the Mamba2 scalars follow the published initialisation (A in
    [1, 16], dt in [1e-3, 1e-1]).

    Embeddings: N(0, 0.02) beside a separate output head. A tied table
    both feeds the residual stream (scaled by sqrt(d) in the program) and
    scores the output, so at 0.02 the input token's own row outscores every
    other by ~10x its spread and greedy decoding only repeats its input,
    whatever the layers compute. Tied tables are drawn at 0.02/sqrt(d)
    (the input row's lead falls to a quarter of the spread), so that the
    token chosen depends on the layers, which is what the comparison with
    the reference has to see.

    A leaf whose name no rule here knows is drawn by ``extra``, the
    family's ``leaf`` rule, where it gives one (None: it has no rule)."""
    name = path[-1]
    f32 = jnp.float32

    def normal(std):
        # drawn in the served dtype: no float32 copy of a large matrix
        return (jax.random.normal(key, shape, dtype) * std).astype(dtype)

    if name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "in_proj", "out_proj", "unembed"):
        return normal(1.0 / math.sqrt(shape[-2]))
    if name == "embed":
        return normal(0.02 / math.sqrt(shape[-1]) if tied else 0.02)
    if name in ("ln1", "ln2", "final_norm", "norm"):
        return normal(0.1)
    if name == "conv_w":
        return normal(0.2)
    if name == "conv_b":
        return normal(0.05)
    if name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, f32) * (hi - lo) + lo)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "D":
        return (1.0 + 0.1 * jax.random.normal(key, shape, f32)).astype(dtype)
    drawn = extra(path, key, shape, dtype, tied) if extra else None
    if drawn is not None:
        return drawn
    raise KeyError(f"no rule for weight leaf {'/'.join(path)}")


def make_weights(model, seed: int, stream: int, device=None,
                 leaf: Optional[Callable] = None):
    """The model's param tree, drawn from (seed, stream) in one jitted call
    on ``device`` in the dtypes the program serves, by the counter-based
    ``rbg`` generator (the default threefry takes tens of seconds for a
    Yi-9B tree on a v5e). ``leaf`` is the family's rule for leaves
    ``_leaf`` does not know."""
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tied = "unembed" not in abstract
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    key0 = int(rng_for(seed, 10, stream).integers(0, 2**31 - 1))

    def draw(seed):
        # the seed enters as an argument: as a constant it would make a new
        # program, and a compile, for every seed
        keys = jax.random.split(jax.random.key(seed, impl="rbg"),
                                len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(_path(p), k, a.shape, a.dtype, tied, leaf)
            for (p, a), k in zip(leaves, keys)])

    fn = jax.jit(draw, device=device) if device is not None \
        else jax.jit(draw)
    return jax.block_until_ready(fn(np.uint32(key0)))


class Fleet:
    """The configuration's models, their weights and the program's tenants.

    Each tenant names a ``weights`` index: tenants with distinct indices
    hold distinct trees (fine-tunes of one base), tenants with the same
    index share one tree."""

    def __init__(self, config_name: str, seed: int):
        from repro.models import Model
        self.name = config_name
        self.cfg = load_config(config_name)
        dtype = jnp.dtype(self.cfg["dtype"])
        self.model_cfgs = {m: model_config(m, s)
                           for m, s in self.cfg["models"].items()}
        self.tenant_names: List[str] = [t["name"]
                                        for t in self.cfg["tenants"]]
        self.tenant_model: List[str] = [t["model"]
                                        for t in self.cfg["tenants"]]
        # tenants naming the same "weights" index share one tree. Each tree
        # gets a Model object of its own: the program keys its compiled
        # programs by model identity and guards them with the params, so
        # two trees behind one Model object would rebuild (and retrace)
        # each other's programs on every step
        keys = [(t["model"], t["weights"]) for t in self.cfg["tenants"]]
        trees = list(dict.fromkeys(keys))
        self.tree_of: List[int] = [trees.index(k) for k in keys]
        models = [Model(self.model_cfgs[m], param_dtype=dtype)
                  for m, _ in trees]
        drawn = [make_weights(mo, seed, w, leaf=getattr(
                     family.of(self.cfg["models"][m]), "leaf", None))
                 for mo, (m, w) in zip(models, trees)]
        self.models = [models[i] for i in self.tree_of]
        self.params: List[Any] = [drawn[i] for i in self.tree_of]

    def vocab(self) -> Dict[int, int]:
        """The tokens a prompt draws from, per tenant: the source's
        vocabulary, not its padding."""
        return {i: self.cfg["models"][m]["vocab_size"]
                for i, m in enumerate(self.tenant_model)}

    def tenants(self):
        from repro.serving import Tenant
        return [Tenant(n, mo, p, cache_len=self.cfg["cache_len"],
                       max_batch=self.cfg["max_batch"])
                for n, mo, p in zip(self.tenant_names, self.models,
                                    self.params)]

    def limit(self, model: str, stat: str):
        """The limit on one compared number, None where it is not
        compared."""
        return self.cfg["limits"][model].get(stat)
