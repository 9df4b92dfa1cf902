"""Sharding-rule invariants — validated WITHOUT multi-device lowering
(tests keep the single-device constraint; full lowering is covered by
launch/dryrun.py over all 68 combinations)."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, \
    pair_is_supported, SKIPPED_PAIRS
from repro.distributed import sharding as sh
from repro.models import Model


class FakeMesh:
    """Just enough of a Mesh for the rule functions (axis sizes + names)."""

    def __init__(self, multi=False):
        self.shape = ({"pod": 2, "data": 16, "model": 16} if multi
                      else {"data": 16, "model": 16})
        self.axis_names = tuple(self.shape)


def _specs(cfg_name, multi=False):
    import jax.numpy as jnp
    model = Model(get_config(cfg_name), param_dtype=jnp.bfloat16)
    mesh = FakeMesh(multi)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out = {}
    for path, leaf in flat:
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out[pstr] = (sh._spec_for(pstr, leaf.shape, mesh), leaf.shape)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("multi", [False, True])
def test_every_spec_divides_evenly(arch, multi):
    mesh = FakeMesh(multi)
    for pstr, (spec, shape) in _specs(arch, multi).items():
        for dim, ax in zip(shape, spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (arch, pstr, shape, spec)


def test_ffn_is_tensor_parallel_dense():
    specs = _specs("yi-9b")
    gate = [v for k, v in specs.items() if k.endswith("w_gate")][0]
    assert gate[0][-1] == "model"          # d_ff TP
    down = [v for k, v in specs.items() if k.endswith("w_down")][0]
    assert down[0][-2] == "model"          # row-parallel pair


def test_moe_expert_parallel_when_divisible():
    def _axes(x):
        return (x,) if isinstance(x, str) else x

    llama = _specs("llama4-maverick-400b-a17b")
    gate = [v for k, v in llama.items() if k.endswith("moe/w_gate")][0]
    assert _axes(gate[0][1]) == ("data",)  # 128 experts over 16
    grok = _specs("grok-1-314b")
    gate_g = [v for k, v in grok.items() if k.endswith("moe/w_gate")][0]
    assert gate_g[0][1] is None            # 8 experts can't split 16 ways
    assert _axes(gate_g[0][2]) == ("data",)  # falls back to FSDP on d_model


def test_vocab_parallel_embeddings():
    specs = _specs("gemma3-1b")
    emb = specs["embed"]
    assert emb[0][0] == "model"


def test_skip_matrix_documented():
    assert ("yi-9b", "long_500k") in SKIPPED_PAIRS
    assert pair_is_supported("mamba2-2.7b", "long_500k")
    assert pair_is_supported("gemma3-1b", "long_500k")
    assert not pair_is_supported("whisper-tiny", "long_500k")
    n_supported = sum(pair_is_supported(a, s) for a in ARCH_IDS
                      for s in INPUT_SHAPES)
    assert n_supported == 37
