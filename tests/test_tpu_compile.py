"""Compile-only checks of the serving path's Pallas kernels at Yi-9B widths.

Each test lowers and compiles one kernel (or jitted dispatch body) in bf16
for one chip of a *described* TPU v5e 2x2 topology — the TPU compiler runs
on this host with no chip attached — and asserts that the Pallas kernel
really became a Mosaic ``tpu_custom_call`` and that the compiled program
fits a 16 GB chip. Tiling and VMEM faults that interpret mode cannot see
fail here, at no chip time. Nothing runs, so results and times are not
checked (tests/test_lane_parity.py does that on a TPU backend).

The topology is described only inside a module-scoped fixture, which skips
when it cannot be; the persistent compilation cache is off around these
compiles (a compile for a described chip is written to it but can never be
read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.dispatch import (_dispatch_grouped, _dispatch_shared,
                                 stacked_envelope)
from repro.core.jit import _scan_gemm, decode_tile, prefill_tile
from repro.kernels import coalesced_gemv, flash_attention

# Yi-9B (configs/yi_9b.py): d_model, d_ff and d_ff's envelope bucket
D, FF, FF_PAD = 4096, 11008, 16384
HBM_BYTES = 16 * 10**9       # one TPU v5e chip
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any reason it cannot be
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_compiled_for_chip(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total <= HBM_BYTES, total


def test_dispatch_shared_gate(one_chip):
    """Two tenants' decode rows against ONE shared gate projection."""
    acts = (_spec(one_chip, (4, D)), _spec(one_chip, (4, D)))
    w = _spec(one_chip, (D, FF_PAD))
    compiled = _dispatch_shared.lower(
        acts, w, n_real=FF, m_tiles=1, bm=8, bn=128, bk=512,
        interpret=False).compile()
    _assert_compiled_for_chip(compiled)


def test_dispatch_grouped_down(one_chip):
    """Two distinct down projections stacked into one grouped GEMM."""
    acts = (_spec(one_chip, (4, FF)), _spec(one_chip, (4, FF)))
    w = _spec(one_chip, (2, FF_PAD, D))
    gids = _spec(one_chip, (2,), jnp.int32)
    compiled = _dispatch_grouped.lower(
        acts, w, gids, n_real=(D, D), m_tiles=2, bm=8, bn=128, bk=512,
        interpret=False).compile()
    _assert_compiled_for_chip(compiled)


def test_stacked_scan_gemm_ffn(one_chip):
    """``_scan_gemm`` inside a scan over 8 layers of stacked, padded FFN
    operands — the shape of a layer-stacked decode body."""
    def body(x, w):
        up = _scan_gemm(x, w["up"], FF, bm=8, bn=128, bk=512,
                        interpret=False)
        return _scan_gemm(up, w["down"], D, bm=8, bn=128, bk=512,
                          interpret=False), None

    def scan(x, w):
        return jax.lax.scan(body, x, w)[0]

    ws = {"up": _spec(one_chip, (8, D, FF_PAD)),
          "down": _spec(one_chip, (8, FF_PAD, D))}
    compiled = jax.jit(scan).lower(_spec(one_chip, (4, D)), ws).compile()
    _assert_compiled_for_chip(compiled)


def test_stacked_scan_gemm_prefill_tile(one_chip):
    """``_scan_gemm`` over 4 layers of a 2048-row prompt at the prefill
    body's tile (``prefill_tile``): the query, FFN up and down
    projections."""
    bm, bn, bk = prefill_tile(2048, 512)

    def gemm(a, w, n):
        return _scan_gemm(a, w, n, bm=bm, bn=bn, bk=bk, interpret=False)

    def body(x, w):
        h = gemm(x, w["wq"], D)
        up = gemm(h, w["up"], FF)
        return gemm(up, w["down"], D), None

    def scan(x, w):
        return jax.lax.scan(body, x, w)[0]

    ws = {"wq": _spec(one_chip, (4, D, D)),
          "up": _spec(one_chip, (4, D, FF_PAD)),
          "down": _spec(one_chip, (4, FF_PAD, D))}
    compiled = jax.jit(scan).lower(_spec(one_chip, (2048, D)), ws).compile()
    _assert_compiled_for_chip(compiled)


def test_stacked_scan_gemm_decode_tile(one_chip):
    """``_scan_gemm`` over 4 layers of 16 slots at the decode bodies' tile
    (``decode_tile``), at Falcon-H1-34B's widths: the SSM in projection at
    its aligned envelope, FFN up and down (5120 -> 21504 -> 5120), then
    the 5120 x 261120 head, so that a VMEM fault at the wide panel shows
    here."""
    d, ff, n_in, vocab = 5120, 21504, 9248, 261120
    bm, bn, bk = decode_tile(16, 512)

    def gemm(a, w, n):
        return _scan_gemm(a, w, n, bm=bm, bn=bn, bk=bk, interpret=False)

    def body(x, w):
        z = gemm(x, w["in_proj"], n_in)
        up = gemm(x + z[:, :d], w["up"], ff)
        return gemm(up, w["down"], d), None

    def step(x, w, head):
        return gemm(jax.lax.scan(body, x, w)[0], head, vocab)

    ws = {"in_proj": _spec(one_chip, (4, d, stacked_envelope(n_in))),
          "up": _spec(one_chip, (4, d, ff)),
          "down": _spec(one_chip, (4, ff, d))}
    compiled = jax.jit(step).lower(_spec(one_chip, (16, d)), ws,
                                   _spec(one_chip, (d, vocab))).compile()
    _assert_compiled_for_chip(compiled)


def test_flash_attention(one_chip):
    q = _spec(one_chip, (32, 1024, 128))
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, interpret=False)).lower(q, q, q).compile()
    _assert_compiled_for_chip(compiled)


def test_coalesced_gemv_four_problems(one_chip):
    x = _spec(one_chip, (4, D))
    w = _spec(one_chip, (4, D, D))
    compiled = jax.jit(lambda x, w: coalesced_gemv(
        x, w, interpret=False)).lower(x, w).compile()
    _assert_compiled_for_chip(compiled)
