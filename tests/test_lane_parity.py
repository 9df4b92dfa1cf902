"""Interpret-vs-compiled Pallas lane parity.

The compiled lane (every Pallas call on a TPU backend) is the wall-clock
regime every perf claim is measured in; interpret mode is the correctness
regime CI runs everywhere. These tests pin the contract between them: at pow2
dims — where the tuned pow2 ``bk`` equals K and both lanes reduce in one
k-step — outputs are BIT-identical; when ``bk`` splits K the compiled
MXU may reassociate the partial-sum adds, so parity is within a documented
last-ulp tolerance instead.

Each test skips unless the backend is a TPU (Pallas only interprets on
CPU: ``Only interpret mode is supported on CPU backend``); the check runs
inside the test, never while the module is imported.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import coalesced_gemm, coalesced_gemv, flash_attention
from repro.kernels.ops import execute_superkernel, pack_problems

# one k-step (bk == K): both lanes reduce identically -> bit parity
EXACT = dict(rtol=0, atol=0)
# bk < K splits the reduction; compiled MXU may reassociate partial sums
SPLIT_TOL = dict(rtol=1e-6, atol=1e-6)


def _require_tpu():
    if jax.default_backend() != "tpu":
        pytest.skip(f"no compiled Pallas lane on the "
                    f"{jax.default_backend()} backend")


def _problems(rng, g, m, n, k, dtype=jnp.float32):
    ks = jax.random.split(rng, 2 * g)
    return [(jax.random.normal(ks[2 * i], (m, k), dtype),
             jax.random.normal(ks[2 * i + 1], (k, n), dtype))
            for i in range(g)]


@pytest.mark.parametrize("shared", [False, True],
                         ids=["grouped", "shared-operand"])
def test_superkernel_parity_pow2(rng, shared):
    _require_tpu()
    probs = _problems(rng, 3, 16, 256, 256)
    if shared:
        w = probs[0][1]
        probs = [(a, w) for a, _ in probs]
    outs_i = execute_superkernel(probs, bm=16, bn=128, bk=256,
                                 shared_operand=shared, interpret=True)
    outs_c = execute_superkernel(probs, bm=16, bn=128, bk=256,
                                 shared_operand=shared, interpret=False)
    for oi, oc in zip(outs_i, outs_c):
        np.testing.assert_allclose(np.asarray(oi), np.asarray(oc), **EXACT)


def test_coalesced_gemm_parity_bk_split(rng):
    """bk=128 over K=512: four-step reduction, documented tolerance."""
    _require_tpu()
    probs = _problems(rng, 2, 32, 128, 512)
    packed = pack_problems(probs, bm=32)
    args = (packed.a_packed, packed.b_stacked, packed.group_ids)
    oi = coalesced_gemm(*args, bm=32, bn=128, bk=128, interpret=True)
    oc = coalesced_gemm(*args, bm=32, bn=128, bk=128, interpret=False)
    np.testing.assert_allclose(np.asarray(oi), np.asarray(oc), **SPLIT_TOL)


def test_coalesced_gemv_parity(rng):
    _require_tpu()
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (4, 256), jnp.float32)
    w = jax.random.normal(k2, (4, 256, 128), jnp.float32)
    oi = coalesced_gemv(x, w, bn=128, bk=256, interpret=True)
    oc = coalesced_gemv(x, w, bn=128, bk=256, interpret=False)
    np.testing.assert_allclose(np.asarray(oi), np.asarray(oc), **EXACT)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_parity(rng, causal):
    """Both lanes run the SAME online-softmax recurrence over identical
    kv-block ordering, so parity is exact at one kv step and last-ulp
    across splits; we pin the split case at the documented tolerance."""
    _require_tpu()
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (2, 256, 64), jnp.float32)
    k = jax.random.normal(k2, (2, 256, 64), jnp.float32)
    v = jax.random.normal(k3, (2, 256, 64), jnp.float32)
    oi = flash_attention(q, k, v, bq=128, bkv=128, causal=causal,
                         interpret=True)
    oc = flash_attention(q, k, v, bq=128, bkv=128, causal=causal,
                         interpret=False)
    np.testing.assert_allclose(np.asarray(oi), np.asarray(oc), **SPLIT_TOL)


def test_stacked_scan_parity(rng):
    """The layer-stacked regime: scan-over-layers drives the same
    coalesced_gemm body once per layer with a fresh weight slice."""
    _require_tpu()
    L, m, k = 3, 16, 256
    ka, kw = jax.random.split(rng)
    a = jax.random.normal(ka, (m, k), jnp.float32)
    ws = jax.random.normal(kw, (L, 1, k, k), jnp.float32)
    gids = jnp.zeros((m // 16,), jnp.int32)

    def run(interpret):
        def body(x, w):
            return coalesced_gemm(x, w, gids, bm=16, bn=128, bk=k,
                                  interpret=interpret), None
        out, _ = jax.lax.scan(body, a, ws)
        return out

    np.testing.assert_allclose(np.asarray(run(True)), np.asarray(run(False)),
                               **EXACT)
