"""Layer-stacked KernelPrograms: scan-over-layers decode templates must
match the plain reference, ``Model.decode_step`` — logits and every cache
leaf — across dense, MoE and SSM at several batch sizes; a template holds
one scanned body per homogeneous sub-stack; the stacked dispatch path must
keep the steady-state plan-cache hit rate and the packed-weight guard
discipline (zero phantom invalidations, real hot-swaps trip the guard);
and a production-depth (48-layer) config must serve end-to-end through
the vliw mode with O(1)-in-depth templates."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.jit import (DECODE_BM_MAX, DECODE_BN, PREFILL_BM_MAX,
                            PREFILL_BN, GemmStage, StackedGemmStage, VLIWJit,
                            _panel, build_dense_decode_template,
                            build_moe_decode_template,
                            build_ssm_decode_template, decode_tile,
                            partition_layers, prefill_tile)
from repro.models import Model
from repro.serving import ServeRequest, ServingEngine, Tenant

DECODE_BUILDERS = {
    "dense": build_dense_decode_template,
    "moe": build_moe_decode_template,
    "ssm": build_ssm_decode_template,
}
# (family, smoke architecture, init seed)
ARCHS = {"dense": ("gemma3-1b", 11), "moe": ("grok-1-314b", 500),
         "ssm": ("mamba2-2.7b", 999)}


@pytest.fixture(scope="module")
def models():
    out = {}
    for fam, (arch, seed) in ARCHS.items():
        cfg = smoke_config(arch)
        m = Model(cfg, param_dtype=jnp.float32)
        out[fam] = (m, m.init(jax.random.PRNGKey(seed)))
    return out


def _setup(m, params, B, S=12, CL=32):
    rng = jax.random.PRNGKey(3)
    batch = {"tokens": jax.random.randint(rng, (B, S), 0,
                                          m.cfg.vocab_size)}
    _, cache = m.prefill(params, batch, cache_len=CL)
    tok = jax.random.randint(jax.random.fold_in(rng, 9), (B, 1), 0,
                             m.cfg.vocab_size)
    return cache, tok


# ---------------------------------------------------------------------------
# the stacked decode templates against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "fam,batch",
    [(f, b) for f in ("dense", "moe", "ssm") for b in (1, 2, 4)]
    + [("dense", 16), ("ssm", 16)])
def test_stacked_decode_matches_reference(fam, batch, models):
    """Over 3 steps, one template's logits and every cache leaf match
    ``Model.decode_step``. Both sides take the reference's greedy tokens,
    so a near-tie cannot compound into different streams. The tolerance
    is float32's, 2e-4 as in the other reference tests: the template's
    GEMMs accumulate K in the Pallas kernel's bk blocks, the reference's
    in XLA's dot order."""
    m, params = models[fam]
    ref_cache, tok = _setup(m, params, batch)
    cache = ref_cache
    tmpl = DECODE_BUILDERS[fam](m, params, batch)
    vj = VLIWJit(max_group=8)
    for step in range(3):
        want, ref_cache = m.decode_step(params, tok, ref_cache)
        prog = tmpl.bind(stream_id=0, tokens=tok, cache=cache)
        vj.run([prog])
        cache = prog.env["cache"]
        np.testing.assert_allclose(prog.env["logits"], want[:, -1],
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"step {step}")
        for leaf in ref_cache["layers"]:
            np.testing.assert_allclose(
                np.asarray(cache["layers"][leaf]),
                np.asarray(ref_cache["layers"][leaf]), rtol=2e-4,
                atol=2e-4, err_msg=f"step {step} {leaf}")
        np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                      np.asarray(ref_cache["pos"]))
        tok = jnp.argmax(want[:, -1], axis=-1).astype(jnp.int32)[:, None]


def test_stacked_template_one_body_stage_per_substack(models):
    """Structure: stage count is O(1) in depth — one StackedGemmStage per
    homogeneous sub-stack, and the unembedding head is the only plain
    GemmStage left."""
    m, params = models["dense"]
    tmpl = build_dense_decode_template(m, params, 2)
    bodies = [st for st in tmpl.stages if isinstance(st, StackedGemmStage)]
    assert len(bodies) == len(partition_layers(
        m.cfg.global_layer_flags()))
    assert [st.tag for st in tmpl.stages
            if isinstance(st, GemmStage)] == ["unembed"]


# ---------------------------------------------------------------------------
# serving: hit rate, hot-swap
# ---------------------------------------------------------------------------

def _tokens(rep):
    return [r.tokens_out for r in sorted(rep.requests,
                                         key=lambda r: r.req_id)]


def test_stacked_steady_state_hit_rate_and_guard(models):
    """Steady state through the stacked path: plan-cache miss only on the
    first step, and the stacked weight closures hand the executor STABLE
    arrays — zero phantom hot-swap invalidations."""
    m, params = models["dense"]
    steps = 5
    trace = [ServeRequest(0, "a", 0.0, 8, steps + 1, 1.0)]
    eng = ServingEngine([Tenant("a", m, params, cache_len=32,
                                max_batch=2)], mode="vliw")
    rep = eng.run(trace)
    pc = rep.jit.plan_cache
    assert pc.hit_rate >= (steps - 1) / steps - 1e-9
    assert pc.invalidations == 0
    assert rep.jit.dispatch.weight_invalidations == 0
    assert rep.jit.dispatch.weight_hits > 0
    # stacked dispatch accounting stays consistent with plain dispatch
    d = rep.jit.dispatch
    assert d.weight_hits + d.weight_misses == d.dispatches


def test_stacked_hot_swap_trips_guard(models):
    """A real weight hot-swap must invalidate the stacked operand cache
    (new params identity → new weight keys + plan-cache invalidation) and
    converge to the same tokens as a fresh engine on the new weights."""
    m, p_old = models["dense"]
    p_new = Model(m.cfg, param_dtype=jnp.float32).init(
        jax.random.PRNGKey(77))
    trace1 = [ServeRequest(0, "a", 0.0, 8, 3, 1.0)]
    trace2 = [ServeRequest(1, "a", 0.0, 8, 3, 1.0)]
    eng = ServingEngine([Tenant("a", m, p_old, cache_len=32, max_batch=2)],
                        mode="vliw")
    eng.run(trace1)
    assert eng.jit.plan_cache.stats.invalidations == 0
    eng.tenants["a"].params = p_new      # hot-swap, same model object
    rep_swapped = eng.run(trace2)
    assert eng.jit.plan_cache.stats.invalidations >= 1
    fresh = ServingEngine([Tenant("a", m, p_new, cache_len=32,
                                  max_batch=2)], mode="vliw")
    rep_fresh = fresh.run(trace2)
    assert _tokens(rep_swapped) == _tokens(rep_fresh)


# ---------------------------------------------------------------------------
# the prefill body's GEMM tile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [8, 16, 64, 512, 2048])
def test_prefill_tile_by_rows(m):
    """A prompt bucket takes its own rows (up to PREFILL_BM_MAX),
    PREFILL_BN columns and the executor's bk."""
    assert prefill_tile(m, 512) == (min(m, PREFILL_BM_MAX), PREFILL_BN, 512)


def test_engine_counts_prefill_bodies_by_bucket():
    """``JitStats.prefill_bodies`` gets one entry per bucket served, its
    counts sum to the prefills admitted (prompts shorter than
    ``prefill_declare_min`` are admitted without one), and ``-`` of two
    readings gives the epoch's own."""
    cfg = smoke_config("yi-9b")
    assert len(partition_layers(cfg.global_layer_flags())) == 1
    m = Model(cfg, param_dtype=jnp.float32)
    eng = ServingEngine([Tenant("a", m, m.init(jax.random.PRNGKey(5)),
                                cache_len=136, max_batch=2)], mode="vliw")
    ex = eng.jit.executor

    def entry(Sp):
        return (Sp, *prefill_tile(Sp, ex.bk))

    eng.run([ServeRequest(0, "a", 0.0, 20, 2, 10.0),
             ServeRequest(1, "a", 0.0, 40, 2, 10.0)])
    before = eng.jit_stats.prefill_bodies
    assert before == {entry(32): 1, entry(64): 1}
    trace = [ServeRequest(i, "a", 0.0, n, 2, 10.0)
             for i, n in enumerate((5, 40, 100, 100))]
    rep = eng.run(trace)
    delta = rep.jit.prefill_bodies - before
    assert delta == {entry(64): 1, entry(128): 2}
    assert sum(delta.values()) == sum(
        r.prompt_len >= eng.prefill_declare_min for r in trace)


# ---------------------------------------------------------------------------
# the decode bodies' GEMM tile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,bm", [(1, 8), (2, 8), (4, 8), (16, 16),
                                  (64, 64)])
def test_decode_tile_by_rows(m, bm):
    """A decode body takes its rows rounded up to a multiple of 8 (one
    m-tile, up to DECODE_BM_MAX), DECODE_BN columns and the executor's
    bk."""
    assert bm <= DECODE_BM_MAX
    assert decode_tile(m, 512) == (bm, DECODE_BN, 512)
    assert decode_tile(DECODE_BM_MAX + 1, 512)[0] == DECODE_BM_MAX


@pytest.mark.parametrize("n,widest", [(512, 512), (10752, 512),
                                      (11264, 1024), (261120, 1024)])
def test_decode_panel_divides_the_envelope(n, widest):
    """``_scan_gemm`` clamps DECODE_BN to a divisor of each aligned
    envelope: Mamba2's in_proj (10752 = 512 x 21), Yi-9B's FFN (11264 =
    1024 x 11) and Falcon-H1's head (261120 = 1024 x 255)."""
    bn = _panel(DECODE_BN, n)
    assert n % bn == 0 and bn == min(widest, DECODE_BN)


def test_engine_counts_decode_bodies_by_tile():
    """``JitStats.decode_bodies`` counts one stacked body per decode
    program at (max_batch, ``decode_tile``), apart from the prefill
    bodies, and ``-`` of two readings gives the epoch's own."""
    cfg = smoke_config("yi-9b")
    assert len(partition_layers(cfg.global_layer_flags())) == 1
    m = Model(cfg, param_dtype=jnp.float32)
    eng = ServingEngine([Tenant("a", m, m.init(jax.random.PRNGKey(5)),
                                cache_len=136, max_batch=2)], mode="vliw")
    entry = (2, *decode_tile(2, eng.jit.executor.bk))
    rep = eng.run([ServeRequest(0, "a", 0.0, 20, 3, 10.0)])
    before = rep.jit.decode_bodies
    assert before == {entry: rep.jit.decode_programs} and \
        rep.jit.decode_programs > 0
    assert all(k[0] == 32 for k in rep.jit.prefill_bodies)
    rep = eng.run([ServeRequest(i, "a", 0.0, 40, 4, 10.0)
                   for i in range(2)])
    delta = rep.jit.decode_bodies - before
    assert delta == {entry: rep.jit.decode_programs
                     - before[entry]} and delta[entry] > 0


# ---------------------------------------------------------------------------
# production depth: 48 layers end-to-end (the tier-1 depth smoke)
# ---------------------------------------------------------------------------

def test_depth_48_serves_end_to_end():
    """A granite-34b-shaped config at REAL depth (48 layers, smoke dims)
    serves through the vliw mode — possible only because templates are
    O(1) in depth — with greedy tokens identical to the batched mode."""
    cfg = dataclasses.replace(smoke_config("granite-34b"), num_layers=48)
    assert len(partition_layers(cfg.global_layer_flags())) == 1
    m = Model(cfg, param_dtype=jnp.float32)
    params = m.init(jax.random.PRNGKey(8))
    trace = [ServeRequest(0, "a", 0.0, 6, 3, 1.0)]
    reps = {}
    for mode in ("vliw", "batched"):
        eng = ServingEngine([Tenant("a", m, params, cache_len=32,
                                    max_batch=2)], mode=mode)
        reps[mode] = eng.run(trace)
    toks = _tokens(reps["vliw"])
    assert toks == _tokens(reps["batched"])
    assert all(len(t) == 3 for t in toks)
    # the stacked emission really is depth-independent: the 48-layer
    # template has exactly as many stages as a 2-layer one
    t48 = build_dense_decode_template(m, params, 1)
    shallow = Model(smoke_config("granite-34b"), param_dtype=jnp.float32)
    p2 = shallow.init(jax.random.PRNGKey(8))
    t2 = build_dense_decode_template(shallow, p2, 1)
    assert len(t48.stages) == len(t2.stages)
