"""Falcon-H1 (parallel GQA + Mamba-2 heads, µP multipliers) end to end on
the CPU: the plain reference against ``transformers``' Falcon-H1, the
program against the reference through the engine's vliw path, the stacked
hybrid decode template against ``Model.decode_step`` (Falcon-H1-like and
Hymba-like), and the chunked SSD with two B/C groups against its
token-by-token recurrence. Every seed is fixed."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.configs.base import ModelConfig, Multipliers, SSMConfig
from repro.core.jit import (VLIWJit, build_hybrid_decode_template,
                            hybrid_program_cache_key)
from repro.models import Model
from repro.models import ssm as ssm_lib
from repro.serving import ServeRequest, ServingEngine, Tenant

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import family  # noqa: E402
from reference import falcon_h1 as reference  # noqa: E402

# a tiny Falcon-H1 in the published config.json's keys: two B/C groups, an
# inner width (96) apart from expand × hidden (128), a chunk (8) that the
# test sequences do not fill, and non-unit multipliers everywhere
SPEC = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
    "mamba_d_ssm": 96, "mamba_n_heads": 6, "mamba_d_head": 16,
    "mamba_n_groups": 2, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mamba_norm_before_gate": False,
    "mamba_rms_norm": True, "attention_in_multiplier": 1.25,
    "attention_out_multiplier": 0.75, "ssm_in_multiplier": 0.5,
    "ssm_out_multiplier": 0.625, "key_multiplier": 0.8,
    "ssm_multipliers": [0.9, 0.7, 1.3, 0.6, 1.1],
    "mlp_multipliers": [1.2, 0.85], "embedding_multiplier": 3.0,
    "lm_head_multiplier": 0.5,
}


def _config(spec=SPEC) -> ModelConfig:
    return family.of({"family": "falconh1"}).model_config("tiny-falcon-h1",
                                                           spec)


def _params(cfg: ModelConfig, seed: int):
    """Seeded random weights in the program's layout, with norm gains and
    conv biases away from their zero init so that every path is live."""
    params = Model(cfg, param_dtype=jnp.float32).init(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, a), k in zip(leaves, keys):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("ln1", "ln2", "final_norm", "norm", "conv_b"):
            a = 0.1 * jax.random.normal(k, a.shape, a.dtype)
        elif name == "embed":
            a = jax.random.normal(k, a.shape, a.dtype)
        out.append(a)
    return jax.tree_util.tree_unflatten(tree, out)


# ---------------------------------------------------------------------------
# (a) the reference against transformers' Falcon-H1
# ---------------------------------------------------------------------------

def _hf_model(spec, params):
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    hf_cfg = tf.FalconH1Config(
        vocab_size=spec["vocab_size"], hidden_size=spec["hidden_size"],
        intermediate_size=spec["intermediate_size"],
        num_hidden_layers=spec["num_hidden_layers"],
        num_attention_heads=spec["num_attention_heads"],
        num_key_value_heads=spec["num_key_value_heads"],
        head_dim=spec["head_dim"], rms_norm_eps=spec["rms_norm_eps"],
        rope_theta=spec["rope_theta"],
        tie_word_embeddings=spec["tie_word_embeddings"],
        **{k: spec[k] for k in spec if k.startswith("mamba_")},
        **{k: spec[k] for k in spec if k.endswith("_multiplier")},
        ssm_multipliers=spec["ssm_multipliers"],
        mlp_multipliers=spec["mlp_multipliers"],
        projectors_bias=False, attn_implementation="eager")
    model = tf.FalconH1ForCausalLM(hf_cfg).eval()

    def t(a, transpose=False):
        a = np.asarray(a, np.float32)
        return torch.from_numpy(np.ascontiguousarray(a.T if transpose else a))

    sd = {"model.embed_tokens.weight": t(params["embed"]),
          "lm_head.weight": t(params["unembed"], True),
          # the program stores a norm's gain as its deviation from 1
          "model.final_layernorm.weight": t(1.0 + params["final_norm"])}
    b = params["blocks"]
    for i in range(spec["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], b)
        pre = f"model.layers.{i}."
        a, m, f = p["attn"], p["mamba"], p["mlp"]
        sd.update({
            pre + "input_layernorm.weight": t(1.0 + p["ln1"]),
            pre + "pre_ff_layernorm.weight": t(1.0 + p["ln2"]),
            pre + "self_attn.q_proj.weight": t(a["wq"], True),
            pre + "self_attn.k_proj.weight": t(a["wk"], True),
            pre + "self_attn.v_proj.weight": t(a["wv"], True),
            pre + "self_attn.o_proj.weight": t(a["wo"], True),
            pre + "mamba.in_proj.weight": t(m["in_proj"], True),
            pre + "mamba.out_proj.weight": t(m["out_proj"], True),
            pre + "mamba.conv1d.weight": t(np.asarray(m["conv_w"]).T[:, None]),
            pre + "mamba.conv1d.bias": t(m["conv_b"]),
            pre + "mamba.dt_bias": t(m["dt_bias"]),
            pre + "mamba.A_log": t(m["A_log"]),
            pre + "mamba.D": t(m["D"]),
            pre + "mamba.norm.weight": t(1.0 + m["norm"]),
            pre + "feed_forward.gate_proj.weight": t(f["w_gate"], True),
            pre + "feed_forward.up_proj.weight": t(f["w_up"], True),
            pre + "feed_forward.down_proj.weight": t(f["w_down"], True),
        })
    missing, unexpected = model.load_state_dict(sd, strict=False)
    # the mixers' mup_vector is a non-persistent buffer computed from the
    # config; every weight is copied
    assert not unexpected and not missing, (missing, unexpected)
    return model, torch


def test_reference_matches_transformers_falcon_h1():
    cfg = _config()
    params = _params(cfg, 7)
    model, torch = _hf_model(SPEC, params)
    S = 21                      # not a multiple of the chunk (8)
    toks = np.random.default_rng(3).integers(0, 256, S).astype(np.int64)
    with torch.no_grad():
        want = model(torch.from_numpy(toks)[None]).logits[0].numpy()
    got = np.asarray(reference.logits(params, SPEC, toks, np.arange(S)))
    # both float32: the reference at the highest matmul precision, torch on
    # the CPU with its chunked SSD and another summation order; the rest
    # is rounding, about 1e-6 of the logits' scale
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * scale)
    # a lower precision than the configuration's float32 does not pass:
    # the reference with bf16 weights lands far outside that tolerance
    bf16 = np.asarray(reference.logits(
        params, SPEC, toks, np.arange(S),
        wfn=lambda w: w.astype(jnp.bfloat16).astype(jnp.float32)))
    assert np.abs(bf16 - want).max() > 20 * 2e-5 * scale


# ---------------------------------------------------------------------------
# (b) the engine's vliw path against the reference
# ---------------------------------------------------------------------------

def test_vliw_engine_matches_reference(monkeypatch):
    cfg = _config()
    model = Model(cfg, param_dtype=jnp.float32)
    params = _params(cfg, 11)
    # no hybrid decode step may take the monolithic path
    monkeypatch.setattr(Model, "decode_step", lambda *a, **k: pytest.fail(
        "a hybrid decode step took Model.decode_step"))
    eng = ServingEngine([Tenant("falcon", model, params, cache_len=48,
                                max_batch=2)], mode="vliw")
    # the logits of every decode step, per request, as the engine lands them
    stepped = {}
    consume = eng._consume

    def record(t, logits, now=0.0):
        for slot in t.active_slots():
            stepped.setdefault(t.slot_req[slot].req_id, []).append(
                np.asarray(logits[slot, -1]))
        return consume(t, logits, now)

    eng._consume = record
    reqs = [ServeRequest(i, "falcon", 0.0005 * i, p, n, 10.0)
            for i, (p, n) in enumerate([(13, 9), (20, 6), (9, 12)])]
    rng = jax.random.PRNGKey(5)
    rep = eng.run(reqs, rng)
    assert rep.unfinished == 0 and len(rep.finished) == 3
    assert eng.jit_stats.nondense_programs > 0
    t = eng.tenants["falcon"]
    bf16 = lambda w: w.astype(jnp.bfloat16).astype(jnp.float32)  # noqa
    for r in rep.requests:
        prompt = np.asarray(eng.make_prompt(t, r, rng))[0]
        toks = np.concatenate([prompt, r.tokens_out[:-1]])
        read = len(prompt) - 1 + np.arange(len(r.tokens_out))
        ref = np.asarray(reference.logits(params, SPEC, toks, read))
        served = np.asarray(r.tokens_out)
        # the prefill's token and every decoded one are the reference's
        # best: float32 program against the float32 reference
        gap = ref.max(-1) - ref[np.arange(len(served)), served]
        assert gap.max() <= 1e-5 * np.abs(ref).max(), gap
        # each decode step's logits, through the cache, within 1e-5 of the
        # logits' scale (float32 rounding in another summation order)
        tol = 1e-5 * np.abs(ref).max()
        np.testing.assert_allclose(np.stack(stepped[r.req_id]), ref[1:],
                                   rtol=0, atol=tol)
        # the reference in bf16 would not pass that tolerance
        low = np.asarray(reference.logits(params, SPEC, toks, read, wfn=bf16))
        assert np.abs(low - ref).max() > 10 * tol


# ---------------------------------------------------------------------------
# (c) the hybrid template against Model.decode_step
# ---------------------------------------------------------------------------

def _hymba_like() -> ModelConfig:
    # Hymba's mean as 0.5 / 0.5 multipliers, window layers and one global
    return dataclasses.replace(
        smoke_config("hymba-1.5b"), num_layers=3,
        multipliers=Multipliers(attention_out=0.5, ssm_out=0.5))


@pytest.mark.parametrize("which", ["falcon-h1", "hymba"])
@pytest.mark.parametrize("batch", [1, 2])
def test_hybrid_template_matches_decode_step(which, batch):
    cfg = _config() if which == "falcon-h1" else _hymba_like()
    m = Model(cfg, param_dtype=jnp.float32)
    params = _params(cfg, 21)
    rng = jax.random.PRNGKey(4)
    prompt = {"tokens": jax.random.randint(rng, (batch, 36), 0, 256)}
    # 40 positions > the smoke window (32): local layers mask the oldest
    _, cache = m.prefill(params, prompt, cache_len=48)
    tok = jax.random.randint(jax.random.fold_in(rng, 1), (batch, 1), 0, 256)
    template = build_hybrid_decode_template(m, params, batch)
    if which == "hymba":
        # sliding-window flags split the stack into homogeneous bodies
        assert sum(1 for st in template.stages
                   if getattr(st, "tag", "").startswith("body_")) == 3
    for _ in range(3):
        want, want_cache = m.decode_step(params, tok, cache)
        prog = template.bind(stream_id=0, tokens=tok, cache=cache)
        VLIWJit(max_group=8).run([prog])
        # float32; the template's GEMMs are the Pallas kernel (interpreted)
        # summing K in 512-wide blocks, decode_step's are XLA dots
        np.testing.assert_allclose(prog.env["logits"], want[:, 0],
                                   rtol=1e-5, atol=1e-5)
        for key in ("k", "v", "conv", "h"):
            np.testing.assert_allclose(prog.env["cache"]["layers"][key],
                                       want_cache["layers"][key],
                                       rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(prog.env["cache"]["pos"],
                                      want_cache["pos"])
        cache = want_cache
        tok = jnp.argmax(want[:, -1], -1).astype(jnp.int32)[:, None]


def test_hybrid_cache_key_covers_kv_and_state():
    cfg = _config()
    m = Model(cfg, param_dtype=jnp.float32)
    params = _params(cfg, 3)
    a = m.init_cache(2, 32)
    b = m.init_cache(2, 48)               # other KV length, same state
    c = dict(a, layers=dict(a["layers"],
                            h=jnp.zeros((2, 2, 6, 16, 8), jnp.float32)))
    keys = {hybrid_program_cache_key(m, params, 2, x) for x in (a, b, c)}
    assert len(keys) == 3


def test_hybrid_tenant_counts_as_nondense_program():
    cfg = _hymba_like()
    m = Model(cfg, param_dtype=jnp.float32)
    eng = ServingEngine([Tenant("h", m, _params(cfg, 5), cache_len=40,
                                max_batch=2)], mode="vliw")
    assert eng._jit_capable(eng.tenants["h"])
    rep = eng.run([ServeRequest(0, "h", 0.0, 12, 5, 10.0)])
    assert rep.unfinished == 0 and len(rep.finished) == 1
    assert eng.jit_stats.nondense_programs == 4     # one per decode step


# ---------------------------------------------------------------------------
# (d) the chunked SSD with two B/C groups against the recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("groups", [1, 2])
def test_ssd_chunked_with_groups_matches_recurrence(groups):
    scfg = SSMConfig(d_state=16, head_dim=16, expand=2, chunk_size=8,
                     n_groups=groups, d_inner=96, in_multiplier=0.5,
                     proj_multipliers=(0.9, 0.7, 1.3, 0.6, 1.1))
    key = jax.random.PRNGKey(8)
    p = ssm_lib.init_mamba(key, 64, scfg, jnp.float32)
    p["norm"] = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (96,))
    u = 0.5 * jax.random.normal(jax.random.fold_in(key, 2), (2, 21, 64))
    yc, state = ssm_lib.ssd_chunked(p, u, scfg, return_state=True)
    yr = ssm_lib.ssd_reference(p, u, scfg)
    # float32: the chunked form sums the same terms in another order
    # (intra-chunk matmuls, inter-chunk scan), 1e-4 of outputs of order 1
    np.testing.assert_allclose(yc, yr, rtol=1e-4, atol=1e-4)
    # the state it hands to decode continues the sequence
    nxt = 0.5 * jax.random.normal(jax.random.fold_in(key, 3), (2, 1, 64))
    y1, _ = ssm_lib.ssd_decode_step(p, nxt, state, scfg)
    yfull = ssm_lib.ssd_chunked(p, jnp.concatenate([u, nxt], 1), scfg)
    np.testing.assert_allclose(y1[:, 0], yfull[:, -1], rtol=1e-4, atol=1e-4)


def test_param_count_is_the_init_tree():
    for name in ("falcon-h1-34b", "hymba-1.5b", "mamba2-2.7b"):
        cfg = get_config(name)
        tree = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
        assert cfg.param_count() == sum(
            math.prod(a.shape) for a in jax.tree_util.tree_leaves(tree))
    # Falcon-H1-34B: 33.6 B parameters, 430.1 M a layer
    cfg = get_config("falcon-h1-34b")
    assert round(cfg.param_count() / 1e8) == 336
