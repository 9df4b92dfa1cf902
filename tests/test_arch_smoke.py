"""Per-architecture smoke tests: reduced variants of every assigned family
run one forward/train step and one prefill→decode step on CPU, asserting
output shapes and no NaNs (assignment requirement (f))."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.models import Model


def _batch(cfg, rng, B=2, S=16):
    batch = {
        "tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
    }
    if cfg.arch_type == "vlm":
        batch["patch_embeds"] = 0.1 * jax.random.normal(
            rng, (B, cfg.num_patch_tokens, cfg.d_model))
    if cfg.is_encdec:
        batch["frames"] = 0.1 * jax.random.normal(
            rng, (B, cfg.encoder_seq_len, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch, rng):
    cfg = smoke_config(arch)
    assert cfg.num_layers <= 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.num_experts <= 4
    model = Model(cfg, param_dtype=jnp.float32)
    params = model.init(rng)
    batch = _batch(cfg, rng)
    logits, aux = model.forward(params, batch)
    S_total = batch["tokens"].shape[1] + (
        cfg.num_patch_tokens if cfg.arch_type == "vlm" else 0)
    assert logits.shape == (2, S_total, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))
    # one real train step
    from repro.training import OptimizerConfig, init_opt_state, make_train_step
    step = jax.jit(make_train_step(model, OptimizerConfig(warmup_steps=1,
                                                          total_steps=10)))
    params2, opt2, metrics = step(params, init_opt_state(params), batch)
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode(arch, rng):
    cfg = smoke_config(arch)
    model = Model(cfg, param_dtype=jnp.float32)
    params = model.init(rng)
    B, S, CL = 2, 12, 24
    batch = {k: v for k, v in _batch(cfg, rng, B, S).items() if k != "labels"}
    logits, cache = model.prefill(params, batch, cache_len=CL)
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))
    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None]
    tok = tok.astype(jnp.int32)
    for _ in range(3):
        logits, cache = model.decode_step(params, tok, cache)
        assert logits.shape == (B, 1, cfg.padded_vocab)
        assert not bool(jnp.any(jnp.isnan(logits)))
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None]
        tok = tok.astype(jnp.int32)
    extra = cfg.num_patch_tokens if cfg.arch_type == "vlm" else 0
    assert int(cache["pos"][0]) == S + extra + 3


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The full (non-smoke) configs carry the exact assigned dimensions."""
    cfg = get_config(arch)
    expected = {
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
        "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
        "falcon-h1-34b": (72, 5120, 20, 4, 21504, 261120),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected
    if arch == "grok-1-314b":
        assert cfg.moe.num_experts == 8 and cfg.moe.top_k == 2
    if arch == "llama4-maverick-400b-a17b":
        assert cfg.moe.num_experts == 128 and cfg.moe.top_k == 1
    if arch == "mamba2-2.7b":
        assert cfg.ssm.d_state == 128
    if arch == "hymba-1.5b":
        assert cfg.ssm.d_state == 16
    if arch == "gemma3-1b":
        assert cfg.window_size == 1024 and cfg.global_every == 6
    if arch == "falcon-h1-34b":
        assert (cfg.ssm.d_state, cfg.ssm.n_groups, cfg.ssm.inner(5120),
                cfg.ssm.num_heads(5120)) == (256, 2, 4096, 32)
