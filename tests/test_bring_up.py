"""The pieces that bring the serving path up on a TPU, checked on the CPU:
backend-resolved interpret mode, the device-profile table, the packed-weight
budget, mesh-slot to device mapping, the published-width builder, the
compile-cache location and ``chip_smoke.py``'s path at smoke size."""
import importlib.util
import types
import warnings
from pathlib import Path

import jax
import pytest

from repro.core import costmodel
from repro.core.costmodel import TPUV5E, attached_device
from repro.core.dispatch import device_weight_budget
from repro.kernels import backend
from repro.launch import serve
from repro.serving import engine

ROOT = Path(__file__).resolve().parents[1]


def _fake_tpu(kind="TPU v5 lite"):
    return types.SimpleNamespace(platform="tpu", device_kind=kind,
                                 memory_stats=lambda: None)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_interpret_mode_follows_the_backend(monkeypatch):
    assert backend.interpret_default() is True          # the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.interpret_default() is False


def test_device_profile_from_device_kind(monkeypatch):
    assert attached_device() is TPUV5E                  # no TPU attached
    monkeypatch.setattr(costmodel.jax, "devices", lambda: [_fake_tpu()])
    assert attached_device() is TPUV5E
    monkeypatch.setattr(costmodel.jax, "devices",
                        lambda: [_fake_tpu("TPU v9 hypothetical")])
    with pytest.raises(ValueError, match="no cost-model profile"):
        attached_device()


def test_weight_budget_sized_from_device_memory(monkeypatch):
    assert device_weight_budget() == 1 << 30           # CPU reports none
    assert device_weight_budget(3) == 3 << 30
    chip = types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": 16 << 30})
    monkeypatch.setattr(jax, "devices", lambda: [chip] * 4)
    assert device_weight_budget() == 8 << 30
    assert device_weight_budget(4) == 32 << 30


def test_mesh_slots_map_to_attached_devices(monkeypatch):
    assert engine._slot_devices(1) is None
    with pytest.warns(UserWarning, match="modeled only"):
        assert engine._slot_devices(4) is None          # one CPU device
    monkeypatch.setattr(engine.jax, "devices", lambda: [_fake_tpu()])
    with pytest.raises(ValueError, match="needs 4 chips"):
        engine._slot_devices(4)
    chips = [_fake_tpu() for _ in range(4)]
    monkeypatch.setattr(engine.jax, "devices", lambda: chips)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert engine._slot_devices(2) == chips[:2]


@pytest.mark.parametrize("arch,layers,ok", [
    ("yi-9b", 8, True), ("yi-9b", 48, True), ("yi-9b", 0, False),
    ("yi-9b", 49, False), ("gemma3-1b", 6, True), ("gemma3-1b", 4, False),
])
def test_serving_config_cuts_only_depth(arch, layers, ok):
    full = serve.get_config(arch)
    if not ok:
        with pytest.raises(ValueError):
            serve.serving_config(arch, layers)
        return
    cfg = serve.serving_config(arch, layers)
    assert cfg.num_layers == layers
    widths = ("d_model", "num_heads", "num_kv_heads", "d_ff", "vocab_size",
              "head_dim", "window_size", "global_every")
    assert all(getattr(cfg, w) == getattr(full, w) for w in widths)
    assert serve.serving_config(arch).name == arch + "-smoke"


def test_compile_cache_dir(monkeypatch):
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert serve.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == old  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert serve.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_chip_smoke_refuses_a_cpu_backend(capsys):
    assert _load_chip_smoke().main([]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err


def test_chip_smoke_path_at_smoke_size(capsys):
    """The one-chip smoke's checks, on the CPU smoke config: declared
    prefill, shared-operand coalescing, steady-state hit rate and zero
    retraces, and served prefill logits against the float32 reference."""
    smoke = _load_chip_smoke()
    assert smoke.serve_check(None, n_per_tenant=2, prompt_len=32,
                             new_tokens=4)
    out = capsys.readouterr().out
    assert "4/4 requests finished" in out
    assert "dispatch retraces 0" in out
