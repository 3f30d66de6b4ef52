"""End-to-end driver smoke: launch.train with crash+restore, in-process."""
import importlib.util
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _restore_compile_cache():
    """The drivers turn the persistent compile cache on; the rest of this
    process keeps the setting it had."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_train_driver_crash_restore(capsys, monkeypatch):
    from repro.launch.train import main
    monkeypatch.setattr(sys, "argv", [
        "train", "--arch", "qwen2.5-3b", "--preset", "smoke",
        "--steps", "8", "--crash-at", "5", "--batch", "2", "--seq", "32",
        "--chunk-interval", "2", "--ckpt-interval", "4"])
    main()
    out = capsys.readouterr().out
    assert "CRASH at step 5" in out
    assert "RECOVERED to step 5" in out
    assert "bit-exact" in out
    assert "done: 8 steps" in out


def test_serve_driver(capsys, monkeypatch):
    from repro.launch.serve import main
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "rwkv6-3b", "--preset", "smoke",
        "--batch", "2", "--prompt-len", "8", "--gen", "3"])
    main()
    out = capsys.readouterr().out
    assert "prefill: batch=2" in out
    assert "decode: 3 steps" in out


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_cpu(capsys):
    """No fallback: without a TPU the smoke fails and reports no result."""
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert jax.devices()[0].platform == "cpu"
    assert mod.main() != 0
    assert '"ok"' not in capsys.readouterr().out
