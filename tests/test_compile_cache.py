"""Entry-point placement of JAX's persistent compilation cache."""
import jax

from repro.launch import compile_cache


def test_env_cache_dir_wins_and_nothing_is_set(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert calls == []


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable()
    assert first == compile_cache.enable()          # same path every run
    assert first == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
    assert calls == [("jax_compilation_cache_dir", first)] * 2
