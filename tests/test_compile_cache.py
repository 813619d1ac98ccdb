"""Where the persistent compilation cache goes (launch/cache.py)."""
import jax

from repro.launch import cache


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "")
    assert cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_cpu_pinned_process_keeps_no_cache(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert cache.enable_compile_cache() is None
    assert calls == []


def test_default_is_one_fixed_dir_in_the_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    want = str(cache.CHECKOUT_CACHE_DIR)
    assert cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    assert (cache.CHECKOUT_CACHE_DIR.parent / "chip_smoke.py").exists()
