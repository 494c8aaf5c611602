"""``repro.compile_cache``: the persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at the checkout's fixed path."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_environment_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                             restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_checkout_cache_receives_compiles(monkeypatch, tmp_path,
                                          restore_cache_config):
    where = tmp_path / ".jax_cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", where)
    assert compile_cache.use_compile_cache() == str(where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3.0 + 1.25)(jnp.ones(11)).block_until_ready()
    assert any(where.iterdir())


def test_checkout_cache_is_a_fixed_path_in_the_checkout():
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "pyproject.toml").exists()



def test_cache_off_block_neither_writes_nor_reads(monkeypatch, tmp_path,
                                                  restore_cache_config):
    where = tmp_path / ".jax_cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", where)
    compile_cache.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    hits = []

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    def compile_once():
        jax.clear_caches()
        jax.jit(lambda x: x * 5.0 - 0.75)(jnp.ones(13)).block_until_ready()

    jax.monitoring.register_event_listener(on_event)
    try:
        with compile_cache.compile_cache_off():
            compile_once()
        assert not where.exists() or not any(where.iterdir())
        compile_once()  # the cache is back on: this compile is written
        assert any(where.iterdir())
        with compile_cache.compile_cache_off():
            compile_once()
        assert hits == []
        compile_once()  # and read again
        assert hits
    finally:
        jax.monitoring.unregister_event_listener(on_event)
