"""Process-level JAX plumbing: trace detection and the compile-cache path."""
import jax
import jax.numpy as jnp

from repro import runtime


def test_trace_state_clean_outside_and_inside_jit():
    seen = []

    @jax.jit
    def f(x):
        seen.append(runtime.trace_state_clean())
        return x + 1

    assert runtime.trace_state_clean()
    f(jnp.ones(2))
    assert seen == [False]


def test_compile_cache_dir_env_first_then_fixed_repo_path(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert runtime.enable_compile_cache() == "/elsewhere/cache"
        # The variable is jax's own; the helper sets no directory itself.
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = runtime.enable_compile_cache()
        assert path == str(runtime.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert (runtime.REPO_CACHE_DIR.parent / "chip_smoke.py").is_file()
        assert runtime.REPO_CACHE_DIR.name == ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
