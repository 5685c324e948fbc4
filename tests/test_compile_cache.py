"""The entry points' persistent compilation cache: where it goes."""
import jax
import pytest

from repro import compile_cache


@pytest.mark.parametrize("env_dir", [True, False])
def test_cache_dir_from_environment_or_fixed_checkout_dir(
        monkeypatch, tmp_path, env_dir):
    """On an accelerator, JAX_COMPILATION_CACHE_DIR wins when set;
    otherwise the fixed, git-ignored directory inside the checkout —
    never a temporary name.  On the CPU the cache stays off."""
    assert compile_cache.enable() is None          # this CPU host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = compile_cache.DEFAULT_DIR
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
    assert compile_cache.DEFAULT_DIR.endswith(".jax_cache")
    with open(compile_cache.DEFAULT_DIR.replace(".jax_cache",
                                                ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
