"""``enable_compile_cache``: a fixed cache directory, or none set in code
when ``JAX_COMPILATION_CACHE_DIR`` names one."""

import jax
import pytest

from repro import compile_cache


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_cache_dir(monkeypatch, env_dir):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    compile_cache.enable_compile_cache()
    if env_dir is None:
        root = compile_cache.DEFAULT_CACHE_DIR.parent
        assert (root / "src" / "repro" / "compile_cache.py").is_file()
        assert updates == [("jax_compilation_cache_dir",
                            str(root / ".jax_cache"))]
    else:
        assert updates == []
