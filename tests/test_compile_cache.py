"""The persistent compile cache rule: JAX_COMPILATION_CACHE_DIR when the
environment sets it (nothing else is set), else one fixed, git-ignored
path inside the checkout."""
import subprocess

import jax
import pytest

from repro.runtime import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_cache_dir_rule(monkeypatch, restore_cache_dir, env):
    if env is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV, env)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.configure_compile_cache()
    if env is None:
        assert got == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.DEFAULT_DIR.parent == compile_cache.CHECKOUT
    else:
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before   # untouched


def test_default_cache_dir_is_git_ignored():
    entry = compile_cache.DEFAULT_DIR / "entry"   # the dir need not exist
    out = subprocess.run(["git", "check-ignore", "-q", str(entry)],
                         cwd=compile_cache.CHECKOUT, capture_output=True)
    if out.returncode == 128:
        pytest.skip("not a git checkout")
    assert out.returncode == 0, ".jax_cache/ is not listed in .gitignore"
