"""The persistent compilation cache lands where the environment says, and
otherwise at the checkout's fixed, git-ignored ``.jax_cache/``."""

import contextlib
import pathlib

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache as jcc

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
_OPTIONS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
)


@contextlib.contextmanager
def _restored_config():
    saved = {name: getattr(jax.config, name) for name in _OPTIONS}
    try:
        yield
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        jcc.reset_cache()


def test_env_dir_receives_the_cache(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    with _restored_config():
        jcc.reset_cache()
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        jax.jit(lambda x: x * 3 + 1).lower(jnp.ones((8, 128))).compile()
    assert any(tmp_path.iterdir()), "nothing was written to the cache dir"


def test_default_dir_is_the_checkout_cache(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    with _restored_config():
        path = compile_cache.enable()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path


def test_checkout_cache_is_gitignored():
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored

