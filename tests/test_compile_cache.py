"""Where the persistent compilation cache goes (repro.launch.compile_cache).

The cache key includes the directory, so a run finds its compiled
programs again only when the directory stays put: the environment's
``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else one
fixed path inside the checkout — never a temp name, pid or time."""
from __future__ import annotations

from pathlib import Path

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_dir_is_one_fixed_path_in_the_checkout():
    first = compile_cache.compile_cache_dir({})
    assert first == compile_cache.compile_cache_dir({})
    assert first == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_environment_dir_wins(tmp_path):
    env = {compile_cache.ENV_VAR: str(tmp_path)}
    assert compile_cache.compile_cache_dir(env) == tmp_path
    # an empty value counts as unset
    assert compile_cache.compile_cache_dir({compile_cache.ENV_VAR: ""}) \
        == compile_cache.DEFAULT_CACHE_DIR


def test_enable_sets_no_dir_when_the_environment_has_one(monkeypatch,
                                                         tmp_path):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == tmp_path
    assert calls == []

    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.enable_compile_cache() \
        == compile_cache.DEFAULT_CACHE_DIR
    assert calls == [("jax_compilation_cache_dir",
                      str(compile_cache.DEFAULT_CACHE_DIR))]
