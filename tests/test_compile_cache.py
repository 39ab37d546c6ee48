"""The persistent compilation cache rule: an environment that names a cache
directory keeps it; otherwise the cache sits at one fixed path in the repo."""
import jax
import pytest

from repro.launch import compile_cache as cc


@pytest.fixture()
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env,want", [
    ({}, str(cc.DEFAULT_DIR)),
    ({cc.ENV_VAR: ""}, str(cc.DEFAULT_DIR)),
    ({cc.ENV_VAR: "/srv/jax-cache"}, None),
])
def test_rule(env, want):
    assert cc.compile_cache_dir(env) == want


def test_default_dir_is_fixed_and_in_the_repo():
    assert cc.DEFAULT_DIR == cc.REPO_ROOT / ".jax_cache"
    assert (cc.REPO_ROOT / "chip_smoke.py").is_file()


def test_unset_points_jax_at_the_repo_cache(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    assert cc.enable_compile_cache() == str(cc.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(cc.DEFAULT_DIR)


def test_set_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "elsewhere"))
    assert cc.enable_compile_cache() == str(tmp_path)
