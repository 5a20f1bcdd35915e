"""Where the launchers keep the persistent compilation cache, and how they
name their device."""

import jax
import pytest

from repro.launch import device


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_follows_the_environment(monkeypatch, tmp_path,
                                       cache_dir_restored):
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # jax reads the env


def test_cache_defaults_to_the_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    path = device.enable_compile_cache()
    assert path == str(device.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert device.CACHE_DIR.parent.joinpath("pyproject.toml").exists()


def test_device_info_names_the_platform():
    info = device.device_info()
    assert info == {"platform": jax.devices()[0].platform,
                    "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}
