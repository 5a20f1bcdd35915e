"""Where the launchers keep the persistent compilation cache, and how they
name their device."""

import os
import subprocess
import sys

import jax
import pytest

from repro.launch import device


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)


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


#: Compiles one program, then the same program inside a named scope, in a
#: fresh cache; prints whether the second executable carries the name.
NAMED_AFTER_PLAIN = """
import jax, jax.numpy as jnp
from repro.launch import device
device.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
def body(x):
    return jnp.sort(x) * 2.0
plain = jax.jit(lambda x: body(x) + 1.0)
named = jax.jit(lambda x: jax.named_scope("pair_table")(body)(x) + 1.0)
plain.lower(jnp.ones(8)).compile()
print("pair_table" in named.lower(jnp.ones(8)).compile().as_text())
"""


def test_cached_programs_keep_their_names(tmp_path):
    """A program whose only change is a named scope is compiled anew, not
    taken from the cache under the unnamed program's key."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(device.CACHE_DIR.parent / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    env[device.CACHE_ENV] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", NAMED_AFTER_PLAIN], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["True"]
    assert any(tmp_path.iterdir())  # the cache was used


def test_device_info_names_the_platform():
    info = device.device_info()
    assert info == {"platform": jax.devices()[0].platform,
                    "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}
