"""repro.runtime: where computations run and where compiled programs go."""

import os

import jax
import pytest

from repro import runtime
from repro.core import metrics as MX
from repro.kernels import ops

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = runtime.enable_compile_cache()
    assert got == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing else is configured here
    assert jax.config.jax_compilation_cache_dir is None


@pytest.mark.parametrize("device", ["object", "platform name"])
def test_cpu_default_device_on_a_tpu_host_takes_host_routes(monkeypatch,
                                                              device):
    """A host-CPU reference inside a TPU process: the kernels interpret
    and the characters take the jnp route, though the backend is a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.default_platform() == "tpu"
    assert not ops._interpret_default() and MX._default_use_kernel()
    cpu = jax.devices("cpu")[0] if device == "object" else "cpu"
    with jax.default_device(cpu):
        assert runtime.default_platform() == "cpu"
        assert ops._interpret_default() and not MX._default_use_kernel()
