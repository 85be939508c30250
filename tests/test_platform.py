"""What the code decides from the platform it finds: the Pallas execution
mode, the client mesh, and the persistent compile cache's directory."""
from pathlib import Path

import jax
import pytest

from repro.kernels import interpret as interpret_lib
from repro.launch import compile_cache, mesh

_REPO = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("platform,expect", [("cpu", True), ("tpu", False)])
def test_interpret_mode_follows_the_platform(monkeypatch, platform, expect):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert interpret_lib.interpret_mode(None) is expect
    assert interpret_lib.interpret_mode(not expect) is (not expect)


def test_interpret_mode_refuses_other_platforms(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        interpret_lib.interpret_mode(None)


def test_client_mesh_uses_every_local_device():
    n = len(jax.local_devices())
    assert mesh.make_client_mesh(2 * n).devices.size == n
    assert mesh.make_client_mesh().devices.size == n


def test_client_mesh_refuses_an_uneven_split():
    two = jax.local_devices()[:1] * 2
    with pytest.raises(ValueError, match="do not split evenly"):
        mesh.make_client_mesh(3, devices=two)


def test_compile_cache_leaves_the_environment_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.place_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.place_compile_cache()
        assert path == str(_REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert ".jax_cache/" in (_REPO / ".gitignore").read_text().split()
