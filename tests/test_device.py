import os

import jax
import pytest

from fedml_tpu import device
from fedml_tpu.arguments import Arguments


def test_virtual_8_devices():
    assert jax.device_count() == 8


def test_build_default_clients_mesh():
    mesh = device.build_mesh()
    assert mesh.axis_names == ("clients",)
    assert mesh.devices.size == 8


def test_build_2d_mesh_with_inference():
    mesh = device.build_mesh({"data": 2, "tensor": -1})
    assert mesh.devices.shape == (2, 4)


def test_mesh_size_mismatch():
    with pytest.raises(ValueError):
        device.build_mesh({"data": 3})


def test_get_mesh_from_args():
    args = Arguments(overrides={"mesh_shape": "clients:8"})
    mesh = device.get_mesh(args)
    assert mesh.axis_names == ("clients",)


class TestCompilationCacheDir:
    """``enable_compilation_cache`` is the one place that decides where the
    persistent cache lives."""

    @pytest.fixture()
    def updates(self, monkeypatch):
        calls = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda key, value: calls.__setitem__(key, value))
        return calls

    def test_env_set_leaves_the_directory_to_jax(self, monkeypatch, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert device.enable_compilation_cache() == "/some/dir"
        assert not [k for k in updates if "cache_dir" in k]

    def test_env_unset_uses_the_checkout(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        assert device.enable_compilation_cache() == want
        assert [v for k, v in updates.items() if "cache_dir" in k] == [want]
