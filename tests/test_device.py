"""``core.device``: interpret mode follows the backend, and the compile
cache goes where the environment says, else to one fixed directory."""

import jax
import pytest

from repro.core import device


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("gpu", True), ("tpu", False)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert device.interpret_mode() is interpret


def test_compile_cache_from_environment_sets_nothing(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert device.enable_compile_cache() == str(tmp_path / "env")
    assert updates == []


def test_compile_cache_defaults_to_fixed_dir(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(device, "REPO_CACHE_DIR", tmp_path / ".jax_cache")
    first = device.enable_compile_cache()
    assert first == device.enable_compile_cache() == str(tmp_path / ".jax_cache")
    assert (tmp_path / ".jax_cache").is_dir()
    assert updates == [("jax_compilation_cache_dir", first)] * 2


def test_repo_cache_dir_is_at_the_repository_root():
    assert (device.REPO_CACHE_DIR.parent / "src" / "repro" / "core" / "device.py").is_file()
