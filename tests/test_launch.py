"""The entry points' process setup: compile cache, device mesh, and the
training launcher's contract (returns its adapters, fails loudly)."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.launch.cache import CHECKOUT, enable_compile_cache
from repro.launch.mesh import make_device_mesh


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_the_env_dir_alone(monkeypatch, tmp_path,
                                                cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert (CHECKOUT / "src" / "repro" / "launch" / "cache.py").exists()


def test_device_mesh_spans_the_devices_present():
    mesh = make_device_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (1, len(jax.devices()))


def _train(monkeypatch, tmp_path, method):
    from repro.launch import train
    # the launcher enables the compile cache; an env dir keeps it off here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    return train.main(["--preset", "reduced", "--steps", "1", "--batch",
                       "1", "--seq", "16", "--rank", "4", "--method",
                       method, "--agg-backend", "ref"])


def test_train_returns_its_trained_adapters(monkeypatch, tmp_path):
    from repro.lora import is_pair, tree_map_pairs
    out = _train(monkeypatch, tmp_path, "rbla")
    pairs = []
    tree_map_pairs(lambda p: pairs.append(p) or p, out)
    assert pairs and all(is_pair(p) for p in pairs)
    assert all(int(np.max(np.asarray(p["rank"]))) == 4 for p in pairs)
    assert all(bool(np.isfinite(np.asarray(p["A"])).all()) for p in pairs)


def test_train_fails_when_the_strategy_cannot_aggregate(monkeypatch,
                                                        tmp_path):
    # rbla_norm's norm target has no layer-stacked path: the launcher
    # must fail rather than save unaggregated adapters
    with pytest.raises(NotImplementedError):
        _train(monkeypatch, tmp_path, "rbla_norm")
