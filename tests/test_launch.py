"""Launcher set-up: the cost-model prior follows the chip it runs on, and
the persistent compilation cache lives where JAX or the checkout says."""
import argparse
import os
from types import SimpleNamespace

import jax
import pytest

from repro.configs.base import get_config, reduced
from repro.launch import cache, train
from repro.sched.cost_model import A100_40G, A10_24G, TPU_V5E


def _args(hw=None):
    return argparse.Namespace(hw=hw, profile_in=None, quant="none")


@pytest.mark.parametrize(
    "backend, kind, hw, want",
    [
        ("cpu", "cpu", None, A100_40G),
        ("tpu", "TPU v5 lite", None, TPU_V5E),
        ("tpu", "TPU v5 lite", "a10-24g", A10_24G),
    ],
)
def test_hw_prior(monkeypatch, backend, kind, hw, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "devices",
                        lambda: [SimpleNamespace(device_kind=kind)])
    est, _ = train._estimator(_args(hw), reduced(get_config("qwen25-7b")))
    assert est.prior.hw is want


def test_hw_prior_refuses_unknown_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices",
                        lambda: [SimpleNamespace(device_kind="TPU v4")])
    with pytest.raises(ValueError, match="TPU v4"):
        train._estimator(_args(), reduced(get_config("qwen25-7b")))


def test_compile_cache_in_checkout(monkeypatch, tmp_path):
    set_to = {}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: set_to.update({name: value}))
    path = cache.enable_compile_cache(str(tmp_path))
    assert path == os.path.join(str(tmp_path), ".jax_cache")
    assert set_to == {"jax_compilation_cache_dir": path}
    assert cache.REPO_ROOT == os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    set_to = {}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: set_to.update({name: value}))
    assert cache.enable_compile_cache() == str(tmp_path / "c")
    assert set_to == {}
