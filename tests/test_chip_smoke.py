"""``chip_smoke.py``: refuses a backend that is not a TPU, and its phases run
end to end on the CPU at a reduced size (Pallas kernels interpreted), with
the losses of both kernel families within the script's tolerance of the
XLA reference."""
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import ClusterRunner, DevicePool, SliceExecutor
from repro.configs.base import get_config, reduced
from repro.sched.cost_model import TPU_V5E, CostModel
from repro.train.checkpoint import CheckpointPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def setup(smoke):
    cfg = reduced(get_config(smoke.ARCH))
    configs = smoke.pick_configs(seq=SEQ)
    base = smoke.init_base(cfg, configs, seed=0)
    return cfg, configs, base, CostModel(cfg, TPU_V5E)


def test_refuses_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_configs_are_mixed_rank_grid_points(smoke):
    configs = smoke.pick_configs()
    assert tuple(c.rank for c in configs) == smoke.RANKS
    assert all(c.batch_size == 1 and c.seq_len == smoke.SEQ for c in configs)


def test_base_is_bf16(setup):
    _, _, base, _ = setup
    assert {x.dtype for x in jax.tree.leaves(base)} == {jnp.dtype(jnp.bfloat16)}


@pytest.mark.parametrize("impl", ["pallas", "fused_pallas"])
def test_kernel_losses_match_xla(smoke, setup, impl):
    """The script's comparison, with the Pallas bodies interpreted."""
    cfg, configs, base, cm = setup
    runs = {}
    for name in ("xla", impl):
        runner = ClusterRunner(SliceExecutor(), DevicePool(jax.devices()[:1]))
        runs[name] = smoke.train(cm, cfg, configs, base, runner, impl=name,
                                 steps=2, seq=SEQ)
    assert runs[impl]["packs"] == runs["xla"]["packs"]
    for step in ("first", "last"):
        smoke.check_losses(impl, runs[impl][step], runs["xla"][step])
    with pytest.raises(smoke.CheckFailed):
        smoke.check_losses(impl, runs[impl]["last"] * 1.01, runs["xla"]["last"])


def test_sweep_then_serve(smoke, setup, tmp_path):
    cfg, configs, base, cm = setup
    pool = CheckpointPool(str(tmp_path))
    runner = ClusterRunner(SliceExecutor(), DevicePool(jax.devices()[:1]))
    r = smoke.train(cm, cfg, configs, base, runner, impl="xla", steps=2,
                    seq=SEQ, pool=pool)
    assert sorted(c for p in r["packs"] for c in p) == list(range(4))
    assert np.all(np.isfinite(r["last"]))
    assert pool.list() == [f"adapter_{i:04d}" for i in range(4)]
    stats = smoke.serve(cfg, base, pool, configs, new_tokens=3)
    assert stats.tokens_emitted == 12
