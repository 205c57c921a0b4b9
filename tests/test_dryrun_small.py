"""Sharding + dry-run machinery on a small FORCED-device host mesh.

These tests run in a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 (the main test process must keep seeing 1 CPU device), and
exercise the same param/batch/cache sharding rules and lower/compile path the
512-device production dry-run uses. The full production sweep is
``python -m repro.launch.dryrun --all`` (results in EXPERIMENTS.md §Dry-run).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess compiles; full set runs on main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=540):
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.path.join(ROOT, "src"),
    )
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def test_shard_and_train_step_on_4x2_mesh():
    """Reduced arch, real 8-device host mesh (4 data x 2 model): shard params
    per the production rules, run one REAL train step, check finiteness and
    that adapter grads stay sharded."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import LoraConfig, get_config, reduced
        from repro.core.adapter import pack_meta
        from repro.launch.mesh import make_host_mesh
        from repro.launch.sharding import batch_specs, make_dist, param_specs, to_named
        from repro.models.model import init_model
        from repro.train.data import packed_batch_iterator
        from repro.train.optimizer import init_opt_state
        from repro.train.trainer import make_train_step

        assert jax.device_count() == 8, jax.device_count()
        cfg = reduced(get_config("qwen25-7b"), d_model=256)
        configs = [LoraConfig(rank=8, alpha=8., learning_rate=1e-3, batch_size=2)
                   for _ in range(4)]
        meta = pack_meta(configs)
        mesh = make_host_mesh(4, 2)
        base, lora = init_model(jax.random.PRNGKey(0), cfg, meta)
        with mesh:
            base_sp = to_named(param_specs(jax.eval_shape(lambda: base), cfg, mesh), mesh)
            lora_sp = to_named(param_specs(jax.eval_shape(lambda: lora), cfg, mesh), mesh)
            base = jax.device_put(base, base_sp)
            lora = jax.device_put(lora, lora_sp)
            opt = init_opt_state(lora)
            it = packed_batch_iterator(cfg, configs, seq=16)
            b = next(it)
            bs = to_named(batch_specs(jax.eval_shape(lambda: b), mesh), mesh)
            b = jax.device_put(b, bs)
            dist = make_dist(mesh, meta.n * meta.max_batch)
            step = make_train_step(cfg, meta, dist=dist, jit=True)
            lora2, opt2, m = step(base, lora, opt, b)
            loss = float(m["loss"])
        assert np.isfinite(loss), loss
        print("OK", loss)
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_production_mesh_shapes():
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        assert m1.devices.shape == (16, 16) and m1.axis_names == ("data", "model")
        assert m2.devices.shape == (2, 16, 16) and m2.axis_names == ("pod", "data", "model")
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-3000:]


def test_dryrun_lower_one_combo():
    """Full-size arch lowers (no compile — compile is the slow production
    sweep) on the 512-device production mesh, from the dryrun module."""
    r = _run("""
        from repro.launch.dryrun import lower_combo
        rep, info = lower_combo("gemma3-1b", "train_4k", compile_=False)
        assert rep is None and info["lower_s"] > 0
        print("OK", round(info["lower_s"], 1))
    """)
    assert r.returncode == 0, r.stderr[-3000:]


def test_dryrun_compile_decode_combo():
    """One full decode combo compiles end-to-end and yields roofline terms."""
    r = _run("""
        from repro.launch.dryrun import lower_combo
        rep, info = lower_combo("internvl2-1b", "decode_32k")
        row = rep.row(info["n_devices"])
        assert row["flops_per_device"] > 0
        assert row["t_compute_s"] > 0 and row["t_memory_s"] > 0
        assert row["bottleneck"] in ("compute", "memory", "collective")
        print("OK", row["bottleneck"])
    """)
    assert r.returncode == 0, r.stderr[-3000:]


def test_seq_parallel_residuals_same_values():
    """seq_sharded_residuals is a sharding CONSTRAINT, not a math change:
    loss and grads must match the baseline bitwise-ish on a real mesh."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import LoraConfig, get_config, reduced
        from repro.core.adapter import pack_meta
        from repro.launch.mesh import make_host_mesh
        from repro.launch.sharding import batch_specs, make_dist, param_specs, to_named
        from repro.models.model import init_model
        from repro.train.data import packed_batch_iterator
        from repro.train.trainer import loss_fn

        cfg = reduced(get_config("starcoder2-7b"), d_model=256)
        configs = [LoraConfig(rank=8, alpha=8., learning_rate=1e-3, batch_size=2)
                   for _ in range(2)]
        meta = pack_meta(configs)
        mesh = make_host_mesh(2, 4)
        base, lora = init_model(jax.random.PRNGKey(0), cfg, meta)
        it = packed_batch_iterator(cfg, configs, seq=16)
        b = next(it)
        nb = meta.n * meta.max_batch
        with mesh:
            losses = []
            for sp in (False, True):
                dist = make_dist(mesh, nb, seq_sharded_residuals=sp)
                l, per = jax.jit(lambda lo: loss_fn(
                    lo, base, b, cfg, meta, dist=dist))(lora)
                losses.append(float(l))
        np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
        print("OK", losses)
    """)
    assert r.returncode == 0, r.stderr[-3000:]


def test_fsdp_mode_same_values():
    """FSDP execution mode (batch over data x model, weights gathered per
    use) is a LAYOUT change only: loss must equal the megatron baseline."""
    r = _run("""
        import jax, numpy as np
        from repro.configs.base import LoraConfig, get_config, reduced
        from repro.core.adapter import pack_meta
        from repro.launch.mesh import make_host_mesh
        from repro.launch.sharding import batch_specs, make_dist, param_specs, to_named
        from repro.models.model import init_model
        from repro.train.data import packed_batch_iterator
        from repro.train.trainer import loss_fn

        cfg = reduced(get_config("starcoder2-7b"), d_model=256)
        configs = [LoraConfig(rank=8, alpha=8., learning_rate=1e-3, batch_size=4)
                   for _ in range(2)]
        meta = pack_meta(configs)
        mesh = make_host_mesh(2, 4)
        base, lora = init_model(jax.random.PRNGKey(0), cfg, meta)
        b = next(packed_batch_iterator(cfg, configs, seq=16))
        nb = meta.n * meta.max_batch
        losses = []
        with mesh:
            for fsdp in (False, True):
                dist = make_dist(mesh, nb, fsdp=fsdp)
                bs = to_named(batch_specs(
                    jax.eval_shape(lambda: b), mesh, include_model=fsdp), mesh)
                bb = jax.device_put(b, bs)
                l, _ = jax.jit(lambda lo: loss_fn(lo, base, bb, cfg, meta,
                                                  dist=dist))(lora)
                losses.append(float(l))
        np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
        print("OK", losses)
    """)
    assert r.returncode == 0, r.stderr[-3000:]


def test_flash_decode_cache_layout_same_values():
    """seq-over-model cache sharding changes collectives, not logits."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import LoraConfig, get_config, reduced
        from repro.core.adapter import pack_meta
        from repro.launch.mesh import make_host_mesh
        from repro.launch.sharding import cache_specs, make_dist, param_specs, to_named
        from repro.models.model import init_caches, init_model
        from repro.serve.decode import make_serve_step

        cfg = reduced(get_config("starcoder2-7b"), d_model=256)
        meta = pack_meta([LoraConfig(rank=8, alpha=8.)] * 2)
        mesh = make_host_mesh(2, 4)
        base, lora = init_model(jax.random.PRNGKey(0), cfg, meta)
        lora = jax.tree.map(lambda x: x + 0.01, lora)
        nb = 4
        caches = init_caches(cfg, nb, 32, jnp.float32)
        tok = jnp.ones((nb, 1), jnp.int32)
        outs = []
        with mesh:
            for som in (False, True):
                dist = make_dist(mesh, nb)
                cs = to_named(cache_specs(
                    jax.eval_shape(lambda: caches), mesh, nb,
                    seq_over_model=som), mesh)
                cc = jax.device_put(caches, cs)
                step = make_serve_step(cfg, meta, dist=dist, jit=False)
                _, lg, _ = jax.jit(step)(base, lora, cc, tok, jnp.int32(3))
                outs.append(np.asarray(lg))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-4)
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-3000:]


def test_moe_ep_shard_map_on_mesh():
    """Expert-parallel MoE under shard_map on a real (2 data x 4 model) mesh
    == the dense oracle, drop-free: under a router that sends every token
    to the first model shard's experts (the skew a capacity-bounded
    dispatch dropped rows under) nothing is lost, and the row counts are the
    whole layer's."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import MoEConfig
        from repro.models.layers.moe import init_moe, routed_experts
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(2, 4)
        mcfg = MoEConfig(n_experts=8, top_k=2, d_expert=8, impl="ep")
        params, _ = init_moe(jax.random.PRNGKey(0), 16, mcfg)
        params["router"]["w"] = (jnp.zeros_like(params["router"]["w"])
                                 .at[:, 0].set(2.0).at[:, 1].set(1.0))
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))) + 0.1
        y_ref, rows_ref = routed_experts(params, x, MoEConfig(
            n_experts=8, top_k=2, d_expert=8, impl="dense"))
        assert rows_ref.tolist() == [32, 32, 0, 0, 0, 0, 0, 0], rows_ref

        def body(p, xx):
            y, rows = routed_experts(p, xx, mcfg, model_axis="model",
                                     model_axis_size=4)
            return y, jax.lax.psum(rows, "data")

        specs = {"router": {"w": P()}, "w_gate": P("model", None, None),
                 "w_up": P("model", None, None), "w_down": P("model", None, None)}
        with mesh:
            y, rows = jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(specs, P("data", None, None)),
                out_specs=(P("data", None, None), P()),
                check_vma=False,
            ))(params, x)
        # every routed row of the hot shard lands: the drop-free answer
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=5e-3, atol=5e-3)
        assert np.asarray(rows).tolist() == rows_ref.tolist()
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-3000:]


def test_deepseek_ep_mesh_matches_single_device():
    """DeepSeek-V2 (reduced, 8 experts top-3, a dense head layer, shared
    experts with LoRA) on a (2 data x 4 model) mesh: each model shard holds
    2 experts and computes their part drop-free; the pack's loss and the
    routed rows of each expert equal the single-device step's."""
    r = _run("""
        import dataclasses
        import jax, numpy as np
        from repro.configs.base import LoraConfig, get_config, reduced
        from repro.core.adapter import pack_meta
        from repro.launch.mesh import make_host_mesh
        from repro.launch.sharding import make_dist, param_specs, to_named
        from repro.models.model import init_model
        from repro.train.data import packed_batch_iterator
        from repro.train.trainer import loss_fn

        cfg = reduced(get_config("deepseek-v2-lite"), d_model=256)
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=3))
        configs = [LoraConfig(rank=8, alpha=8., learning_rate=1e-3, batch_size=2)
                   for _ in range(2)]
        meta = pack_meta(configs)
        base, lora = init_model(jax.random.PRNGKey(0), cfg, meta)
        b = next(packed_batch_iterator(cfg, configs, seq=16))
        l0, (_, rows0) = loss_fn(lora, base, b, cfg, meta)
        mesh = make_host_mesh(2, 4)
        with mesh:
            dist = make_dist(mesh, meta.n * meta.max_batch)
            spec = lambda t: to_named(param_specs(jax.eval_shape(lambda: t), cfg, mesh), mesh)
            base_d = jax.device_put(base, spec(base))
            lora_d = jax.device_put(lora, spec(lora))
            l1, (_, rows1) = jax.jit(
                lambda lo: loss_fn(lo, base_d, b, cfg, meta, dist=dist))(lora_d)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-4)
        assert np.array_equal(np.asarray(rows0), np.asarray(rows1))
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-3000:]
