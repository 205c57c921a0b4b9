"""Per-architecture smoke tests (assignment requirement).

For every assigned architecture: instantiate the REDUCED variant of the same
family (≤4 layers effective pattern, d_model ≤ 512, ≤4 experts), run one
forward and one train step on CPU, assert output shapes and no NaNs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LoraConfig, get_config, list_archs, reduced
from repro.core.adapter import pack_meta
from repro.models import model as M
from repro.train.data import packed_batch_iterator
from repro.train.optimizer import init_opt_state
from repro.train.trainer import make_train_step

ARCHS = list_archs()
SEQ = 32


def _batch_for(cfg, key, nb, seq):
    batch = {"tokens": jax.random.randint(key, (nb, seq), 0, cfg.vocab_size)}
    if cfg.is_encdec:
        batch["frames"] = 0.1 * jax.random.normal(
            key, (nb, cfg.encoder_seq_len, cfg.d_model)
        )
    if cfg.n_patch_tokens:
        batch["patches"] = 0.1 * jax.random.normal(
            key, (nb, cfg.n_patch_tokens, cfg.d_model)
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_config_is_reduced(arch):
    cfg = reduced(get_config(arch))
    assert cfg.d_model <= 512
    assert cfg.n_layers <= 6
    if cfg.moe.enabled:
        assert cfg.moe.n_experts <= 4


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, key, meta2):
    cfg = reduced(get_config(arch))
    base, lora = M.init_model(key, cfg, meta2)
    nb = meta2.n * meta2.max_batch
    batch = _batch_for(cfg, key, nb, SEQ)
    h, _, rows = M.forward(base, lora, meta2.scales(), batch, cfg, n_pack=meta2.n)
    s_total = SEQ + (cfg.n_patch_tokens or 0)
    assert h.shape == (nb, s_total, cfg.d_model)
    assert bool(jnp.isfinite(h).all())
    # every MoE layer routes each token to top_k experts, none dropped
    n_moe = cfg.ffn_kinds().count("moe")
    assert rows.shape == (n_moe, cfg.moe.n_experts)
    assert (np.asarray(rows).sum(-1) == nb * s_total * cfg.moe.top_k).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch, key):
    cfg = reduced(get_config(arch))
    configs = [
        LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3, batch_size=1),
        LoraConfig(rank=16, alpha=16.0, learning_rate=5e-4, batch_size=1),
    ]
    meta = pack_meta(configs)
    base, lora = M.init_model(key, cfg, meta)
    it = packed_batch_iterator(cfg, configs, seq=SEQ)
    step = make_train_step(cfg, meta, jit=False)
    opt = init_opt_state(lora)
    lora2, opt2, metrics = step(base, lora, opt, next(it))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert metrics["per_adapter_loss"].shape == (meta.n,)
    assert bool(jnp.isfinite(metrics["per_adapter_loss"]).all())
    # adapter B must have moved away from zero after one step
    moved = jax.tree.reduce(
        lambda acc, x: acc + float(jnp.abs(x).sum()),
        jax.tree.map(lambda a, b: a - b, lora, lora2),
        0.0,
    )
    assert moved > 0.0
    assert int(opt2["step"]) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_dims_match_assignment(arch):
    """The FULL configs carry the exact published dimensions."""
    spec = {
        "mamba2-370m": dict(n_layers=48, d_model=1024, vocab_size=50_280),
        "qwen3-moe-30b-a3b": dict(n_layers=48, d_model=2048, vocab_size=151_936),
        "whisper-tiny": dict(n_layers=4, d_model=384, vocab_size=51_865),
        "minicpm3-4b": dict(n_layers=62, d_model=2560, vocab_size=73_448),
        "deepseek-v2-lite": dict(n_layers=27, d_model=2048, vocab_size=102_400),
        "gemma3-1b": dict(n_layers=26, d_model=1152, vocab_size=262_144),
        "command-r-35b": dict(n_layers=40, d_model=8192, vocab_size=256_000),
        "jamba-v0.1-52b": dict(n_layers=32, d_model=4096, vocab_size=65_536),
        "starcoder2-7b": dict(n_layers=32, d_model=4608, vocab_size=49_152),
        "grok-1-314b": dict(n_layers=64, d_model=6144, vocab_size=131_072),
        "internvl2-1b": dict(n_layers=24, d_model=896, vocab_size=151_655),
        "qwen25-7b": dict(n_layers=28, d_model=3584, vocab_size=152_064),
    }[arch]
    cfg = get_config(arch)
    for k, v in spec.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_moe_config_details():
    q = get_config("qwen3-moe-30b-a3b")
    assert q.moe.n_experts == 128 and q.moe.top_k == 8
    g = get_config("grok-1-314b")
    assert g.moe.n_experts == 8 and g.moe.top_k == 2
    j = get_config("jamba-v0.1-52b")
    assert j.moe.n_experts == 16 and j.moe.top_k == 2


def test_jamba_layer_pattern():
    cfg = get_config("jamba-v0.1-52b")
    kinds = cfg.layer_kinds()
    assert kinds.count("attn") == 4  # 32 layers, 1 attn per 8
    assert kinds[3] == "attn"
    ffns = cfg.ffn_kinds()
    assert ffns.count("moe") == 16  # every other layer


def test_gemma_local_global_pattern():
    from repro.models.transformer import layer_specs

    cfg = get_config("gemma3-1b")
    specs = layer_specs(cfg)
    windows = [s.window for s in specs]
    # every 6th layer global (window 0), rest local 512
    assert windows[5] == 0 and windows[0] == 512
    assert sum(1 for w in windows if w == 0) == 26 // 6
    thetas = {s.theta for s in specs}
    assert thetas == {10_000.0, 1e6}


@pytest.mark.parametrize("arch", ["mamba2-370m", "gemma3-1b", "jamba-v0.1-52b"])
def test_long_context_archs_marked(arch):
    assert get_config(arch).supports_long_context


def test_lora_starts_at_zero_delta(key, meta2):
    """B=0 init => packed model output == base model output at step 0."""
    cfg = reduced(get_config("starcoder2-7b"))
    base, lora = M.init_model(key, cfg, meta2)
    nb = meta2.n * 2
    batch = _batch_for(cfg, key, nb, SEQ)
    h_with, _, _ = M.forward(base, lora, meta2.scales(), batch, cfg, n_pack=meta2.n)
    h_without, _, _ = M.forward(base, {}, meta2.scales(), batch, cfg, n_pack=meta2.n)
    np.testing.assert_allclose(
        np.asarray(h_with), np.asarray(h_without), rtol=1e-6, atol=1e-6
    )
