"""DeepSeek-V2-Lite in the program: the registered configuration, YaRN
rope, MLA with a direct q projection, the dense head layer ahead of the
scanned MoE period, drop-free routing inside a packed step (packing
isolation), the expert row counts the step returns and the executor puts on
its span, and the cost model's counts of the published configuration."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LoraConfig, get_config, reduced
from repro.core.adapter import pack_meta
from repro.kernels.moe_gmm import grid_rows
from repro.models import model as M
from repro.models.layers.attention import init_mla, mla_softmax_scale
from repro.models.layers.rope import rope_tables, yarn_inv_freq, yarn_mscale
from repro.sched.cost_model import (
    active_param_count,
    lora_param_count,
    model_param_count,
)
from repro.train.optimizer import init_opt_state
from repro.train.trainer import make_train_step, packed_loss_fn

ARCH = "deepseek-v2-lite"


def _tiny(**moe):
    cfg = reduced(get_config(ARCH))
    return cfg.replace(moe=dataclasses.replace(cfg.moe, **moe))


def test_published_values():
    cfg = get_config(ARCH)
    a, m = cfg.attention, cfg.moe
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == (27, 2048, 10944, 102400)
    assert (a.n_heads, a.q_lora_rank, a.kv_lora_rank) == (16, 0, 512)
    assert (a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim) == (128, 64, 128)
    assert (a.rope_theta, a.yarn_factor, a.yarn_original_max_positions) == (1e4, 40, 4096)
    assert (a.yarn_beta_fast, a.yarn_beta_slow, a.yarn_mscale_all_dim) == (32, 1, 0.707)
    assert (m.n_experts, m.top_k, m.d_expert, m.n_shared_experts) == (64, 6, 1408, 2)
    assert (m.first_k_dense, m.norm_topk_prob) == (1, False)
    assert cfg.ffn_kinds() == ("dense",) + ("moe",) * 26
    assert not cfg.tie_embeddings and not a.use_bias


def test_first_k_dense_leaves_other_patterns_alone():
    """Every registered MoE config without a dense head keeps its pattern."""
    jamba = get_config("jamba-v0.1-52b")
    assert jamba.ffn_kinds()[:4] == ("dense", "moe", "dense", "moe")
    assert set(get_config("qwen3-moe-30b-a3b").ffn_kinds()) == {"moe"}


def test_yarn_tables_at_hand_computed_points():
    """dim 64, theta 1e4, factor 40 over 4096 positions, beta 32 / 1: the
    ramp runs from pair 10 (floor of 10.47) to pair 23 (ceil of 22.51)."""
    inv = np.asarray(yarn_inv_freq(64, 1e4, 40.0, 4096, 32.0, 1.0))
    extra = lambda i: 1e4 ** (-2 * i / 64)  # noqa: E731
    np.testing.assert_allclose(inv[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(inv[10], extra(10), rtol=1e-6)  # ramp 0
    m = 1 - 6 / 13  # pair 16: ramp (16 - 10) / (23 - 10)
    np.testing.assert_allclose(inv[16], extra(16) * (m + (1 - m) / 40), rtol=1e-5)
    np.testing.assert_allclose(inv[23], extra(23) / 40, rtol=1e-6)  # ramp 1
    np.testing.assert_allclose(inv[31], extra(31) / 40, rtol=1e-6)
    cos, sin = rope_tables(jnp.arange(8), 64, 1e4,
                           (40.0, 4096, 32.0, 1.0))
    np.testing.assert_allclose(np.asarray(cos[3, 16]), math.cos(3 * inv[16]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sin[5, 31]), math.sin(5 * inv[31]), rtol=1e-5)


def test_mla_softmax_scale_and_direct_q():
    a = get_config(ARCH).attention
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40.0, 0.707) == pytest.approx(mscale)
    assert mla_softmax_scale(a) == pytest.approx(192 ** -0.5 * mscale ** 2)
    p, lo = init_mla(jax.random.PRNGKey(0), reduced(get_config(ARCH)).attention,
                     256, pack_meta([LoraConfig(rank=8)]), ("q", "kv", "o"))
    assert "q" in p and "q_a" not in p and "q_norm" not in p
    assert set(lo) == {"q", "kv_a", "o"}
    assert p["q"]["w"].shape == (256, 4 * 32)  # H * (nope + rope)
    # minicpm3 keeps its low-rank q
    p, lo = init_mla(jax.random.PRNGKey(0), reduced(get_config("minicpm3-4b")).attention,
                     256, pack_meta([LoraConfig(rank=8)]), ("q", "kv", "o"))
    assert "q_a" in p and set(lo) == {"q_a", "kv_a", "o"}


def test_dense_head_runs_ahead_of_the_scanned_moe_period():
    cfg = _tiny()
    base, lora = M.init_model(jax.random.PRNGKey(0), cfg, pack_meta([LoraConfig(rank=8)]))
    dec = base["decoder"]
    assert set(dec["head"]) == {"l0"} and "mlp" in dec["head"]["l0"]
    assert set(dec["blocks"]) == {"l0"} and "moe" in dec["blocks"]["l0"]
    assert dec["blocks"]["l0"]["moe"]["w_gate"].shape[0] == cfg.n_layers - 1
    assert dec["rest"] == {}
    shared = lora["decoder"]["blocks"]["l0"]["moe"]["shared"]
    assert set(shared) == {"gate", "up", "down"}


def _batch(toks):
    return {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}


def test_packing_isolation_under_a_flooding_partner():
    """An adapter's loss and gradients are the same packed with a partner
    whose rows route to the very same experts (its own tokens, twice) as
    trained alone: routing is drop-free, and no loss term spans the pack."""
    cfg = _tiny(n_experts=8, top_k=3, n_shared_experts=1)
    a = LoraConfig(rank=8, alpha=16.0, learning_rate=1e-3, batch_size=1)
    b = LoraConfig(rank=16, alpha=16.0, learning_rate=1e-3, batch_size=2)
    base, _ = M.init_model(jax.random.PRNGKey(0), cfg, None)
    pack = pack_meta([a, b])
    lora = M.init_lora(jax.random.PRNGKey(1), cfg, pack)
    # the same adapter alone: slot 0 of the pack, at the pack's bucket rank
    alone = jax.tree_util.tree_map_with_path(
        lambda p, x: x[:, :1] if any(getattr(k, "key", "") == "blocks" for k in p)
        else x[:1], lora)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 32), 0, cfg.vocab_size)
    packed_toks = jnp.concatenate([toks, toks, toks, toks])  # a pads to 2 rows
    grad = lambda lo, batch, n, bs, sc: jax.grad(  # noqa: E731
        lambda l_: packed_loss_fn(l_, base, batch, cfg, n, sc,
                                  batch_sizes=bs)[0], has_aux=False)(lo)
    loss = lambda lo, batch, n, bs, sc: packed_loss_fn(  # noqa: E731
        lo, base, batch, cfg, n, sc, batch_sizes=bs)[1]
    sc = pack.scales()
    l_pack, rows_pack = loss(lora, _batch(packed_toks), 2, (1, 2), sc)
    l_alone, rows_alone = loss(alone, _batch(toks), 1, (1,), sc[:1])
    np.testing.assert_allclose(float(l_pack[0]), float(l_alone[0]), rtol=1e-5)
    # the partner triples every expert's rows, and nothing is dropped
    np.testing.assert_array_equal(np.asarray(rows_pack), 3 * np.asarray(rows_alone))
    g_pack = grad(lora, _batch(packed_toks), 2, (1, 2), sc)
    g_alone = grad(alone, _batch(toks), 1, (1,), sc[:1])
    g_pack0 = jax.tree_util.tree_map_with_path(
        lambda p, x: x[:, :1] if any(getattr(k, "key", "") == "blocks" for k in p)
        else x[:1], g_pack)
    # float32 sums in another order: within 1e-4 of each matrix's largest
    for x, y in zip(jax.tree.leaves(g_pack0), jax.tree.leaves(g_alone)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-4 * float(np.abs(y).max() + 1e-12))


def test_train_step_returns_expert_rows():
    cfg = _tiny()
    configs = [LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3, batch_size=1),
               LoraConfig(rank=16, alpha=16.0, learning_rate=1e-3, batch_size=2)]
    meta = pack_meta(configs)
    base, lora = M.init_model(jax.random.PRNGKey(0), cfg, meta)
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size)
    step = make_train_step(cfg, meta)
    _, _, m = step(base, lora, init_opt_state(lora), _batch(toks))
    rows = np.asarray(m["expert_rows"])
    # 3 real rows (row slots) x 32 tokens x top_k, in each MoE layer
    assert rows.shape == (cfg.n_layers - 1, cfg.moe.n_experts)
    assert (rows.sum(-1) == 3 * 32 * cfg.moe.top_k).all()
    # the rows the grouped matmuls compute, counted in the step
    computed = int(m["expert_rows_computed"])
    assert computed == int(grid_rows(rows)) >= rows.sum()
    assert computed % 128 == 0


def test_executor_span_carries_routed_rows():
    """Each ``executor.train`` span of an MoE model gets the last step's
    routed rows and the rows its grouped matmuls computed, as the step
    counted them; the executor only copies them."""
    from repro.cluster import ClusterRunner, DevicePool, SliceExecutor
    from repro.obs import Tracer
    from repro.sched.cost_model import A100_40G, CostModel
    from repro.sched.engine import ExecutionEngine
    from repro.sched.planner import Schedule, ScheduledJob

    cfg = _tiny()
    configs = [LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3, batch_size=1,
                          seq_len=16),
               LoraConfig(rank=16, alpha=16.0, learning_rate=1e-3,
                          batch_size=2, seq_len=16)]
    base, _ = M.init_model(jax.random.PRNGKey(0), cfg, pack_meta(configs))
    tracer = Tracer()
    runner = ClusterRunner(SliceExecutor(), DevicePool(jax.devices()[:1]),
                           concurrent=False, tracer=tracer)
    ExecutionEngine(CostModel(cfg, A100_40G), 1).run_local(
        Schedule([ScheduledJob((0, 1), 1, 0.0, 1.0)], 1.0, 1), configs, cfg,
        base, n_steps=2, seq=16, runner=runner)
    trains = [s for s in tracer.spans() if s.name == "executor.train"]
    assert len(trains) == 1
    args = trains[0].args
    moe_layers = cfg.ffn_kinds().count("moe")
    # 3 real rows (row slots) x 16 tokens x top_k in each MoE layer
    assert args["routed_rows"] == 3 * 16 * cfg.moe.top_k * moe_layers
    assert args["routed_rows_computed"] >= args["routed_rows"]
    assert args["routed_rows_computed"] % 128 == 0


def test_cost_model_counts_the_published_cut():
    """The 5-layer cut (1 dense + 4 MoE, all 64 experts, whole vocabulary)
    has 2.84B parameters; the full model 15.7B, as published."""
    cfg = get_config(ARCH).replace(n_layers=5)
    assert model_param_count(cfg) == pytest.approx(2.8398e9, rel=1e-4)
    assert model_param_count(get_config(ARCH)) == pytest.approx(15.706e9, rel=1e-3)
    # 6 of 64 routed experts and both shared experts are active
    assert active_param_count(cfg) == pytest.approx(
        model_param_count(cfg) - 4 * 58 * 3 * 2048 * 1408)
    # q (direct), kv_a and o in every layer; the dense MLP in layer 0; the
    # shared experts (width 2816) in the MoE layers
    r = 128
    attn = (2048 + 16 * 192) + (2048 + 576) + (16 * 128 + 2048)
    want = r * (5 * attn + 3 * (2048 + 10944) + 4 * 3 * (2048 + 2816))
    assert lora_param_count(cfg, r) == want
