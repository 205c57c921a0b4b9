"""MoE: the drop-free routed path vs the dense oracle, skewed routing, expert
shares, the router's gate rule, the grouped-matmul kernel, and the shard_map
path on a forced multi-device host mesh (separate-process test lives in
test_dryrun_small)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # guarded hypothesis import (skips sans hypothesis)

from repro.configs.base import MoEConfig
from repro.kernels.moe_gmm import _gmm, grid_rows, moe_gmm
from repro.models.layers.moe import (
    _moe_dense,
    _moe_routed,
    _router,
    apply_shared,
    expert_rows,
    init_moe,
    routed_experts,
)


def _setup(key, d=16, e=4, f=8, topk=2, **kw):
    mcfg = MoEConfig(n_experts=e, top_k=topk, d_expert=f, impl="ep", **kw)
    params, _ = init_moe(key, d, mcfg)
    return params, mcfg


def _oracle(params, x, mcfg):
    gates, idx = _router(x, params, mcfg)
    return _moe_dense(params, x, gates, idx, mcfg.n_experts)


def test_ep_local_matches_dense_when_no_dropping():
    """The drop-free routed path computes exactly the dense answer."""
    params, mcfg = _setup(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 16))
    gates, idx = _router(x, params, mcfg)
    y_ep = _moe_routed(params, x, gates, idx, 0, mcfg.n_experts, mcfg.n_experts)
    np.testing.assert_allclose(np.asarray(_oracle(params, x, mcfg)),
                               np.asarray(y_ep), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("norm", [True, False])
def test_drop_free_matches_dense_oracle(norm):
    """With and without renormalised gates (DeepSeek-V2 runs without),
    E=8 top-3, values and input gradients."""
    params, mcfg = _setup(jax.random.PRNGKey(2), e=8, topk=3,
                          norm_topk_prob=norm)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 16))
    ep = lambda xx: routed_experts(params, xx, mcfg)[0]  # noqa: E731
    dense = lambda xx: routed_experts(  # noqa: E731
        params, xx, dataclasses.replace(mcfg, impl="dense"))[0]
    np.testing.assert_allclose(np.asarray(ep(x)), np.asarray(dense(x)),
                               rtol=1e-4, atol=1e-4)
    g_ep = jax.grad(lambda xx: (ep(xx) ** 2).sum())(x)
    g_dense = jax.grad(lambda xx: (dense(xx) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g_ep), np.asarray(g_dense),
                               rtol=1e-3, atol=1e-3)


def test_expert_slices_sum_to_full():
    """Sum of per-slice partial outputs == all-experts output (the psum
    identity the shard_map path relies on)."""
    params, mcfg = _setup(jax.random.PRNGKey(2), e=4)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 16))
    gates, idx = _router(x, params, mcfg)
    full = _moe_routed(params, x, gates, idx, 0, 4, 4)
    parts = []
    for lo in range(0, 4, 2):
        # the shard_map path hands each shard only its expert slice
        # (router stays replicated)
        local = dict(
            params,
            w_gate=params["w_gate"][lo : lo + 2],
            w_up=params["w_up"][lo : lo + 2],
            w_down=params["w_down"][lo : lo + 2],
        )
        parts.append(_moe_routed(local, x, gates, idx, lo, 2, 4))
    np.testing.assert_allclose(
        np.asarray(sum(parts)), np.asarray(full), rtol=1e-4, atol=1e-4
    )


def test_shares_and_shared_expert_make_the_whole_layer():
    """E=8 top-3 with a shared expert: the parts that experts [0,4) and
    [4,8) give, with the shared expert counted once, add up to the uncut
    layer, here the dense oracle plus the shared SwiGLU."""
    mcfg = MoEConfig(n_experts=8, top_k=3, d_expert=8, n_shared_experts=1,
                     norm_topk_prob=False, impl="ep")
    params, _ = init_moe(jax.random.PRNGKey(4), 16, mcfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 16))
    xt = x.reshape(24, 16)
    gates, idx = _router(xt, params, mcfg)
    parts = 0
    for lo in (0, 4):
        local = dict(params, **{k: params[k][lo:lo + 4]
                                for k in ("w_gate", "w_up", "w_down")})
        parts = parts + _moe_routed(local, xt, gates, idx, lo, 4, 8)
    shared = apply_shared(params, None, None, x)
    whole = _oracle(params, xt, mcfg).reshape(x.shape) + shared
    np.testing.assert_allclose(np.asarray(parts.reshape(x.shape) + shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-4)
    y, rows = routed_experts(params, x, mcfg)
    np.testing.assert_allclose(np.asarray(y + shared), np.asarray(whole),
                               rtol=1e-4, atol=1e-4)
    assert int(rows.sum()) == 24 * 3


def test_capacity_drops_tokens_gracefully():
    """The skew that overflowed a capacity-bounded dispatch (every token to
    one expert) drops nothing now: every token keeps its expert's full
    contribution, equal to the dense oracle."""
    params, mcfg = _setup(jax.random.PRNGKey(4), topk=1, norm_topk_prob=False)
    # a router that sends every token to expert 2
    params["router"]["w"] = jnp.zeros_like(params["router"]["w"]).at[:, 2].set(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (32, 16))) + 0.1
    gates, idx = _router(x, params, mcfg)
    assert bool((idx == 2).all())
    assert expert_rows(idx, 4).tolist() == [0, 0, 32, 0]
    y = _moe_routed(params, x, gates, idx, 0, 4, 4)
    assert bool(jnp.isfinite(y).all())
    assert bool((jnp.abs(y).sum(-1) > 0).all())  # no token dropped
    np.testing.assert_allclose(np.asarray(y), np.asarray(_oracle(params, x, mcfg)),
                               rtol=1e-4, atol=1e-4)


def test_maximal_skew_top_k_all_rows_land():
    """Top-2 of 4 with two experts taking every token: the two hot groups
    hold all T rows each, the cold groups none, and the answer is the
    oracle's."""
    params, mcfg = _setup(jax.random.PRNGKey(6), topk=2)
    params["router"]["w"] = (jnp.zeros_like(params["router"]["w"])
                             .at[:, 1].set(2.0).at[:, 3].set(1.0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (40, 16))) + 0.1
    gates, idx = _router(x, params, mcfg)
    assert expert_rows(idx, 4).tolist() == [0, 40, 0, 40]
    y = _moe_routed(params, x, gates, idx, 0, 4, 4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_oracle(params, x, mcfg)),
                               rtol=1e-4, atol=1e-4)


def test_apply_moe_shapes():
    params, mcfg = _setup(jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 12, 16))
    y, rows = routed_experts(params, x, mcfg)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    assert rows.shape == (4,) and rows.dtype == jnp.int32
    assert int(rows.sum()) == 2 * 12 * mcfg.top_k


@settings(max_examples=10, deadline=None)
@given(t=st.integers(4, 64), e=st.sampled_from([2, 4]), topk=st.integers(1, 2))
def test_moe_dense_chunking_invariance(t, e, topk):
    """_moe_dense chunk boundary must not change values."""
    key = jax.random.PRNGKey(t)
    mcfg = MoEConfig(n_experts=e, top_k=topk, d_expert=8)
    params, _ = init_moe(key, 16, mcfg)
    x = jax.random.normal(jax.random.PRNGKey(t + 1), (t, 16))
    gates, idx = _router(x, params, mcfg)
    y1 = _moe_dense(params, x, gates, idx, e, chunk=8)
    y2 = _moe_dense(params, x, gates, idx, e, chunk=1024)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)


def test_gate_weights_normalized():
    mcfg = MoEConfig(n_experts=8, top_k=2, d_expert=8)
    params, _ = init_moe(jax.random.PRNGKey(0), 16, mcfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    gates, idx = _router(x, params, mcfg)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert int(idx.max()) < 8 and int(idx.min()) >= 0


def test_gates_unnormalised_and_scaled():
    """norm_topk_prob False: the gates are the softmax's top-k values, as
    they stand (DeepSeek-V2's routed_scaling_factor 1)."""
    mcfg = MoEConfig(n_experts=8, top_k=3, d_expert=8, norm_topk_prob=False)
    params, _ = init_moe(jax.random.PRNGKey(0), 16, mcfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    gates, idx = _router(x, params, mcfg)
    probs = jax.nn.softmax(x @ params["router"]["w"], -1)
    want = jnp.take_along_axis(probs, idx, -1)
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want), rtol=1e-5)
    assert float(gates.sum(-1).max()) < 1.0


@pytest.mark.parametrize("sizes", [[100, 0, 37, 200, 55], [0, 0, 128, 0, 1],
                                   [300, 0, 0, 0, 0]])
@pytest.mark.parametrize("zero_rest", [False, True])
def test_moe_gmm_matches_einsum_ragged(sizes, zero_rest):
    """The interpreted kernel equals an einsum over ragged groups, empty
    ones included, and its input gradient is the transposed product;
    rows past the groups are zero where asked."""
    g, k, n = len(sizes), 128, 64
    sizes = np.asarray(sizes, np.int32)
    extra = 20 if zero_rest else 0
    m = int(sizes.sum()) + extra
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (g, k, n))
    gid = np.repeat(np.arange(g), sizes)

    def ref(x):
        y = jnp.einsum("mk,mkn->mn", x[:len(gid)], rhs[gid])
        return jnp.concatenate([y, jnp.zeros((extra, n))])

    out = moe_gmm(lhs, rhs, jnp.asarray(sizes), zero_rest)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(lhs)),
                               rtol=1e-4, atol=1e-3)
    cot = jax.random.normal(jax.random.PRNGKey(2), (m, n))
    dx = jax.grad(lambda x: (moe_gmm(x, rhs, jnp.asarray(sizes),
                                     zero_rest) * cot).sum())(lhs)
    dx_ref = jax.grad(lambda x: (ref(x) * cot).sum())(lhs)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-3)


def test_moe_gmm_transposed_rhs():
    """The kernel against (G, n, k) weights, as the backward pass runs it."""
    sizes = np.asarray([50, 0, 78], np.int32)
    lhs = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 128, 64))  # (G, n, k)
    out = _gmm(lhs, rhs, jnp.asarray(sizes), transpose_rhs=True)
    gid = np.repeat(np.arange(3), sizes)
    want = jnp.einsum("mk,mnk->mn", lhs, rhs[gid])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_grid_rows_counts_whole_tiles_per_group():
    """Each group computes from the m-tile of its first row to that of its
    last; an empty group computes nothing; layers add up."""
    assert grid_rows([128, 128], 128) == 256
    assert grid_rows([100, 0, 37, 200, 55], 128) == 128 * (1 + 0 + 2 + 2 + 2)
    assert grid_rows([[1, 0], [0, 1]], 128) == 256
    assert grid_rows([0, 0, 0], 128) == 0
