"""Pallas packed-matmul kernel vs the pure-jnp oracle.

Sweeps shapes (odd/aligned/tiny/large), dtypes (f32/bf16), block shapes, and
pack sizes; checks both forward values and (through the custom-vjp wrapper)
all four backward dataflows of the paper (§5.2 cases 1-4). The kernel runs in
interpret mode on CPU — the same kernel body that compiles for TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # guarded hypothesis import (skips sans hypothesis)

from repro.kernels.ops import (
    delta_flops,
    grouped_matmul,
    packed_lora_delta,
    rank_segments,
)
from repro.kernels.packed_matmul import packed_matmul
from repro.kernels import ref


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "n,m,k,l",
    [
        (1, 8, 16, 8),        # tiny, nothing aligned
        (2, 128, 128, 128),   # exactly one tile
        (3, 100, 36, 52),     # odd everything
        (4, 256, 8, 512),     # rank-like K=8 (never tiled)
        (8, 64, 128, 300),    # L not multiple of 128
        (2, 516, 260, 132),   # multiple tiles with remainders
    ],
)
def test_packed_matmul_matches_ref(dtype, n, m, k, l):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n * 1000 + m))
    x = _rand(k1, (n, m, k), dtype)
    w = _rand(k2, (n, k, l), dtype)
    scale = jnp.linspace(0.5, 2.0, n, dtype=jnp.float32)
    got = packed_matmul(x, w, scale, interpret=True)
    want = ref.packed_matmul_ref(x, w, scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("bm,bl,bk", [(8, 128, 128), (16, 256, 128), (256, 256, 512)])
def test_packed_matmul_block_shapes(bm, bl, bk):
    """Same values regardless of the BlockSpec tiling chosen."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    x = _rand(k1, (3, 40, 200), jnp.float32)
    w = _rand(k2, (3, 200, 72), jnp.float32)
    got = packed_matmul(x, w, None, block_m=bm, block_l=bl, block_k=bk, interpret=True)
    want = ref.packed_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_no_scale_is_identity_scale():
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = _rand(k1, (2, 16, 32), jnp.float32)
    w = _rand(k2, (2, 32, 16), jnp.float32)
    a = packed_matmul(x, w, None, interpret=True)
    b = packed_matmul(x, w, jnp.ones((2,)), interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_lora_delta_forward(impl):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    n, t, d, r, k = 4, 24, 48, 8, 40
    x = _rand(keys[0], (n, t, d), jnp.float32)
    a = _rand(keys[1], (n, d, r), jnp.float32)
    b = _rand(keys[2], (n, r, k), jnp.float32)
    alpha = jnp.asarray([0.5, 1.0, 2.0, 0.25])
    got = packed_lora_delta(x, a, b, alpha, impl=impl)
    want = ref.packed_lora_delta_ref(x, a, b, alpha)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_lora_delta_grads_all_four_cases(impl):
    """The custom VJP (paper backward cases 1-4) against jax autodiff on the
    reference einsum implementation."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    n, t, d, r, k = 3, 16, 32, 8, 24
    x = _rand(keys[0], (n, t, d), jnp.float32)
    a = _rand(keys[1], (n, d, r), jnp.float32)
    b = _rand(keys[2], (n, r, k), jnp.float32)
    alpha = jnp.asarray([0.5, 1.0, 2.0])

    def f_kernel(x, a, b):
        return (packed_lora_delta(x, a, b, alpha, impl=impl) ** 2).sum()

    def f_ref(x, a, b):
        return (ref.packed_lora_delta_ref(x, a, b, alpha) ** 2).sum()

    gx, ga, gb = jax.grad(f_kernel, argnums=(0, 1, 2))(x, a, b)
    rx, ra, rb = jax.grad(f_ref, argnums=(0, 1, 2))(x, a, b)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ra), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb), rtol=1e-4, atol=1e-4)


def test_alpha_gets_zero_cotangent():
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    n, t, d, r, k = 2, 8, 16, 4, 12
    x = _rand(keys[0], (n, t, d), jnp.float32)
    a = _rand(keys[1], (n, d, r), jnp.float32)
    b = _rand(keys[2], (n, r, k), jnp.float32)
    alpha = jnp.asarray([1.0, 2.0])
    g = jax.grad(lambda al: packed_lora_delta(x, a, b, al).sum())(alpha)
    np.testing.assert_allclose(np.asarray(g), 0.0)


def test_sequential_matches_packed():
    """The paper's equivalence claim (§3.2): per-adapter math identical to
    single-adapter computation."""
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    n, t, d, r, k = 5, 12, 20, 8, 28
    x = _rand(keys[0], (n, t, d), jnp.float32)
    a = _rand(keys[1], (n, d, r), jnp.float32)
    b = _rand(keys[2], (n, r, k), jnp.float32)
    alpha = jnp.linspace(0.25, 2.0, n)
    packed = packed_lora_delta(x, a, b, alpha, impl="pallas")
    seq = ref.sequential_lora_delta_ref(x, a, b, alpha)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(seq), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Property-based sweeps
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 6),
    m=st.integers(1, 96),
    k=st.integers(1, 96),
    l=st.integers(1, 160),
)
def test_packed_matmul_property(n, m, k, l):
    k1, k2 = jax.random.split(jax.random.PRNGKey(m * 7 + k * 3 + l))
    x = _rand(k1, (n, m, k), jnp.float32)
    w = _rand(k2, (n, k, l), jnp.float32)
    got = packed_matmul(x, w, None, interpret=True)
    want = ref.packed_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(1, 4),
    r_real=st.integers(1, 16),
    r_pad=st.integers(0, 16),
)
def test_rank_padding_exact(n, r_real, r_pad):
    """Zero-padded rank columns contribute exactly 0 to output AND grads —
    the correctness basis of heterogeneous-rank packing."""
    t, d, k = 8, 24, 20
    keys = jax.random.split(jax.random.PRNGKey(r_real * 31 + r_pad), 3)
    x = _rand(keys[0], (n, t, d), jnp.float32)
    a_real = _rand(keys[1], (n, d, r_real), jnp.float32)
    b_real = _rand(keys[2], (n, r_real, k), jnp.float32)
    alpha = jnp.ones((n,))
    a_padded = jnp.pad(a_real, ((0, 0), (0, 0), (0, r_pad)))
    b_padded = jnp.pad(b_real, ((0, 0), (0, r_pad), (0, 0)))

    out_r = packed_lora_delta(x, a_real, b_real, alpha)
    out_p = packed_lora_delta(x, a_padded, b_padded, alpha)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_p), rtol=1e-5, atol=1e-5)

    ga_p, gb_p = jax.grad(
        lambda a, b: (packed_lora_delta(x, a, b, alpha) ** 2).sum(), argnums=(0, 1)
    )(a_padded, b_padded)
    # gradient w.r.t. padded region of B is exactly 0 (A-pad columns are 0)
    np.testing.assert_allclose(np.asarray(gb_p[:, r_real:, :]), 0.0, atol=1e-6)


def test_rank_segments_structure():
    order, inv, segs = rank_segments((8, 4, 8, 16, 4))
    assert order == (1, 4, 0, 2, 3)
    assert segs == [(0, 2, 4), (2, 4, 8), (4, 5, 16)]
    # inv undoes order
    assert tuple(order[i] for i in inv) != inv  # non-trivial permutation
    assert [order[inv[i]] for i in range(5)] == list(range(5))
    # homogeneous pack: one segment, identity order
    order, inv, segs = rank_segments((8, 8, 8))
    assert order == (0, 1, 2) and segs == [(0, 3, 8)]


def test_delta_flops_ragged_savings():
    ranks = (8, 8, 64, 64)
    padded = delta_flops(ranks, 2048, 2048, 16, ragged=False)
    ragged = delta_flops(ranks, 2048, 2048, 16, ragged=True)
    # bucket = 64: the two rank-8 adapters each save (64-8)/64 of their work
    assert padded == pytest.approx(4 * 2.0 * 16 * 64 * 4096)
    assert ragged == pytest.approx(2.0 * 16 * 4096 * (8 + 8 + 64 + 64))
    assert ragged < padded
    # homogeneous packs save nothing
    assert delta_flops((16, 16), 64, 64, 8, ragged=True) == delta_flops(
        (16, 16), 64, 64, 8, ragged=False
    )


@settings(max_examples=15, deadline=None)
@given(
    ranks=st.lists(st.integers(1, 24), min_size=2, max_size=5),
)
def test_ragged_segments_match_padded_property(ranks):
    """Property (ISSUE 5 satellite): for ANY rank mixture, ragged same-rank
    segments produce the padded pack's values, and the sliced-off padding
    receives bit-zero gradient — the region where zero-padding used to
    contribute (numerically) zero now structurally contributes nothing."""
    ranks = tuple(ranks)
    n, t, d, k = len(ranks), 6, 20, 16
    bucket = max(ranks)
    keys = jax.random.split(jax.random.PRNGKey(sum(ranks)), 3)
    x = _rand(keys[0], (n, t, d), jnp.float32)
    a = _rand(keys[1], (n, d, bucket), jnp.float32)
    b = _rand(keys[2], (n, bucket, k), jnp.float32)
    mask_a = jnp.arange(bucket)[None, None, :] < jnp.asarray(ranks)[:, None, None]
    mask_b = jnp.arange(bucket)[None, :, None] < jnp.asarray(ranks)[:, None, None]
    a, b = a * mask_a, b * mask_b
    alpha = jnp.ones((n,))

    out_p = packed_lora_delta(x, a, b, alpha)
    out_r = packed_lora_delta(x, a, b, alpha, ranks=ranks)
    np.testing.assert_allclose(
        np.asarray(out_r), np.asarray(out_p), rtol=2e-5, atol=2e-5
    )

    ga, gb = jax.grad(
        lambda a, b: (packed_lora_delta(x, a, b, alpha, ranks=ranks) ** 2).sum(),
        argnums=(0, 1),
    )(a, b)
    for i, r in enumerate(ranks):
        assert (np.asarray(ga)[i, :, r:] == 0.0).all()
        assert (np.asarray(gb)[i, r:, :] == 0.0).all()


def test_grouped_matmul_dispatch():
    """auto == xla off-TPU; explicit pallas gives the same numbers."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(9))
    x = _rand(k1, (2, 16, 32), jnp.float32)
    w = _rand(k2, (2, 32, 48), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(grouped_matmul(x, w, impl="auto")),
        np.asarray(grouped_matmul(x, w, impl="pallas")),
        rtol=1e-5, atol=1e-5,
    )


def test_sharded_impl_takes_xla_forms_where_kernels_compile(monkeypatch):
    """On a TPU a sharded step cannot hold a Mosaic kernel: auto/fused take
    their XLA forms and an explicit Pallas impl is refused. Interpreted
    kernels (CPU) stay as asked."""
    from repro.kernels import ops

    assert ops.sharded_impl("pallas") == "pallas"
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    assert ops.sharded_impl("auto") == "xla"
    assert ops.sharded_impl("fused") == "fused_xla"
    assert ops.sharded_impl("fused_xla") == "fused_xla"
    assert ops.sharded_impl(None) == "xla"  # the context default, "auto"
    for impl in ("pallas", "fused_pallas"):
        with pytest.raises(ValueError, match="Mosaic"):
            ops.sharded_impl(impl)


def test_pallas_interpret_follows_backend(monkeypatch):
    from repro.kernels import packed_matmul as pm

    assert pm.pallas_interpret() is True  # the tests run on the CPU
    monkeypatch.setattr(pm.jax, "default_backend", lambda: "tpu")
    assert pm.pallas_interpret() is False
    monkeypatch.setattr(pm.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        pm.pallas_interpret()
