"""Pallas TPU kernel: grouped batched GEMM over packed LoRA adapters.

This is the TPU adaptation of PLoRA's CUTLASS grouped kernels (paper §5.2).
One ``pallas_call`` covers all N adapters: the adapter index is the leading
grid dimension, so small per-adapter GEMMs (rank as low as 8) are batched into
a single kernel with MXU-aligned (seq/hidden) tiles — never tiling the rank
dimension, which lives inside a single K-tile (rank <= 128 = one lane width).

Grid: (N, M/bm, L/bl, K/bk); K is innermost so a VMEM f32 scratch accumulates
partial products across K-steps and the output tile is written once on the
last step (optionally scaled by the per-adapter alpha).

All four backward dataflows of the paper (§5.2 cases 1-4) are expressed as
this same primitive with transposed operands — see ``ops.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pallas_interpret() -> bool:
    """Whether the Pallas kernels run interpreted: compiled on a TPU,
    interpreted on the CPU (the test oracle). Any other backend is refused
    rather than given a silent slow path."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and interpret on CPU; backend "
        f"{backend!r} is neither (use impl='xla' or 'fused_xla')"
    )


def _matmul_kernel(x_ref, w_ref, scale_ref, out_ref, acc_ref, *, n_k: int):
    """One (adapter, m-tile, l-tile, k-step) grid cell. ``scale_ref`` is the
    whole (N,) per-adapter scale vector in SMEM."""
    n = pl.program_id(0)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _store():
        out_ref[0, ...] = (acc_ref[...] * scale_ref[n]).astype(out_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_l", "block_k", "interpret"),
)
def packed_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    scale: Optional[jnp.ndarray] = None,
    *,
    block_m: int = 256,
    block_l: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """out[n] = scale[n] * (x[n] @ w[n]).

    x: (N, M, K); w: (N, K, L); scale: (N,) or None. Inputs are zero-padded to
    tile multiples (exact for the contraction; output is sliced back), so any
    shape is accepted. ``interpret=None`` follows :func:`pallas_interpret`.
    """
    if interpret is None:
        interpret = pallas_interpret()
    n, m, k = x.shape
    n2, k2, l = w.shape
    assert n == n2 and k == k2, (x.shape, w.shape)
    if scale is None:
        scale = jnp.ones((n,), dtype=jnp.float32)
    scale = scale.astype(jnp.float32).reshape(n)

    # TPU-aligned tiles: last dim multiple of 128 (lanes), 2nd-to-last of 8.
    bm = min(block_m, _round_up(m, 8))
    bl = min(block_l, _round_up(l, 128))
    bk = min(block_k, _round_up(k, 128))
    mp, lp, kp = _round_up(m, bm), _round_up(l, bl), _round_up(k, bk)
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, 0), (0, mp - m), (0, kp - k)))
    if (kp, lp) != (k, l):
        w = jnp.pad(w, ((0, 0), (0, kp - k), (0, lp - l)))

    n_k = kp // bk
    grid = (n, mp // bm, lp // bl, n_k)

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda a, i, j, s: (a, i, s)),
            pl.BlockSpec((1, bk, bl), lambda a, i, j, s: (a, s, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, bm, bl), lambda a, i, j, s: (a, i, j)),
        out_shape=jax.ShapeDtypeStruct((n, mp, lp), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bl), jnp.float32)],
        interpret=interpret,
    )(x, w, scale)
    return out[:, :m, :l]
