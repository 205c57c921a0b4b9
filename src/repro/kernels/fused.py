"""Fused base+delta LoRA megakernel: ``y = x @ W + alpha * (x @ A) @ B``.

The two-pass formulation (``packed_lora.lora_linear``: base GEMM, then the
grouped delta of ``ops.packed_lora_delta``) reads the activations twice and
dispatches two kernel sequences per projection. LoRAFusion's observation
(PAPERS.md) is that the LoRA computation is small enough to ride the base
GEMM's tiles: the A-contraction consumes exactly the x tiles the base matmul
is already streaming through VMEM (rank <= 128 = one lane width, so the whole
rank dimension lives inside a single K-tile), and the delta is applied when
the output tile is written. One grid pass, one read of x, one write of y.

Two implementations with identical semantics:

  * ``fused_matmul`` — the Pallas TPU kernel. Grid (N, M/bm, L/bl, K/bk),
    K innermost; two VMEM f32 scratch accumulators (base tile ``acc`` and
    running ``xa``); on the last K step the output tile is written once as
    ``acc + alpha * xa @ B_tile``. ``interpret=True`` runs the same kernel
    body on CPU as a correctness oracle.
  * ``_fused_xla`` — the same computation as one jit-fusable XLA expression,
    used off-TPU so CPU CI measures real wall-clock (interpret mode is a
    semantics check, not a timing path).

Both are wrapped in ONE ``custom_vjp`` (``fused_lora``): the backward's
``dx = g @ W^T + d(xA) @ A^T`` is *again* the fused primitive with transposed
operands — ``fused(g, W^T, B^T, A^T, alpha)`` — so dx shares g tiles exactly
like the forward shares x tiles. The xA intermediate needed for dB follows a
configurable remat policy: ``remat="save"`` (the measured-crossover default,
``ops.DEFAULT_REMAT``: the (N, ..., r<=128) residual buys one full-d_in GEMM
off the backward) or ``remat="recompute"`` — see ``benchmarks/bench_kernels
.py`` remat rows. Both policies produce bit-identical gradients; the Pallas
path always recomputes (xA never leaves VMEM scratch).

``w``'s cotangent is computed honestly (the primitive is differentiable in
every array argument) — training takes grads w.r.t. adapters only, so XLA
dead-code-eliminates the base-weight gradient GEMM.

Quantized frozen base (ISSUE 8): ``w`` may also be a ``{"codes", "scales"}``
dict (see ``kernels/quant.py``). The Pallas kernel then dequantizes each W
tile in-register inside the K-loop (scales ride as a second operand; the
VMEM scratch accumulators are unchanged), and the XLA path dequantizes once
before the same expression. Elementwise dequant is tiling-invariant, so the
in-kernel per-tile form is bit-exact against dequantize-then-same-kernel on
identical quantized weights. The backward dequantizes once and reuses the
dense tiles for ``dx = g @ W^T + d(xA) @ A^T``; the codes' cotangent is the
mandatory ``float0`` zero (integers have no tangent space) — the base stays
frozen by construction.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_matmul import pallas_interpret
from repro.kernels.quant import NF4_CODEBOOK, dequantize, is_quantized

# default Pallas tile sizes; the autotuner (kernels/autotune.py) overrides
# them per (backend, shape bucket)
DEFAULT_BLOCKS = (256, 256, 512)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Pallas megakernel
# ---------------------------------------------------------------------------


def _fused_kernel(
    x_ref, w_ref, a_ref, b_ref, scale_ref, out_ref, acc_ref, xa_ref, *, n_k: int
):
    """One (adapter, m-tile, l-tile, k-step) grid cell.

    ``acc`` accumulates the base tile ``x @ W``; ``xa`` accumulates the
    A-contraction off the SAME x tile (rank is never tiled: it fits one lane
    width). On the last K step the delta is applied in-register and the
    output tile is written exactly once. ``scale_ref`` is the whole (N,)
    per-adapter scale vector in SMEM.
    """
    n = pl.program_id(0)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[0]
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    xa_ref[...] += jnp.dot(x, a_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        scale = scale_ref[n]
        delta = jnp.dot(
            xa_ref[...],
            b_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        out_ref[0, ...] = (acc_ref[...] + scale * delta).astype(out_ref.dtype)


def _dequant_tile(wq, ws, mode, blk, dtype):
    """Dequantize one (bk, bl) W tile in-register from its codes/scales tiles.

    Elementwise per tile, so per-tile dequant == global dequant exactly; the
    final cast to ``dtype`` mirrors the dense path's ``w.astype(x.dtype)``
    (bit-exactness vs dequantize-then-dense-kernel requires identical casts
    before the dot).
    """
    if mode == "int8":
        w = wq.astype(jnp.float32) * ws  # (bk, bl) * (1, bl)
    else:  # nf4: unpack 2 codes per uint8 (low nibble = even K-row)
        # widened first: Mosaic cannot lower shifts of 8-bit integers
        w32 = wq.astype(jnp.int32)
        lo = w32 & 0xF
        hi = w32 >> 4
        p, bl = wq.shape
        idx = jnp.stack([lo, hi], axis=1).reshape(2 * p, bl)
        # codebook lookup as a select chain: Pallas kernels cannot capture
        # array constants, and 16 scalar selects vectorize on the VPU; the
        # result is value-identical to the gather ``dequantize`` uses.
        vals = jnp.zeros(idx.shape, jnp.float32)
        for i, c in enumerate(NF4_CODEBOOK.tolist()):
            vals = jnp.where(idx == i, jnp.float32(c), vals)
        nb = ws.shape[0]  # = bk // blk scale rows in this tile
        w = (vals.reshape(nb, blk, bl) * ws[:, None, :]).reshape(2 * p, bl)
    return w.astype(dtype)


def _fused_kernel_q(
    x_ref, wq_ref, ws_ref, a_ref, b_ref, scale_ref, out_ref, acc_ref, xa_ref,
    *, n_k: int, mode: str, blk: int
):
    """Quantized-base variant of ``_fused_kernel``: identical grid, identical
    VMEM scratch; the only change is that the W tile is dequantized
    in-register before the base dot (codes + scales stream in as two
    operands instead of one dense tile)."""
    n = pl.program_id(0)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[0]
    w = _dequant_tile(wq_ref[...], ws_ref[...], mode, blk, x.dtype)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    xa_ref[...] += jnp.dot(x, a_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        scale = scale_ref[n]
        delta = jnp.dot(
            xa_ref[...],
            b_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        out_ref[0, ...] = (acc_ref[...] + scale * delta).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_l", "block_k", "interpret"),
)
def fused_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    scale: Optional[jnp.ndarray] = None,
    w_scales: Optional[jnp.ndarray] = None,
    *,
    block_m: int = DEFAULT_BLOCKS[0],
    block_l: int = DEFAULT_BLOCKS[1],
    block_k: int = DEFAULT_BLOCKS[2],
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """out[n] = x[n] @ w + scale[n] * (x[n] @ a[n]) @ b[n].

    x: (N, M, K); w: (K, L) shared; a: (N, K, r); b: (N, r, L); scale: (N,).
    Inputs are zero-padded to tile multiples (exact for contractions; the
    output is sliced back); the rank dim is padded to one lane width and
    never tiled. ``interpret=None`` follows ``pallas_interpret()``: compiled
    on a TPU, interpreted on the CPU.

    With ``w_scales``, ``w`` is quantized codes instead of a dense weight —
    int8 codes (K, L) with per-channel scales (1, L), or packed nf4 uint8
    codes (K//2, L) with block scales (K//blk, L) — and the kernel
    dequantizes each W tile in-register inside the K-loop.
    """
    if interpret is None:
        interpret = pallas_interpret()
    n, m, k = x.shape
    if w_scales is None:
        mode, blk = None, 0
        k2, l = w.shape
    else:
        mode = "int8" if w.dtype == jnp.int8 else "nf4"
        k2 = w.shape[0] * (2 if mode == "nf4" else 1)
        l = w.shape[1]
        blk = 0 if mode == "int8" else k2 // w_scales.shape[0]
    n2, k3, r = a.shape
    n3, r2, l2 = b.shape
    assert k == k2 == k3 and n == n2 == n3 and r == r2 and l == l2, (
        x.shape, w.shape, a.shape, b.shape,
    )
    if scale is None:
        scale = jnp.ones((n,), dtype=jnp.float32)
    scale = scale.astype(jnp.float32).reshape(n)

    # TPU-aligned tiles: last dim multiple of 128 (lanes), 2nd-to-last of 8;
    # the rank lives inside one 128-lane register tile (never grid-tiled).
    bm = min(block_m, _round_up(m, 8))
    bl = min(block_l, _round_up(l, 128))
    bk = min(block_k, _round_up(k, 128))
    rp = _round_up(r, 128)
    mp, lp, kp = _round_up(m, bm), _round_up(l, bl), _round_up(k, bk)
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, 0), (0, mp - m), (0, kp - k)))
    if mode is None:
        if (kp, lp) != (k, l):
            w = jnp.pad(w, ((0, kp - k), (0, lp - l)))
    else:
        # K-padding of codes/scales with zeros is exact: the padded K rows of
        # x are zeros, and 0 * finite == 0 in f32 whatever the padded codes
        # dequantize to.
        if mode == "int8":
            w = jnp.pad(w, ((0, kp - k), (0, lp - l)))
            w_scales = jnp.pad(w_scales, ((0, 0), (0, lp - l)))
        else:
            assert bk % 2 == 0 and blk > 0 and bk % blk == 0, (bk, blk)
            w = jnp.pad(w, ((0, (kp - k) // 2), (0, lp - l)))
            w_scales = jnp.pad(
                w_scales, ((0, (kp - k) // blk), (0, lp - l))
            )

    if (kp, rp) != (k, r):
        a = jnp.pad(a, ((0, 0), (0, kp - k), (0, rp - r)))
    if (rp, lp) != (r, l):
        b = jnp.pad(b, ((0, 0), (0, rp - r), (0, lp - l)))

    n_k = kp // bk
    grid = (n, mp // bm, lp // bl, n_k)

    x_spec = pl.BlockSpec((1, bm, bk), lambda ad, i, j, s: (ad, i, s))
    a_spec = pl.BlockSpec((1, bk, rp), lambda ad, i, j, s: (ad, s, 0))
    b_spec = pl.BlockSpec((1, rp, bl), lambda ad, i, j, s: (ad, 0, j))
    s_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    if mode is None:
        kernel = functools.partial(_fused_kernel, n_k=n_k)
        in_specs = [
            x_spec,
            pl.BlockSpec((bk, bl), lambda ad, i, j, s: (s, j)),
            a_spec, b_spec, s_spec,
        ]
        operands = (x, w, a, b, scale)
    else:
        kernel = functools.partial(
            _fused_kernel_q, n_k=n_k, mode=mode, blk=blk
        )
        wq_rows = bk // 2 if mode == "nf4" else bk
        ws_rows = 1 if mode == "int8" else bk // blk
        in_specs = [
            x_spec,
            pl.BlockSpec((wq_rows, bl), lambda ad, i, j, s: (s, j)),
            pl.BlockSpec(
                (ws_rows, bl),
                (lambda ad, i, j, s: (0, j)) if mode == "int8"
                else (lambda ad, i, j, s: (s, j)),
            ),
            a_spec, b_spec, s_spec,
        ]
        operands = (x, w, w_scales, a, b, scale)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bl), lambda ad, i, j, s: (ad, i, j)),
        out_shape=jax.ShapeDtypeStruct((n, mp, lp), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bl), jnp.float32),
            pltpu.VMEM((bm, rp), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return out[:, :m, :l]


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------


def _bcast(alpha: jnp.ndarray, ndim: int) -> jnp.ndarray:
    return alpha.reshape(alpha.shape[0], *([1] * (ndim - 1)))


def _xa(x, a):
    return jnp.einsum("n...k,nkr->n...r", x, a, preferred_element_type=jnp.float32).astype(x.dtype)


def _fused_xla(x, w, a, b, alpha):
    """One fusable XLA expression: base + scaled delta in a single add.

    The base contraction matches the two-pass path's ``x @ w`` bit-for-bit
    (same dot_general dims); the single final add is the only reassociation
    versus two-pass (which adds bias between base and delta when present).
    A quantized ``w`` is dequantized up front — the identical jnp formula the
    two-pass reference uses, so parity stays bit-exact.
    """
    if is_quantized(w):
        w = dequantize(w)
    base = x @ w.astype(x.dtype)
    xa = _xa(x, a)
    delta = jnp.einsum(
        "n...r,nrl->n...l", xa, b, preferred_element_type=jnp.float32
    )
    delta = delta * _bcast(alpha, delta.ndim)
    return base + delta.astype(x.dtype)


def _run_fwd(x, w, a, b, alpha, impl, blocks):
    if impl == "fused_pallas":
        lead = x.shape[1:-1]
        x3 = x.reshape(x.shape[0], -1, x.shape[-1])
        bm, bl, bk = blocks or DEFAULT_BLOCKS
        if is_quantized(w):
            wq, ws = w["codes"], w["scales"]
            d_out = wq.shape[-1]
            out = fused_matmul(
                x3, wq, a.astype(x.dtype), b.astype(x.dtype),
                alpha, ws,
                block_m=bm, block_l=bl, block_k=bk,
            )
        else:
            d_out = w.shape[-1]
            out = fused_matmul(
                x3, w.astype(x.dtype), a.astype(x.dtype), b.astype(x.dtype),
                alpha,
                block_m=bm, block_l=bl, block_k=bk,
            )
        return out.reshape(x.shape[0], *lead, d_out)
    return _fused_xla(x, w, a.astype(x.dtype), b.astype(x.dtype), alpha)


# ---------------------------------------------------------------------------
# One custom_vjp covering both implementations
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused_lora(x, w, a, b, alpha, impl, remat, blocks):
    return _run_fwd(x, w, a, b, alpha, impl, blocks)


def _fwd(x, w, a, b, alpha, impl, remat, blocks):
    out = _run_fwd(x, w, a, b, alpha, impl, blocks)
    # cast exactly as the backward's recompute would: both policies must be
    # bit-identical even for callers passing a in a different dtype than x
    saved_xa = (
        _xa(x, a.astype(x.dtype))
        if remat == "save" and impl != "fused_pallas"
        else None
    )
    return out, (x, w, a, b, alpha, saved_xa)


def _bwd(impl, remat, blocks, res, g):
    x, w, a, b, alpha, saved_xa = res
    # Quantized base: dequantize ONCE, then reuse the dense tiles for the
    # whole dx GEMM — identical ops to the reference backward run on
    # pre-dequantized weights, hence bit-exact against it.
    wd = dequantize(w) if is_quantized(w) else w
    g = g.astype(x.dtype)
    al = _bcast(alpha, g.ndim).astype(g.dtype)
    g_s = g * al
    a_c = a.astype(x.dtype)
    b_c = b.astype(x.dtype)
    # d(xA) = g_s @ B^T  (needed for dA either way)
    dxa = jnp.einsum(
        "n...l,nrl->n...r", g_s, b_c, preferred_element_type=jnp.float32
    ).astype(x.dtype)
    # dx = g @ W^T + d(xA) @ A^T — the fused primitive again, on transposed
    # operands: fused(g, W^T, B^T, A^T, alpha) shares g tiles the way the
    # forward shares x tiles.
    if impl == "fused_pallas":
        lead = g.shape[1:-1]
        g3 = g.reshape(g.shape[0], -1, g.shape[-1])
        bm, bl, bk = blocks or DEFAULT_BLOCKS
        dx = fused_matmul(
            g3,
            jnp.swapaxes(wd.astype(x.dtype), 0, 1),
            jnp.swapaxes(b_c, 1, 2),
            jnp.swapaxes(a_c, 1, 2),
            alpha,
            block_m=bm, block_l=bl, block_k=bk,
        ).reshape(g.shape[0], *lead, wd.shape[0])
    else:
        dx = (
            jnp.einsum(
                "n...l,kl->n...k", g, wd.astype(g.dtype),
                preferred_element_type=jnp.float32,
            ).astype(x.dtype)
            + jnp.einsum(
                "n...r,nkr->n...k", dxa, a_c,
                preferred_element_type=jnp.float32,
            ).astype(x.dtype)
        )
    xa = saved_xa if saved_xa is not None else _xa(x, a_c)
    da = jnp.einsum("n...k,n...r->nkr", x, dxa).astype(a.dtype)
    db = jnp.einsum("n...r,n...l->nrl", xa, g_s).astype(b.dtype)
    if is_quantized(w):
        # frozen by construction: integer codes have no tangent space (the
        # mandatory float0 zero), and the scales' cotangent is zero.
        dw = {
            "codes": np.zeros(w["codes"].shape, dtype=jax.dtypes.float0),
            "scales": jnp.zeros_like(w["scales"]),
        }
    else:
        # base weights are frozen in training (grads only w.r.t. adapters),
        # so XLA dead-code-eliminates this GEMM there; it exists so the
        # primitive is honestly differentiable in w for any dense caller.
        dw = jnp.einsum("n...k,n...l->kl", x, g).astype(w.dtype)
    return dx, dw, da, db, jnp.zeros_like(alpha)


_fused_lora.defvjp(_fwd, _bwd)


def fused_lora(
    x: jnp.ndarray,
    w: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    impl: str = "fused_xla",
    remat: Optional[str] = None,
    blocks: Optional[Tuple[int, int, int]] = None,
) -> jnp.ndarray:
    """``x @ w + alpha_n * (x_n @ A_n) @ B_n`` for N packed adapters.

    x: (N, ..., d_in); w: (d_in, d_out) shared frozen base — dense array or
    quantized ``{"codes", "scales"}`` dict; a: (N, d_in, r);
    b: (N, r, d_out); alpha: (N,). ``impl`` is the *resolved* backend
    ("fused_pallas" | "fused_xla" — dispatch lives in ``ops.py``); ``remat``
    picks the backward xA policy (None -> ``ops.DEFAULT_REMAT``, the
    measured-crossover default every production path uses); ``blocks``
    overrides the Pallas tile sizes (autotuner hook).
    """
    if remat is None:
        from repro.kernels.ops import DEFAULT_REMAT

        remat = DEFAULT_REMAT
    assert impl in ("fused_pallas", "fused_xla"), impl
    assert remat in ("recompute", "save"), remat
    return _fused_lora(
        x, w, a, b, alpha.astype(jnp.float32), impl, remat,
        tuple(blocks) if blocks is not None else None,
    )
