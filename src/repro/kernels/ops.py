"""Jit'd public ops for packed-LoRA computation.

``packed_lora_delta(x, a, b, alpha)`` computes the adapter-side contribution
``alpha_n * (x_n @ A_n) @ B_n`` for all N packed adapters with a custom VJP
whose four gradient dataflows mirror the paper's backward cases (§5.2):

  case 1  dB    = (xA)^T @ g        (tile over output dim, contract over seq)
  case 2  d(xA) = g @ B^T           (tile over seq + rank, contract over k)
  case 3  dA    = x^T @ d(xA)       (tile over d + rank, contract over seq)
  case 4  dx    = d(xA) @ A^T       (tile over seq + d, contract over rank)

All four are the grouped-GEMM primitive with transposed operands; on TPU the
rank-dim reduction of case 4 is a single K-step inside the tile (rank <= 128),
avoiding the scratch-buffer bookkeeping the paper describes on GPU.

Backend selection (``KernelConfig.impl`` / the ``impl=`` kwarg):
  impl="pallas"       : two-pass Pallas grouped kernel (interpret off-TPU)
  impl="xla"          : two-pass batched einsum (XLA-fused GEMMs)
  impl="fused"        : base+delta megakernel (kernels/fused.py) — resolves
                        to fused_pallas on TPU, fused_xla elsewhere
  impl="fused_pallas" : the Pallas megakernel explicitly
  impl="fused_xla"    : the one-custom_vjp XLA formulation explicitly
  impl="auto"         : pallas on TPU, xla elsewhere (default — CPU tests/
                        benches measure real XLA wall-clock, TPU gets the
                        custom kernel)

A step sharded over several TPU chips takes the XLA forms instead
(``sharded_impl``): XLA cannot partition a Mosaic kernel.

The process default is a ``contextvars.ContextVar`` (NOT a mutable global):
``set_default_impl`` only affects the calling context, so the thread-per-
slice ``ClusterRunner`` can never race it. New threads do NOT inherit the
calling thread's value — cross-thread executors must capture
``default_impl()`` at dispatch time and plumb it explicitly (the trainer /
cluster executor take ``impl=`` for exactly this reason).

Heterogeneous-rank packs: pass ``ranks=`` (the pack's static per-adapter
rank tuple, carried by ``core.adapter.PackMeta``) and same-rank adapters are
grouped into grid *segments* — each segment computes at its own rank, so a
rank-8 adapter packed with a rank-128 one stops paying the bucket-padding
FLOPs (``(r_bucket - r) / r_bucket`` of the delta work). The padded weight
columns are sliced off before the kernel ever sees them, so their gradient
is *structurally* zero (stronger than the numerically-zero padding
invariant the bucket path relies on).

``alpha`` is a hyperparameter, not a trainable weight: its cotangent is zero.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.packed_matmul import packed_matmul as _pallas_matmul
from repro.kernels.packed_matmul import pallas_interpret

IMPLS = ("auto", "pallas", "xla", "fused", "fused_pallas", "fused_xla")

# Backward xA policy: "recompute" re-derives the (N, ..., r<=128) xA
# intermediate in the backward (one extra GEMM over the full d_in), "save"
# stores it as a residual. Both are bit-identical (same op on the same
# inputs). Measured crossover (bench_kernels remat rows, d=2048..18944,
# N=8..32, seq=16): "save" wins the backward by 1.2-1.5x on typical runs and
# stays within CPU timing noise on the rest — the recomputed GEMM contracts
# over the LARGE d_in, while the saved residual is only (N, T, r<=128).
# Under the jax.checkpoint'd block stacks every model here trains with, the
# residual is block-local (saved during the block's backward re-forward,
# freed at the block boundary), so the memory cost is one projection's xA,
# not the whole stack's. Hence "save" is the default.
DEFAULT_REMAT = "save"

_IMPL_VAR: contextvars.ContextVar = contextvars.ContextVar(
    "plora_impl", default="auto"
)


def set_default_impl(impl: str) -> None:
    """Set the *context-local* default impl (see module docstring)."""
    assert impl in IMPLS, impl
    _IMPL_VAR.set(impl)


def default_impl() -> str:
    return _IMPL_VAR.get()


@contextlib.contextmanager
def use_impl(impl: str):
    """Scoped impl override: ``with use_impl("fused"): ...``."""
    assert impl in IMPLS, impl
    token = _IMPL_VAR.set(impl)
    try:
        yield
    finally:
        _IMPL_VAR.reset(token)


def _resolve(impl: Optional[str]) -> str:
    impl = impl or _IMPL_VAR.get()
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        return "pallas" if on_tpu else "xla"
    if impl == "fused":
        return "fused_pallas" if on_tpu else "fused_xla"
    return impl


def sharded_impl(impl: Optional[str]) -> Optional[str]:
    """The impl of a step sharded over several devices.

    XLA cannot partition a compiled Mosaic kernel, and the kernels here are
    not wrapped in ``shard_map``, so on a TPU ``auto`` and ``fused`` take
    their XLA forms there and an explicit Pallas impl is refused. Kernels
    that are interpreted (CPU) lower to plain XLA and stay as asked."""
    if pallas_interpret():
        return impl
    impl = impl or _IMPL_VAR.get()
    if impl in ("pallas", "fused_pallas"):
        raise ValueError(
            f"impl={impl!r} cannot run on a step sharded over several "
            "devices: XLA cannot partition a Mosaic kernel (use 'auto', "
            "'fused' or an XLA impl)"
        )
    return {"auto": "xla", "fused": "fused_xla"}.get(impl, impl)


def _unfused(impl: str) -> str:
    """The two-pass backend implied by a resolved impl (the grouped delta
    primitive underlying a fused variant's auxiliary contractions)."""
    return {"fused_pallas": "pallas", "fused_xla": "xla"}.get(impl, impl)


@dataclass(frozen=True)
class KernelConfig:
    """Static kernel policy threaded from the trainer down to every
    ``lora_linear`` call site (hashable: safe as a jit-static argument).

    impl   : backend name from ``IMPLS`` (None -> context default)
    remat  : backward xA policy "recompute" | "save" (None -> DEFAULT_REMAT)
    ranks  : the pack's per-adapter rank tuple; heterogeneous tuples switch
             the delta to ragged same-rank grid segments (None -> treat the
             pack as rank-homogeneous at the bucket rank)
    blocks : Pallas (block_m, block_l, block_k) override (autotuner hook)
    base_dtype : frozen-base storage scheme — None (dense, whatever dtype
             the checkpoint carries) or "int8"/"nf4" (kernels/quant.py);
             part of the policy so executor caches and the multihost wire
             distinguish quantized from dense compilations
    """

    impl: Optional[str] = None
    remat: Optional[str] = None
    ranks: Optional[Tuple[int, ...]] = None
    blocks: Optional[Tuple[int, int, int]] = None
    base_dtype: Optional[str] = None

    def resolved_impl(self) -> str:
        return _resolve(self.impl)

    def resolved_remat(self) -> str:
        return self.remat or DEFAULT_REMAT

    @property
    def ragged(self) -> bool:
        return self.ranks is not None and len(set(self.ranks)) > 1


def rank_segments(
    ranks: Sequence[int],
) -> Tuple[Tuple[int, ...], Tuple[int, ...], List[Tuple[int, int, int]]]:
    """Group a pack's adapters into same-rank segments.

    Returns ``(order, inv, segments)``: ``order`` is a static permutation
    sorting adapters by rank (stable, so same-rank adapters keep their
    relative slot order), ``inv`` undoes it, and each segment ``(lo, hi, r)``
    is a contiguous run of rank-``r`` adapters in the sorted view.
    """
    n = len(ranks)
    order = tuple(sorted(range(n), key=lambda i: (ranks[i], i)))
    inv = tuple(
        int(i) for i in sorted(range(n), key=lambda i: order[i])
    )
    segments: List[Tuple[int, int, int]] = []
    lo = 0
    for hi in range(1, n + 1):
        if hi == n or ranks[order[hi]] != ranks[order[lo]]:
            segments.append((lo, hi, int(ranks[order[lo]])))
            lo = hi
    return order, inv, segments


def delta_flops(
    ranks: Sequence[int], d_in: int, d_out: int, tokens: int, *,
    ragged: bool,
) -> float:
    """Forward delta FLOPs of one projection for a pack — the structural
    metric ``bench_kernels`` reports: bucket-padded packs compute every
    adapter at ``r_bucket`` (max rank rounded up to 8); ragged segments
    compute each adapter at its own rank."""
    if not ranks:
        return 0.0
    bucket = max(8, (max(ranks) + 7) // 8 * 8)
    total = 0.0
    for r in ranks:
        r_eff = r if ragged else bucket
        total += 2.0 * tokens * r_eff * (d_in + d_out)
    return total


def grouped_matmul(x, w, scale=None, *, impl: Optional[str] = None):
    """out[n] = scale[n] * x[n] @ w[n]; dispatches pallas/xla.

    x may carry extra token dims (N, ..., K). The Pallas kernel is a 3D
    grouped GEMM, so those dims are flattened around the call; the xla path
    keeps them (sharding-friendly under pjit — see packed_matmul_ref)."""
    if _unfused(_resolve(impl)) == "pallas":
        lead = x.shape[1:-1]
        x3 = x.reshape(x.shape[0], -1, x.shape[-1])
        out = _pallas_matmul(x3, w, scale)
        return out.reshape(x.shape[0], *lead, w.shape[-1])
    return _ref.packed_matmul_ref(x, w, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _packed_lora_delta(x, a, b, alpha, impl, remat):
    xa = grouped_matmul(x, a, impl=impl)
    return grouped_matmul(xa, b, alpha, impl=impl)


def _fwd(x, a, b, alpha, impl, remat):
    xa = grouped_matmul(x, a, impl=impl)
    out = grouped_matmul(xa, b, alpha, impl=impl)
    return out, (x, a, b, alpha, xa if remat == "save" else None)


def _bwd(impl, remat, res, g):
    x, a, b, alpha, saved_xa = res
    g = g.astype(x.dtype)
    # xA policy: recompute (cheap: (N, ..., r<=128)) or reuse the residual —
    # bit-identical either way (same op on the same inputs)
    xa = saved_xa if saved_xa is not None else grouped_matmul(x, a, impl=impl)
    g_s = g * alpha.reshape(alpha.shape[0], *([1] * (g.ndim - 1))).astype(g.dtype)
    if x.ndim == 3:
        # 3D: all four cases go through the grouped kernel (paper §5.2)
        # case 1: dB = (xA)^T @ g_s               (N, r, k)
        db = grouped_matmul(jnp.swapaxes(xa, 1, 2), g_s, impl=impl)
        # case 2: d(xA) = g_s @ B^T               (N, T, r)
        dxa = grouped_matmul(g_s, jnp.swapaxes(b, 1, 2), impl=impl)
        # case 3: dA = x^T @ d(xA)                (N, d, r)
        da = grouped_matmul(jnp.swapaxes(x, 1, 2), dxa, impl=impl)
        # case 4: dx = d(xA) @ A^T                (N, T, d)
        dx = grouped_matmul(dxa, jnp.swapaxes(a, 1, 2), impl=impl)
        return dx, da, db, jnp.zeros_like(alpha)
    # N-D (FSDP pack layout): weight grads contract over ALL token dims
    db = jnp.einsum("n...r,n...k->nrk", xa, g_s)
    dxa = grouped_matmul(g_s, jnp.swapaxes(b, 1, 2), impl=impl)
    da = jnp.einsum("n...d,n...r->ndr", x, dxa)
    dx = grouped_matmul(dxa, jnp.swapaxes(a, 1, 2), impl=impl)
    return dx, da.astype(a.dtype), db.astype(b.dtype), jnp.zeros_like(alpha)


_packed_lora_delta.defvjp(_fwd, _bwd)


def _ragged_call(fn, x, a, b, alpha, ranks):
    """Run a per-segment delta/fused op over same-rank grid segments.

    ``fn(x_seg, a_seg, b_seg, alpha_seg)`` sees each segment's weights
    sliced to the segment's true rank; outputs are reassembled in original
    slot order. The permutation is static (``jnp.take`` with constant
    indices), so gradients route exactly and the sliced-off padding columns
    receive no gradient at all.
    """
    assert len(ranks) == x.shape[0], (ranks, x.shape)
    order, inv, segments = rank_segments(ranks)
    xs = jnp.take(x, jnp.asarray(order), axis=0)
    a_s = jnp.take(a, jnp.asarray(order), axis=0)
    b_s = jnp.take(b, jnp.asarray(order), axis=0)
    al_s = jnp.take(alpha, jnp.asarray(order), axis=0)
    outs = []
    for lo, hi, r in segments:
        outs.append(
            fn(
                xs[lo:hi],
                a_s[lo:hi, :, :r],
                b_s[lo:hi, :r, :],
                al_s[lo:hi],
            )
        )
    out = jnp.concatenate(outs, axis=0)
    return jnp.take(out, jnp.asarray(inv), axis=0)


def packed_lora_delta(
    x,
    a,
    b,
    alpha,
    *,
    impl: Optional[str] = None,
    remat: Optional[str] = None,
    ranks: Optional[Tuple[int, ...]] = None,
):
    """alpha_n * (x_n @ A_n) @ B_n for N packed adapters.

    x: (N, T, d); a: (N, d, r); b: (N, r, k); alpha: (N,) -> (N, T, k).
    Heterogeneous ranks are zero-padded to the pack's bucket rank by
    ``repro.core.pack``; with ``ranks=None`` padded columns/rows contribute
    exactly zero to both the output and every gradient, and with the pack's
    static rank tuple passed the padding is sliced away entirely (ragged
    same-rank segments — no wasted FLOPs, structurally zero pad grads).
    ``remat`` picks the backward xA policy (module docstring).
    """
    impl_r = _unfused(_resolve(impl))
    remat_r = remat or DEFAULT_REMAT
    assert remat_r in ("recompute", "save"), remat_r
    alpha = alpha.astype(jnp.float32)
    if ranks is not None and len(set(ranks)) > 1:
        return _ragged_call(
            lambda xs, as_, bs, als: _packed_lora_delta(
                xs, as_, bs, als, impl_r, remat_r
            ),
            x, a, b, alpha, ranks,
        )
    return _packed_lora_delta(x, a, b, alpha, impl_r, remat_r)


def fused_lora_linear(
    x,
    w,
    a,
    b,
    alpha,
    *,
    impl: Optional[str] = None,
    remat: Optional[str] = None,
    ranks: Optional[Tuple[int, ...]] = None,
    blocks: Optional[Tuple[int, int, int]] = None,
):
    """Fused ``x @ W + alpha_n * (x_n @ A_n) @ B_n`` (kernels/fused.py),
    with the same ragged-rank segmentation as :func:`packed_lora_delta` —
    each same-rank segment runs its own fused grid pass (the base GEMM rides
    along per segment, so a segment never re-reads another segment's rows).

    x: (N, ..., d_in); w: (d_in, d_out); a/b/alpha as usual.
    """
    from repro.kernels.fused import fused_lora

    impl_r = _resolve(impl)
    if impl_r in ("pallas", "xla", "auto"):
        impl_r = {"pallas": "fused_pallas", "xla": "fused_xla"}.get(
            impl_r, "fused_xla"
        )
    remat_r = remat or DEFAULT_REMAT
    alpha = alpha.astype(jnp.float32)
    if ranks is not None and len(set(ranks)) > 1:
        return _ragged_call(
            lambda xs, as_, bs, als: fused_lora(
                xs, w, as_, bs, als, impl=impl_r, remat=remat_r, blocks=blocks
            ),
            x, a, b, alpha, ranks,
        )
    return fused_lora(
        x, w, a, b, alpha, impl=impl_r, remat=remat_r, blocks=blocks
    )
