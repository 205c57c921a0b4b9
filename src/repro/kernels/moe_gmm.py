"""Pallas TPU kernel: grouped matmul over the routed rows of an MoE layer.

The rows arrive sorted by expert, one group per expert, with ragged group
sizes (``group_sizes``, a runtime vector): ``out[rows of g] = lhs[rows of g]
@ rhs[g]``. Grid (N/tn, active m-tiles, K/tk): an m-tile is visited once by
each group that has rows in it, and stores only those rows, so every routed
row is computed once and no row is dropped or padded to a capacity. Empty
groups take no tile. The tile metadata (each grid step's group and m-tile)
is megablox's ``make_group_metadata`` (``jax.experimental.pallas.ops.tpu.
megablox``), scalar-prefetched into SMEM; the kernel follows megablox's
``gmm`` without its sharded-output options.

``moe_gmm`` carries a custom VJP that gives the input gradient alone, as the
same kernel against the transposed weights: the experts are frozen, so no
weight gradient is ever made. The pallas call is named ``moe_gmm``, which is
how the device trace names its operations. On the CPU it runs interpreted
(``packed_matmul.pallas_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.kernels.packed_matmul import pallas_interpret

TM = 128  # rows of an m-tile
# Largest k and n tiles. A whole contraction in one k tile keeps the rhs
# block index fixed across an expert's consecutive m-tiles, so its weights
# are fetched once per expert, not once per m-tile (d_model 2048 fits);
# the n cap bounds the double-buffered (tk, tn) rhs block in VMEM.
_K_CAP, _N_CAP = 2048, 1536


def _tile(dim: int, cap: int, unit: int = 128) -> int:
    """The whole dimension up to ``cap``, else the largest multiple of
    ``unit`` under ``cap`` that divides it."""
    if dim <= cap:
        return dim
    for t in range(cap - cap % unit, 0, -unit):
        if dim % t == 0:
            return t
    return dim


def _kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref, *, tm, tn, n_k,
            transpose_rhs):
    group_offsets, group_ids, m_tile_ids = meta
    grid_id = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
    acc_ref[...] += lax.dot_general(
        lhs_ref[...], rhs_ref[...].astype(lhs_ref.dtype), dims,
        preferred_element_type=jnp.float32)

    @pl.when(k_i == n_k - 1)
    def _store():
        g = group_ids[grid_id]
        row = m_tile_ids[grid_id] * tm + lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= group_offsets[g]) & (row < group_offsets[g + 1])
        out_ref[...] = jnp.where(
            mine, acc_ref[...], out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("transpose_rhs", "zero_rest", "interpret"))
def _gmm(lhs, rhs, group_sizes, *, transpose_rhs=False, zero_rest=False,
         interpret=None):
    """(m, k) rows sorted by group, (G, k, n) weights ((G, n, k) with
    ``transpose_rhs``) -> (m, n). Rows past ``sum(group_sizes)`` are left
    unwritten, or zeroed with ``zero_rest``."""
    if interpret is None:
        interpret = pallas_interpret()
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tk, tn = _tile(k, _K_CAP), _tile(n, _N_CAP)
    mp = -(-m // TM) * TM
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    meta, n_tiles = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=mp, tm=TM,
        start_group=jnp.int32(0), num_nonzero_groups=rhs.shape[0],
        visit_empty_groups=False)
    n_k = k // tk

    def lhs_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], k_i

    def rhs_map(n_i, grid_id, k_i, meta):
        g = meta[1][grid_id]
        return (g, n_i, k_i) if transpose_rhs else (g, k_i, n_i)

    def out_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=TM, tn=tn, n_k=n_k,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((TM, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((TM, tn), out_map),
            grid=(n // tn, n_tiles, n_k),
            scratch_shapes=[pltpu.VMEM((TM, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(meta, lhs, rhs)
    out = out[:m] if mp != m else out
    if zero_rest:
        row = lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        out = jnp.where(row < meta[0][-1], out, 0)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def moe_gmm(lhs, rhs, group_sizes, zero_rest=False):
    """Grouped matmul of expert-sorted rows against frozen expert weights:
    ``lhs`` (m, k), ``rhs`` (G, k, n), ``group_sizes`` (G,) int32. Where the
    groups may cover fewer than m rows (a layer holding a share of the
    experts), ``zero_rest`` zeroes the rows past them, forward and
    backward. Differentiable in ``lhs`` only."""
    return _gmm(lhs, rhs, group_sizes, zero_rest=zero_rest)


def _moe_gmm_fwd(lhs, rhs, group_sizes, zero_rest):
    return _gmm(lhs, rhs, group_sizes, zero_rest=zero_rest), (rhs, group_sizes)


def _moe_gmm_bwd(zero_rest, res, g):
    rhs, group_sizes = res
    dlhs = _gmm(g, rhs, group_sizes, transpose_rhs=True, zero_rest=zero_rest)
    return dlhs, None, None


moe_gmm.defvjp(_moe_gmm_fwd, _moe_gmm_bwd)


def grid_rows(group_sizes, tm: int = TM):
    """Rows the ``moe_gmm`` grids compute for these group sizes (groups on
    the last axis, one grid per leading index, summed): whole m-tiles, each
    group from the tile its first row is in to the tile its last row is
    in. A scalar int32 array; traceable."""
    sizes = jnp.asarray(group_sizes, jnp.int32)
    ends = jnp.cumsum(sizes, axis=-1)
    starts = ends - sizes
    tiles = -(-ends // tm) - starts // tm
    return jnp.where(sizes > 0, tiles, 0).sum() * tm
