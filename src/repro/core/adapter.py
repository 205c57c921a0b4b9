"""Adapter-level definitions: pack metadata and per-adapter initialization.

A *pack* is the paper's unit of execution: N LoRA configurations fine-tuned in
one job over a shared frozen base model. Heterogeneous ranks are zero-padded
to the pack's bucket rank ``r_bucket`` (max rank in the pack, rounded up to a
multiple of 8 for TPU sublane alignment); the padding is exact — it
contributes 0 to outputs and all gradients (tests/test_kernels.py::test_rank_padding_exact proves it).

Effective per-adapter scale follows LoRA convention: scale_n = alpha_n / r_n
(paper Table 4 reports alpha as this ratio).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LoraConfig


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclass(frozen=True)
class PackMeta:
    """Static description of a pack of LoRA configurations."""

    ranks: Tuple[int, ...]
    alphas: Tuple[float, ...]
    learning_rates: Tuple[float, ...]
    batch_sizes: Tuple[int, ...]

    @property
    def n(self) -> int:
        return len(self.ranks)

    @property
    def r_bucket(self) -> int:
        return max(8, _round_up(max(self.ranks), 8))

    @property
    def max_batch(self) -> int:
        return max(self.batch_sizes)

    def scales(self) -> jnp.ndarray:
        """Effective multiplier alpha_n / r_n, padded ranks notwithstanding."""
        return jnp.asarray(
            [a / r for a, r in zip(self.alphas, self.ranks)], jnp.float32
        )

    def lr_vector(self) -> jnp.ndarray:
        return jnp.asarray(self.learning_rates, jnp.float32)

    def rank_mask(self) -> jnp.ndarray:
        """(N, r_bucket) 1.0 for real rank columns, 0.0 for padding."""
        r = self.r_bucket
        iota = jnp.arange(r)[None, :]
        return (iota < jnp.asarray(self.ranks)[:, None]).astype(jnp.float32)

    def kernel_config(self, impl=None, remat=None, blocks=None,
                      base_dtype=None):
        """Static kernel policy for this pack: carries the per-adapter rank
        vector down to the kernels so heterogeneous-rank packs run as ragged
        same-rank grid segments instead of computing every adapter at
        ``r_bucket`` (see ``repro.kernels.ops.KernelConfig``)."""
        from repro.kernels.ops import KernelConfig

        return KernelConfig(
            impl=impl, remat=remat, ranks=self.ranks, blocks=blocks,
            base_dtype=base_dtype,
        )


def pack_meta(configs: Sequence[LoraConfig]) -> PackMeta:
    return PackMeta(
        ranks=tuple(c.rank for c in configs),
        alphas=tuple(float(c.alpha) for c in configs),
        learning_rates=tuple(float(c.learning_rate) for c in configs),
        batch_sizes=tuple(int(c.batch_size) for c in configs),
    )


def single_meta(rank: int = 16, alpha: float = 16.0, lr: float = 1e-4, bs: int = 1) -> PackMeta:
    return pack_meta([LoraConfig(rank=rank, alpha=alpha, learning_rate=lr, batch_size=bs)])


def init_lora_pair(
    key: jax.Array, meta: PackMeta, d_in: int, d_out: int, dtype=jnp.float32
) -> dict:
    """Packed (A, B) for one target projection across all N adapters.

    A ~ N(0, 1/d_in) on the first r_n columns (rest zero); B = 0 so the delta
    starts at exactly zero (standard LoRA init, paper Fig. 1 convention).
    """
    n, r = meta.n, meta.r_bucket
    # the barrier keeps XLA from rewriting the division as a multiply by the
    # reciprocal under jit, so jitted and eager inits agree bit for bit
    std = jax.lax.optimization_barrier(jnp.sqrt(d_in).astype(dtype))
    a = jax.random.normal(key, (n, d_in, r), dtype) / std
    a = a * meta.rank_mask()[:, None, :].astype(dtype)
    b = jnp.zeros((n, r, d_out), dtype)
    return {"a": a, "b": b}
