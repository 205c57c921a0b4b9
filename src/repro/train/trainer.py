"""Packed-LoRA training step and loop.

``make_train_step`` builds the jitted step for a pack of N adapters on one
frozen base model: forward with packed-LoRA deltas, chunked CE with
per-adapter reduction, grads w.r.t. adapter params only, AdamW with the
per-adapter learning-rate vector. Base params enter as inputs but are never
differentiated — XLA sees them as constants of the step (no base grads, no
base optimizer state: the paper's packing-memory property).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.adapter import PackMeta
from repro.kernels.ops import KernelConfig, sharded_impl
from repro.models.model import forward, unembed_w
from repro.models.transformer import DistContext
from repro.train.losses import chunked_cross_entropy
from repro.train.optimizer import adamw_update, init_opt_state


def packed_loss_fn(
    lora,
    base,
    batch,
    cfg: ModelConfig,
    n_pack: int,
    scales,
    *,
    dist: Optional[DistContext] = None,
    chunk_q: int = 512,
    vocab_chunk: int = 512,
    aux_weight: float = 0.01,
    kcfg: Optional[KernelConfig] = None,
):
    """Pack loss with the per-adapter scale vector as a runtime value (a
    traced argument under ``make_packed_step``, a constant under
    ``make_train_step``). ``kcfg`` is the static kernel policy (backend
    impl, backward remat, the pack's rank vector for ragged grouping)."""
    h, _, aux = forward(
        base, lora, scales, batch, cfg,
        n_pack=n_pack, dist=dist, chunk_q=chunk_q, kcfg=kcfg,
    )
    per_adapter, total = chunked_cross_entropy(
        h, unembed_w(base, cfg), batch["labels"], n_pack,
        chunk=vocab_chunk, vocab=cfg.vocab_size,
    )
    return total + aux_weight * aux, per_adapter


def loss_fn(
    lora,
    base,
    batch,
    cfg: ModelConfig,
    meta: PackMeta,
    *,
    dist: Optional[DistContext] = None,
    chunk_q: int = 512,
    vocab_chunk: int = 512,
    aux_weight: float = 0.01,
    kcfg: Optional[KernelConfig] = None,
):
    return packed_loss_fn(
        lora, base, batch, cfg, meta.n, meta.scales(),
        dist=dist, chunk_q=chunk_q, vocab_chunk=vocab_chunk,
        aux_weight=aux_weight,
        kcfg=kcfg if kcfg is not None else meta.kernel_config(),
    )


def make_packed_step(
    cfg: ModelConfig,
    n_pack: int,
    *,
    dist: Optional[DistContext] = None,
    chunk_q: int = 512,
    vocab_chunk: int = 512,
    weight_decay: float = 0.0,
    jit: bool = True,
    impl: Optional[str] = None,
    remat: Optional[str] = None,
    ranks: Optional[tuple] = None,
    blocks: Optional[tuple] = None,
    base_dtype: Optional[str] = None,
):
    """Shape-keyed packed train step (cluster executor's compile unit).

    Unlike :func:`make_train_step`, the per-adapter hyperparameter vectors —
    ``scales`` (alpha/r), ``lr_vec`` and ``budgets`` (per-adapter step
    caps) — enter as *runtime arguments* rather than closed-over constants,
    so one compiled executable serves every pack with the same
    (n, r_bucket, batch, seq) shape regardless of which alphas / learning
    rates / step budgets the pack carries. ``repro.cluster.SliceExecutor``
    caches the returned callable per (model-config, pack-width, slice-shape).

    ``impl``/``remat`` select the kernel backend and backward xA policy
    (kernels/ops.py) — plumbed *explicitly* because the context-local
    default does not cross the cluster runner's worker threads; ``ranks``
    is the pack's static per-adapter rank tuple, which switches
    heterogeneous-rank packs onto ragged same-rank kernel segments (no
    bucket-padding FLOPs). ``base_dtype`` marks a quantized frozen base
    ("int8"/"nf4", kernels/quant.py) — the base argument then carries
    {"codes","scales"} dicts in its "w" slots. All are part of the
    executor's cache key. On a slice of several chips the Pallas impls
    take their XLA forms (``kernels.ops.sharded_impl``).
    """
    # homogeneous rank tuples normalize to None: they trace identically
    # (ragged segmentation only engages on mixed ranks), so same-width packs
    # of different uniform ranks keep sharing one executor cache entry
    ranks = tuple(ranks) if ranks and len(set(ranks)) > 1 else None
    if dist is not None and dist.mesh.size > 1:
        impl = sharded_impl(impl)
    kcfg = KernelConfig(
        impl=impl, remat=remat, ranks=ranks,
        blocks=tuple(blocks) if blocks is not None else None,
        base_dtype=base_dtype,
    )

    def train_step(base, lora, opt_state, batch, scales, lr_vec, budgets):
        (total, per_adapter), grads = jax.value_and_grad(
            packed_loss_fn, has_aux=True
        )(lora, base, batch, cfg, n_pack, scales,
          dist=dist, chunk_q=chunk_q, vocab_chunk=vocab_chunk, kcfg=kcfg)
        lora_new, opt_state = adamw_update(
            grads, opt_state, lora, lr_vec, weight_decay=weight_decay,
            step_budget=budgets,
        )
        metrics = {"loss": total, "per_adapter_loss": per_adapter}
        return lora_new, opt_state, metrics

    return jax.jit(train_step, donate_argnums=(1, 2)) if jit else train_step


def make_train_step(
    cfg: ModelConfig,
    meta: PackMeta,
    *,
    dist: Optional[DistContext] = None,
    chunk_q: int = 512,
    vocab_chunk: int = 512,
    weight_decay: float = 0.0,
    step_budgets=None,  # (N,) per-adapter max step counts (online engine)
    jit: bool = True,
    impl: Optional[str] = None,
    remat: Optional[str] = None,
    base_dtype: Optional[str] = None,
):
    lr_vec = meta.lr_vector()
    budgets = (
        jnp.asarray(step_budgets, jnp.int32) if step_budgets is not None else None
    )
    if dist is not None and dist.mesh.size > 1:
        impl = sharded_impl(impl)
    kcfg = meta.kernel_config(impl=impl, remat=remat, base_dtype=base_dtype)

    def train_step(base, lora, opt_state, batch):
        (total, per_adapter), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(lora, base, batch, cfg, meta,
          dist=dist, chunk_q=chunk_q, vocab_chunk=vocab_chunk, kcfg=kcfg)
        lora_new, opt_state = adamw_update(
            grads, opt_state, lora, lr_vec, weight_decay=weight_decay,
            step_budget=budgets,
        )
        metrics = {"loss": total, "per_adapter_loss": per_adapter}
        return lora_new, opt_state, metrics

    return jax.jit(train_step, donate_argnums=(1, 2)) if jit else train_step


def train_loop(
    base,
    lora,
    cfg: ModelConfig,
    meta: PackMeta,
    data_iter,
    n_steps: int,
    *,
    dist=None,
    chunk_q: int = 512,
    vocab_chunk: int = 512,
    log_every: int = 0,
) -> Dict[str, Any]:
    """Run n_steps; returns final state + loss history."""
    step_fn = make_train_step(
        cfg, meta, dist=dist, chunk_q=chunk_q, vocab_chunk=vocab_chunk
    )
    opt_state = init_opt_state(lora)
    history = []
    for i in range(n_steps):
        batch = next(data_iter)
        lora, opt_state, m = step_fn(base, lora, opt_state, batch)
        history.append(jax.device_get(m["per_adapter_loss"]))
        if log_every and (i % log_every == 0):
            print(f"step {i}: loss={float(m['loss']):.4f}")
    return {"lora": lora, "opt_state": opt_state, "history": history}
