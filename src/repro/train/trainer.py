"""Packed-LoRA training step and loop.

``make_train_step`` builds the jitted step for a pack of N adapters on one
frozen base model: forward with packed-LoRA deltas, chunked CE with
per-adapter reduction, grads w.r.t. adapter params only, AdamW with the
per-adapter learning-rate vector. Base params enter as inputs but are never
differentiated — XLA sees them as constants of the step (no base grads, no
base optimizer state: the paper's packing-memory property).

Row slots: a pack whose adapters differ in batch size arrives padded, each
adapter's rows filled to the largest batch with ignored rows. The step takes
out the real rows and runs each as one slot of the packed forward, carrying
its owner adapter's LoRA weights and scale (``row_slots``), so padding rows
are never computed. Gradients flow back through the static gathers and sum
over each adapter's slots; optimizer state stays per adapter.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.adapter import PackMeta
from repro.kernels.moe_gmm import grid_rows
from repro.kernels.ops import KernelConfig, sharded_impl
from repro.models.model import forward, unembed_w
from repro.models.transformer import DistContext
from repro.train.losses import chunked_cross_entropy
from repro.train.optimizer import adamw_update, init_opt_state, pack_axis


def row_slots(batch_sizes, dist: Optional[DistContext] = None):
    """The owner adapter of each row the step computes, or None where the
    step keeps the padded ``(n * max_batch, S)`` layout: when the pack's
    batch sizes are all equal (no padding to drop), or when the step's mesh
    splits the row axis over chips (the batch was sharded for that
    layout)."""
    if not batch_sizes or len(set(batch_sizes)) == 1:
        return None
    if dist is not None and dist.mesh is not None and any(
        dict(zip(dist.mesh.axis_names, dist.mesh.devices.shape))[a] > 1
        for a in dist.data_axes
    ):
        return None
    return tuple(k for k, b in enumerate(batch_sizes) for _ in range(b))


def step_rows(batch_sizes, dist: Optional[DistContext] = None) -> int:
    """Rows the packed step computes for a pack of these batch sizes."""
    owner = row_slots(batch_sizes, dist)
    if owner is not None:
        return len(owner)
    return len(batch_sizes) * max(batch_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _slot_leaf(x, owner, axis, dtype):
    """An adapter leaf expanded to one copy per slot, in ``dtype``."""
    return jnp.take(x, jnp.asarray(owner, jnp.int32), axis=axis).astype(dtype)


def _slot_leaf_fwd(x, owner, axis, dtype):
    return _slot_leaf(x, owner, axis, dtype), jnp.zeros((), x.dtype)


def _slot_leaf_bwd(owner, axis, dtype, like, g):
    # each adapter's gradient is the float32 sum of its slots' gradients
    g = jnp.moveaxis(g.astype(jnp.float32), axis, 0)
    g = jax.ops.segment_sum(g, jnp.asarray(owner, jnp.int32), max(owner) + 1,
                            indices_are_sorted=True)
    return (jnp.moveaxis(g, 0, axis).astype(like.dtype),)


_slot_leaf.defvjp(_slot_leaf_fwd, _slot_leaf_bwd)


def _to_slots(lora, batch, scales, kcfg, batch_sizes, owner, dtype):
    """The pack's real rows as slots: each real row of the padded batch,
    with its owner adapter's LoRA leaves (cast to ``dtype``, the dtype the
    projections compute in, so the copies are made once and in that width),
    scale and rank."""
    bmax = max(batch_sizes)
    rows = jnp.asarray([k * bmax + j for k, b in enumerate(batch_sizes)
                        for j in range(b)], jnp.int32)
    nb = len(batch_sizes) * bmax
    idx = jnp.asarray(owner, jnp.int32)
    lora = jax.tree_util.tree_map_with_path(
        lambda path, x: _slot_leaf(x, owner, pack_axis(path), dtype), lora
    )
    batch = {k: jnp.take(v, rows, axis=0) if v.shape[0] == nb else v
             for k, v in batch.items()}
    if kcfg is not None and kcfg.ranks is not None:
        kcfg = dataclasses.replace(
            kcfg, ranks=tuple(kcfg.ranks[k] for k in owner)
        )
    return lora, batch, jnp.take(scales, idx), kcfg


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
    kcfg: Optional[KernelConfig] = None,
    batch_sizes: Optional[tuple] = None,
):
    """Pack loss with the per-adapter scale vector as a runtime value (a
    traced argument under ``make_packed_step``, a constant under
    ``make_train_step``). ``kcfg`` is the static kernel policy (backend
    impl, backward remat, the pack's rank vector for ragged grouping).
    ``batch_sizes``, the pack's static batch-size tuple, runs a mixed-batch
    pack's real rows as slots (``row_slots``); None keeps the padded
    layout.

    The loss is the sum of the adapters' LM losses and nothing else: a term
    over the whole pack, such as an MoE load-balance loss, would mix the
    packed adapters' gradients. Returns (total, (per_adapter, expert_rows)),
    the last the (MoE layers, E) routed rows of each expert."""
    owner = row_slots(batch_sizes, dist)
    n_rows = n_pack
    if owner is not None:
        lora, batch, scales, kcfg = _to_slots(
            lora, batch, scales, kcfg, batch_sizes, owner,
            jnp.dtype(base["embed"]["w"].dtype),
        )
        n_rows = len(owner)
    h, _, rows = forward(
        base, lora, scales, batch, cfg,
        n_pack=n_rows, dist=dist, chunk_q=chunk_q, kcfg=kcfg,
    )
    per_adapter, total = chunked_cross_entropy(
        h, unembed_w(base, cfg), batch["labels"], n_pack,
        chunk=vocab_chunk, vocab=cfg.vocab_size, row_owner=owner,
    )
    return total, (per_adapter, rows)


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
    kcfg: Optional[KernelConfig] = None,
):
    return packed_loss_fn(
        lora, base, batch, cfg, meta.n, meta.scales(),
        dist=dist, chunk_q=chunk_q, vocab_chunk=vocab_chunk,
        kcfg=kcfg if kcfg is not None else meta.kernel_config(),
        batch_sizes=meta.batch_sizes,
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
    batch_sizes: Optional[tuple] = None,
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
    {"codes","scales"} dicts in its "w" slots. ``batch_sizes`` is the
    pack's static batch-size tuple: a mixed one computes only the real rows
    of the padded batch (``row_slots``). All are part of the executor's
    cache key. On a slice of several chips the Pallas impls take their XLA
    forms (``kernels.ops.sharded_impl``).
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
        (total, (per_adapter, rows)), grads = jax.value_and_grad(
            packed_loss_fn, has_aux=True
        )(lora, base, batch, cfg, n_pack, scales,
          dist=dist, chunk_q=chunk_q, vocab_chunk=vocab_chunk, kcfg=kcfg,
          batch_sizes=batch_sizes)
        lora_new, opt_state = adamw_update(
            grads, opt_state, lora, lr_vec, weight_decay=weight_decay,
            step_budget=budgets,
        )
        metrics = {"loss": total, "per_adapter_loss": per_adapter,
                   "expert_rows": rows,
                   "expert_rows_computed": grid_rows(rows)}
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
        (total, (per_adapter, rows)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(lora, base, batch, cfg, meta,
          dist=dist, chunk_q=chunk_q, vocab_chunk=vocab_chunk, kcfg=kcfg)
        lora_new, opt_state = adamw_update(
            grads, opt_state, lora, lr_vec, weight_decay=weight_decay,
            step_budget=budgets,
        )
        metrics = {"loss": total, "per_adapter_loss": per_adapter,
                   "expert_rows": rows,
                   "expert_rows_computed": grid_rows(rows)}
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
