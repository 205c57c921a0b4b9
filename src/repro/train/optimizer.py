"""AdamW over packed adapter parameters with PER-ADAPTER learning rates.

Only LoRA parameters carry optimizer state — the base model is frozen (the
paper's memory argument, §3.2/Appendix A: no base grads, no base moments).
The pack dimension N sits at axis 0 of unstacked leaves and axis 1 of
layer-stacked ("blocks") leaves; each adapter n is stepped with its own
learning rate lr_n from the hyperparameter configuration — hyperparameter
heterogeneity inside a single jitted update.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def init_opt_state(lora_params, n_pack: int = 0) -> Dict[str, Any]:
    """``n_pack > 0`` makes ``step`` a per-adapter (N,) vector instead of a
    scalar — required by the online engine, where a pack can mix fresh
    adapters (step 0) with adapters resumed from a preempted job (step k):
    each adapter's Adam bias correction continues from its own count."""
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    return {
        "m": zeros(lora_params),
        "v": zeros(lora_params),
        "step": jnp.zeros((n_pack,) if n_pack else (), jnp.int32),
    }


def pack_axis(path) -> int:
    """Axis of the pack dim of an adapter leaf: 1 under a 'blocks' stack,
    else 0."""
    return 1 if any(getattr(k, "key", None) == "blocks" for k in path) else 0


def _lr_shape(path, leaf, n_pack: int):
    """Broadcast shape of a per-adapter vector against this leaf."""
    ax = pack_axis(path)
    assert leaf.shape[ax] == n_pack, (path, leaf.shape, n_pack)
    shape = [1] * leaf.ndim
    shape[ax] = n_pack
    return shape


def adamw_update(
    grads,
    opt_state,
    params,
    lr_vector: jnp.ndarray,  # (N,)
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    step_budget: Optional[jnp.ndarray] = None,  # (N,) max steps per adapter
) -> Tuple[Any, Dict[str, Any]]:
    """``step_budget`` (online engine) freezes adapter n — params, moments
    and step count — once it has trained its own budgeted iterations, while
    packmates with longer residuals keep updating: packed jobs can then mix
    adapters with heterogeneous remaining-step counts and real execution
    matches the virtual scheduler's per-adapter accounting."""
    active = None
    if step_budget is not None:
        active = (opt_state["step"] < step_budget).astype(jnp.float32)  # (N,)
        step = opt_state["step"] + active.astype(opt_state["step"].dtype)
    else:
        step = opt_state["step"] + 1
    n_pack = lr_vector.shape[0]
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    flat_g = jax.tree_util.tree_flatten_with_path(grads)[0]
    flat_m = jax.tree.leaves(opt_state["m"])
    flat_v = jax.tree.leaves(opt_state["v"])
    flat_p = jax.tree.leaves(params)
    new_p, new_m, new_v = [], [], []
    for (path, g), m, v, p in zip(flat_g, flat_m, flat_v, flat_p):
        shape = _lr_shape(path, p, n_pack)
        # per-adapter step vector (online engine): broadcast bias correction
        # along the pack axis, same as the learning rate
        c1l = c1.reshape(shape) if c1.ndim else c1
        c2l = c2.reshape(shape) if c2.ndim else c2
        if active is not None:
            g = g * active.reshape(shape).astype(g.dtype)
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * (g * g)
        if active is not None:
            act = active.reshape(shape)
            m_new = act * m_new + (1 - act) * m
            v_new = act * v_new + (1 - act) * v
        mh = m_new / jnp.maximum(c1l, 1e-12)
        vh = v_new / jnp.maximum(c2l, 1e-12)
        lr = lr_vector.reshape(shape).astype(p.dtype)
        upd = mh / (jnp.sqrt(vh) + eps)
        if weight_decay:
            upd = upd + weight_decay * p
        if active is not None:
            upd = upd * active.reshape(shape).astype(p.dtype)
        new_p.append(p - lr * upd)
        new_m.append(m_new)
        new_v.append(v_new)
    treedef = jax.tree.structure(params)
    return (
        jax.tree.unflatten(treedef, new_p),
        {
            "m": jax.tree.unflatten(treedef, new_m),
            "v": jax.tree.unflatten(treedef, new_v),
            "step": step,
        },
    )
