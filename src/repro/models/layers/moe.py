"""Mixture-of-Experts FFN: routed experts, drop-free, plus shared experts.

Routing: a softmax over the router's logits in float32, greedy top-k,
renormalised only when ``norm_topk_prob`` is set (DeepSeek-V2's ``MoEGate``
with ``routed_scaling_factor`` 1). The router is frozen
and carries no adapter; the layer adds no loss term (a balance term over a
pack would mix its adapters' gradients).

``impl="dense"`` computes every expert for every token and combines with the
top-k gates — exact, used for small E (Grok-1's E=8) and as the test oracle.

``impl="ep"`` is the routed path, and drops nothing: the (token, expert)
pairs are sorted by expert and the three expert products run as one grouped
matmul each (``kernels.moe_gmm``) over exactly ``T * top_k`` rows, however
skewed the routing; the backward pass makes the input gradients only (the
experts are frozen). The layer is told which experts it holds
(``e_lo``, ``e_local``): all of them on one chip, its share of the model
axis inside the expert-parallel ``shard_map``, where every shard routes all
local tokens, computes its own experts' part and the parts are summed by a
psum over the axis. So one packed adapter's rows can never push another's
out of an expert.

Shared experts (``n_shared_experts``) are one SwiGLU of width
``n_shared * d_expert`` run for every token, with LoRA on gate/up/down
through the same ``lora_linear`` as the dense MLP.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels.moe_gmm import moe_gmm
from repro.models.layers.common import apply_mlp, init_linear, init_mlp


def init_moe(key, d_model: int, mcfg: MoEConfig, meta=None, targets=(),
             dtype=jnp.float32):
    """(params, lora) of one MoE layer; LoRA only on the shared experts."""
    ks = jax.random.split(key, 5)
    e, f = mcfg.n_experts, mcfg.d_expert
    s = d_model**-0.5
    params = {
        "router": init_linear(ks[0], d_model, e, False, jnp.float32),
        "w_gate": jax.random.normal(ks[1], (e, d_model, f), dtype) * s,
        "w_up": jax.random.normal(ks[2], (e, d_model, f), dtype) * s,
        "w_down": jax.random.normal(ks[3], (e, f, d_model), dtype) * (f**-0.5),
    }
    lora = {}
    if mcfg.n_shared_experts:
        p, lo = init_mlp(ks[4], d_model, mcfg.n_shared_experts * f, "swiglu",
                         False, meta, targets, dtype)
        params["shared"] = p
        if lo:
            lora["shared"] = lo
    return params, lora


def _router(x, params, mcfg: MoEConfig):
    """x: (T, d) -> (gates (T, k) float32, idx (T, k))."""
    logits = jnp.dot(x.astype(jnp.float32),
                     params["router"]["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, mcfg.top_k)
    if mcfg.norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates, idx


def expert_rows(idx, n_experts: int):
    """(E,) int32: the routed rows each expert receives."""
    return jnp.zeros((n_experts,), jnp.int32).at[idx.reshape(-1)].add(1)


def _moe_dense(params, x, gates, idx, n_experts: int, chunk: int = 1024):
    """All-experts compute, gate-weighted combine; token-chunked so the
    (chunk, E, f) intermediate stays small. FLOPs are E/top_k x the routed
    cost — acceptable for small E (Grok-1 E=8) and exact for testing."""
    t, d = x.shape
    comb = jnp.zeros((t, n_experts), x.dtype)
    comb = comb.at[jnp.arange(t)[:, None], idx].set(gates.astype(x.dtype))

    def one_chunk(args):
        xc, cc = args  # (c, d), (c, E)
        g = jnp.einsum("td,edf->etf", xc, params["w_gate"].astype(xc.dtype))
        u = jnp.einsum("td,edf->etf", xc, params["w_up"].astype(xc.dtype))
        h = jax.nn.silu(g) * u
        # fold gate weight in before the down projection
        return jnp.einsum(
            "etf,efd,te->td", h, params["w_down"].astype(xc.dtype), cc
        )

    if t <= chunk:
        return one_chunk((x, comb))
    n = -(-t // chunk)
    xpad = jnp.pad(x, ((0, n * chunk - t), (0, 0)))
    cpad = jnp.pad(comb, ((0, n * chunk - t), (0, 0)))
    return jax.lax.map(
        jax.checkpoint(one_chunk),
        (xpad.reshape(n, chunk, d), cpad.reshape(n, chunk, -1)),
    ).reshape(n * chunk, d)[:t]


@jax.custom_vjp
def _permute(x, idx, inv):
    """``x[idx]`` for a permutation ``idx`` whose inverse is ``inv``; the
    backward pass is the gather ``g[inv]``, not a scatter."""
    return jnp.take(x, idx, axis=0)


def _permute_fwd(x, idx, inv):
    return _permute(x, idx, inv), (idx, inv)


def _permute_bwd(res, g):
    idx, inv = res
    return jnp.take(g, inv, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _moe_routed(params, x, gates, idx, e_lo, e_local: int, n_experts: int):
    """The part of the layer's routed output that experts
    [e_lo, e_lo + e_local) give, drop-free: every (token, expert) pair of a
    held expert is one row of the grouped matmuls."""
    t, d = x.shape
    k = idx.shape[1]
    e = idx.reshape(-1) - e_lo
    held = (e >= 0) & (e < e_local)
    with jax.named_scope("moe.dispatch"):
        # rows of held experts first, in expert order; the rest after them
        key = jnp.where(held, e, e_local)
        order = jnp.argsort(key, stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        sizes = jnp.zeros((e_local + 1,), jnp.int32).at[key].add(1)[:e_local]
        xs = _permute(jnp.repeat(x, k, axis=0), order, inv)
    share = e_local < n_experts  # rows of other shards' experts: zeroed
    with jax.named_scope("moe.experts"):
        g = moe_gmm(xs, params["w_gate"], sizes, share)
        u = moe_gmm(xs, params["w_up"], sizes, share)
        ys = moe_gmm(jax.nn.silu(g) * u, params["w_down"], sizes, share)
    with jax.named_scope("moe.combine"):
        y = _permute(ys, inv, order).reshape(t, k, d)
        w = jnp.where(held.reshape(t, k), gates, 0.0)
        return jnp.einsum("tkd,tk->td", y.astype(jnp.float32), w).astype(x.dtype)


def routed_experts(
    params,
    x: jnp.ndarray,
    mcfg: MoEConfig,
    *,
    model_axis: Optional[str] = None,
    model_axis_size: int = 1,
):
    """The routed experts of one layer. x: (NB, S, d) -> (y, rows), rows the
    (E,) int32 routed rows of each expert.

    Inside shard_map with ``model_axis`` set (``impl="ep"``), params hold only
    this shard's experts and the partial outputs are psum'd over the axis.
    """
    nb, s, d = x.shape
    xt = x.reshape(nb * s, d)
    with jax.named_scope("moe.route"):
        gates, idx = _router(xt, params, mcfg)
        rows = expert_rows(idx, mcfg.n_experts)
    if mcfg.impl == "dense":
        y = _moe_dense(params, xt, gates, idx, mcfg.n_experts)
    elif model_axis is not None:
        e_local = mcfg.n_experts // model_axis_size
        e_lo = jax.lax.axis_index(model_axis) * e_local
        y = _moe_routed(params, xt, gates, idx, e_lo, e_local, mcfg.n_experts)
        y = jax.lax.psum(y, model_axis)
    else:
        y = _moe_routed(params, xt, gates, idx, 0, mcfg.n_experts,
                        mcfg.n_experts)
    return y.reshape(nb, s, d), rows


def apply_shared(params, lora, scales, x, n_pack: int = 1, kcfg=None):
    """The shared experts' output for every token (x: (NB, S, d))."""
    return apply_mlp(params["shared"], (lora or {}).get("shared"), scales, x,
                     "swiglu", n_pack, kcfg=kcfg)
