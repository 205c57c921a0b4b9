"""Composable decoder (+ optional encoder) stack over all assigned families.

Layers are grouped into *pattern blocks*: the per-layer spec sequence (mixer
kind, ffn kind, window, rope theta, cross-attn) has a minimal period p; the
stack is a ``lax.scan`` over L//p stacked blocks (compile-time O(p) at 512
devices) plus an unstacked remainder prefix (L % p layers, e.g. Gemma-3's
26 = 4*6 + 2). Every per-position spec inside a block body is static, so
sliding-window layers get the sub-quadratic sliced-band attention path and
hybrid (Jamba) blocks mix SSD and attention sublayers without traced
branching. Block bodies are rematerialized (jax.checkpoint).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.adapter import PackMeta
from repro.models.layers.attention import (
    apply_gqa,
    apply_mla,
    init_gqa,
    init_gqa_cache,
    init_mla,
    init_mla_cache,
)
from repro.models.layers.common import apply_mlp, apply_norm, init_linear, init_mlp, init_norm
from repro.models.layers.moe import apply_moe, init_moe
from repro.models.layers.rope import rope_tables
from repro.models.layers.ssm import (
    apply_ssm,
    apply_ssm_chunk,
    apply_ssm_decode,
    init_ssm,
    init_ssm_cache,
)


@dataclass(frozen=True)
class LayerSpec:
    mixer: str  # "attn" | "ssm"
    ffn: str  # "dense" | "moe" | "none"
    window: int = 0
    theta: float = 10_000.0
    cross: bool = False  # whisper decoder cross-attention sublayer


@dataclass(frozen=True)
class DistContext:
    """Distribution info threaded through layers (None on single device)."""

    mesh: Any = None
    data_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    model_axis_size: int = 1
    # Sequence-parallel residuals (beyond-paper §Perf optimization): constrain
    # the inter-block hidden state to be sharded over the model axis on the
    # sequence dim. XLA then converts the megatron all-reduce pairs into
    # all-gather + reduce-scatter (same wire bytes) and — the point — the
    # lax.scan residual carry stack saved for backward shrinks by the TP
    # degree. Applies to train/prefill (S >= model_axis_size); decode
    # (S == 1) ignores it.
    seq_sharded_residuals: bool = False
    # FSDP execution mode: pin the residual stream to fully-batch-sharded
    # (over data AND model axes) at block boundaries, so SPMD propagation
    # can't invent tensor-parallel intermediate layouts that all-reduce
    # activations (EXPERIMENTS.md §Perf, starcoder2 train hillclimb).
    fsdp: bool = False

    def residual_constraint(self, x):
        if self.mesh is None or x.ndim != 3:
            return x
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        if self.fsdp and self.data_axes:
            spec = P(self.data_axes, None, None)
            return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))
        if (
            not self.seq_sharded_residuals
            or self.model_axis is None
            or x.shape[1] % self.model_axis_size != 0
            or x.shape[1] <= 1
        ):
            return x
        spec = P(self.data_axes if self.data_axes else None, self.model_axis, None)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))


def layer_specs(cfg: ModelConfig) -> List[LayerSpec]:
    a = cfg.attention
    specs = []
    mixers = cfg.layer_kinds()
    ffns = cfg.ffn_kinds()
    for i in range(cfg.n_layers):
        window, theta = 0, a.rope_theta
        if mixers[i] == "attn" and a.global_every:
            if (i % a.global_every) == (a.global_every - 1):
                theta = a.global_rope_theta or a.rope_theta
            else:
                window = a.sliding_window
        elif mixers[i] == "attn":
            window = a.sliding_window
        specs.append(
            LayerSpec(
                mixer=mixers[i],
                ffn=ffns[i],
                window=window,
                theta=theta,
                cross=cfg.is_encdec,
            )
        )
    return specs


def find_period(specs: List[LayerSpec]) -> int:
    L = len(specs)
    for p in range(1, L + 1):
        if all(specs[i] == specs[i % p] for i in range(L)):
            return p
    return L


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, spec: LayerSpec, meta, dtype):
    ks = jax.random.split(key, 6)
    a = cfg.attention
    params: Dict[str, Any] = {"norm1": init_norm(cfg.d_model, cfg.norm_kind, dtype)}
    lora: Dict[str, Any] = {}
    if spec.mixer == "ssm":
        p, lo = init_ssm(ks[0], cfg.d_model, cfg.ssm, meta, cfg.lora_targets, dtype)
        params["ssm"] = p
        if lo:
            lora["ssm"] = lo
    else:
        init_fn = init_mla if a.is_mla else init_gqa
        p, lo = init_fn(ks[0], a, cfg.d_model, meta, cfg.lora_targets, dtype)
        params["attn"] = p
        if lo:
            lora["attn"] = lo
    if spec.cross:
        p, lo = init_gqa(ks[1], a, cfg.d_model, meta, cfg.lora_targets, dtype)
        params["cross"] = p
        params["norm_cross"] = init_norm(cfg.d_model, cfg.norm_kind, dtype)
        if lo:
            lora["cross"] = lo
    if spec.ffn == "dense":
        p, lo = init_mlp(
            ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_kind,
            a.use_bias, meta, cfg.lora_targets, dtype,
        )
        params["mlp"] = p
        params["norm2"] = init_norm(cfg.d_model, cfg.norm_kind, dtype)
        if lo:
            lora["mlp"] = lo
    elif spec.ffn == "moe":
        params["moe"] = init_moe(ks[2], cfg.d_model, cfg.moe, dtype)
        params["norm2"] = init_norm(cfg.d_model, cfg.norm_kind, dtype)
    return params, lora


def _ropes_for(spec: LayerSpec, rope_cache):
    return rope_cache[spec.theta]


def apply_layer(
    params,
    lora,
    scales,
    x,
    spec: LayerSpec,
    cfg: ModelConfig,
    *,
    n_pack: int,
    rope_cache,
    dist: Optional[DistContext],
    enc_out=None,
    cache=None,
    pos=None,
    make_cache: bool = False,
    chunk_q: int = 512,
    causal: bool = True,
    kcfg=None,
):
    """Pre-norm residual layer. Returns (x, new_cache, aux_loss)."""
    lo = lora or {}
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    h = apply_norm(params["norm1"], x, cfg.norm_kind)
    if spec.mixer == "ssm":
        if cache is not None:
            # single-token decode vs multi-token chunked prefill: the chunk
            # path replays the conv window and resumes the SSD state
            ssm_fn = apply_ssm_decode if h.shape[1] == 1 else apply_ssm_chunk
            y, c = ssm_fn(
                params["ssm"], lo.get("ssm"), scales, h,
                cache["ssm"], scfg=cfg.ssm, n_pack=n_pack, kcfg=kcfg,
            )
        else:
            y, c = apply_ssm(
                params["ssm"], lo.get("ssm"), scales, h,
                scfg=cfg.ssm, n_pack=n_pack, return_state=make_cache,
                kcfg=kcfg,
            )
        if c is not None:
            new_cache["ssm"] = c
    else:
        a = cfg.attention
        rope = _ropes_for(spec, rope_cache)
        if a.is_mla:
            y, c = apply_mla(
                params["attn"], lo.get("attn"), scales, h,
                acfg=a, n_pack=n_pack, rope=rope,
                cache=cache.get("attn") if cache else None,
                pos=pos, make_cache=make_cache, chunk_q=chunk_q, kcfg=kcfg,
            )
        else:
            y, c = apply_gqa(
                params["attn"], lo.get("attn"), scales, h,
                acfg=a, n_pack=n_pack, rope=rope, window=spec.window,
                causal=causal,
                cache=cache.get("attn") if cache else None,
                pos=pos, make_cache=make_cache, chunk_q=chunk_q, kcfg=kcfg,
            )
        if c is not None:
            new_cache["attn"] = c
    x = x + y

    if spec.cross and (enc_out is not None or (cache is not None and "cross_kv" in cache)):
        h = apply_norm(params["norm_cross"], x, cfg.norm_kind)
        if enc_out is None:
            ckv = cache["cross_kv"]
        else:
            a = cfg.attention
            kv, hd = a.n_kv_heads, a.head_dim
            nb = enc_out.shape[0]
            k = (enc_out @ params["cross"]["k"]["w"].astype(enc_out.dtype))
            v = (enc_out @ params["cross"]["v"]["w"].astype(enc_out.dtype))
            if "b" in params["cross"]["k"]:
                k = k + params["cross"]["k"]["b"].astype(k.dtype)
                v = v + params["cross"]["v"]["b"].astype(v.dtype)
            ckv = {
                "k": k.reshape(nb, -1, kv, hd),
                "v": v.reshape(nb, -1, kv, hd),
            }
        y, _ = apply_gqa(
            params["cross"], lo.get("cross"), scales, h,
            acfg=cfg.attention, n_pack=n_pack, rope=None,
            causal=False, cross_kv=ckv, chunk_q=chunk_q, kcfg=kcfg,
        )
        if make_cache or cache is not None:
            new_cache["cross_kv"] = ckv
        x = x + y

    if spec.ffn == "dense":
        h = apply_norm(params["norm2"], x, cfg.norm_kind)
        x = x + apply_mlp(params["mlp"], lo.get("mlp"), scales, h, cfg.mlp_kind, n_pack, kcfg=kcfg)
    elif spec.ffn == "moe":
        h = apply_norm(params["norm2"], x, cfg.norm_kind)
        if dist is not None and dist.model_axis is not None and cfg.moe.impl == "ep":
            from jax.sharding import PartitionSpec as P

            da = dist.data_axes
            x_spec = P(da if da else None, None, None)
            moe_specs = {
                "router": {"w": P()},
                "w_gate": P(dist.model_axis, None, None),
                "w_up": P(dist.model_axis, None, None),
                "w_down": P(dist.model_axis, None, None),
            }

            def _moe_body(mp, hh):
                y, aux_local = apply_moe(
                    mp, hh, cfg.moe,
                    model_axis=dist.model_axis,
                    model_axis_size=dist.model_axis_size,
                )
                if da:
                    aux_local = jax.lax.pmean(aux_local, da)
                return y, aux_local

            y, aux_l = jax.shard_map(
                _moe_body,
                mesh=dist.mesh,
                in_specs=(moe_specs, x_spec),
                out_specs=(x_spec, P()),
                check_vma=False,
            )(params["moe"], h)
        else:
            y, aux_l = apply_moe(params["moe"], h, cfg.moe)
        aux = aux + aux_l
        x = x + y
    return x, (new_cache or None), aux


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------


def _stack(trees: List[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_stack(key, cfg: ModelConfig, specs: List[LayerSpec], meta, dtype):
    """Returns ({"blocks": stacked, "rest": dict}, same-for-lora, period)."""
    p = find_period(specs)
    L = len(specs)
    n_blocks, n_rest = L // p, L % p
    keys = jax.random.split(key, L)
    blocks_p, blocks_l = [], []
    for b in range(n_blocks):
        bp, bl = {}, {}
        for i in range(p):
            lp, ll = init_layer(keys[b * p + i], cfg, specs[i], meta, dtype)
            bp[f"l{i}"] = lp
            if ll:
                bl[f"l{i}"] = ll
        blocks_p.append(bp)
        blocks_l.append(bl)
    rest_p, rest_l = {}, {}
    for i in range(n_rest):
        lp, ll = init_layer(keys[n_blocks * p + i], cfg, specs[i], meta, dtype)
        rest_p[f"l{i}"] = lp
        if ll:
            rest_l[f"l{i}"] = ll
    params = {"blocks": _stack(blocks_p) if n_blocks else {}, "rest": rest_p}
    lora = {"blocks": _stack(blocks_l) if (n_blocks and blocks_l[0]) else {}, "rest": rest_l}
    return params, lora, p


def apply_stack(
    params,
    lora,
    scales,
    x,
    cfg: ModelConfig,
    specs: List[LayerSpec],
    *,
    n_pack: int,
    rope_cache,
    dist,
    enc_out=None,
    caches=None,
    pos=None,
    make_cache: bool = False,
    chunk_q: int = 512,
    causal: bool = True,
    remat: bool = True,
    kcfg=None,
):
    """Run the whole stack. Returns (x, new_caches, total_aux)."""
    p = find_period(specs)
    L = len(specs)
    n_blocks, n_rest = L // p, L % p
    kw = dict(
        cfg=cfg, n_pack=n_pack, rope_cache=rope_cache, dist=dist,
        chunk_q=chunk_q, causal=causal, kcfg=kcfg,
    )

    def block_body(x, inp):
        bp, bl, bc = inp
        new_c = {}
        aux = jnp.zeros((), jnp.float32)
        if dist is not None:
            x = dist.residual_constraint(x)
        for i in range(p):
            x, c, a = apply_layer(
                bp[f"l{i}"], (bl or {}).get(f"l{i}"), scales, x, specs[i],
                enc_out=enc_out,
                cache=(bc or {}).get(f"l{i}") if bc is not None else None,
                pos=pos, make_cache=make_cache, **kw,
            )
            if c is not None:
                new_c[f"l{i}"] = c
            aux = aux + a
        return x, (new_c if (make_cache or caches is not None) else None, aux)

    body = jax.checkpoint(block_body) if remat else block_body
    total_aux = jnp.zeros((), jnp.float32)
    new_caches = {"blocks": None, "rest": {}}
    if n_blocks:
        bc = caches["blocks"] if caches is not None else None
        xs_in = (params["blocks"], lora.get("blocks") or None, bc)
        if caches is None:
            xs_in = (params["blocks"], lora.get("blocks") or None, None)
            x, (cs, auxs) = jax.lax.scan(
                lambda xx, inp: body(xx, (inp[0], inp[1], None)),
                x,
                (params["blocks"], _none_like(lora.get("blocks"))),
            )
        else:
            x, (cs, auxs) = jax.lax.scan(
                lambda xx, inp: body(xx, inp),
                x,
                (params["blocks"], _none_like(lora.get("blocks")), bc),
            )
        new_caches["blocks"] = cs
        total_aux = total_aux + auxs.sum()
    for i in range(n_rest):
        x, c, a = apply_layer(
            params["rest"][f"l{i}"], (lora.get("rest") or {}).get(f"l{i}"),
            scales, x, specs[i], enc_out=enc_out,
            cache=(caches["rest"].get(f"l{i}") if caches is not None else None),
            pos=pos, make_cache=make_cache, **kw,
        )
        if c is not None:
            new_caches["rest"][f"l{i}"] = c
        total_aux = total_aux + a
    return x, new_caches, total_aux


def _none_like(tree):
    """scan can't take None as an xs leaf container mismatch; use {} for
    'no lora' so tree structure is consistent."""
    return tree if tree else {}


def make_rope_cache(cfg: ModelConfig, positions: jnp.ndarray):
    """Precompute cos/sin per distinct theta; rope dim depends on attn kind."""
    a = cfg.attention
    dim = a.qk_rope_head_dim if a.is_mla else a.head_dim
    thetas = {s.theta for s in layer_specs(cfg) if s.mixer == "attn"}
    if not thetas:
        thetas = {a.rope_theta}
    return {t: rope_tables(positions, dim, t) for t in thetas}


def init_stack_cache(cfg, specs, nb: int, smax: int, dtype=jnp.bfloat16):
    """Cache pytree matching apply_stack(caches=...) structure."""
    a = cfg.attention
    p = find_period(specs)
    L = len(specs)
    n_blocks, n_rest = L // p, L % p

    def one(spec: LayerSpec):
        c = {}
        if spec.mixer == "ssm":
            c["ssm"] = init_ssm_cache(nb, cfg.d_model, cfg.ssm, jnp.float32)
        else:
            c["attn"] = (
                init_mla_cache(nb, smax, a, dtype)
                if a.is_mla
                else init_gqa_cache(nb, smax, a, dtype)
            )
        if spec.cross:
            kv, hd = a.n_kv_heads, a.head_dim
            c["cross_kv"] = {
                "k": jnp.zeros((nb, cfg.encoder_seq_len, kv, hd), dtype),
                "v": jnp.zeros((nb, cfg.encoder_seq_len, kv, hd), dtype),
            }
        return c

    blocks = [
        {f"l{i}": one(specs[i]) for i in range(p)} for _ in range(n_blocks)
    ]
    return {
        "blocks": _stack(blocks) if n_blocks else None,
        "rest": {f"l{i}": one(specs[i]) for i in range(n_rest)},
    }
