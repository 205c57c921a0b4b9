"""Attention: GQA (with sliding window) and MLA, prefill/train + cached decode.

Memory-safe attention for long sequences: the scores matrix is never
materialized for the full sequence — we scan over query chunks (flash-style)
with the chunk body rematerialized in the backward pass. When a *static*
sliding window is set, key/value are sliced to the reachable band per query
chunk, so local-attention layers (Gemma-3) get sub-quadratic FLOPs, not just
masking.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.core.adapter import PackMeta, init_lora_pair
from repro.core.packed_lora import lora_linear
from repro.models.layers.common import init_linear
from repro.models.layers.rope import apply_rope, rope_tables, yarn_mscale

# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _attend_chunk(q, k, v, qpos, kpos, causal, window, scale):
    """q: (B, cq, H, D); k/v: (B, Sk, KV, Dk/Dv); returns (B, cq, H, Dv)."""
    b, cq, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, cq, kv, g, d)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    mask = jnp.ones((cq, k.shape[1]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(b, cq, h, v.shape[-1])


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    chunk_q: int = 512,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Chunked attention. q: (B, Sq, H, D); k/v: (B, Sk, KV, D*).

    Scans over query chunks; with a static ``window`` the K/V band is sliced
    per chunk (sub-quadratic local attention). Bodies are ``jax.checkpoint``ed
    so the backward pass re-materializes per-chunk scores.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else d**-0.5
    kpos = jnp.arange(sk)

    if sq <= chunk_q:
        qpos = q_offset + jnp.arange(sq)
        return _attend_chunk(q, k, v, qpos, kpos, causal, window, scale)

    # pad queries to a chunk multiple (padding rows are sliced off at the
    # end; they never influence real outputs)
    sq_pad = (-sq) % chunk_q
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0), (0, 0)))
    sq_p = sq + sq_pad
    n_chunks = sq_p // chunk_q
    qc = q.reshape(b, n_chunks, chunk_q, h, d)

    if window and causal:
        # band slice: queries in chunk c reach keys in
        # [c*chunk_q - window + 1, c*chunk_q + chunk_q). Pad K/V on the left
        # so every chunk reads a fixed-size band of length window+chunk_q.
        band = window + chunk_q
        pad = window
        kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        kpos_p = jnp.concatenate([jnp.full((pad,), -(10**9)), kpos])

        @jax.checkpoint
        def body(_, c):
            start = c * chunk_q  # band start in padded coords
            kb = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=1)
            kpb = jax.lax.dynamic_slice_in_dim(kpos_p, start, band, axis=0)
            qpos = q_offset + c * chunk_q + jnp.arange(chunk_q)
            o = _attend_chunk(qc[:, c], kb, vb, qpos, kpb, causal, window, scale)
            return None, o

        _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks))
    else:

        @jax.checkpoint
        def body(_, c):
            qpos = q_offset + c * chunk_q + jnp.arange(chunk_q)
            o = _attend_chunk(qc[:, c], k, v, qpos, kpos, causal, window, scale)
            return None, o

        _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks))
    # outs: (n_chunks, B, cq, H, D) -> (B, Sq, H, D)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq_p, h, v.shape[-1])
    return out[:, :sq] if sq_pad else out


def decode_attention(q, k, v, pos, *, window: int = 0, scale=None):
    """Single-step attention against a cache. q: (B, 1, H, D);
    k/v: (B, Smax, KV, D*); pos: () shared position, or (B,) per-row
    positions (continuous batching — each row is its own request). Cache
    entries beyond a row's position are masked."""
    b, _, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = scale if scale is not None else d**-0.5
    kpos = jnp.arange(k.shape[1])
    qg = q.reshape(b, kv, g, d)
    scores = jnp.einsum(
        "bhgd,bkhd->bhgk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if jnp.ndim(pos) > 0:
        mask = kpos[None, :] <= pos[:, None]  # (B, Smax)
        if window:
            mask &= (pos[:, None] - kpos[None, :]) < window
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    else:
        mask = kpos <= pos
        if window:
            mask &= (pos - kpos) < window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v.dtype), v)
    return out.reshape(b, 1, h, v.shape[-1])


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------


def init_gqa(key, acfg: AttentionConfig, d_model, meta, targets, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    h, kv, hd = acfg.n_heads, acfg.n_kv_heads, acfg.head_dim
    params = {
        "q": init_linear(ks[0], d_model, h * hd, acfg.use_bias, dtype),
        "k": init_linear(ks[1], d_model, kv * hd, acfg.use_bias, dtype),
        "v": init_linear(ks[2], d_model, kv * hd, acfg.use_bias, dtype),
        "o": init_linear(ks[3], h * hd, d_model, False, dtype),
    }
    lora = {}
    if meta is not None:
        for i, nm in enumerate(("q", "k", "v", "o")):
            if nm in targets:
                d_in, d_out = params[nm]["w"].shape
                lora[nm] = init_lora_pair(ks[4 + i], meta, d_in, d_out, dtype)
    return params, lora


def apply_gqa(
    params,
    lora,
    scales,
    x,
    *,
    acfg: AttentionConfig,
    n_pack: int,
    rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
    window: int = 0,
    causal: bool = True,
    cache: Optional[dict] = None,
    pos=None,
    cross_kv: Optional[dict] = None,
    make_cache: bool = False,
    chunk_q: int = 512,
    kcfg=None,
):
    """x: (NB, S, d). Returns (out, new_cache_or_None)."""
    lo = lora or {}
    nb, s, _ = x.shape
    h, kvh, hd = acfg.n_heads, acfg.n_kv_heads, acfg.head_dim
    q = lora_linear(x, params["q"], lo.get("q"), scales, n_pack, kcfg=kcfg).reshape(nb, s, h, hd)

    if cross_kv is not None:
        k, v = cross_kv["k"], cross_kv["v"]
        out = flash_attention(q, k, v, causal=False, chunk_q=chunk_q)
        new_cache = None
    else:
        k = lora_linear(x, params["k"], lo.get("k"), scales, n_pack, kcfg=kcfg)
        v = lora_linear(x, params["v"], lo.get("v"), scales, n_pack, kcfg=kcfg)
        k = k.reshape(nb, s, kvh, hd)
        v = v.reshape(nb, s, kvh, hd)
        if rope is not None:
            cos, sin = rope
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if cache is not None:
            # decode / chunked prefill: write this step's (or chunk's) k/v at
            # `pos`, attend to <= pos. Vector pos = per-row positions: each
            # row writes at its own slot (single-token decode only).
            if jnp.ndim(pos) > 0:
                assert s == 1, "vector pos requires single-token decode"
                row_upd = jax.vmap(
                    lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0, 0))
                )
                ck = row_upd(cache["k"], k.astype(cache["k"].dtype), pos)
                cv = row_upd(cache["v"], v.astype(cache["v"].dtype), pos)
            else:
                ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
            if s == 1:
                out = decode_attention(q, ck, cv, pos, window=window)
            else:
                # chunk-resumable prefill: the chunk's queries attend the
                # whole cache under the same causal/window masks as the
                # one-shot path — with cache capacity == s_total the shapes
                # match flash_attention's single-chunk body exactly, so the
                # outputs are bitwise identical (tests/test_serve_engine.py)
                out = _attend_chunk(
                    q, ck, cv, pos + jnp.arange(s),
                    jnp.arange(ck.shape[1]), causal, window, hd**-0.5,
                )
            new_cache = {"k": ck, "v": cv}
        else:
            out = flash_attention(
                q, k, v, causal=causal, window=window, chunk_q=chunk_q
            )
            new_cache = {"k": k, "v": v} if make_cache else None

    out = out.reshape(nb, s, h * hd)
    out = lora_linear(out, params["o"], lo.get("o"), scales, n_pack, kcfg=kcfg)
    return out, new_cache


def init_gqa_cache(nb, smax, acfg: AttentionConfig, dtype=jnp.bfloat16):
    kv, hd = acfg.n_kv_heads, acfg.head_dim
    return {
        "k": jnp.zeros((nb, smax, kv, hd), dtype),
        "v": jnp.zeros((nb, smax, kv, hd), dtype),
    }


# ---------------------------------------------------------------------------
# MLA block (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------


def init_mla(key, acfg: AttentionConfig, d_model, meta, targets, dtype=jnp.float32):
    ks = jax.random.split(key, 10)
    h = acfg.n_heads
    qlr, kvlr = acfg.q_lora_rank, acfg.kv_lora_rank
    dn, dr, dv = acfg.qk_nope_head_dim, acfg.qk_rope_head_dim, acfg.v_head_dim
    if qlr:
        params = {
            "q_a": init_linear(ks[0], d_model, qlr, False, dtype),
            "q_norm": {"scale": jnp.ones((qlr,), dtype)},
            "q_b": init_linear(ks[1], qlr, h * (dn + dr), False, dtype),
        }
    else:  # direct q projection (DeepSeek-V2-Lite)
        params = {"q": init_linear(ks[0], d_model, h * (dn + dr), False, dtype)}
    params.update({
        "kv_a": init_linear(ks[2], d_model, kvlr + dr, False, dtype),
        "kv_norm": {"scale": jnp.ones((kvlr,), dtype)},
        "kv_b_k": init_linear(ks[3], kvlr, h * dn, False, dtype),
        "kv_b_v": init_linear(ks[4], kvlr, h * dv, False, dtype),
        "o": init_linear(ks[5], h * dv, d_model, False, dtype),
    })
    lora = {}
    if meta is not None:
        tmap = {"q": "q_a" if qlr else "q", "kv": "kv_a", "o": "o"}
        for i, (t, pname) in enumerate(tmap.items()):
            if t in targets:
                d_in, d_out = params[pname]["w"].shape
                lora[pname] = init_lora_pair(ks[6 + i], meta, d_in, d_out, dtype)
    return params, lora


def mla_softmax_scale(acfg: AttentionConfig) -> float:
    """``(nope + rope)^-0.5``, times YaRN's ``mscale(mscale_all_dim)^2``
    where the rope is YaRN-scaled (DeepSeek-V2)."""
    scale = (acfg.qk_nope_head_dim + acfg.qk_rope_head_dim) ** -0.5
    if acfg.yarn_factor and acfg.yarn_mscale_all_dim:
        m = yarn_mscale(acfg.yarn_factor, acfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def _mla_qkv(params, lo, scales, x, n_pack, acfg, rope, kcfg=None):
    """Shared projections for the MLA train/prefill path."""
    from repro.models.layers.common import apply_norm

    nb, s, _ = x.shape
    h = acfg.n_heads
    dn, dr, dv = acfg.qk_nope_head_dim, acfg.qk_rope_head_dim, acfg.v_head_dim
    cos, sin = rope
    if "q" in params:
        q = lora_linear(x, params["q"], lo.get("q"), scales, n_pack, kcfg=kcfg)
    else:
        cq = lora_linear(x, params["q_a"], lo.get("q_a"), scales, n_pack, kcfg=kcfg)
        cq = apply_norm(params["q_norm"], cq, "rmsnorm")
        q = lora_linear(cq, params["q_b"], None, scales, n_pack, kcfg=kcfg)
    q = q.reshape(nb, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, cos, sin)

    ckv_full = lora_linear(x, params["kv_a"], lo.get("kv_a"), scales, n_pack, kcfg=kcfg)
    ckv, k_rope = ckv_full[..., : acfg.kv_lora_rank], ckv_full[..., acfg.kv_lora_rank :]
    ckv = apply_norm(params["kv_norm"], ckv, "rmsnorm")
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)  # (NB,S,1,dr)
    return q_nope, q_rope, ckv, k_rope


def apply_mla(
    params,
    lora,
    scales,
    x,
    *,
    acfg: AttentionConfig,
    n_pack: int,
    rope,
    cache: Optional[dict] = None,
    pos=None,
    make_cache: bool = False,
    chunk_q: int = 512,
    kcfg=None,
):
    lo = lora or {}
    nb, s, _ = x.shape
    h = acfg.n_heads
    dn, dr, dv = acfg.qk_nope_head_dim, acfg.qk_rope_head_dim, acfg.v_head_dim
    scale = mla_softmax_scale(acfg)
    q_nope, q_rope, ckv, k_rope = _mla_qkv(params, lo, scales, x, n_pack, acfg, rope, kcfg)

    if cache is None:
        # train/prefill: expand compressed KV to per-head K/V
        with jax.named_scope("mla.attend"):
            k_nope = (ckv @ params["kv_b_k"]["w"].astype(ckv.dtype)).reshape(nb, s, h, dn)
            v = (ckv @ params["kv_b_v"]["w"].astype(ckv.dtype)).reshape(nb, s, h, dv)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (nb, s, h, dr))], -1)
            q = jnp.concatenate([q_nope, q_rope], -1)
            out = flash_attention(q, k, v, causal=True, chunk_q=chunk_q, scale=scale)
        new_cache = {"ckv": ckv, "k_rope": k_rope[:, :, 0, :]} if make_cache else None
    else:
        # absorbed decode: score against the compressed cache directly.
        # Vector pos = per-row positions (continuous batching).
        if jnp.ndim(pos) > 0:
            assert s == 1, "vector pos requires single-token decode"
            row_upd = jax.vmap(
                lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0))
            )
            ckv_c = row_upd(cache["ckv"], ckv.astype(cache["ckv"].dtype), pos)
            kr_c = row_upd(
                cache["k_rope"],
                k_rope[:, :, 0, :].astype(cache["k_rope"].dtype), pos,
            )
        else:
            ckv_c = jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0)
            )
            kr_c = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope[:, :, 0, :].astype(cache["k_rope"].dtype), (0, pos, 0)
            )
        if s > 1:
            # chunk-resumable prefill: the absorbed decode formulation is
            # algebraically but NOT bitwise equal to the prefill path, so
            # chunks expand the full compressed cache through kv_b_k/kv_b_v
            # exactly like the train/prefill branch — with cache capacity ==
            # s_total every shape (and therefore every bit) matches one-shot
            sc = ckv_c.shape[1]
            k_nope = (
                ckv_c @ params["kv_b_k"]["w"].astype(ckv_c.dtype)
            ).reshape(nb, sc, h, dn)
            vc = (
                ckv_c @ params["kv_b_v"]["w"].astype(ckv_c.dtype)
            ).reshape(nb, sc, h, dv)
            kc = jnp.concatenate(
                [k_nope, jnp.broadcast_to(kr_c[:, :, None, :], (nb, sc, h, dr))],
                -1,
            )
            q = jnp.concatenate([q_nope, q_rope], -1)
            out = _attend_chunk(
                q, kc, vc, pos + jnp.arange(s), jnp.arange(sc),
                True, 0, scale,
            )
            out = lora_linear(
                out.reshape(nb, s, h * dv), params["o"], lo.get("o"),
                scales, n_pack, kcfg=kcfg,
            )
            return out, {"ckv": ckv_c, "k_rope": kr_c}
        wk = params["kv_b_k"]["w"].reshape(acfg.kv_lora_rank, h, dn)
        # absorb W_uk into q: (NB,1,H,dn) x (kvlr,H,dn) -> (NB,H,kvlr)
        q_abs = jnp.einsum("bshd,rhd->bhr", q_nope, wk.astype(q_nope.dtype))
        s1 = jnp.einsum(
            "bhr,bkr->bhk", q_abs, ckv_c.astype(q_abs.dtype),
            preferred_element_type=jnp.float32,
        )
        s2 = jnp.einsum(
            "bshd,bkd->bhk", q_rope, kr_c.astype(q_rope.dtype),
            preferred_element_type=jnp.float32,
        )
        scores = (s1 + s2) * scale
        kpos = jnp.arange(ckv_c.shape[1])
        if jnp.ndim(pos) > 0:
            scores = jnp.where(
                (kpos[None, :] <= pos[:, None])[:, None, :], scores, -1e30
            )
        else:
            scores = jnp.where((kpos <= pos)[None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        # attend in compressed space then expand through W_uv
        ctx = jnp.einsum("bhk,bkr->bhr", p.astype(ckv_c.dtype), ckv_c)
        wv = params["kv_b_v"]["w"].reshape(acfg.kv_lora_rank, h, dv)
        out = jnp.einsum("bhr,rhd->bhd", ctx, wv.astype(ctx.dtype))[:, None]
        new_cache = {"ckv": ckv_c, "k_rope": kr_c}

    out = out.reshape(nb, s, h * dv)
    out = lora_linear(out, params["o"], lo.get("o"), scales, n_pack, kcfg=kcfg)
    return out, new_cache


def init_mla_cache(nb, smax, acfg: AttentionConfig, dtype=jnp.bfloat16):
    return {
        "ckv": jnp.zeros((nb, smax, acfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((nb, smax, acfg.qk_rope_head_dim), dtype),
    }
