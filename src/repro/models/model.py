"""Top-level model: embeddings, (optional) encoder, decoder stack, LM head.

Public API (all functional, params are plain pytrees):

  init_model(key, cfg, meta, dtype)          -> (base_params, lora_params)
  init_lora(key, cfg, meta, dtype)           -> lora_params (no base built)
  forward(base, lora, scales, batch, cfg, .) -> (hidden (NB,S,d), aux)
  logits(base, hidden, cfg)                  -> (NB,S,V)   [small seqs only]
  init_caches(cfg, nb, smax)                 -> cache pytree
  prefill(...)                               -> (hidden, caches, aux)
  decode_step(...)                           -> (logits (NB,1,V), caches)

The pack dim N is folded into the leading batch: every tensor is (N*B, ...).
Modality frontends are stubs per the assignment: audio/vlm batches carry
precomputed frame/patch embeddings ("frames"/"patches").
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.adapter import PackMeta
from repro.models.layers.common import apply_norm, init_linear, init_norm
from repro.models.transformer import (
    DistContext,
    LayerSpec,
    apply_stack,
    init_stack,
    init_stack_cache,
    layer_specs,
    make_rope_cache,
)


def encoder_specs(cfg: ModelConfig):
    return [
        LayerSpec(mixer="attn", ffn="dense", theta=cfg.attention.rope_theta)
        for _ in range(cfg.encoder_layers)
    ]


def init_model(key, cfg: ModelConfig, meta: Optional[PackMeta], dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    base: Dict[str, Any] = {
        "embed": {"w": jax.random.normal(ks[0], (cfg.padded_vocab, cfg.d_model), dtype) * 0.02},
        "final_norm": init_norm(cfg.d_model, cfg.norm_kind, dtype),
    }
    lora: Dict[str, Any] = {}
    dec_p, dec_l, _ = init_stack(ks[1], cfg, layer_specs(cfg), meta, dtype)
    base["decoder"] = dec_p
    lora["decoder"] = dec_l
    if not cfg.tie_embeddings:
        base["lm_head"] = init_linear(ks[2], cfg.d_model, cfg.padded_vocab, False, dtype)
    if cfg.is_encdec:
        enc_p, enc_l, _ = init_stack(ks[3], cfg, encoder_specs(cfg), meta, dtype)
        base["encoder"] = enc_p
        base["enc_norm"] = init_norm(cfg.d_model, cfg.norm_kind, dtype)
        lora["encoder"] = enc_l
    if cfg.n_patch_tokens:
        base["patch_proj"] = init_linear(ks[4], cfg.d_model, cfg.d_model, True, dtype)
    return base, lora


def init_lora(key, cfg: ModelConfig, meta: PackMeta, dtype=jnp.float32):
    """The adapter tree of ``init_model``, bit for bit, without building the
    base: the init runs under ``jit`` with the base output discarded, so XLA
    drops its computation. At published widths that base is gigabytes of
    temporaries."""
    return jax.jit(lambda k: init_model(k, cfg, meta, dtype)[1])(key)


def _embed(base, tokens, cfg, batch):
    x = jnp.take(base["embed"]["w"], tokens, axis=0)
    if cfg.n_patch_tokens and "patches" in batch:
        pp = base["patch_proj"]
        pe = batch["patches"].astype(x.dtype) @ pp["w"].astype(x.dtype) + pp["b"].astype(x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
    return x


def _encode(base, lora, scales, frames, cfg, *, n_pack, dist, chunk_q, kcfg=None):
    """Whisper encoder over precomputed frame embeddings (B, S_enc, d)."""
    espec = encoder_specs(cfg)
    pos = jnp.arange(frames.shape[1])
    rc = make_rope_cache(cfg, pos)
    h, _, _ = apply_stack(
        base["encoder"], lora.get("encoder", {"blocks": {}, "rest": {}}),
        scales, frames, cfg, espec,
        n_pack=n_pack, rope_cache=rc, dist=dist, chunk_q=chunk_q, causal=False,
        kcfg=kcfg,
    )
    return apply_norm(base["enc_norm"], h, cfg.norm_kind)


def forward(
    base,
    lora,
    scales,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    *,
    n_pack: int = 1,
    dist: Optional[DistContext] = None,
    chunk_q: int = 512,
    make_cache: bool = False,
    kcfg=None,
):
    """batch: {"tokens": (NB, S)[, "frames": (NB,Se,d)][, "patches": (NB,P,d)]}.
    Returns (hidden (NB, S_total, d), caches|None, aux). ``kcfg`` is the
    static kernel policy (impl / remat / pack rank vector) every
    ``lora_linear`` below runs under."""
    tokens = batch["tokens"]
    x = _embed(base, tokens, cfg, batch)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(
            base, lora, scales, batch["frames"].astype(x.dtype), cfg,
            n_pack=n_pack, dist=dist, chunk_q=chunk_q, kcfg=kcfg,
        )
    s_total = x.shape[1]
    positions = jnp.arange(s_total)
    rc = make_rope_cache(cfg, positions)
    specs = layer_specs(cfg)
    x, caches, aux = apply_stack(
        base["decoder"], lora.get("decoder", {"blocks": {}, "rest": {}}),
        scales, x, cfg, specs,
        n_pack=n_pack, rope_cache=rc, dist=dist, enc_out=enc_out,
        make_cache=make_cache, chunk_q=chunk_q, kcfg=kcfg,
    )
    x = apply_norm(base["final_norm"], x, cfg.norm_kind)
    return x, (caches if make_cache else None), aux


def unembed_w(base, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return base["embed"]["w"].T  # (d, V)
    return base["lm_head"]["w"]


def logits(base, hidden, cfg: ModelConfig):
    """(NB, S, padded_vocab); padded columns masked to -inf."""
    lg = hidden @ unembed_w(base, cfg).astype(hidden.dtype)
    if cfg.padded_vocab != cfg.vocab_size:
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        lg = jnp.where(mask, lg, -1e30)
    return lg


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, nb: int, smax: int, dtype=jnp.bfloat16):
    return init_stack_cache(cfg, layer_specs(cfg), nb, smax, dtype)


def decode_step(
    base,
    lora,
    scales,
    token: jnp.ndarray,  # (NB, 1) int32
    caches,
    pos,  # () int32 shared, or (NB,) int32 per-row write/attend positions
    cfg: ModelConfig,
    *,
    n_pack: int = 1,
    dist: Optional[DistContext] = None,
    enc_out=None,
    kcfg=None,
):
    """One serve step: embed token at `pos`, run stack against caches,
    return (logits (NB, 1, V), new_caches). For enc-dec models the cached
    cross-KV is used unless `enc_out` is passed explicitly."""
    x = jnp.take(base["embed"]["w"], token, axis=0)
    # scalar pos -> shared (1, D/2) tables; vector pos (NB,) -> per-row
    # (NB, 1, D/2) tables (apply_rope's per-example decode layout). A flat
    # (NB,) argument would build (NB, D/2) tables that broadcast wrongly.
    rc = make_rope_cache(cfg, pos[None] if jnp.ndim(pos) == 0 else pos[:, None])
    specs = layer_specs(cfg)
    x, new_caches, _ = apply_stack(
        base["decoder"], lora.get("decoder", {"blocks": {}, "rest": {}}),
        scales, x, cfg, specs,
        n_pack=n_pack, rope_cache=rc, dist=dist, enc_out=enc_out,
        caches=caches, pos=pos, remat=False, kcfg=kcfg,
    )
    x = apply_norm(base["final_norm"], x, cfg.norm_kind)
    return logits(base, x, cfg), new_caches


def prefill(
    base, lora, scales, batch, cfg: ModelConfig, *,
    n_pack: int = 1, dist=None, chunk_q: int = 512, kcfg=None,
):
    """Full-sequence forward that also returns the KV caches (inference
    prefill). Returns (last-position logits (NB,1,V), caches)."""
    hidden, caches, _ = forward(
        base, lora, scales, batch, cfg,
        n_pack=n_pack, dist=dist, chunk_q=chunk_q, make_cache=True,
        kcfg=kcfg,
    )
    lg = logits(base, hidden[:, -1:, :], cfg)
    return lg, caches


def prefill_chunk(
    base,
    lora,
    scales,
    tokens: jnp.ndarray,  # (NB, C) int32 — one chunk of the prompt
    caches,
    pos,  # () int32: absolute position of the chunk's first token
    cfg: ModelConfig,
    *,
    n_pack: int = 1,
    dist: Optional[DistContext] = None,
    kcfg=None,
):
    """One chunk of a chunk-resumable prefill: embed ``tokens`` at absolute
    positions ``pos + [0, C)``, run the stack against partially-filled
    ``caches`` (attention writes the chunk's K/V at ``pos`` and attends the
    whole cache under the causal/window masks; SSM resumes conv window +
    SSD state), and return (last-position logits (NB,1,V), new_caches).

    With cache capacity exactly equal to the prompt length, iterating this
    over consecutive chunks reproduces ``prefill``'s caches and final-token
    logits *bitwise* — the serve engine's interleaved-admission invariant
    (chunk boundaries commute with causal attention; for SSM stacks ``pos``
    must advance in multiples of ``cfg.ssm.chunk_size``). Encoder-decoder
    and patch-prefix (VLM) configs still require one-shot ``prefill``."""
    assert not cfg.is_encdec, "chunked prefill: enc-dec needs one-shot prefill"
    s = tokens.shape[1]
    x = jnp.take(base["embed"]["w"], tokens, axis=0)
    rc = make_rope_cache(cfg, pos + jnp.arange(s))
    specs = layer_specs(cfg)
    x, new_caches, _ = apply_stack(
        base["decoder"], lora.get("decoder", {"blocks": {}, "rest": {}}),
        scales, x, cfg, specs,
        n_pack=n_pack, rope_cache=rc, dist=dist,
        caches=caches, pos=pos, remat=False, kcfg=kcfg,
    )
    x = apply_norm(base["final_norm"], x, cfg.norm_kind)
    return logits(base, x[:, -1:, :], cfg), new_caches
