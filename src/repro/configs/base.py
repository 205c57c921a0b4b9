"""Configuration system for repro: model architectures, input shapes, LoRA spaces.

Every assigned architecture gets one ``src/repro/configs/<id>.py`` that builds a
:class:`ModelConfig` with the exact published dimensions (source cited in the
module docstring), plus a ``reduced()`` variant used by CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Attention variants
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AttentionConfig:
    """Grouped-query attention; MLA and sliding-window are expressed on top."""

    n_heads: int = 8
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 10_000.0
    use_bias: bool = False
    causal: bool = True

    # Sliding-window attention (Gemma-3 style local layers). 0 = full.
    sliding_window: int = 0
    # Pattern of local:global layers, e.g. gemma3 = 6 (5 local + 1 global,
    # every 6th layer is global). 0 = all layers use `sliding_window` as-is.
    global_every: int = 0
    # rope theta used by "global" layers when global_every > 0
    global_rope_theta: float = 0.0

    # Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style). When
    # kv_lora_rank > 0 the layer uses MLA projections instead of plain GQA;
    # q_lora_rank 0 then means a direct q projection (d, H * (nope + rope)).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # YaRN rope scaling (DeepSeek-V2). factor 0 = plain rope.
    yarn_factor: float = 0.0
    yarn_original_max_positions: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    # the softmax scale's mscale; the cos/sin tables are left unscaled, as
    # they are where mscale equals it (DeepSeek-V2)
    yarn_mscale_all_dim: float = 0.0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0  # expert hidden (ffn) size
    # every `moe_every` layers the FFN is MoE (1 = all layers, 2 = alternating)
    moe_every: int = 1
    # the first `first_k_dense` layers keep a dense FFN (DeepSeek-V2's
    # first_k_dense_replace); the pattern above starts after them
    first_k_dense: int = 0
    # shared experts, run for every token as one SwiGLU of width
    # n_shared_experts * d_expert and added to the routed output
    n_shared_experts: int = 0
    # renormalise the top-k gates to sum to 1
    norm_topk_prob: bool = True
    # "dense" = all-experts einsum (exact, small-scale / oracle)
    # "ep"    = drop-free routed experts (grouped matmul over the routed
    #           rows); under a mesh each model-axis shard holds E / tp
    impl: str = "dense"

    @property
    def enabled(self) -> bool:
        return self.n_experts > 0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) block configuration [arXiv:2405.21060]."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk_size: int = 256

    @property
    def enabled(self) -> bool:
        return self.d_state > 0

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ModelConfig:
    """One architecture. `family` in {dense, moe, ssm, hybrid, audio, vlm}."""

    name: str
    family: str
    n_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attention: AttentionConfig = field(default_factory=AttentionConfig)
    moe: MoEConfig = field(default_factory=MoEConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)

    # Layer mixing for hybrids: an attention layer every `attn_every` layers
    # (jamba = 8: layers 3, 11, 19, 27 given offset). 0 = attn everywhere
    # (or nowhere if family == "ssm").
    attn_every: int = 0
    attn_offset: int = 3

    # MLP kind: "swiglu" | "gelu" (gelu implies the classic 2-matrix MLP)
    mlp_kind: str = "swiglu"
    norm_kind: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    tie_embeddings: bool = False
    max_seq_len: int = 131_072

    # Encoder-decoder (whisper): encoder consumes precomputed frame embeddings
    encoder_layers: int = 0
    encoder_seq_len: int = 0  # e.g. whisper 1500 frames

    # VLM: number of prefix patch-embedding positions supplied by the stub
    n_patch_tokens: int = 0

    # Which projections get LoRA adapters (see DESIGN.md §5)
    lora_targets: Tuple[str, ...] = ("q", "k", "v", "o", "gate", "up", "down")

    # long_500k applicability (sub-quadratic path exists)
    supports_long_context: bool = False
    citation: str = ""

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def padded_vocab(self) -> int:
        """Embedding/LM-head rows padded to a 256 multiple so vocab-parallel
        sharding divides the 16-way model axis (several published vocab
        sizes — 151655, 50280, 51865, 73448 — are odd). Padded logits are
        masked to -inf in the loss and at decode."""
        return (self.vocab_size + 255) // 256 * 256

    def layer_kinds(self) -> Tuple[str, ...]:
        """Mixer kind per decoder layer: 'attn' or 'ssm'."""
        kinds = []
        for i in range(self.n_layers):
            if self.family == "ssm":
                kinds.append("ssm")
            elif self.family == "hybrid":
                kinds.append(
                    "attn" if (i % self.attn_every) == self.attn_offset else "ssm"
                )
            else:
                kinds.append("attn")
        return tuple(kinds)

    def ffn_kinds(self) -> Tuple[str, ...]:
        """FFN kind per decoder layer: 'dense' | 'moe' | 'none'."""
        kinds = []
        for i in range(self.n_layers):
            if self.family == "ssm":
                kinds.append("none")  # mamba2 blocks have no separate FFN
            elif (self.moe.enabled and i >= self.moe.first_k_dense
                  and (i - self.moe.first_k_dense) % self.moe.moe_every
                  == self.moe.moe_every - 1):
                kinds.append("moe")
            else:
                kinds.append("dense")
        return tuple(kinds)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# LoRA hyperparameter space (paper Table 1)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LoraConfig:
    """One point of the hyperparameter search space (a 'LoRA configuration')."""

    rank: int = 8
    alpha: float = 8.0
    learning_rate: float = 1e-4
    batch_size: int = 1
    seq_len: int = 1024
    targets: Tuple[str, ...] = ("q", "k", "v", "o", "gate", "up", "down")

    def key(self) -> Tuple:
        return (self.rank, self.alpha, self.learning_rate, self.batch_size)


def default_search_space(n: int = 120, seq_len: int = 1024) -> list:
    """Grid over paper Table 1 ranges: LR 2e-5..4e-4, BS 1..32, r 8..128,
    alpha r/4..4r. Returns the first `n` points of a deterministic grid."""
    lrs = [2e-5, 6e-5, 1e-4, 2e-4, 4e-4]
    bss = [1, 2, 4, 8]
    ranks = [8, 16, 32, 64, 128]
    alpha_mult = [0.25, 1.0, 4.0]
    space = []
    for r in ranks:
        for lr in lrs:
            for bs in bss:
                for am in alpha_mult:
                    space.append(
                        LoraConfig(
                            rank=r,
                            alpha=am * r,
                            learning_rate=lr,
                            batch_size=bs,
                            seq_len=seq_len,
                        )
                    )
    return space[:n]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def reduced(cfg: ModelConfig, n_layers: int = 2, d_model: int = 256) -> ModelConfig:
    """Smoke-test variant of the same family: ≤2 layers (scaled so the layer
    pattern still contains every mixer/ffn kind), d_model ≤ 512, ≤4 experts."""
    attn = cfg.attention
    head_dim = 32
    n_heads = max(2, min(4, attn.n_heads))
    n_kv = max(1, min(n_heads, attn.n_kv_heads))
    while n_heads % n_kv:
        n_kv -= 1
    new_attn = dataclasses.replace(
        attn,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=head_dim,
        sliding_window=min(attn.sliding_window, 64) if attn.sliding_window else 0,
        q_lora_rank=48 if attn.q_lora_rank else 0,
        kv_lora_rank=32 if attn.kv_lora_rank else 0,
        qk_nope_head_dim=16 if attn.is_mla else 0,
        qk_rope_head_dim=16 if attn.is_mla else 0,
        v_head_dim=32 if attn.is_mla else 0,
    )
    moe = cfg.moe
    if moe.enabled:
        moe = dataclasses.replace(
            moe, n_experts=4, top_k=min(2, moe.top_k), d_expert=64,
        )
    ssm = cfg.ssm
    if ssm.enabled:
        ssm = dataclasses.replace(ssm, d_state=16, head_dim=32, chunk_size=32)
    nl = n_layers
    if moe.first_k_dense:
        nl = max(nl, moe.first_k_dense + 2)  # the dense head + 2 MoE layers
    if cfg.family == "hybrid":
        # keep one attn + ssm layers; shrink the attn period instead
        nl = 4
        cfg = cfg.replace(attn_every=4, attn_offset=1)
    if attn.global_every:
        nl = max(nl, attn.global_every)  # keep one global layer in the pattern
        nl = min(nl, 6)
    enc_layers = 2 if cfg.encoder_layers else 0
    return cfg.replace(
        name=cfg.name + "-reduced",
        n_layers=nl,
        d_model=min(d_model, cfg.d_model),
        d_ff=min(384, cfg.d_ff) if cfg.d_ff else 0,
        vocab_size=512,
        attention=new_attn,
        moe=moe,
        ssm=ssm,
        encoder_layers=enc_layers,
        encoder_seq_len=32 if cfg.encoder_seq_len else 0,
        n_patch_tokens=8 if cfg.n_patch_tokens else 0,
        max_seq_len=512,
    )


_REGISTRY = {}


def register(cfg_fn):
    """Decorator: register `<module>.config()` under the arch id."""
    cfg = cfg_fn()
    _REGISTRY[cfg.name] = cfg_fn
    return cfg_fn


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs() -> list:
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded():
    global _LOADED
    if _LOADED:
        return
    # import all arch modules for registration side effects
    from repro.configs import (  # noqa: F401
        mamba2_370m,
        qwen3_moe_30b_a3b,
        whisper_tiny,
        minicpm3_4b,
        deepseek_v2_lite,
        gemma3_1b,
        command_r_35b,
        jamba_v01_52b,
        starcoder2_7b,
        grok_1_314b,
        internvl2_1b,
        qwen25_7b,
    )

    _LOADED = True
