"""A dense decoder of one repeated layer (Qwen2.5, StarCoder2): q/k/v/o
attention and a SwiGLU or two-projection GELU MLP, with LoRA on the
projections the file names. The contract is in ``bench/models/__init__.py``.

Weights are bf16, the type the base is served in, made on the device by one
jitted call. Layer weights are stacked over layers: ``q_w`` is
``(L, d, H * hd)``.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp


def spec(cfg_file: dict) -> dict:
    """The sizes and kinds of a configuration file, under short names."""
    out = {
        "d": cfg_file["hidden_size"],
        "H": cfg_file["num_attention_heads"],
        "KV": cfg_file["num_key_value_heads"],
        "hd": cfg_file["head_dim"],
        "F": cfg_file["intermediate_size"],
        "L": cfg_file["num_hidden_layers"],
        "V": cfg_file["vocab_size"],
        "theta": float(cfg_file["rope_theta"]),
        "eps": float(cfg_file["norm_eps_as_run"]),
        "norm": cfg_file["norm"],
        "mlp": cfg_file["mlp"],
        "biases": tuple(cfg_file["biases"]),
        "targets": tuple(cfg_file["lora_targets"]),
    }
    if out["norm"] not in ("rmsnorm", "layernorm"):
        raise ValueError(f"unknown norm {out['norm']!r}")
    if out["mlp"] not in ("swiglu", "gelu2"):
        raise ValueError(f"unknown mlp {out['mlp']!r}")
    return out


def projections(spec: dict) -> dict:
    """Every projection of a layer: name -> (d_in, d_out)."""
    d, H, KV, hd, F = spec["d"], spec["H"], spec["KV"], spec["hd"], spec["F"]
    out = {"q": (d, H * hd), "k": (d, KV * hd), "v": (d, KV * hd),
           "o": (H * hd, d)}
    if spec["mlp"] == "swiglu":
        out["gate"] = (d, F)
    out["up"] = (d, F)
    out["down"] = (F, d)
    return out


def lora_projections(spec: dict) -> dict:
    return {k: v for k, v in projections(spec).items() if k in spec["targets"]}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _key(root, name: str):
    return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _shapes(spec: dict) -> dict:
    """name -> (shape, kind) of every weight; kinds set the distribution."""
    d, L, V = spec["d"], spec["L"], spec["V"]
    out = {"embed": ((V, d), "embed"), "lm_head": ((d, V), "proj"),
           "final_norm_scale": ((d,), "scale"),
           "norm1_scale": ((L, d), "scale"), "norm2_scale": ((L, d), "scale")}
    if spec["norm"] == "layernorm":
        for n in ("final_norm", "norm1", "norm2"):
            shape = (d,) if n == "final_norm" else (L, d)
            out[f"{n}_bias"] = (shape, "shift")
    for name, (d_in, d_out) in projections(spec).items():
        out[f"{name}_w"] = ((L, d_in, d_out), "proj")
        if name in spec["biases"]:
            out[f"{name}_b"] = ((L, d_out), "bias")
    return out


def _make(seed, spec):
    root = jax.random.PRNGKey(seed)
    out = {}
    for name, (shape, kind) in _shapes(spec).items():
        z = jax.random.normal(_key(root, name), shape, jnp.float32)
        if kind == "proj":
            w = z * (shape[-2] ** -0.5)
        elif kind == "embed":
            w = z * 0.02
        elif kind == "scale":
            w = 1.0 + 0.1 * z
        elif kind == "shift":
            w = 0.1 * z
        else:  # bias
            w = 0.02 * z
        out[name] = w.astype(jnp.bfloat16)
    return out


@functools.lru_cache(maxsize=None)
def _maker(spec_items):
    spec = dict(spec_items)
    return jax.jit(lambda seed: _make(seed, spec))


def make_weights(seed: int, spec: dict, device=None):
    """All base weights, bf16, on ``device`` (the default one when None)."""
    items = tuple((k, v) for k, v in sorted(spec.items()))
    key = jnp.asarray(seed % (2**31 - 1), jnp.int32)
    if device is not None:
        key = jax.device_put(key, device)
    return _maker(items)(key)


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def program_config(cfg_file: dict):
    """The program's ModelConfig with every size taken from the file."""
    from repro.configs.base import get_config

    cfg = get_config(cfg_file["arch"])
    attn = dataclasses.replace(
        cfg.attention,
        n_heads=cfg_file["num_attention_heads"],
        n_kv_heads=cfg_file["num_key_value_heads"],
        head_dim=cfg_file["head_dim"],
        rope_theta=float(cfg_file["rope_theta"]),
    )
    cfg = cfg.replace(
        n_layers=cfg_file["num_hidden_layers"],
        d_model=cfg_file["hidden_size"],
        d_ff=cfg_file["intermediate_size"],
        vocab_size=cfg_file["vocab_size"],
        attention=attn,
    )
    want = {"norm_kind": cfg_file["norm"], "mlp_kind": cfg_file["mlp"],
            "tie_embeddings": False,
            "lora_targets": tuple(cfg_file["lora_targets"])}
    # the program puts an adapter on each target its layers have
    has = ("q", "k", "v", "o") + (("up", "down") if cfg.mlp_kind == "gelu2"
                                  else ("gate", "up", "down"))
    got = {"norm_kind": cfg.norm_kind, "mlp_kind": cfg.mlp_kind,
           "tie_embeddings": cfg.tie_embeddings,
           "lora_targets": tuple(t for t in cfg.lora_targets if t in has)}
    if want != got:
        raise ValueError(f"{cfg_file['name']}: the program's {cfg.name} runs "
                         f"{got}, the file says {want}")
    return cfg


def base_tree(weights: dict, cfg):
    """The program's base-parameter tree, filled with the benchmark's
    weights. A bias the program carries and the architecture lacks is
    zero."""
    from repro.models.model import init_model

    shapes = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg, None, jnp.bfloat16)[0])
    blocks = shapes["decoder"]["blocks"]
    if set(blocks) != {"l0"} or shapes["decoder"]["rest"]:
        raise ValueError("the dense model maps stacks of one repeated layer "
                         "only")

    def name_of(path):
        keys = [getattr(k, "key", None) for k in path]
        if keys[:1] == ["embed"]:
            return "embed"
        if keys[:1] == ["lm_head"]:
            return "lm_head"
        if keys[0] == "final_norm":
            return f"final_norm_{keys[1]}"
        return f"{keys[-2]}_{keys[-1]}"  # norm1_scale, q_w, up_b, ...

    def fill(path, sds):
        name = name_of(path)
        if name in weights:
            w = weights[name]
            if w.shape != sds.shape:  # vocabulary padded to a 256 multiple
                pad = [(0, t - s) for s, t in zip(w.shape, sds.shape)]
                w = jnp.pad(w, pad)
            return w
        if name.endswith("_b") or name.endswith("_bias"):
            return jnp.zeros(sds.shape, sds.dtype)
        raise KeyError(f"no benchmark weight for the program's {name}")

    return jax.tree_util.tree_map_with_path(fill, shapes)


def adapter_norms(tree):
    """{proj: {a|b: (L, n)}} Frobenius norms of each layer's matrices of
    every adapter slot, from the program's packed adapter tree."""
    blocks = tree["decoder"]["blocks"]["l0"]
    out = {}
    for part in blocks.values():
        for proj, ab in part.items():
            out[proj] = {k: jnp.sqrt(jnp.sum(jnp.square(v), (2, 3)))
                         for k, v in ab.items()}
    return out
