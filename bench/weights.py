"""The frozen base's weights, made from the run's seed by the benchmark.

Both the system under test and the reference train on these arrays, so the
program is given its weights and the reference takes nothing that the
program made. Weights are bf16, the type the base is served in, and are made
on the device by one jitted call. Layer weights are stacked over layers:
``q_w`` is ``(L, d, H * hd)``.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp


def model_spec(cfg_file: dict) -> dict:
    """The sizes and kinds of a configuration file, under short names."""
    spec = {
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
    if spec["norm"] not in ("rmsnorm", "layernorm"):
        raise ValueError(f"unknown norm {spec['norm']!r}")
    if spec["mlp"] not in ("swiglu", "gelu2"):
        raise ValueError(f"unknown mlp {spec['mlp']!r}")
    return spec


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
