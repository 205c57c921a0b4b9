"""DeepSeek-V2 (V2-Lite): latent attention with a direct q projection and
YaRN rope in every layer; the first ``first_k_dense_replace`` layers have a
dense SwiGLU, the rest an MoE of routed experts (greedy top-k over a softmax)
and shared experts. LoRA sits on q, kv_a and o of every layer, on gate, up
and down of the dense layers and on the shared experts' gate, up and down;
the router, kv_b and the routed experts carry none. The contract is in
``bench/models/__init__.py``.

Weights are bf16, made on the device by one jitted call. A name's leading
axis counts the layers it is in: attention weights all ``L`` layers
(``q_w`` is ``(L, d, H * (nope + rope))``), ``gate_w`` the dense layers,
``router_w``, ``w_gate`` and ``shared_gate_w`` the MoE layers (``w_gate``
is ``(L_moe, E, d, F_expert)``).
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

# catalog keys (the published config.json) and the program's fields they set
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale_all_dim")


def spec(cfg_file: dict) -> dict:
    """The sizes and kinds of a configuration file, under short names."""
    rs = cfg_file["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError(f"rope_scaling {rs.get('type')!r} is not yarn")
    if cfg_file["q_lora_rank"] is not None:
        raise ValueError("the model maps a direct q projection only")
    if cfg_file["topk_method"] != "greedy" or cfg_file["scoring_func"] != "softmax":
        raise ValueError("the model maps greedy top-k over a softmax only")
    if cfg_file["moe_layer_freq"] != 1:
        raise ValueError("the model maps an MoE in every layer after the dense ones")
    if cfg_file["routed_scaling_factor"] != 1:
        raise ValueError("the model maps unscaled routed gates only")
    if rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("the model maps unscaled rope tables only "
                         "(mscale equal to mscale_all_dim)")
    L, n_dense = cfg_file["num_hidden_layers"], cfg_file["first_k_dense_replace"]
    return {
        "d": cfg_file["hidden_size"],
        "H": cfg_file["num_attention_heads"],
        "dn": cfg_file["qk_nope_head_dim"],
        "dr": cfg_file["qk_rope_head_dim"],
        "dv": cfg_file["v_head_dim"],
        "kvr": cfg_file["kv_lora_rank"],
        "F": cfg_file["intermediate_size"],
        "Fe": cfg_file["moe_intermediate_size"],
        "E": cfg_file["n_routed_experts"],
        "k": cfg_file["num_experts_per_tok"],
        "Fs": cfg_file["n_shared_experts"] * cfg_file["moe_intermediate_size"],
        "L": L,
        "L_dense": n_dense,
        "L_moe": L - n_dense,
        "V": cfg_file["vocab_size"],
        "theta": float(cfg_file["rope_theta"]),
        "yarn": tuple(float(rs[k]) for k in YARN_KEYS),
        "eps": float(cfg_file["rms_norm_eps"]),
        "norm_topk": bool(cfg_file["norm_topk_prob"]),
        "targets": tuple(cfg_file["lora_targets"]),
    }


def lora_shapes(spec: dict) -> dict:
    """Every adapted matrix: name -> (layers, d_in, d_out)."""
    d, H = spec["d"], spec["H"]
    out = {"q": (spec["L"], d, H * (spec["dn"] + spec["dr"])),
           "kv_a": (spec["L"], d, spec["kvr"] + spec["dr"]),
           "o": (spec["L"], H * spec["dv"], d)}
    for pre, n, f in (("", spec["L_dense"], spec["F"]),
                      ("shared_", spec["L_moe"], spec["Fs"])):
        out.update({f"{pre}gate": (n, d, f), f"{pre}up": (n, d, f),
                    f"{pre}down": (n, f, d)})
    return out


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _key(root, name: str):
    return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _shapes(spec: dict) -> dict:
    """name -> (shape, kind) of every weight; kinds set the distribution."""
    d, H, V, L = spec["d"], spec["H"], spec["V"], spec["L"]
    Ld, Lm, E = spec["L_dense"], spec["L_moe"], spec["E"]
    dn, dr, dv, kvr = spec["dn"], spec["dr"], spec["dv"], spec["kvr"]
    F, Fe, Fs = spec["F"], spec["Fe"], spec["Fs"]
    return {
        "embed": ((V, d), "embed"), "lm_head": ((d, V), "proj"),
        "final_norm_scale": ((d,), "scale"),
        "norm1_scale": ((L, d), "scale"), "norm2_scale": ((L, d), "scale"),
        "q_w": ((L, d, H * (dn + dr)), "proj"),
        "kv_a_w": ((L, d, kvr + dr), "proj"),
        "kv_norm_scale": ((L, kvr), "scale"),
        "kv_b_k_w": ((L, kvr, H * dn), "proj"),
        "kv_b_v_w": ((L, kvr, H * dv), "proj"),
        "o_w": ((L, H * dv, d), "proj"),
        "gate_w": ((Ld, d, F), "proj"), "up_w": ((Ld, d, F), "proj"),
        "down_w": ((Ld, F, d), "proj"),
        "router_w": ((Lm, d, E), "proj"),
        "w_gate": ((Lm, E, d, Fe), "proj"), "w_up": ((Lm, E, d, Fe), "proj"),
        "w_down": ((Lm, E, Fe, d), "proj"),
        "shared_gate_w": ((Lm, d, Fs), "proj"),
        "shared_up_w": ((Lm, d, Fs), "proj"),
        "shared_down_w": ((Lm, Fs, d), "proj"),
    }


def _make(seed, spec):
    root = jax.random.PRNGKey(seed)
    out = {}
    for name, (shape, kind) in _shapes(spec).items():
        z = jax.random.normal(_key(root, name), shape, jnp.float32)
        if kind == "proj":
            w = z * (shape[-2] ** -0.5)
        elif kind == "embed":
            w = z * 0.02
        else:  # scale
            w = 1.0 + 0.1 * z
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
    """The program's ModelConfig with every size taken from the file;
    raises where the program would run something else."""
    import dataclasses

    from repro.configs.base import get_config

    s = spec(cfg_file)
    cfg = get_config(cfg_file["arch"])
    yarn = dict(zip(YARN_KEYS, s["yarn"]))
    attn = dataclasses.replace(
        cfg.attention, n_heads=s["H"], n_kv_heads=cfg_file["num_key_value_heads"],
        head_dim=s["dn"] + s["dr"], rope_theta=s["theta"], q_lora_rank=0,
        kv_lora_rank=s["kvr"], qk_nope_head_dim=s["dn"], qk_rope_head_dim=s["dr"],
        v_head_dim=s["dv"], yarn_factor=yarn["factor"],
        yarn_original_max_positions=int(yarn["original_max_position_embeddings"]),
        yarn_beta_fast=yarn["beta_fast"], yarn_beta_slow=yarn["beta_slow"],
        yarn_mscale_all_dim=yarn["mscale_all_dim"],
    )
    moe = dataclasses.replace(
        cfg.moe, n_experts=s["E"], top_k=s["k"], d_expert=s["Fe"], moe_every=1,
        first_k_dense=s["L_dense"],
        n_shared_experts=cfg_file["n_shared_experts"],
        norm_topk_prob=s["norm_topk"],
    )
    cfg = cfg.replace(n_layers=s["L"], d_model=s["d"], d_ff=s["F"],
                      vocab_size=s["V"], attention=attn, moe=moe)
    want = {"norm_kind": "rmsnorm", "mlp_kind": "swiglu", "tie_embeddings":
            cfg_file["tie_word_embeddings"], "use_bias": cfg_file["attention_bias"],
            "moe_impl": "ep", "lora_targets": s["targets"]}
    got = {"norm_kind": cfg.norm_kind, "mlp_kind": cfg.mlp_kind,
           "tie_embeddings": cfg.tie_embeddings, "use_bias": attn.use_bias,
           "moe_impl": moe.impl, "lora_targets": tuple(cfg.lora_targets)}
    if want != got or cfg_file["hidden_act"] != "silu" or s["eps"] != 1e-6:
        raise ValueError(f"{cfg_file['name']}: the program's {cfg.name} runs "
                         f"{got}, the file says {want}")
    return cfg


def base_tree(weights: dict, cfg):
    """The program's base-parameter tree, filled with the benchmark's
    weights: the dense layers as the stack's head, the MoE layers as its
    scanned blocks."""
    from repro.models.model import init_model

    shapes = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg, None, jnp.bfloat16)[0])
    dec = shapes["decoder"]
    n_dense = cfg.moe.first_k_dense
    if set(dec["blocks"]) != {"l0"} or dec["rest"] or len(dec.get("head", {})) != n_dense:
        raise ValueError("the model maps dense head layers and one repeated "
                         "MoE layer only")
    all_layers = {"norm1_scale", "norm2_scale", "q_w", "kv_a_w", "kv_norm_scale",
                  "kv_b_k_w", "kv_b_v_w", "o_w"}

    def name_of(leaf):
        if leaf[0] in ("norm1", "norm2"):
            return f"{leaf[0]}_scale"
        if leaf[0] == "attn":
            return "kv_norm_scale" if leaf[1] == "kv_norm" else f"{leaf[1]}_w"
        if leaf[0] == "mlp":
            return f"{leaf[1]}_w"
        if leaf[1] == "shared":  # moe / shared / gate / w
            return f"shared_{leaf[2]}_w"
        return "router_w" if leaf[1] == "router" else leaf[1]  # w_gate, ...

    def fill(path, sds):
        keys = [getattr(k, "key", None) for k in path]
        if keys[0] in ("embed", "lm_head"):
            return weights[keys[0]]
        if keys[0] == "final_norm":
            return weights["final_norm_scale"]
        part, layer = keys[1], keys[2]  # decoder / head|blocks / l<i> / ...
        name = name_of(keys[3:])
        w = weights[name]
        if part == "head":
            return w[int(layer[1:])]
        return w[n_dense:] if name in all_layers else w

    return jax.tree_util.tree_map_with_path(fill, shapes)


def adapter_norms(tree):
    """{name: {a|b: (layers, n)}} Frobenius norms of each layer's matrices
    of every adapter slot, from the program's packed adapter tree: q, kv_a
    and o over all layers, gate/up/down over the dense layers and
    shared_gate/up/down over the MoE layers."""
    dec = tree["decoder"]
    norm = lambda v, axes: jnp.sqrt(jnp.sum(jnp.square(v), axes))  # noqa: E731
    head = [dec["head"][f"l{i}"] for i in range(len(dec["head"]))]
    blk = dec["blocks"]["l0"]
    out = {}
    for proj in ("q", "kv_a", "o"):
        out[proj] = {ab: jnp.concatenate(
            [jnp.stack([norm(h["attn"][proj][ab], (1, 2)) for h in head]),
             norm(blk["attn"][proj][ab], (2, 3))]) for ab in ("a", "b")}
    for proj in ("gate", "up", "down"):
        out[proj] = {ab: jnp.stack([norm(h["mlp"][proj][ab], (1, 2))
                                    for h in head]) for ab in ("a", "b")}
        out[f"shared_{proj}"] = {
            ab: norm(blk["moe"]["shared"][proj][ab], (2, 3)) for ab in ("a", "b")}
    return out
