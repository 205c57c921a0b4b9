"""Required work of DeepSeek-V2 (V2-Lite) trained with LoRA on a frozen base.

Required means the least a step must compute, per real token:

- every base projection forward, and its input-gradient (the base is
  frozen: no weight-gradient), except the first layer's q and kv_a, below
  which sit only the frozen embedding and norm. kv_b's key and value halves
  and the router count as projections;
- the routed experts at ``top_k`` rows a token: gate, up and down forward
  and their three input-gradients, no weight-gradient (they are frozen);
- the shared experts and the dense layer's SwiGLU, as projections;
- each LoRA delta forward (two products), and backward: ``dB``, ``d(xA)``,
  ``dA`` and ``dx`` (not ``dx`` at the first layer's q and kv_a);
- causal attention, forward (QK^T over nope + rope, PV over v) and
  backward (two of each), over ``(S + 1) / 2`` keys on average;
- the LM head forward and its input-gradient.

Recomputation, padding rows, padded rank columns and the grouped matmul's
partial tiles do not count. Every product is ``2 * M * K * N`` operations;
its bytes the least it must move: each operand read and the result written
once, bf16 (2 bytes) for base weights and activations and f32 (4 bytes) for
the adapters.
"""
from __future__ import annotations

from bench.models.deepseek_v2 import lora_shapes

FIRST_LAYER_INPUTS = ("q", "kv_a")
ACT, ADAPTER = 2, 4  # bytes per element


def _mm(m, k, n, a_bytes=ACT, b_bytes=ACT, c_bytes=ACT):
    """(flops, bytes) of one (m, k) @ (k, n) product."""
    return 2.0 * m * k * n, float(m * k * a_bytes + k * n * b_bytes
                                 + m * n * c_bytes)


def _projections(spec: dict) -> dict:
    """name -> (layers, d_in, d_out) of every base projection."""
    d, H, L = spec["d"], spec["H"], spec["L"]
    Ld, Lm = spec["L_dense"], spec["L_moe"]
    return {
        "q": (L, d, H * (spec["dn"] + spec["dr"])),
        "kv_a": (L, d, spec["kvr"] + spec["dr"]),
        "kv_b_k": (L, spec["kvr"], H * spec["dn"]),
        "kv_b_v": (L, spec["kvr"], H * spec["dv"]),
        "o": (L, H * spec["dv"], d),
        "gate": (Ld, d, spec["F"]), "up": (Ld, d, spec["F"]),
        "down": (Ld, spec["F"], d),
        "router": (Lm, d, spec["E"]),
        "shared_gate": (Lm, d, spec["Fs"]), "shared_up": (Lm, d, spec["Fs"]),
        "shared_down": (Lm, spec["Fs"], d),
    }


def expert_products(spec: dict, seq: int, adapters):
    """The routed experts' products of one training step of a job:
    (name, flops, bytes). Each of ``top_k`` rows a token runs gate, up and
    down forward and their input-gradients; every expert's weights are read
    once per product."""
    t = sum(rows for rows, _ in adapters) * seq
    tk, d, Fe, E, Lm = t * spec["k"], spec["d"], spec["Fe"], spec["E"], spec["L_moe"]
    out = []
    for name, k, n in (("gate", d, Fe), ("up", d, Fe), ("down", Fe, d)):
        for part, kk, nn in (("fwd", k, n), ("dx", n, k)):
            f = 2.0 * tk * kk * nn
            b = float(tk * kk * ACT + E * kk * nn * ACT + tk * nn * ACT)
            out.append((f"experts.{name}.{part}", Lm * f, Lm * b))
    return out


def job_products(spec: dict, seq: int, adapters):
    """Every product one training step of a job requires: ``adapters`` is
    a list of (rows, rank), one per adapter of the job. The base products
    run once over the real rows of all adapters together; each adapter has
    its own LoRA products. Returns a list of (name, flops, bytes)."""
    t = sum(rows for rows, _ in adapters) * seq
    out = []
    for name, (n_l, d_in, d_out) in _projections(spec).items():
        f, b = _mm(t, d_in, d_out)
        out.append((f"{name}.fwd", n_l * f, n_l * b))
        n_dx = n_l - 1 if name in FIRST_LAYER_INPUTS else n_l
        f, b = _mm(t, d_out, d_in)
        out.append((f"{name}.dx", n_dx * f, n_dx * b))
    out += expert_products(spec, seq, adapters)
    for rows, rank in adapters:
        ta = rows * seq
        for name, (n_l, d_in, d_out) in lora_shapes(spec).items():
            n_dx = n_l - 1 if name in FIRST_LAYER_INPUTS else n_l
            parts = [
                ("xa", n_l, _mm(ta, d_in, rank, ACT, ADAPTER, ADAPTER)),
                ("xab", n_l, _mm(ta, rank, d_out, ADAPTER, ADAPTER, ACT)),
                ("db", n_l, _mm(rank, ta, d_out, ADAPTER, ACT, ADAPTER)),
                ("dxa", n_l, _mm(ta, d_out, rank, ACT, ADAPTER, ADAPTER)),
                ("da", n_l, _mm(d_in, ta, rank, ACT, ADAPTER, ADAPTER)),
                ("dx", n_dx, _mm(ta, rank, d_in, ADAPTER, ADAPTER, ACT)),
            ]
            for part, n, (f, b) in parts:
                out.append((f"lora.{name}.{part}", n * f, n * b))
    # causal attention over (S + 1) / 2 keys: QK^T and PV forward, two of
    # each backward; flash-style, so only Q, K, V and the output are moved
    keys = (seq + 1) / 2.0
    H, L, dqk, dv = spec["H"], spec["L"], spec["dn"] + spec["dr"], spec["dv"]
    f = 3 * 2.0 * t * H * keys * (dqk + dv)
    b = 3 * float(t * H * (2 * dqk + 2 * dv) * ACT)
    out.append(("attention", L * f, L * b))
    d, V = spec["d"], spec["V"]
    for part in ("fwd", "dx"):
        f, b = _mm(t, d, V)
        out.append((f"lm_head.{part}", f, b))
    return out


def _least(products, peak_flops, hbm_bw):
    return sum(max(f / peak_flops, b / hbm_bw) for _, f, b in products)


def job_flops(spec: dict, seq: int, adapters) -> float:
    return sum(f for _, f, _ in job_products(spec, seq, adapters))


def job_least_seconds(spec: dict, seq: int, adapters, peak_flops: float,
                      hbm_bw: float) -> float:
    """The least time of one step's products on a chip: each product at the
    larger of its compute and its memory bound."""
    return _least(job_products(spec, seq, adapters), peak_flops, hbm_bw)


def expert_least_seconds(spec: dict, seq: int, adapters, peak_flops: float,
                         hbm_bw: float) -> float:
    """The least time of one step's routed-expert products alone."""
    return _least(expert_products(spec, seq, adapters), peak_flops, hbm_bw)
