"""Required work of a dense decoder trained with LoRA on a frozen base.

Required means the least a step must compute, per real token:

- the base projections forward, and their input-gradient (the base is
  frozen: no weight-gradient). The first layer's input projections (q, k,
  v, gate, up) need no input-gradient: below them sit only the frozen
  embedding and norm;
- each LoRA delta forward (two products), and backward: ``dB``, ``d(xA)``,
  ``dA`` and ``dx`` (not ``dx`` at the first layer's input projections);
- causal attention, forward (QK^T, PV) and backward (four products), over
  ``(S + 1) / 2`` keys on average;
- the LM head forward and its input-gradient.

Recomputation, padding rows and padded rank columns do not count. Every
product is counted as ``2 * M * K * N`` operations; its bytes are the
least it must move: each operand read and the result written once, in
bf16 (2 bytes) for base weights and activations and f32 (4 bytes) for the
adapters.
"""
from __future__ import annotations

from bench.models.dense_lora import lora_projections, projections

INPUT_PROJS = ("q", "k", "v", "gate", "up")
ACT, ADAPTER = 2, 4  # bytes per element


def _mm(m, k, n, a_bytes=ACT, b_bytes=ACT, c_bytes=ACT):
    """(flops, bytes) of one (m, k) @ (k, n) product."""
    return 2.0 * m * k * n, float(m * k * a_bytes + k * n * b_bytes
                                 + m * n * c_bytes)


def job_products(spec: dict, seq: int, adapters):
    """Every product one training step of a job requires: ``adapters`` is
    a list of (rows, rank), one per adapter of the job. The base products
    run once over the real rows of all adapters together (the base weights
    are read once); each adapter has its own LoRA products. Returns a list
    of (name, flops, bytes)."""
    t = sum(rows for rows, _ in adapters) * seq
    L, H, KV, hd = spec["L"], spec["H"], spec["KV"], spec["hd"]
    out = []
    for name, (d_in, d_out) in projections(spec).items():
        # forward, then input-gradient (dy @ W^T) except at layer 0's inputs
        f, b = _mm(t, d_in, d_out)
        out.append((f"{name}.fwd", L * f, L * b))
        n_grad = L - 1 if name in INPUT_PROJS else L
        f, b = _mm(t, d_out, d_in)
        out.append((f"{name}.dx", n_grad * f, n_grad * b))
    for rows, rank in adapters:
        ta = rows * seq
        for name, (d_in, d_out) in lora_projections(spec).items():
            n_dx = L - 1 if name in INPUT_PROJS else L
            parts = [
                ("xa", L, _mm(ta, d_in, rank, ACT, ADAPTER, ADAPTER)),
                ("xab", L, _mm(ta, rank, d_out, ADAPTER, ADAPTER, ACT)),
                ("db", L, _mm(rank, ta, d_out, ADAPTER, ACT, ADAPTER)),
                ("dxa", L, _mm(ta, d_out, rank, ACT, ADAPTER, ADAPTER)),
                ("da", L, _mm(d_in, ta, rank, ACT, ADAPTER, ADAPTER)),
                ("dx", n_dx, _mm(ta, rank, d_in, ADAPTER, ADAPTER, ACT)),
            ]
            for part, n, (f, b) in parts:
                out.append((f"lora.{name}.{part}", n * f, n * b))
    # causal attention over (S + 1) / 2 keys: 2 products forward, 4
    # backward; flash-style, so only Q, K, V and the output are moved
    keys = (seq + 1) / 2.0
    f = 6 * 2.0 * t * H * hd * keys
    b = 3 * float(t * hd * (2 * H + 2 * KV) * ACT)
    out.append(("attention", L * f, L * b))
    d, V = spec["d"], spec["V"]
    for part in ("fwd", "dx"):
        f, b = _mm(t, d, V)
        out.append((f"lm_head.{part}", f, b))
    return out


def job_flops(spec: dict, seq: int, adapters) -> float:
    return sum(f for _, f, _ in job_products(spec, seq, adapters))


def job_least_seconds(spec: dict, seq: int, adapters, peak_flops: float,
                      hbm_bw: float) -> float:
    """The least time of one step's products on a chip: each product at the
    larger of its compute and its memory bound."""
    return sum(max(f / peak_flops, b / hbm_bw)
               for _, f, b in job_products(spec, seq, adapters))
