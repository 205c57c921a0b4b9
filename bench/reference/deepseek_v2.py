"""Plain float32 reference: DeepSeek-V2 (V2-Lite) with LoRA on its
projections, each adapter trained alone with Adam.

It imports nothing of the program. It follows the published modelling code
(``modeling_deepseek.py`` of DeepSeek-V2-Lite): pre-norm RMSNorm layers;
latent attention with a direct q projection, ``kv_a`` to the compressed KV
and the shared rope key, RMSNorm on the compressed KV, ``kv_b`` to per-head
keys and values; YaRN rope, its frequencies blended between the plain and
the stretched ones over a linear ramp, and the softmax scale
``(nope + rope)^-0.5 * (0.1 * mscale_all_dim * ln(factor) + 1)^2``; a dense
SwiGLU in the first ``first_k_dense_replace`` layers; after them a router
(softmax in float32, greedy top-k, renormalised only with
``norm_topk_prob``), the routed experts and the shared experts (one SwiGLU
of width ``n_shared * moe_intermediate``).
The training rule is the program's: per-adapter mean cross-entropy over the
labelled positions, delta ``(alpha / r) * (x A) B`` with ``B = 0`` at the
start, Adam (b1 0.9, b2 0.999, eps 1e-8, bias corrected, no weight decay) at
the adapter's own learning rate. Matrix products run at ``highest``
precision, so they are float32 on a TPU too.

The routed experts are computed the plain way: every expert for every
token, summed with the top-k gates (zero for the experts not chosen). To fit
on one chip the experts run in blocks of ``EXPERT_BLOCK`` under
``jax.checkpoint``, each block's output added to the layer's.

Departures from the published code: the rope part of ``q`` and of ``kv_a``
is rotated in split-half layout, where the published code stores it
interleaved (a column permutation of random weights); no balance loss
(``seq_aux``) is added, as the program adds none (its router is frozen).
Files whose ``routed_scaling_factor`` is not 1, or whose ``mscale`` differs
from ``mscale_all_dim``, are refused by the model's ``spec``, so neither the
gates nor the cos/sin tables are scaled here.

The adapters' initial ``A`` follows the program's documented init: for a
pack of ranks ``(r_1..r_n)``, each adapted matrix of each layer draws
``N(0, 1) / sqrt(d_in)`` of shape ``(n, d_in, r_bucket)`` from the LoRA
seed's key tree (``_lora_keys``), and adapter ``i`` keeps ``[i, :, :r_i]``.
Every adapter is held at one rank, ``rank_pad``, with exact zeros past its
own (``bench/reference/dense_lora.py`` says why they stay zero).

``lowp=True`` is the control: every matrix product takes its operands
rounded to float8 (e4m3, one scale per tensor), the precision below the
bf16 that the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.deepseek_v2 import lora_shapes

IGNORE = -100
B1, B2, EPS = 0.9, 0.999, 1e-8
# sequence positions per block of the LM head and its loss
LOSS_BLOCK = 128
# rows per forward and backward (see dense_lora.ROW_BLOCK)
ROW_BLOCK = 1
# routed experts per block of the all-experts sum
EXPERT_BLOCK = 8
F8_MAX = 448.0


@jax.custom_vjp
def _q8(x):
    """x rounded to float8 e4m3 under one scale for the tensor."""
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


# the cotangent passes straight through in float32
_q8.defvjp(lambda x: (_q8(x), None), lambda _, g: (g,))


def _mm(lowp):
    def mm(eq, a, b):
        if lowp:
            a, b = _q8(a), _q8(b)
        return jnp.einsum(eq, a, b, precision="highest")
    return mm


# ---------------------------------------------------------------------------
# initial adapters
# ---------------------------------------------------------------------------

def _lora_keys(lora_seed: int, spec: dict):
    """name -> [key of each of its layers], the program's init key tree:
    the decoder's key splits into one key per layer; a layer's key into
    six, the attention taking the first (split in ten: q, kv_a, o at 6, 7,
    8) and the FFN the third (a dense MLP split in six: gate, up, down at
    3, 4, 5; an MoE split in five, its shared experts' MLP at 4)."""
    k = jax.random.split(jax.random.PRNGKey(lora_seed), 6)[1]
    layer_keys = jax.random.split(k, spec["L"])
    out = {}
    for l in range(spec["L"]):
        kl = jax.random.split(layer_keys[l], 6)
        ka = jax.random.split(kl[0], 10)
        for i, nm in enumerate(("q", "kv_a", "o")):
            out.setdefault(nm, []).append(ka[6 + i])
        if l < spec["L_dense"]:
            km, pre = jax.random.split(kl[2], 6), ""
        else:
            km, pre = jax.random.split(jax.random.split(kl[2], 5)[4], 6), "shared_"
        for i, nm in enumerate(("gate", "up", "down")):
            out.setdefault(pre + nm, []).append(km[3 + i])
    return out


@functools.lru_cache(maxsize=None)
def _init_fn(spec_items, packs, rank_pad):
    spec = dict(spec_items)

    def init(lora_seed):
        keys = _lora_keys(lora_seed, spec)
        out = {}
        for pack_ranks in packs:
            n = len(pack_ranks)
            r_bucket = max(8, -(-max(pack_ranks) // 8) * 8)
            for nm, (_, d_in, _) in lora_shapes(spec).items():
                std = jnp.sqrt(jnp.float32(d_in))
                full = jnp.stack([
                    jax.random.normal(key, (n, d_in, r_bucket), jnp.float32) / std
                    for key in keys[nm]
                ], 1)
                for slot, r in enumerate(pack_ranks):
                    out.setdefault(f"{pack_ranks}/{slot}", {})[nm] = jnp.pad(
                        full[slot, :, :, :r],
                        ((0, 0), (0, 0), (0, rank_pad - r)))
        return out

    return jax.jit(init)


def init_adapters(lora_seed: int, spec: dict, packs, rank_pad: int):
    """The initial ``A`` of every adapter of every pack of ``packs`` (rank
    tuples), in one compiled program whatever the seed:
    {(pack_ranks, slot): {name: (layers, d_in, rank_pad)}}, zero past the
    adapter's own rank."""
    packs = tuple(sorted(set(map(tuple, packs))))
    a = _init_fn(tuple(sorted(spec.items())), packs,
                 rank_pad)(jnp.int32(lora_seed))
    return {(p, s): a[f"{p}/{s}"] for p in packs for s in range(len(p))}


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_tables(s, spec):
    """cos/sin (S, rope/2) of DeepSeek-V2's YaRN rope."""
    dim, base = spec["dr"], spec["theta"]
    factor, orig, beta_fast, beta_slow, _ = spec["yarn"]

    def corr(n):
        return dim * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inter = 1.0 / (factor * base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    inv = inter * (1.0 - keep) + extra * keep
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """Split-half rotary embedding; x: (b, S, heads, dim)."""
    h = x.shape[-1] // 2
    c, sn = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _swiglu(lin, pre, h):
    return lin(f"{pre}down", jax.nn.silu(lin(f"{pre}gate", h)) * lin(f"{pre}up", h))


def _experts(h, w, spec, mm):
    """Routed experts, the plain way: the top-k gates scattered over all E
    experts, and every expert run for every token, in blocks of
    EXPERT_BLOCK experts under jax.checkpoint."""
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    logits = mm("td,de->te", x, w["router_w"])
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, spec["k"])
    if spec["norm_topk"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    gates = jnp.zeros_like(probs).at[jnp.arange(b * s)[:, None], idx].set(top)
    eb = min(EXPERT_BLOCK, spec["E"])
    nblk = spec["E"] // eb
    blocks = [v.reshape(nblk, eb, *v.shape[1:])
              for v in (w["w_gate"], w["w_up"], w["w_down"])]
    gb = jnp.moveaxis(gates.reshape(b * s, nblk, eb), 1, 0)

    @jax.checkpoint
    def block(y, inp):
        wg, wu, wd, g = (t.astype(jnp.float32) for t in inp)
        hid = jax.nn.silu(mm("td,edf->etf", x, wg)) * mm("td,edf->etf", x, wu)
        out = mm("etf,efd->etd", hid, wd)
        return y + jnp.einsum("etd,te->td", out, g, precision="highest"), None

    y, _ = jax.lax.scan(block, jnp.zeros_like(x), (*blocks, gb))
    return y.reshape(b, s, d)


def _layer(x, w, lo, scale, spec, mm, rope, moe):
    """One pre-norm layer; ``w`` holds this layer's weights (f32), ``lo``
    its adapters."""
    def lin(name, h):
        y = mm("bsi,io->bso", h, w[f"{name}_w"])
        if name in lo:
            y = y + scale * mm("bsr,ro->bso",
                               mm("bsi,ir->bsr", h, lo[name]["a"]),
                               lo[name]["b"])
        return y

    b, s, _ = x.shape
    H, dn, dr, dv, kvr = spec["H"], spec["dn"], spec["dr"], spec["dv"], spec["kvr"]
    cos, sin = rope
    h = _rms(x, w["norm1_scale"], spec["eps"])
    q = lin("q", h).reshape(b, s, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin)], -1)
    kv = lin("kv_a", h)
    ckv = _rms(kv[..., :kvr], w["kv_norm_scale"], spec["eps"])
    k_pe = _rope(kv[..., None, kvr:], cos, sin)  # one rope key for all heads
    k_nope = mm("bsr,ro->bso", ckv, w["kv_b_k_w"]).reshape(b, s, H, dn)
    v = mm("bsr,ro->bso", ckv, w["kv_b_v_w"]).reshape(b, s, H, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, H, dr))], -1)
    factor, _, _, _, mscale_all = spec["yarn"]
    sm = (dn + dr) ** -0.5 * _yarn_mscale(factor, mscale_all) ** 2
    scores = mm("bqhd,bkhd->bhqk", q, k) * sm
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = mm("bhqk,bkhd->bqhd", p, v).reshape(b, s, H * dv)
    x = x + lin("o", att)
    h = _rms(x, w["norm2_scale"], spec["eps"])
    if not moe:
        return x + _swiglu(lin, "", h)
    return x + _experts(h, w, spec, mm) + _swiglu(lin, "shared_", h)


ATTN = ("norm1_scale", "norm2_scale", "q_w", "kv_a_w", "kv_norm_scale",
        "kv_b_k_w", "kv_b_v_w", "o_w")
DENSE = ("gate_w", "up_w", "down_w")
EXPERTS = ("w_gate", "w_up", "w_down")
MOE = ("router_w", "shared_gate_w", "shared_up_w", "shared_down_w") + EXPERTS


def nll_fn(lora, weights, tokens, labels, scale, spec, lowp=False):
    """Summed cross-entropy of one adapter's rows over their labelled
    positions, and the number of those positions."""
    mm = _mm(lowp)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    nd = spec["L_dense"]
    rope = _rope_tables(tokens.shape[1], spec)
    x = f32(weights["embed"][tokens])
    for l in range(nd):
        w = {k: f32(weights[k][l]) for k in ATTN + DENSE}
        lo = {nm: {ab: v[l] for ab, v in lora[nm].items()}
              for nm in ("q", "kv_a", "o", "gate", "up", "down")}
        x = jax.checkpoint(
            lambda x_, w_, lo_: _layer(x_, w_, lo_, scale, spec, mm, rope, False)
        )(x, w, lo)

    @jax.checkpoint
    def body(x_, inp):
        w, lo = inp
        # the experts stay bf16 here: _experts widens one block at a time
        w = {k: v if k in EXPERTS else f32(v) for k, v in w.items()}
        return _layer(x_, w, lo, scale, spec, mm, rope, True), None

    w_moe = {k: weights[k][nd:] for k in ATTN}
    w_moe.update({k: weights[k] for k in MOE})
    lo_moe = {nm: {ab: v[nd:] for ab, v in lora[nm].items()}
              for nm in ("q", "kv_a", "o")}
    lo_moe.update({nm: lora[nm] for nm in ("shared_gate", "shared_up",
                                           "shared_down")})
    x, _ = jax.lax.scan(body, x, (w_moe, lo_moe))
    x = _rms(x, f32(weights["final_norm_scale"]), spec["eps"])
    b, s, d = x.shape
    blk = min(LOSS_BLOCK, s)
    nblk = s // blk
    xs = jnp.moveaxis(x.reshape(b, nblk, blk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, nblk, blk), 1, 0)
    head = f32(weights["lm_head"])

    @jax.checkpoint
    def block(acc, inp):
        h, lab = inp
        logits = mm("btd,dv->btv", h, head)
        lse = jax.nn.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[..., None],
                                  -1)[..., 0]
        valid = (lab != IGNORE).astype(jnp.float32)
        return (acc[0] + ((lse - tgt) * valid).sum(),
                acc[1] + valid.sum()), None

    (nll, cnt), _ = jax.lax.scan(block, (0.0, 0.0), (xs, ls))
    return nll, cnt


@functools.lru_cache(maxsize=None)
def _grad_fn(spec_items, lowp):
    spec = dict(spec_items)

    def grad(lora, weights, tokens, labels, scale):
        (nll, cnt), g = jax.value_and_grad(nll_fn, has_aux=True)(
            lora, weights, tokens, labels, scale, spec, lowp)
        return nll, cnt, g

    return jax.jit(grad)


@jax.jit
def _adam(lora, m, v, g, t, lr):
    m = jax.tree.map(lambda m_, g_: B1 * m_ + (1 - B1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: B2 * v_ + (1 - B2) * g_ * g_, v, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    lora = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + EPS),
        lora, m, v)
    return lora, m, v


def loss_and_grad(lora, weights, tokens, labels, scale, spec, lowp=False):
    """Mean cross-entropy over the labelled positions of all rows, and its
    gradient, summed over blocks of ROW_BLOCK rows."""
    grad = _grad_fn(tuple(sorted(spec.items())), lowp)
    nll = cnt = g = None
    for i in range(0, tokens.shape[0], ROW_BLOCK):
        n_, c_, g_ = grad(lora, weights, jnp.asarray(tokens[i:i + ROW_BLOCK]),
                          jnp.asarray(labels[i:i + ROW_BLOCK]), scale)
        if g is None:
            nll, cnt, g = n_, c_, g_
        else:
            nll, cnt = nll + n_, cnt + c_
            g = jax.tree.map(jnp.add, g, g_)
    return nll / cnt, jax.tree.map(lambda x: x / cnt, g)


@jax.jit
def leaf_norms(tree):
    """{name: {a|b: (layers,)}}: the Frobenius norm of each layer's matrix."""
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x), (1, 2))),
                        tree)


def train(weights, spec, point, rows, a0, *, n_steps=3, lowp=False):
    """Train one adapter alone for ``n_steps`` on ``rows`` (one batch per
    step) from its initial ``A``, ``a0`` (``init_adapters``), and ``B = 0``.
    Returns numpy readings: ``losses`` (n_steps,), ``grads``, one
    {name: {a|b: (layers,)}} tree of gradient norms per step, and
    ``update``, the norms of each matrix's change after the last step."""
    shapes = lora_shapes(spec)
    lora0 = {nm: {"a": a, "b": jnp.zeros((a.shape[0], a.shape[2],
                                          shapes[nm][2]), jnp.float32)}
             for nm, a in a0.items()}
    zeros = jax.tree.map(jnp.zeros_like, lora0)
    lora, m, v = lora0, zeros, zeros
    scale = jnp.float32(point["alpha"] / point["rank"])
    lr = jnp.float32(point["learning_rate"])
    losses, grads = [], []
    for t in range(1, n_steps + 1):
        batch = rows[t - 1]
        loss, g = loss_and_grad(lora, weights, batch["tokens"],
                                batch["labels"], scale, spec, lowp)
        lora, m, v = _adam(lora, m, v, g, jnp.float32(t), lr)
        losses.append(loss)
        grads.append(leaf_norms(g))
    update = leaf_norms(jax.tree.map(lambda a, b: a - b, lora, lora0))
    return jax.tree.map(np.asarray, {
        "losses": jnp.stack(losses), "grads": grads, "update": update,
    })
