"""Plain float32 reference: a dense decoder with LoRA on its projections,
each adapter trained alone with Adam.

It imports nothing of the program. It follows the published architecture
(Qwen2.5 / StarCoder2 family: pre-norm, GQA with split-half RoPE, SwiGLU or
tanh-GELU MLP) and the program's training rule: per-adapter mean
cross-entropy over the labelled positions, delta ``(alpha / r) * (x A) B``
with ``B = 0`` at the start, Adam (b1 0.9, b2 0.999, eps 1e-8, bias
corrected, no weight decay) at the adapter's own learning rate. Matrix
products run at ``highest`` precision, so they are float32 on a TPU too.

The adapters' initial ``A`` follows the program's documented init: for a
pack of ranks ``(r_1..r_n)``, each projection of each layer draws
``N(0, 1) / sqrt(d_in)`` of shape ``(n, d_in, r_bucket)`` from a fixed key
tree of the LoRA seed, and adapter ``i`` keeps ``[i, :, :r_i]``.

Every adapter is held at one rank, ``rank_pad``, with zeros past its own
rank in ``A``'s columns and ``B``'s rows, so that one compiled program
serves every adapter of a grid. The zeros are exact and stay so: the
gradient of ``A[:, r:]`` is ``h^T (dY B[r:]^T)`` and that of ``B[r:]`` is
``(h A[:, r:])^T dY``, both 0, and Adam moves a parameter whose gradient
has always been 0 by ``0 / (0 + eps)``. Norms are unchanged by them.

``lowp=True`` is the control: every matrix product takes its operands
rounded to float8 (e4m3, one scale per tensor), the precision below the
bf16 that the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.dense_lora import lora_projections

IGNORE = -100
B1, B2, EPS = 0.9, 0.999, 1e-8
# sequence positions per block of the LM head and its loss: bounds the
# (rows, block, vocab) float32 logits held at once
LOSS_BLOCK = 128
# rows per forward and backward: a batch is summed over blocks of rows,
# which bounds the float32 activations held at once and gives every batch
# size the same compiled program
ROW_BLOCK = 1
F8_MAX = 448.0


@jax.custom_vjp
def _q8(x):
    """x rounded to float8 e4m3 under one scale for the tensor."""
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


# the cotangent passes straight through in float32: a float8 cast would
# flush the small gradients of a loss averaged over thousands of tokens
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

def _proj_keys(lora_seed: int, spec: dict):
    """layer -> projection -> key, the program's init key tree."""
    k = jax.random.split(jax.random.PRNGKey(lora_seed), 6)[1]
    layer_keys = jax.random.split(k, spec["L"])
    mlp_names = ["gate", "up", "down"] if spec["mlp"] == "swiglu" else ["up", "down"]
    out = []
    for l in range(spec["L"]):
        kl = jax.random.split(layer_keys[l], 6)
        ka = jax.random.split(kl[0], 8)
        km = jax.random.split(kl[2], 6)
        keys = {nm: ka[4 + i] for i, nm in enumerate(("q", "k", "v", "o"))}
        keys.update({nm: km[3 + i] for i, nm in enumerate(mlp_names)})
        out.append(keys)
    return out


@functools.lru_cache(maxsize=None)
def _init_fn(spec_items, packs, rank_pad):
    spec = dict(spec_items)

    def init(lora_seed):
        keys = _proj_keys(lora_seed, spec)
        out = {}
        for pack_ranks in packs:
            n = len(pack_ranks)
            r_bucket = max(8, -(-max(pack_ranks) // 8) * 8)
            for nm, (d_in, _) in lora_projections(spec).items():
                std = jnp.sqrt(jnp.float32(d_in))
                full = jnp.stack([
                    jax.random.normal(keys[l][nm], (n, d_in, r_bucket),
                                      jnp.float32) / std
                    for l in range(spec["L"])
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
    {(pack_ranks, slot): {proj: (L, d_in, rank_pad)}}, zero past the
    adapter's own rank."""
    packs = tuple(sorted(set(map(tuple, packs))))
    a = _init_fn(tuple(sorted(spec.items())), packs,
                 rank_pad)(jnp.int32(lora_seed))
    return {(p, s): a[f"{p}/{s}"] for p in packs for s in range(len(p))}


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def _norm(x, scale, bias, spec):
    if spec["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + spec["eps"]) * scale + bias
    var = (x ** 2).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(var + spec["eps"]) * scale


def _rope(x, theta):
    """Split-half rotary embedding; x: (b, S, heads, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _layer(x, w, lo, scale, spec, mm):
    """One pre-norm layer; ``w`` holds this layer's weights (f32)."""
    def lin(name, h):
        y = mm("bsi,io->bso", h, w[f"{name}_w"])
        if f"{name}_b" in w:
            y = y + w[f"{name}_b"]
        if name in lo:
            y = y + scale * mm("bsr,ro->bso",
                               mm("bsi,ir->bsr", h, lo[name]["a"]),
                               lo[name]["b"])
        return y

    b, s, _ = x.shape
    H, KV, hd = spec["H"], spec["KV"], spec["hd"]
    h = _norm(x, w["norm1_scale"], w.get("norm1_bias"), spec)
    q = _rope(lin("q", h).reshape(b, s, H, hd), spec["theta"])
    k = _rope(lin("k", h).reshape(b, s, KV, hd), spec["theta"])
    v = lin("v", h).reshape(b, s, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = mm("bhqk,bkhd->bqhd", p, v).reshape(b, s, H * hd)
    x = x + lin("o", att)
    h = _norm(x, w["norm2_scale"], w.get("norm2_bias"), spec)
    if spec["mlp"] == "swiglu":
        m = jax.nn.silu(lin("gate", h)) * lin("up", h)
    else:
        m = jax.nn.gelu(lin("up", h), approximate=True)
    return x + lin("down", m)


def nll_fn(lora, weights, tokens, labels, scale, spec, lowp=False):
    """Summed cross-entropy of one adapter's rows over their labelled
    positions, and the number of those positions."""
    mm = _mm(lowp)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    layer_w = {k: v for k, v in weights.items()
               if k not in ("embed", "lm_head") and not k.startswith("final")}

    @jax.checkpoint
    def body(x, inp):
        w, lo = inp
        return _layer(x, jax.tree.map(f32, w), lo, scale, spec, mm), None

    x = f32(weights["embed"][tokens])
    x, _ = jax.lax.scan(body, x, (layer_w, lora))
    x = _norm(x, f32(weights["final_norm_scale"]),
              f32(weights["final_norm_bias"]) if "final_norm_bias" in weights
              else None, spec)
    b, s, d = x.shape
    nblk = s // LOSS_BLOCK
    xs = jnp.moveaxis(x.reshape(b, nblk, LOSS_BLOCK, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, nblk, LOSS_BLOCK), 1, 0)
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
    """{proj: {a|b: (L,)}}: the Frobenius norm of each layer's matrix."""
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x), (1, 2))),
                        tree)


def train(weights, spec, point, rows, a0, *, n_steps=3, lowp=False):
    """Train one adapter alone for ``n_steps`` on ``rows`` (one batch per
    step) from its initial ``A``, ``a0`` (``init_adapters``), and ``B = 0``.
    Returns numpy readings: ``losses`` (n_steps,), ``grads``, one
    {proj: {a|b: (L,)}} tree of gradient norms per step, and ``update``,
    the norms of each matrix's change after the last step."""
    lora0 = {nm: {"a": a, "b": jnp.zeros((a.shape[0], a.shape[2],
                                          lora_projections(spec)[nm][1]),
                                         jnp.float32)}
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
