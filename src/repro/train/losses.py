"""Losses: vocab-chunked cross-entropy with per-adapter reduction.

The CE never materializes the full (NB, S, V) logits tensor: the sequence is
scanned in chunks, each chunk's logits computed, reduced, and discarded
(rematerialized in backward). With a 262k vocab (gemma3) at 4k x 256 tokens
this is the difference between ~550 GB and ~0.5 GB of logits live at once.

Per-adapter reduction: total = sum_n mean-CE_n, so each adapter's gradient is
exactly what it would be when fine-tuned alone (the paper's packing-identity
property, tested in tests/test_train_packed.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

IGNORE = -100


def _chunk_ce(h, w, labels, mask, vocab=None):
    """h: (NB, c, d); w: (d, Vpad); labels: (NB, c). Returns (nll_sum, cnt)."""
    lg = (h @ w.astype(h.dtype)).astype(jnp.float32)  # (NB, c, Vpad)
    if vocab is not None and vocab < lg.shape[-1]:
        lg = jnp.where(jnp.arange(lg.shape[-1]) < vocab, lg, -1e30)
    lse = jax.nn.logsumexp(lg, axis=-1)
    safe = jnp.maximum(labels, 0)
    tgt = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
    nll = (lse - tgt) * mask
    return nll.sum(-1), mask.sum(-1)


def chunked_cross_entropy(
    hidden: jnp.ndarray,
    unembed: jnp.ndarray,
    labels: jnp.ndarray,
    n_pack: int,
    *,
    chunk: int = 512,
    vocab: int = None,
    row_owner: Optional[Tuple[int, ...]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (per_adapter_mean (N,), total scalar = sum of per-adapter means).

    hidden: (NB, S, d); labels: (NB, S) with IGNORE for masked positions.
    `vocab`: true vocabulary size when `unembed` is padded.
    `row_owner`: the adapter of each row, when rows are not N equal groups
    (a pack's row slots, ``train.trainer.row_slots``); an adapter's mean is
    still its summed NLL over its own counted tokens.
    """
    nb, s, d = hidden.shape
    mask = (labels != IGNORE).astype(jnp.float32)
    if s <= chunk:
        nll, cnt = _chunk_ce(hidden, unembed, labels, mask, vocab)
    else:
        pad = (-s) % chunk
        if pad:
            hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=IGNORE)
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        n = hidden.shape[1] // chunk
        hc = jnp.moveaxis(hidden.reshape(nb, n, chunk, d), 1, 0)
        lc = jnp.moveaxis(labels.reshape(nb, n, chunk), 1, 0)
        mc = jnp.moveaxis(mask.reshape(nb, n, chunk), 1, 0)

        @jax.checkpoint
        def body(carry, inp):
            h, l, m = inp
            a, b = _chunk_ce(h, unembed, l, m, vocab)
            return (carry[0] + a, carry[1] + b), None

        (nll, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((nb,), jnp.float32), jnp.zeros((nb,), jnp.float32)),
            (hc, lc, mc),
        )
    # fold (N*B,) -> per-adapter means
    if row_owner is None:
        nll_n = nll.reshape(n_pack, -1).sum(-1)
        cnt_n = cnt.reshape(n_pack, -1).sum(-1)
    else:
        owner = jnp.asarray(row_owner, jnp.int32)
        nll_n = jax.ops.segment_sum(nll, owner, n_pack, indices_are_sorted=True)
        cnt_n = jax.ops.segment_sum(cnt, owner, n_pack, indices_are_sorted=True)
    per_adapter = nll_n / jnp.maximum(cnt_n, 1.0)
    return per_adapter, per_adapter.sum()


def top1_accuracy(logits: jnp.ndarray, labels: jnp.ndarray, n_pack: int):
    """Per-adapter next-token top-1 accuracy (quality benchmarks)."""
    pred = jnp.argmax(logits, -1)
    mask = labels != IGNORE
    hit = ((pred == labels) & mask).astype(jnp.float32)
    hit_n = hit.reshape(n_pack, -1).sum(-1)
    cnt_n = mask.astype(jnp.float32).reshape(n_pack, -1).sum(-1)
    return hit_n / jnp.maximum(cnt_n, 1.0)
