"""Training traffic: the sweep grid of a mix and the rows each adapter trains on.

A mix is a JSON file beside this module (``<mix>.json``). This one generator
reads every mix; a new mix is a new data file and no code.

Rows come from the permutation-LM task of ``src/repro/train/data.py``,
copied here so that the benchmark owns its inputs and can seed them: a fixed
random permutation ``pi`` of the vocabulary, ``x[t+1] = pi(x[t])`` with
probability ``1 - noise``, else a uniform token. Each adapter's rows depend
only on the run's seed and on the adapter's own hyperparameters, never on
the pack it lands in, so a packed run and the one-adapter reference see the
same rows.
"""
from __future__ import annotations

import json
import os
import zlib
from typing import Dict, Iterator, List, Sequence

import numpy as np

IGNORE = -100
HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, f"{name}.json")) as f:
        mix = json.load(f)
    for key in ("seq", "steps_per_job", "grid"):
        if key not in mix:
            raise ValueError(f"traffic mix {name!r} has no {key!r}")
    return mix


def grid_points(mix: dict) -> List[dict]:
    """The adapters of a mix: one dict per point, by rank, then batch.

    ``grid`` is a list of groups; each group gives ``ranks`` and
    ``batch_sizes`` (their product is taken), one ``learning_rate`` and
    ``alpha_over_rank`` (alpha = rank * alpha_over_rank)."""
    out = []
    for group in mix["grid"]:
        for r in group["ranks"]:
            for b in group["batch_sizes"]:
                out.append({
                    "rank": int(r),
                    "batch_size": int(b),
                    "learning_rate": float(group["learning_rate"]),
                    "alpha": float(r) * float(group["alpha_over_rank"]),
                })
    out.sort(key=lambda p: (p["rank"], p["batch_size"]))
    keys = [point_key(p) for p in out]
    if len(set(keys)) != len(keys):
        raise ValueError("a traffic mix lists the same grid point twice")
    return out


def point_key(p: dict) -> tuple:
    return (p["rank"], p["alpha"], p["learning_rate"], p["batch_size"])


def _seed32(*parts) -> int:
    """A 32-bit seed from the run's seed and a stable description."""
    return zlib.crc32(repr(parts).encode()) & 0xFFFFFFFF


def permutation(seed: int, vocab: int) -> np.ndarray:
    return np.random.RandomState(_seed32("perm", seed)).permutation(vocab)


def sample_rows(rng, perm, batch: int, seq: int, vocab: int,
                noise: float) -> np.ndarray:
    x = np.empty((batch, seq), np.int32)
    x[:, 0] = rng.randint(0, vocab, batch)
    for t in range(1, seq):
        nxt = perm[x[:, t - 1]]
        flip = rng.rand(batch) < noise
        x[:, t] = np.where(flip, rng.randint(0, vocab, batch), nxt)
    return x


def adapter_rows(seed: int, point: dict, *, n_steps: int, seq: int,
                 vocab: int, noise: float, perm=None) -> List[Dict]:
    """``n_steps`` batches of one adapter: ``tokens`` and ``labels`` of
    shape ``(batch_size, seq)``; a label is the next token, and the last
    position has none (``IGNORE``)."""
    perm = permutation(seed, vocab) if perm is None else perm
    rng = np.random.RandomState(_seed32("rows", seed, point_key(point)))
    out = []
    for _ in range(n_steps):
        x = sample_rows(rng, perm, point["batch_size"], seq, vocab, noise)
        labels = np.full_like(x, IGNORE)
        labels[:, :-1] = x[:, 1:]
        out.append({"tokens": x, "labels": labels})
    return out


class RowBank:
    """Every adapter's rows for ``n_steps`` steps, made once from the seed.

    ``iterator`` is the ``data_iter_fn`` the sweep engine takes: it packs the
    rows of a job's adapters into ``(N * Bmax, seq)`` arrays, padding rows
    labelled ``IGNORE``, as the program's own iterator does. Every pass
    replays the same rows, so every pass does the same work."""

    def __init__(self, seed: int, points: Sequence[dict], *, n_steps: int,
                 seq: int, vocab: int, noise: float):
        self.seq = seq
        perm = permutation(seed, vocab)
        self.rows = {
            point_key(p): adapter_rows(seed, p, n_steps=n_steps, seq=seq,
                                       vocab=vocab, noise=noise, perm=perm)
            for p in points
        }

    def iterator(self, cfg, configs, seq) -> Iterator[Dict[str, np.ndarray]]:
        if seq != self.seq:
            raise ValueError(f"rows were made for seq {self.seq}, not {seq}")
        streams = [self.rows[c.key()] for c in configs]
        bmax = max(c.batch_size for c in configs)
        n = len(configs)
        for step in range(len(streams[0])):
            tokens = np.zeros((n, bmax, seq), np.int32)
            labels = np.full((n, bmax, seq), IGNORE, np.int32)
            for i, (c, rows) in enumerate(zip(configs, streams)):
                tokens[i, : c.batch_size] = rows[step]["tokens"]
                labels[i, : c.batch_size] = rows[step]["labels"]
            yield {"tokens": tokens.reshape(n * bmax, seq),
                   "labels": labels.reshape(n * bmax, seq)}
