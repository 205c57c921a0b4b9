"""Faults planted under the timed path, for the check's own tests and for
the readings its limits are set from. Each patches one executor's compiled
step; none is used by a benchmark run.

- ``unchanged_state``: the step computes, then returns the adapters and
  the optimizer state it was given;
- ``half_batch``: the step leaves out the second half of each adapter's
  rows (their labels ignored), so each mean is taken over the rest;
- ``altered_answer``: the loss the step returns for the first adapter of
  each pack is off by ``ALTER`` of itself.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

IGNORE = -100
ALTER = 0.01


def _unchanged(step):
    def broken(base, lora, opt, batch, *vecs):
        copy = lambda t: jax.tree.map(lambda x: x + 0, t)  # noqa: E731
        _, _, m = step(base, copy(lora), copy(opt), batch, *vecs)
        return lora, opt, m
    return broken


def _half_batch(step, n_pack):
    def broken(base, lora, opt, batch, *vecs):
        labels = batch["labels"]
        rows = labels.shape[0] // n_pack
        keep = (jnp.arange(labels.shape[0]) % rows) < max(1, rows // 2)
        batch = dict(batch, labels=jnp.where(keep[:, None], labels, IGNORE))
        return step(base, lora, opt, batch, *vecs)
    return broken


def _altered(step):
    def broken(base, lora, opt, batch, *vecs):
        lora, opt, m = step(base, lora, opt, batch, *vecs)
        loss = m["per_adapter_loss"]
        m = dict(m, per_adapter_loss=loss.at[0].multiply(1 + ALTER))
        return lora, opt, m
    return broken


def broken(step, fault: str, n_pack: int):
    """``step`` with ``fault`` planted in it."""
    if fault == "unchanged_state":
        return _unchanged(step)
    if fault == "half_batch":
        return _half_batch(step, n_pack)
    if fault == "altered_answer":
        return _altered(step)
    raise ValueError(f"unknown fault {fault!r}")


@contextlib.contextmanager
def planted(executor, fault: str):
    """Within the block, ``executor`` runs its steps with ``fault``."""
    orig = executor.step_fn

    def step_fn(cfg, n_pack, *a, **k):
        step, dist = orig(cfg, n_pack, *a, **k)
        return broken(step, fault, n_pack), dist

    executor.step_fn = step_fn
    try:
        yield
    finally:
        del executor.step_fn
