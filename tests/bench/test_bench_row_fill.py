"""``train.row_fill_pct``: the share of the rows the training step computed
that hold real samples, read from the ``executor.train`` spans' args."""
import os
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from bench import harness  # noqa: E402
from repro.obs.trace import Span  # noqa: E402


def reader():
    return harness.load_module(
        os.path.join(ROOT, "bench", "metrics", "train.row_fill_pct.py"))


def train_span(t, n_steps, **args):
    return Span(name="executor.train", cat="executor", track="unit0",
                span_id=t, parent_id=None, start=float(t), end=t + 0.5,
                args={"n_pack": 2, "n_steps": n_steps, **args})


def ctx(spans):
    other = Span(name="executor.place", cat="executor", track="unit0",
                 span_id=99, parent_id=None, start=0.0, end=0.1,
                 args={"rows": 8, "real_rows": 1, "n_steps": 1})
    return SimpleNamespace(spans=[other, *spans])


def test_row_slot_packs_read_100():
    spans = [train_span(i, 4, rows=3, real_rows=3) for i in range(5)]
    assert reader().read(ctx(spans)) == 100.0


def test_padded_packs_read_75_weighted_by_steps():
    spans = [train_span(i, 4, rows=4, real_rows=3) for i in range(5)]
    assert reader().read(ctx(spans)) == pytest.approx(75.0)
    # a uniform pack of 8 steps beside a padded one of 4
    mixed = [train_span(0, 4, rows=4, real_rows=3),
             train_span(1, 8, rows=2, real_rows=2)]
    assert reader().read(ctx(mixed)) == pytest.approx(100 * 28 / 32)


def test_spans_without_the_args_read_nothing():
    assert reader().read(ctx([train_span(i, 4) for i in range(5)])) is None
    assert reader().read(ctx([])) is None
