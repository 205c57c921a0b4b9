"""The trace reduction on a small trace recorded on a TPU v5e: 40 ms of
the qwen25-7b grid sweep around a job switch."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from bench.trace import reduce as tr  # noqa: E402

RECORDED = os.path.join(ROOT, "bench", "trace", "recorded_small.json.gz")


@pytest.fixture(scope="module")
def rec():
    r = tr.load(RECORDED)
    return r, tr.window(r), r["devices"]["0"]


def test_window_and_events(rec):
    r, win, ops = rec
    assert win is not None and win[1] - win[0] == 40_270_041
    assert len(ops) == 340
    assert all(len(op) == 4 for op in ops)


def test_busy_union_and_idle_gaps_partition_the_window(rec):
    r, (a, b), ops = rec
    busy = tr.busy_ns(ops, a, b)
    gaps = tr.idle_gaps(ops, a, b)
    assert busy == 13_736_621
    assert busy + sum(e - s for s, e in gaps) == b - a
    # no operation runs inside a gap, and gaps do not overlap
    for (s, e), nxt in zip(gaps, gaps[1:] + [(b, b)]):
        assert s < e <= nxt[0]
    for start, dur, *_ in ops:
        for s, e in gaps:
            assert start + dur <= s or start >= e


def test_busy_union_by_hand():
    ops = [[0, 10, "x", "other"], [5, 10, "y", "other"], [30, 5, "z", "other"],
           [32, 1, "w", "other"]]
    assert tr.busy_intervals(ops, 0, 40) == [[0, 15], [30, 35]]
    assert tr.busy_ns(ops, 2, 31) == 14
    assert tr.idle_gaps(ops, 0, 40) == [(15, 30), (35, 40)]


@pytest.mark.parametrize("text,cls", [
    ('%packed_matmul.3 = bf16[2,2048,128]{2,1,0} custom-call(bf16[2,2048,3584] '
     '%a), custom_call_target="tpu_custom_call"', "pallas"),
    ("%fusion.19 = bf16[4,1024,18944]{2,1,0} fusion(bf16[4,1024,3584] %a), "
     "kind=kOutput, calls=%fused_computation.252", "matmul"),
    ("%convolution.4 = f32[8,8]{1,0} convolution(f32[8,8] %a, f32[8,8] %b)",
     "matmul"),
    ("%fusion.7 = f32[6,2,128,512]{3,2,1,0} fusion(f32[6,2,128,512] %x), "
     "kind=kLoop, calls=%fused_computation.3", "other"),
    ("%while.180 = (s32[]) while((s32[]) %tuple.430), condition=%c, body=%b",
     "control"),
    ("%copy-start.2 = (s32[2]) copy-start(s32[2] %x.1)", "copy"),
    ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8] %x), replica_groups={}",
     "collective"),
])
def test_op_class(text, cls):
    assert tr.op_class(text) == cls


def test_short_name():
    assert tr.short_name("%fusion.522 = (bf16[4,512]{1,0}, f32[4]) fusion(x)") \
        == "%fusion.522 bf16[4,512]"


def test_class_totals_cover_every_operation(rec):
    r, win, ops = rec
    total = tr.op_seconds(ops, *win)
    parts = sum(tr.op_seconds(ops, *win, c) for c in
                ("matmul", "pallas", "collective", "copy", "other"))
    assert parts == pytest.approx(total)
    assert tr.op_seconds(ops, *win, "matmul") == pytest.approx(0.003896563)
    assert tr.op_seconds(ops, *win, "pallas") == pytest.approx(0.000378374)


def test_breakdown_lists(rec):
    r, win, ops = rec
    top = tr.top_ops(r, *win)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    gaps = tr.longest_gaps(r, *win)
    assert len(gaps) == 10 and gaps[0][1] == pytest.approx(0.00355814)
    labels = {h[2] for h in r["host"]}
    assert all(label in labels for label, _ in gaps)
