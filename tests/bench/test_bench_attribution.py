"""The readers that name the chip's idle time: what a job switch costs
the chip (``executor.switch_idle_ms``), idle time inside the step loop
(``executor.stall_ms_per_pass``) and the collector's pauses
(``process.gc_ms_per_pass``), on the small recorded trace and on a record
made by hand."""
import os
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench.trace import attribution  # noqa: E402
from bench.trace import reduce as tr  # noqa: E402

RECORDED = os.path.join(ROOT, "bench", "trace", "recorded_small.json.gz")
NAMES = ("executor.switch_idle_ms", "executor.stall_ms_per_pass",
         "process.gc_ms_per_pass", "device.idle_pct")


def readers():
    return {n: harness.load_module(os.path.join(ROOT, "bench", "metrics",
                                                f"{n}.py"))
            for n in NAMES}


def context(record, passes):
    """The reader's context as ``harness.read_trace`` builds it."""
    win = tr.window(record)
    trace = tr.clip(record, *win)
    busy = [tr.busy_ns(ops, *win) / 1e9 for ops in trace["devices"].values()]
    return SimpleNamespace(trace=trace, window_ns=win, passes=passes,
                           busy_s=sum(busy) / len(busy),
                           traced_window_s=(win[1] - win[0]) / 1e9)


def by_hand():
    """A 1000 ns window on two chips: chip 0 idle 700 ns, of which 400 ns
    inside ``executor.train``; chip 1 busy throughout."""
    op = "other"
    return {
        "devices": {
            "0": [[100, 100, "a", op], [300, 100, "b", op], [700, 100, "c", op]],
            "1": [[0, 1000, "d", op]],
        },
        "host": [
            [-50, 100, "span.process.gc"],
            [0, 1000, "bench.window"],
            [0, 600, "span.runner.segment"],
            [150, 350, "span.executor.train"],
            [600, 10, "span.process.gc"],
            [650, 600, "span.runner.segment"],
            [700, 400, "span.runner.segment"],
            [750, 450, "span.executor.train"],
        ],
    }


def identity_ms(values, ctx, segments):
    """Both idle readers put back together, and ``device.idle_pct`` of
    the traced window, in ms."""
    parts = (values["executor.switch_idle_ms"] * segments
             + values["executor.stall_ms_per_pass"] * ctx.passes)
    whole = values["device.idle_pct"] / 100 * ctx.traced_window_s * 1e3
    return parts, whole


def test_readers_on_a_record_by_hand():
    ctx = context(by_hand(), passes=2)
    got = {n: m.read(ctx) for n, m in readers().items()}
    # chip 0 idles [0,100), [200,300), [400,700) and [800,1000); of that,
    # [200,300), [400,500) and [800,1000) lie inside executor.train.
    # Chip 1 never idles, so the mean over chips halves both parts
    assert got["executor.switch_idle_ms"] == pytest.approx(300 / 2 / 3 / 1e6)
    assert got["executor.stall_ms_per_pass"] == pytest.approx(400 / 2 / 2 / 1e6)
    # the pause that began before the window counts from its start
    assert got["process.gc_ms_per_pass"] == pytest.approx(60 / 2 / 1e6)
    assert got["device.idle_pct"] == pytest.approx(35.0)
    parts, whole = identity_ms(got, ctx, segments=3)
    assert parts == pytest.approx(whole, rel=1e-9)


def test_readers_on_the_recorded_trace():
    ctx = context(tr.load(RECORDED), passes=1)
    got = {n: m.read(ctx) for n, m in readers().items()}
    # two segments meet the 40 ms window; one executor.train ends in it
    assert attribution.count(ctx.trace, "span.runner.segment") == 2
    assert got["executor.switch_idle_ms"] == pytest.approx(24.376818 / 2)
    assert got["executor.stall_ms_per_pass"] == pytest.approx(2.156602)
    # recorded before the program watched the collector: no pause there
    assert got["process.gc_ms_per_pass"] == 0.0
    parts, whole = identity_ms(got, ctx, segments=2)
    assert parts == pytest.approx(whole, rel=1e-9)
    assert whole == pytest.approx(40.270041 - 13.736621)


def test_readers_report_nothing_without_their_spans():
    record = by_hand()
    record["host"] = [h for h in record["host"]
                      if h[2] in ("bench.window", "span.process.gc")]
    ctx = context(record, passes=2)
    got = {n: m.read(ctx) for n, m in readers().items()}
    assert got["executor.switch_idle_ms"] is None
    assert got["executor.stall_ms_per_pass"] is None


def test_gc_reader_reports_nothing_for_a_program_without_the_hook(
        monkeypatch):
    from repro.obs import Tracer

    monkeypatch.delattr(Tracer, "watch_gc")
    ctx = context(by_hand(), passes=2)
    assert readers()["process.gc_ms_per_pass"].read(ctx) is None


def test_overlap_of_interval_unions():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [[5, 25], [28, 45]]
    assert attribution.overlap_ns(a, b) == 5 + 5 + 2 + 5
    assert attribution.overlap_ns(a, []) == 0
    assert attribution.intervals(
        {"host": [[0, 10, "x"], [5, 10, "x"], [30, 100, "x"], [0, 5, "y"]]},
        "x", 0, 50) == [[0, 15], [30, 50]]
