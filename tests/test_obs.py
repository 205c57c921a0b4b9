"""Unified tracing + metrics layer (ISSUE 7 tentpole).

Four layers, cheapest first:

  * span mechanics — nesting/parenting on one thread, isolation of the
    thread-local stacks under concurrency, the disabled tracer being a
    *true* no-op (same shared context manager object, zero state);
  * metrics registry — counter/gauge/histogram semantics and the
    percentile summaries the serve stats surface;
  * Chrome trace-event export — the JSON must satisfy the event schema
    ``validate_chrome_trace`` checks (the same check CI runs on the
    bench_serve artifact) and carry one metadata track per span track;
  * cross-process stitching — a traced ``HostDispatcher`` over the
    in-memory ``FakeHostTransport`` ships a ``TraceCtx`` with every run
    request and ingests the worker-shaped span replies under the dispatch
    span, rebased onto the dispatcher clock, on ``host{h}/``-prefixed
    tracks.
"""
import json
import pickle
import threading
import time

import pytest
from harness import DictPool, ScriptedExecutor, fake_pool

from repro.obs import (
    NULL_TRACER,
    Histogram,
    MetricsRegistry,
    TraceCtx,
    Tracer,
    percentile,
    trace_tiers,
    validate_chrome_trace,
)

# ---------------------------------------------------------------------------
# Span mechanics
# ---------------------------------------------------------------------------


def test_span_nesting_parents_and_ordering():
    tr = Tracer()
    with tr.span("a", cat="engine") as a:
        with tr.span("b", cat="engine") as b:
            with tr.span("c", cat="engine") as c:
                pass
        with tr.span("d", cat="engine") as d:
            pass
    by_name = {s.name: s for s in tr.spans()}
    assert set(by_name) == {"a", "b", "c", "d"}
    assert by_name["a"].parent_id is None
    assert by_name["b"].parent_id == a.span_id
    assert by_name["c"].parent_id == b.span_id
    assert by_name["d"].parent_id == a.span_id
    # every span roots at the outermost one
    assert {s.root_id for s in tr.spans()} == {a.span_id}
    # children close before (and start after) their parent
    assert a.start <= b.start and b.end <= a.end
    assert b.end <= d.start  # sequential siblings don't overlap
    assert c.span_id != d.span_id != b.span_id


def test_explicit_parent_overrides_thread_stack():
    tr = Tracer()
    with tr.span("root", cat="runner") as root:
        pass
    with tr.span("w", cat="runner", parent=root.span_id) as w:
        pass
    got = {s.name: s for s in tr.spans()}
    assert got["w"].parent_id == root.span_id


def test_concurrent_threads_have_isolated_stacks():
    tr = Tracer()
    barrier = threading.Barrier(2)
    ids = {}

    def work(name):
        with tr.span(f"outer.{name}", cat="engine") as o:
            barrier.wait()  # both threads are now inside their outer span
            with tr.span(f"inner.{name}", cat="engine") as i:
                pass
            ids[name] = (o.span_id, i.span_id)

    ts = [threading.Thread(target=work, args=(n,)) for n in ("t0", "t1")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    by_name = {s.name: s for s in tr.spans()}
    assert len(by_name) == 4
    for n in ("t0", "t1"):
        # each inner span parents to ITS thread's outer span, never the
        # other thread's (the stacks are thread-local)
        assert by_name[f"inner.{n}"].parent_id == ids[n][0]
        assert by_name[f"inner.{n}"].root_id == ids[n][0]
    assert ids["t0"][0] != ids["t1"][0]


def test_disabled_tracer_is_a_true_noop():
    cm1 = NULL_TRACER.span("anything", cat="engine", job_id=1)
    cm2 = NULL_TRACER.span("else", cat="serve")
    assert cm1 is cm2  # one shared context manager, no allocation
    with cm1 as sp:
        assert sp.span_id == 0
    NULL_TRACER.instant("marker", cat="engine")
    NULL_TRACER.add_span("ext", 0.0, 1.0, cat="serve")
    assert NULL_TRACER.spans() == []
    assert NULL_TRACER.current_span_id() is None
    # its metrics sink is stateless too
    c = NULL_TRACER.metrics.counter("x")
    c.inc()
    h = NULL_TRACER.metrics.histogram("y")
    h.record(1.0)
    assert NULL_TRACER.metrics.to_json() == {
        "counters": {}, "gauges": {}, "histograms": {}
    }


def test_add_span_and_instant():
    tr = Tracer()
    t = time.perf_counter()
    tr.add_span("serve.request", t, t + 0.5, cat="serve", track="row1",
                request_id=3)
    with tr.span("outer", cat="engine"):
        tr.instant("engine.launch", cat="engine", job_id=9)
    by_name = {s.name: s for s in tr.spans()}
    req = by_name["serve.request"]
    assert req.end - req.start == pytest.approx(0.5)
    assert req.args["request_id"] == 3
    mark = by_name["engine.launch"]
    assert mark.start == mark.end  # zero-duration
    assert mark.parent_id == by_name["outer"].span_id


def test_add_span_with_parent_joins_the_parents_tree():
    tr = Tracer()
    with tr.span("root", cat="host") as root:
        with tr.span("child", cat="executor") as child:
            pass
    t = time.perf_counter()
    tr.add_span("late", t, t + 0.1, cat="serve", parent=child.span_id)
    with tr.span("other", cat="engine"):
        pass
    late = next(s for s in tr.spans() if s.name == "late")
    assert late.parent_id == child.span_id
    assert late.root_id == root.span_id
    # the worker-side flush keeps a child recorded after the fact with
    # its tree, and leaves the unrelated root behind
    flushed = tr.pop_root(root.span_id)
    assert {d["name"] for d in flushed} == {"root", "child", "late"}
    assert [s.name for s in tr.spans()] == ["other"]
    # an unknown parent leaves the span a root of its own
    tr.add_span("orphan", t, t + 0.1, parent=10**6)
    orphan = next(s for s in tr.spans() if s.name == "orphan")
    assert orphan.root_id == orphan.span_id


# ---------------------------------------------------------------------------
# Profiler annotations
# ---------------------------------------------------------------------------


def _profiled_host_events(tmp_path, body):
    """{name: [(start_ns, end_ns)]} of the host events a CPU
    ``jax.profiler`` capture of ``body()`` holds."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.numpy.ones(2).block_until_ready()  # backend up before the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_enabled_span_writes_nested_profiler_annotations(tmp_path):
    tr = Tracer()

    def body():
        with tr.span("executor.train", cat="executor"):
            with tr.span("executor.dispatch", cat="executor", first_step=0):
                time.sleep(0.002)
            with tr.span("executor.wait", cat="executor"):
                time.sleep(0.002)

    events = _profiled_host_events(tmp_path, body)
    (train,) = events["executor.train"]
    (dispatch,) = events["executor.dispatch"]
    (wait,) = events["executor.wait"]
    # named as the spans, nested as they were opened
    assert train[0] <= dispatch[0] < dispatch[1] <= wait[0] < wait[1] \
        <= train[1]
    spans = {s.name: s for s in tr.spans()}
    for name, (a, b) in (("executor.dispatch", dispatch),
                         ("executor.wait", wait)):
        assert (b - a) / 1e9 == pytest.approx(
            spans[name].end - spans[name].start, abs=5e-4)


def test_disabled_span_writes_no_profiler_annotation(tmp_path):
    def body():
        with NULL_TRACER.span("executor.train", cat="executor"):
            with NULL_TRACER.span("executor.wait", cat="executor"):
                time.sleep(0.001)

    events = _profiled_host_events(tmp_path, body)
    assert "executor.train" not in events and "executor.wait" not in events


# ---------------------------------------------------------------------------
# Garbage-collection pauses
# ---------------------------------------------------------------------------


def _garbage():
    """Reference cycles only the collector frees."""
    for _ in range(2000):
        a = []
        a.append(a)


def test_watch_gc_records_a_collection_under_the_open_span(monkeypatch):
    import gc

    from repro.obs import trace as trace_mod

    monkeypatch.setattr(trace_mod, "GC_SPAN_MIN_S", 0.0)
    tr = Tracer()
    was_enabled = gc.isenabled()
    gc.disable()  # the cycles wait for the forced collection
    try:
        with tr.watch_gc():
            with tr.span("runner.segment", cat="runner",
                         track="unit0") as seg:
                _garbage()
                gc.collect()
    finally:
        if was_enabled:
            gc.enable()
    gcs = [s for s in tr.spans() if s.name == "process.gc"]
    full = [s for s in gcs if s.args["generation"] == 2
            and s.parent_id == seg.span_id]
    assert full, gcs
    sp = full[-1]
    assert sp.cat == "process" and sp.track == "unit0"
    assert sp.root_id == seg.root_id
    assert sp.args["collected"] >= 2000
    assert seg.start <= sp.start <= sp.end <= seg.end
    assert tr.metrics.histogram("process.gc_pause").count >= len(gcs)


def test_watch_gc_keeps_short_pauses_out_of_the_spans():
    import gc

    tr = Tracer()
    with tr.watch_gc():
        gc.collect(0)  # a young collection: far under a millisecond
    assert tr.metrics.histogram("process.gc_pause").count >= 1
    short = [v for v in tr.metrics.histogram("process.gc_pause").values()
             if v < 1e-3]
    spans = [s for s in tr.spans() if s.name == "process.gc"]
    assert len(spans) == tr.metrics.histogram("process.gc_pause").count \
        - len(short)


def test_watch_gc_hooks_once_and_restores_gc_callbacks():
    import gc

    before = list(gc.callbacks)
    tr = Tracer()
    with tr.watch_gc():
        assert len(gc.callbacks) == len(before) + 1
        with tr.watch_gc():  # nested watches share one hook
            assert len(gc.callbacks) == len(before) + 1
        assert len(gc.callbacks) == len(before) + 1
    assert gc.callbacks == before
    with pytest.raises(RuntimeError):
        with tr.watch_gc():
            raise RuntimeError("boom")
    assert gc.callbacks == before


def test_watch_gc_on_null_tracer_adds_no_hook():
    import gc

    before = list(gc.callbacks)
    cm1 = NULL_TRACER.watch_gc()
    cm2 = NULL_TRACER.watch_gc()
    assert cm1 is cm2  # the shared no-op context manager
    with cm1:
        assert gc.callbacks == before
        _garbage()
        gc.collect()
    assert gc.callbacks == before
    assert NULL_TRACER.spans() == []
    assert NULL_TRACER.metrics.to_json()["histograms"] == {}


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_histogram_percentiles():
    h = Histogram("t")
    for v in range(1, 101):  # 1..100
        h.record(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["p50"] == pytest.approx(50.5)
    assert s["p95"] == pytest.approx(95.05)
    assert s["p99"] == pytest.approx(99.01)
    empty = Histogram("e").summary()
    assert empty["count"] == 0
    assert empty["p50"] != empty["p50"]  # NaN
    assert percentile([1.0, 2.0], 0.5) == pytest.approx(1.5)


def test_registry_get_or_create_and_json():
    m = MetricsRegistry()
    m.counter("hits").inc()
    m.counter("hits").inc(2)
    assert m.counter("hits").value == 3
    m.gauge("free").set(4)
    m.gauge("free").set(2)
    m.histogram("lat").record(0.25)
    blob = m.to_json()
    assert blob["counters"]["hits"] == 3
    assert blob["gauges"]["free"] == 2
    assert blob["histograms"]["lat"]["count"] == 1
    # sampled gauges keep a (t, v) history for counter tracks
    assert [v for _, v in m.gauge("free").samples()] == [4, 2]


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------


def test_chrome_export_schema_and_tracks(tmp_path):
    tr = Tracer()
    with tr.span("engine.plan", cat="engine", track="main"):
        with tr.span("runner.segment", cat="runner", track="unit0",
                     job_id=1):
            pass
    tr.metrics.gauge("cluster.free_units").set(3)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    obj = json.loads(path.read_text())
    assert validate_chrome_trace(obj) == []
    assert trace_tiers(obj) == ["engine", "runner"]
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 2
    child = next(e for e in xs if e["name"] == "runner.segment")
    parent = next(e for e in xs if e["name"] == "engine.plan")
    assert child["args"]["parent_span"] == parent["args"]["span_id"]
    assert child["dur"] >= 0 and child["ts"] >= parent["ts"]
    # one thread_name metadata row per track, plus the gauge counter track
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"main", "unit0", "counter:cluster.free_units"} <= names
    assert any(e["ph"] == "C" for e in obj["traceEvents"])
    assert obj["otherData"]["trace_id"] == tr.trace_id


def test_validate_rejects_malformed_events():
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": -5.0, "dur": 1},
        {"ph": "Z", "name": "b", "pid": 1},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 1},
    ]}
    problems = validate_chrome_trace(bad)
    assert len(problems) == 3


# ---------------------------------------------------------------------------
# Ingest + cross-process stitching
# ---------------------------------------------------------------------------


def test_ingest_remaps_ids_offsets_clocks_and_prefixes_tracks():
    tr = Tracer()
    with tr.span("dispatch.segment", cat="dispatch", track="host0") as d:
        pass
    worker_spans = [
        {"name": "host0.segment", "cat": "host", "track": "",
         "span_id": 1, "parent_id": None, "root_id": 1,
         "start": 0.0, "end": 2.0, "args": {}},
        {"name": "executor.train", "cat": "executor", "track": "unit1",
         "span_id": 2, "parent_id": 1, "root_id": 1,
         "start": 0.5, "end": 1.5, "args": {}},
    ]
    tr.ingest(worker_spans, offset=100.0, parent_id=d.span_id,
              track_prefix="host0/")
    by_name = {s.name: s for s in tr.spans()}
    root = by_name["host0.segment"]
    child = by_name["executor.train"]
    assert root.parent_id == d.span_id  # stitched under the dispatch span
    assert root.start == 100.0 and root.end == 102.0  # rebased clock
    assert child.parent_id == root.span_id  # remapped, not the worker's 1
    assert child.span_id != 2 and root.span_id != 1
    assert root.track == "host0/worker" and child.track == "host0/unit1"
    assert child.root_id == root.span_id


def test_trace_ctx_rides_the_wire_and_worker_spans_stitch():
    from repro.cluster.multihost import HostDispatcher

    from test_multihost import _cfg, _fake_factory, _seg

    tracer = Tracer()
    made = []
    cfgs = {i: _cfg(alpha=8.0 * (i + 1)) for i in range(4)}
    segs = [_seg(job_id=i, cids=(i,), units=(i,)) for i in range(4)]
    pool = DictPool()
    with HostDispatcher(
        [2, 2], transport_factory=_fake_factory(made), tracer=tracer
    ) as disp:
        result = disp.run(
            segs, cfgs, {i: 3 for i in range(4)}, None, None,
            seq=16, pool=pool,
        )
    assert len(result.records) == 4
    # every run request shipped a pickled TraceCtx of THIS trace
    ctxs = [c for trp in made for c in trp.trace_ctxs]
    assert len(ctxs) == 4
    for ctx in ctxs:
        assert isinstance(ctx, TraceCtx)
        assert ctx.trace_id == tracer.trace_id
        assert isinstance(ctx.parent, int)  # the dispatch span's id
    assert pickle.loads(pickle.dumps(ctxs[0])) == ctxs[0]

    spans = tracer.spans()
    dispatch = {s.span_id: s for s in spans if s.name == "dispatch.segment"}
    assert len(dispatch) == 4
    hosts = [s for s in spans if s.cat == "host"]
    assert len(hosts) == 4
    for h in hosts:
        # stitched: the worker root's parent IS a dispatch span, and the
        # worker clock was rebased inside the dispatch window
        assert h.parent_id in dispatch
        d = dispatch[h.parent_id]
        assert h.track.startswith(f"host{d.args['host']}/")
        assert h.start >= d.start - 1e-6
    assert {h.name for h in hosts} == {"host0.segment", "host1.segment"}
    # the fabricated executor child rides along, reparented under its root
    execs = [s for s in spans if s.name == "executor.segment"]
    assert len(execs) == 4
    host_ids = {h.span_id for h in hosts}
    assert all(e.parent_id in host_ids for e in execs)
    # the whole thing exports as a valid multi-tier chrome trace
    obj = tracer.to_chrome()
    assert validate_chrome_trace(obj) == []
    assert {"dispatch", "host", "executor", "runner"} <= set(trace_tiers(obj))


def test_untraced_dispatch_ships_no_ctx_or_spans():
    from repro.cluster.multihost import HostDispatcher

    from test_multihost import _cfg, _fake_factory, _seg

    made = []
    segs = [_seg(job_id=0, cids=(0,), units=(0,))]
    with HostDispatcher([1], transport_factory=_fake_factory(made)) as disp:
        disp.run(segs, {0: _cfg()}, {0: 3}, None, None, seq=16,
                 pool=DictPool())
    assert made[0].trace_ctxs == [None]


# ---------------------------------------------------------------------------
# Runner integration (scripted executor, no jax)
# ---------------------------------------------------------------------------


def test_runner_emits_spans_and_free_units_gauge():
    from repro.cluster.runner import ClusterRunner
    from repro.configs.base import LoraConfig, get_config, reduced
    from repro.sched.cost_model import A100_40G, CostModel
    from test_multihost import _seg

    cfg = reduced(get_config("qwen25-7b"))
    prior = CostModel(cfg, A100_40G)
    tracer = Tracer()
    runner = ClusterRunner(
        ScriptedExecutor(prior), fake_pool(2), concurrent=True,
        tracer=tracer,
    )
    segs = [_seg(job_id=i, cids=(i,), units=(i,)) for i in range(2)]
    cfgs = {i: LoraConfig(rank=8, alpha=8.0, seq_len=16) for i in range(2)}
    runner.run(segs, cfgs, {0: 3, 1: 3}, None, None, seq=16)
    by_name = {}
    for s in tracer.spans():
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["runner.run"]) == 1
    assert len(by_name["runner.segment"]) == 2
    assert len(by_name["runner.wait_units"]) == 2
    run_id = by_name["runner.run"][0].span_id
    # pool-thread segment spans stitch under the dispatcher-thread run span
    assert all(s.parent_id == run_id for s in by_name["runner.segment"])
    samples = tracer.metrics.gauge("cluster.free_units").samples()
    assert samples and samples[-1][1] == 2  # all units returned at the end
