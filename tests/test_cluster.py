"""Cluster subsystem: device-pool accounting, planned unit assignment,
executor compile-cache behavior, and — on a multi-device (forced) host —
concurrent-vs-sequential bit-exactness of per-adapter losses.

The multi-device tests skip on a 1-device host; CI runs the fast set a
second time under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so
the concurrent path is exercised on every PR.
"""
import jax
import numpy as np
import pytest
from harness import InjectedCrash, ScriptedExecutor, fake_pool

from repro.cluster import (
    ClusterRunner,
    DevicePool,
    SliceExecutor,
    assign_units,
    peak_overlap,
    pick_host_units,
)
from repro.configs.base import LoraConfig, default_search_space, get_config, reduced
from repro.core.adapter import pack_meta
from repro.launch.mesh import make_host_mesh, slice_mesh
from repro.models.model import init_model
from repro.sched.cost_model import A100_40G, CostModel
from repro.sched.engine import ExecutionEngine, poisson_trace
from repro.sched.planner import Schedule, ScheduledJob

MULTIDEV = jax.device_count() >= 4


# ---------------------------------------------------------------------------
# Device pool accounting
# ---------------------------------------------------------------------------


def test_pool_acquire_release_accounting():
    pool = fake_pool(8)  # accounting needs no jax devs
    assert pool.total == 8 and pool.free == 8
    s1 = pool.acquire(3)
    assert s1.units == (0, 1, 2) and s1.width == 3
    s2 = pool.acquire(5)
    assert s2.units == (3, 4, 5, 6, 7)
    assert pool.free == 0
    assert pool.try_acquire(1) is None  # exhausted
    pool.release(s1)
    assert pool.free == 3
    s3 = pool.try_acquire(2)
    assert s3 is not None and set(s3.units) <= {0, 1, 2}
    pool.release(s2)
    pool.release(s3)
    assert pool.free == 8


def test_pool_exhaustion_and_errors():
    pool = fake_pool(4)
    with pytest.raises(ValueError, match="only 4"):
        pool.acquire(5)
    s = pool.acquire(4)
    with pytest.raises(TimeoutError):
        pool.acquire(1, timeout=0.01)
    pool.release(s)
    with pytest.raises(RuntimeError, match="double release"):
        pool.release(s)


def test_pool_acquire_specific_units():
    pool = fake_pool(4)
    s = pool.acquire_units((1, 3))
    assert s.units == (1, 3) and s.devices == ("fake1", "fake3")
    with pytest.raises(TimeoutError, match=r"\[1\]"):
        pool.acquire_units((0, 1), timeout=0.01)
    pool.release(s)
    assert pool.free == 4


def test_pool_lease_releases_on_crash():
    """Acquisition as a context manager (ISSUE 4 satellite): the unit comes
    back even when the body dies — no release-after-crash leak."""
    pool = fake_pool(4)
    with pytest.raises(InjectedCrash):
        with pool.lease(2):
            assert pool.free == 2
            raise InjectedCrash("boom")
    assert pool.free == 4
    with pytest.raises(InjectedCrash):
        with pool.lease_units((0, 3)):
            raise InjectedCrash("boom")
    assert pool.free == 4
    s = pool.acquire(1)  # adopt-an-acquired-slice variant
    with pytest.raises(InjectedCrash):
        with pool.held(s):
            raise InjectedCrash("boom")
    assert pool.free == 4


def test_pool_map_units_wraps_degenerate():
    pool = fake_pool(1)
    assert pool.map_units((0, 3, 5)) == (0,)  # everything folds onto dev 0


def test_runner_crash_releases_units_and_raises():
    """Regression (ISSUE 4): a segment whose executor dies mid-run must not
    leak its device units — the run raises the crash AND the pool drains
    back to fully free (ClusterRunner asserts this itself on the success
    path; here we check the crash path)."""
    from repro.sched.engine import JobSegment

    prior = CostModel(get_config("qwen25-7b"), A100_40G)
    cfgs = {
        0: LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3, batch_size=1, seq_len=16),
        1: LoraConfig(rank=8, alpha=16.0, learning_rate=1e-3, batch_size=1, seq_len=16),
    }
    segs = [
        JobSegment(
            job_id=i, config_ids=(i,), degree=1, start=float(i), end=i + 1.0,
            start_steps=(0,), run_steps=2, done_ids=(i,), units=(i,),
        )
        for i in range(2)
    ]
    for concurrent in (False, True):
        pool = fake_pool(4)
        ex = ScriptedExecutor(prior, crash_on=lambda idx, seg: idx == 0)
        runner = ClusterRunner(ex, pool, concurrent=concurrent)
        with pytest.raises(InjectedCrash):
            runner.run(segs, cfgs, {0: 2, 1: 2}, None, None, seq=16)
        assert pool.free == pool.total, (concurrent, pool.free)


def test_pick_host_units_host_disjoint_and_best_fit():
    free = [0, 1, 4, 5, 6, 7]
    # host_size None: plain lowest-first (single-host behavior)
    assert pick_host_units(free, 3, None) == (0, 1, 4)
    # degree 2 fits host 0 (2 free) better than host 1 (4 free): best-fit
    assert pick_host_units(free, 2, 4) == (0, 1)
    assert pick_host_units(free, 4, 4) == (4, 5, 6, 7)
    # no single host has 3 free units on host_size=2 pools
    assert pick_host_units([0, 3, 4, 7], 2, 2) is None
    assert pick_host_units([0, 1], 4, 4) is None


# ---------------------------------------------------------------------------
# Unit assignment (static + online planner)
# ---------------------------------------------------------------------------


def test_assign_units_disjoint_and_reusing():
    units = assign_units(
        [(0.0, 2.0, 2), (0.0, 1.0, 2), (1.0, 2.0, 2), (2.0, 3.0, 4)], 4
    )
    assert units[0] == (0, 1)
    assert units[1] == (2, 3)
    assert units[2] == (2, 3)  # reuses the units freed at t=1
    assert units[3] == (0, 1, 2, 3)
    with pytest.raises(RuntimeError, match="oversubscribe"):
        assign_units([(0.0, 1.0, 3), (0.0, 1.0, 2)], 4)


def test_assign_units_host_aware():
    units = assign_units(
        [(0.0, 1.0, 2), (0.0, 1.0, 1), (0.0, 1.0, 1)], 4, host_size=2
    )
    # the degree-2 job gets a whole host; the singles share the other
    assert units[0] in ((0, 1), (2, 3))
    for u in units:
        assert len({x // 2 for x in u}) == 1  # host-disjoint
    assert sorted(x for u in units for x in u) == [0, 1, 2, 3]
    # a degree-2 interval that only fits by spanning hosts must raise
    with pytest.raises(RuntimeError, match="host"):
        assign_units(
            [(0.0, 2.0, 1), (0.0, 1.0, 1), (1.0, 2.0, 2), (0.0, 2.0, 1)],
            4,
            host_size=2,
        )


def test_plan_online_assigns_disjoint_units():
    cm = CostModel(get_config("command-r-35b"), A100_40G)
    eng = ExecutionEngine(cm, 8)
    configs = default_search_space(16, 1024)
    steps = np.random.RandomState(0).choice([200, 500, 1000, 2000], size=16)
    trace = poisson_trace(configs, 800.0, seed=1, steps=steps)
    sched = eng.plan_online(trace, 1024, 1000, migration_budget=2)
    assert all(len(s.units) == s.degree for s in sched.segments)
    sched.validate()  # checks unit range + overlap disjointness
    # corrupting a unit assignment must be caught
    import dataclasses

    bad = dataclasses.replace(
        sched.segments[0], units=(99,) * sched.segments[0].degree
    )
    sched.segments[0] = bad
    with pytest.raises(RuntimeError, match="units"):
        sched.validate()


def test_resume_deps_latest_writer_no_self_dep():
    """Regression: a zero-step re-preemption re-writes the same (cid, step)
    checkpoint key; the resumer must depend on the latest *earlier* writer,
    never on itself (which would deadlock the dispatcher)."""
    from repro.cluster import resume_deps
    from repro.sched.engine import JobSegment

    def seg(job_id, start, start_step, run_steps, preempted):
        return JobSegment(
            job_id=job_id, config_ids=(0,), degree=1,
            start=start, end=start + 1.0,
            start_steps=(start_step,), run_steps=run_steps,
            done_ids=() if preempted else (0,), preempted=preempted,
        )

    order = [
        seg(0, 0.0, 0, 3, True),   # writes (0, 3)
        seg(1, 1.0, 3, 0, True),   # resumes @3, preempted after 0 steps:
                                   # re-writes (0, 3)
        seg(2, 2.0, 3, 5, False),  # resumes @3: depends on seg 1, not 0
    ]
    assert resume_deps(order) == [[], [0], [1]]


# ---------------------------------------------------------------------------
# Executor compile cache
# ---------------------------------------------------------------------------


def test_executor_cache_hits_same_shape_packs():
    """Two packs with identical (n, shape) but different hyperparameters
    share one step build — hyperparameters are runtime args."""
    cfg = reduced(get_config("qwen25-7b"))
    ex = SliceExecutor()
    s1, _ = ex.step_fn(cfg, 2)
    s2, _ = ex.step_fn(cfg, 2)
    assert s1 is s2
    assert ex.n_builds == 1 and ex.n_hits == 1
    s3, _ = ex.step_fn(cfg, 3)  # different pack width: new build
    assert s3 is not s1
    assert ex.n_builds == 2


@pytest.mark.parametrize("arch", ["qwen25-7b", "minicpm3-4b", "jamba-v0.1-52b"])
def test_pack_template_matches_init_model(arch):
    """The template is built without the base model, yet its adapters and
    fresh optimizer state equal, bit for bit, the adapter tree of a full
    ``init_model`` with the same seed."""
    from repro.train.optimizer import init_opt_state

    cfg = reduced(get_config(arch))
    configs = [LoraConfig(rank=r) for r in (8, 16, 32)]
    meta = pack_meta(configs)
    _, ref = init_model(jax.random.PRNGKey(5), cfg, meta)
    lora, opt = SliceExecutor().pack_template(cfg, configs, seed=5)
    ref_opt = init_opt_state(ref, n_pack=meta.n)
    for want, got in ((ref, lora), (ref_opt, opt)):
        assert jax.tree.structure(want) == jax.tree.structure(got)
        for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_executor_cache_integration_run_segments():
    """Running two same-shape packs through the engine builds one step and
    one pack template; a third, different-shape pack adds one more."""
    cfg = reduced(get_config("qwen25-7b"))
    cm = CostModel(cfg, A100_40G)
    configs = [
        LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3, batch_size=1, seq_len=16),
        LoraConfig(rank=8, alpha=16.0, learning_rate=5e-4, batch_size=1, seq_len=16),
        LoraConfig(rank=16, alpha=16.0, learning_rate=1e-3, batch_size=1, seq_len=16),
    ]
    jobs = [ScheduledJob((i,), 1, float(i), float(i + 1)) for i in range(3)]
    sched = Schedule(jobs, 3.0, 1)
    eng = ExecutionEngine(cm, 1)
    base, _ = init_model(jax.random.PRNGKey(0), cfg, pack_meta(configs))
    ex = SliceExecutor()
    runner = ClusterRunner(ex, DevicePool(jax.devices()[:1]), concurrent=False)
    records, _ = eng.run_local(
        sched, configs, cfg, base, n_steps=2, seq=16, runner=runner
    )
    assert len(records) == 3
    # 3 single-config packs, all n=1: ONE step build; but two r_buckets
    # (8 and 16) -> two pack templates
    assert ex.n_builds == 1
    assert ex.n_hits == 2
    assert len(ex._templates) == 2


def test_executor_keys_steps_by_mixed_batch_tuple():
    """Packs of one width and rank tuple but different mixed batch tuples
    run different rows, so they get different steps; uniform batch tuples
    keep sharing one. Their records have one form, and each
    ``executor.train`` span says how many rows the step computed."""
    from repro.obs import Tracer

    cfg = reduced(get_config("qwen25-7b"))
    ex = SliceExecutor(tracer=Tracer())
    s12, _ = ex.step_fn(cfg, 2, ranks=(8, 8), batch_sizes=(1, 2))
    s21, _ = ex.step_fn(cfg, 2, ranks=(8, 8), batch_sizes=(2, 1))
    s11, _ = ex.step_fn(cfg, 2, ranks=(8, 8), batch_sizes=(1, 1))
    s22, _ = ex.step_fn(cfg, 2, ranks=(8, 8), batch_sizes=(2, 2))
    assert s12 is not s21 and s11 is s22 and s12 is not s11
    packs = {bs: [LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3,
                             batch_size=b, seq_len=16) for b in bs]
             for bs in ((1, 2), (2, 1), (2, 2))}
    base, _ = init_model(jax.random.PRNGKey(0), cfg, pack_meta(packs[(1, 2)]))
    res = {bs: ex.train_pack(cfg, c, n_steps=2, seq=16, base=base)
           for bs, c in packs.items()}
    for r in res.values():
        assert r.losses.shape == (2,) and np.isfinite(r.losses).all()
        assert jax.tree.structure(r.lora) == jax.tree.structure(
            res[(1, 2)].lora)
        for x, y in zip(jax.tree.leaves((r.lora, r.opt)),
                        jax.tree.leaves((res[(1, 2)].lora, res[(1, 2)].opt))):
            assert x.shape == y.shape and x.dtype == y.dtype
    spans = ex.tracer.spans()
    trains = [s for s in spans if s.name == "executor.train"]
    assert [(s.args["rows"], s.args["real_rows"]) for s in trains] == [
        (3, 3), (3, 3), (4, 4)]
    # each of the three steps is warmed (compiled) before its own window
    assert sum(s.name == "executor.compile" for s in spans) == 3


def test_grid_plan_compiles_five_step_programs():
    """The planner packs the ten-point grid (ranks 8-128 x batch 1-2) at
    qwen2.5-7b widths into five mixed-batch jobs on one chip; run at a small
    size, those jobs compile five step programs, each computing only the
    three real rows, and a second pass compiles nothing."""
    from repro.obs import Tracer
    from repro.sched.cost_model import tpu_prior
    from repro.sched.planner import plan

    configs = [LoraConfig(rank=r, alpha=float(r) * a, learning_rate=lr,
                          batch_size=b, seq_len=16)
               for r in (8, 16, 32, 64, 128)
               for b, a, lr in ((1, 1.0, 1e-4), (2, 0.25, 4e-4))]
    sched = plan(CostModel(get_config("qwen25-7b").replace(n_layers=6),
                           tpu_prior("TPU v5 lite")), configs, 1, 1024, 4)
    assert sorted(sorted(configs[i].batch_size for i in j.config_ids)
                  for j in sched.jobs) == [[1, 2]] * 5
    cfg = reduced(get_config("qwen25-7b"))
    tracer = Tracer()
    runner = ClusterRunner(SliceExecutor(), DevicePool(jax.devices()[:1]),
                           concurrent=False, tracer=tracer)
    base, _ = init_model(jax.random.PRNGKey(0), cfg, pack_meta(configs[:1]))
    eng = ExecutionEngine(CostModel(cfg, A100_40G), 1)
    for _ in range(2):
        eng.run_local(sched, configs, cfg, base, n_steps=1, seq=16,
                      runner=runner)
    spans = tracer.spans()
    assert sum(s.name == "executor.compile" for s in spans) == 5
    trains = [s for s in spans if s.name == "executor.train"]
    assert len(trains) == 10
    assert all(s.args["rows"] == s.args["real_rows"] == 3 for s in trains)


def test_executor_spans_tile_each_segment(tmp_path, monkeypatch):
    """Each executed segment's spans: template, placement, first batches,
    the step loop, the losses' copy to the host and the save under
    ``executor.segment``; the chunked
    dispatches, the later batches and the final wait under
    ``executor.train``, one ``executor.train`` per ``runner.segment``."""
    from repro.cluster import executor as executor_mod
    from repro.obs import Tracer
    from repro.train.checkpoint import CheckpointPool

    monkeypatch.setattr(executor_mod, "PREGEN_CHUNK", 2)  # 3 steps: 2 chunks
    cfg = reduced(get_config("qwen25-7b"))
    cm = CostModel(cfg, A100_40G)
    configs = [
        LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3, batch_size=1, seq_len=16),
        LoraConfig(rank=16, alpha=16.0, learning_rate=1e-3, batch_size=1, seq_len=16),
    ]
    jobs = [ScheduledJob((i,), 1, float(i), float(i + 1)) for i in range(2)]
    base, _ = init_model(jax.random.PRNGKey(0), cfg, pack_meta(configs))
    tracer = Tracer()
    runner = ClusterRunner(SliceExecutor(), DevicePool(jax.devices()[:1]),
                           concurrent=False, tracer=tracer)
    ExecutionEngine(cm, 1).run_local(
        Schedule(jobs, 2.0, 1), configs, cfg, base, n_steps=3, seq=16,
        runner=runner, pool=CheckpointPool(str(tmp_path / "pool")),
    )
    spans = tracer.spans()
    by_id = {s.span_id: s for s in spans}

    def named(name):
        return [s for s in spans if s.name == name]

    def children(parent):
        return sorted((s for s in spans if s.parent_id == parent.span_id),
                      key=lambda s: s.start)

    segs, runs = named("executor.segment"), named("runner.segment")
    assert len(segs) == len(runs) == 2
    for run in runs:
        inner = [t for t in named("executor.train")
                 if run.start <= t.start and t.end <= run.end
                 and t.track == run.track]
        assert len(inner) == 1
    for seg in segs:
        assert by_id[seg.parent_id].name == "runner.segment"
        kids = [s.name for s in children(seg) if s.name != "executor.compile"]
        assert kids == ["executor.template", "executor.place",
                        "executor.batches", "executor.train",
                        "executor.losses", "executor.save"]
        for kid in children(seg):
            assert kid.track == seg.track and kid.cat == "executor"
            assert seg.start <= kid.start <= kid.end <= seg.end
        first = next(s for s in children(seg) if s.name == "executor.batches")
        assert first.args["n_steps"] == 2
        train = next(s for s in children(seg) if s.name == "executor.train")
        loop = children(train)
        assert [s.name for s in loop] == ["executor.dispatch",
                                          "executor.batches",
                                          "executor.dispatch",
                                          "executor.wait"]
        assert [(s.args["first_step"], s.args["n_steps"]) for s in loop
                if s.name == "executor.dispatch"] == [(0, 2), (2, 1)]
        assert loop[1].args["n_steps"] == 1
        assert all(s.track == seg.track for s in loop)
        save = next(s for s in children(seg) if s.name == "executor.save")
        # the pool's own span keeps its place, under the save
        assert [s.name for s in children(save)] == ["executor.checkpoint_save"]
    # the runner watched the collector: every collection is in the histogram
    assert "process.gc_pause" in tracer.metrics.to_json()["histograms"]


# ---------------------------------------------------------------------------
# Mesh helpers
# ---------------------------------------------------------------------------


def test_make_host_mesh_clear_error():
    need = 4 * jax.device_count()
    with pytest.raises(RuntimeError) as ei:
        make_host_mesh(4, jax.device_count())
    msg = str(ei.value)
    assert str(need) in msg and str(jax.device_count()) in msg
    assert "xla_force_host_platform_device_count" in msg


def test_slice_mesh_subset():
    devs = jax.devices()
    m = slice_mesh(devs, 1)
    assert m.devices.shape == (1, 1)
    with pytest.raises(RuntimeError, match="only"):
        slice_mesh(devs[:1], 2)


# ---------------------------------------------------------------------------
# Concurrent vs sequential on a multi-device host
# ---------------------------------------------------------------------------


@pytest.mark.skipif(not MULTIDEV, reason="needs >=4 (forced) host devices")
def test_concurrent_matches_sequential_bitexact():
    """The acceptance property: a 4-group schedule executed concurrently on
    disjoint mesh slices produces bit-identical per-adapter losses to the
    sequential baseline, and the segments really overlap."""
    cfg = reduced(get_config("qwen25-7b"))
    cm = CostModel(cfg, A100_40G)
    seq = 16
    grid = [
        LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3, batch_size=1, seq_len=seq),
        LoraConfig(rank=8, alpha=16.0, learning_rate=5e-4, batch_size=1, seq_len=seq),
        LoraConfig(rank=16, alpha=16.0, learning_rate=1e-3, batch_size=1, seq_len=seq),
        LoraConfig(rank=16, alpha=32.0, learning_rate=2e-4, batch_size=1, seq_len=seq),
    ]
    jobs = [ScheduledJob((i,), 1, 0.0, 1.0) for i in range(4)]
    sched = Schedule(jobs, 1.0, 4)
    eng = ExecutionEngine(cm, 4)
    base, _ = init_model(jax.random.PRNGKey(0), cfg, pack_meta(grid))
    ex = SliceExecutor()  # shared: both modes use the same compiled steps
    devs = jax.devices()[:4]
    out = {}
    for mode in (False, True):
        runner = ClusterRunner(ex, DevicePool(devs), concurrent=mode)
        records, _ = eng.run_local(
            sched, grid, cfg, base, n_steps=3, seq=seq, runner=runner
        )
        losses = np.concatenate([r.final_losses for r in records])
        assert np.isfinite(losses).all()
        out[mode] = (records, losses)
    np.testing.assert_array_equal(out[False][1], out[True][1])
    # concurrent mode really overlapped (>= 2 segments at one instant)
    peak = peak_overlap(
        [(r.real_start, r.real_end) for r in out[True][0]]
    )
    assert peak >= 2, peak


@pytest.mark.skipif(not MULTIDEV, reason="needs >=4 (forced) host devices")
def test_width2_slice_runs_and_matches():
    """A degree-2 segment executes tensor-parallel on its 2-device slice and
    still matches the sequential run bit-for-bit."""
    cfg = reduced(get_config("qwen25-7b"))
    cm = CostModel(cfg, A100_40G)
    seq = 16
    grid = [
        LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3, batch_size=1, seq_len=seq),
        LoraConfig(rank=8, alpha=16.0, learning_rate=5e-4, batch_size=1, seq_len=seq),
    ]
    jobs = [ScheduledJob((0,), 2, 0.0, 1.0), ScheduledJob((1,), 2, 0.0, 1.0)]
    sched = Schedule(jobs, 1.0, 4)
    eng = ExecutionEngine(cm, 4)
    base, _ = init_model(jax.random.PRNGKey(0), cfg, pack_meta(grid))
    ex = SliceExecutor()
    out = {}
    for mode in (False, True):
        runner = ClusterRunner(
            ex, DevicePool(jax.devices()[:4]), concurrent=mode
        )
        records, _ = eng.run_local(
            sched, grid, cfg, base, n_steps=3, seq=seq, runner=runner
        )
        out[mode] = np.concatenate([r.final_losses for r in records])
        assert np.isfinite(out[mode]).all()
    np.testing.assert_array_equal(out[False], out[True])


@pytest.mark.skipif(not MULTIDEV, reason="needs >=4 (forced) host devices")
def test_online_preempt_resume_concurrent(tmp_path):
    """run_online_local with a migration executes concurrently: the resumed
    segment waits for its predecessor's checkpoint (cross-slice dependency)
    and every adapter still finishes its exact budget."""
    from repro.train.checkpoint import CheckpointPool

    cfg = reduced(get_config("qwen25-7b"))
    cm = CostModel(cfg, A100_40G)
    cm.setup_time = 0.0
    eng = ExecutionEngine(cm, 1)
    a = LoraConfig(rank=8, alpha=8.0, learning_rate=1e-3, batch_size=1, seq_len=16)
    b = LoraConfig(rank=16, alpha=16.0, learning_rate=5e-4, batch_size=1, seq_len=16)
    it = cm.iter_time([a], 1, 16)
    from repro.sched.engine import Arrival

    trace = [Arrival(0.0, a, 6), Arrival(2.5 * it, b, 5)]
    pool = CheckpointPool(str(tmp_path / "pool"))
    base, _ = init_model(jax.random.PRNGKey(0), cfg, pack_meta([a]))
    runner = ClusterRunner(SliceExecutor(), DevicePool(), concurrent=True)
    records, sched = eng.run_online_local(
        trace, cfg, base, n_steps=6, seq=16, pool=pool,
        migration_budget=1, preempt_min_remaining=0.0, runner=runner,
    )
    assert sched.n_migrations == 1
    executed = {0: 0, 1: 0}
    for seg in sched.segments:
        for cid, st0 in zip(seg.config_ids, seg.start_steps):
            executed[cid] += min(sched.total_steps[cid] - st0, seg.run_steps)
    assert executed == {0: 6, 1: 5}
    for cid, total in ((0, 6), (1, 5)):
        meta = pool.load_meta(f"adapter_{cid:04d}")
        assert meta["total_steps"] == total
        assert np.isfinite(meta["final_loss"])
