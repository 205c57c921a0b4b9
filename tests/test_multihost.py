"""Multi-host dispatch tier (ISSUE 4 tentpole).

Three layers, cheapest first:

  * wire-protocol round-trips — segment/checkpoint serialization must be
    bit-exact through a real pickle boundary;
  * dispatcher semantics over the in-memory ``FakeHostTransport`` from
    tests/harness.py — (host, unit) addressing, checkpoint traffic,
    worker-death re-queue through the preempt path — in milliseconds;
  * real-subprocess runs (marked ``slow``; CI's multihost matrix entry runs
    them explicitly): a 2-host x 4-device plan is loss-bit-identical to the
    1-host 8-device run, and a SIGKILLed worker mid-segment recovers with
    exact step budgets.
"""
import pickle
import threading
import time

import numpy as np
import pytest
from harness import DictPool, FakeHostTransport

from repro.cluster.multihost import (
    HostDispatcher,
    MemoryPool,
    WorkerDied,
    decode_record,
    decode_segment,
    encode_record,
    encode_segment,
    encode_tree,
)
from repro.configs.base import LoraConfig, get_config, reduced
from repro.sched.engine import JobRecord, JobSegment
from repro.sched.planner import ScheduledJob

SEQ = 16


def _cfg(rank=8, alpha=8.0, lr=1e-3, bs=1):
    return LoraConfig(
        rank=rank, alpha=alpha, learning_rate=lr, batch_size=bs, seq_len=SEQ
    )


def _seg(job_id=0, cids=(0,), degree=1, start_steps=None, run_steps=3,
         done=None, preempted=False, units=None, start=0.0, end=1.0):
    cids = tuple(cids)
    return JobSegment(
        job_id=job_id,
        config_ids=cids,
        degree=degree,
        start=start,
        end=end,
        start_steps=tuple(start_steps or (0,) * len(cids)),
        run_steps=run_steps,
        done_ids=tuple(cids if done is None else done),
        preempted=preempted,
        units=tuple(units if units is not None else range(degree)),
    )


# ---------------------------------------------------------------------------
# Protocol round-trips (bit-exactness through a real pickle boundary)
# ---------------------------------------------------------------------------


def _wire(x):
    return pickle.loads(pickle.dumps(x))


def test_segment_roundtrip_bitexact():
    seg = _seg(
        job_id=7, cids=(3, 1), degree=2, start_steps=(5, 0), run_steps=11,
        done=(1,), preempted=True, units=(4, 5), start=1.25, end=9.75,
    )
    assert decode_segment(_wire(encode_segment(seg))) == seg


def test_tree_roundtrip_bitexact():
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    tree = {
        "w": {"a": rng.randn(3, 4).astype(np.float32),
              "b": jnp.arange(6, dtype=jnp.int32)},
        "m": rng.randn(2, 2),  # float64 stays float64
    }
    out = _wire(encode_tree(tree))
    assert isinstance(out["w"]["b"], np.ndarray)
    for got, want in (
        (out["w"]["a"], tree["w"]["a"]),
        (out["w"]["b"], np.asarray(tree["w"]["b"])),
        (out["m"], tree["m"]),
    ):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def test_record_roundtrip():
    rec = JobRecord(
        ScheduledJob((2, 0), 2, 0.5, 3.5), 1.25,
        np.asarray([1.5, 2.5], np.float32),
    )
    out = decode_record(_wire(encode_record(rec)))
    assert out.job == rec.job and out.wall_seconds == rec.wall_seconds
    np.testing.assert_array_equal(out.final_losses, rec.final_losses)
    none = JobRecord(ScheduledJob((0,), 1, 0.0, 1.0), 0.0, None)
    assert decode_record(_wire(encode_record(none))).final_losses is None


def test_memory_pool_capture_contract():
    state = {"w": np.ones(2, np.float32)}
    mp_ = MemoryPool({"0003": (state, {"steps_done": 5})})
    assert mp_.has_adapter_state("0003") and not mp_.has_adapter_state("0001")
    tree, meta = mp_.load_adapter_state("0003")
    assert meta["steps_done"] == 5
    mp_.save_adapter("adapter_0003", {"w": np.zeros(2)}, {"final_loss": 1.0})
    mp_.save_adapter_state("0004", state, {"steps_done": 2})
    kinds = [w.kind for w in mp_.writes]
    assert kinds == ["adapter", "state"]
    assert [w.adapter_id for w in mp_.writes] == ["adapter_0003", "0004"]


# ---------------------------------------------------------------------------
# Dispatcher semantics over in-memory fake transports (no subprocesses)
# ---------------------------------------------------------------------------


def _fake_factory(made, kwargs_by_index=None):
    """Transport factory that records every instantiation; per-instantiation
    kwargs come from ``kwargs_by_index`` (key = 0-based creation index)."""
    kwargs_by_index = kwargs_by_index or {}

    def factory(host_id, n_devices):
        tr = FakeHostTransport(
            host_id, n_devices, **kwargs_by_index.get(len(made), {})
        )
        made.append(tr)
        return tr

    return factory


def test_dispatch_across_hosts_translates_units_and_applies_writes():
    made = []
    cfgs = {i: _cfg(alpha=8.0 * (i + 1)) for i in range(4)}
    segs = [_seg(job_id=i, cids=(i,), units=(i,)) for i in range(4)]
    pool = DictPool()
    with HostDispatcher([2, 2], transport_factory=_fake_factory(made)) as disp:
        result = disp.run(
            segs, cfgs, {i: 3 for i in range(4)}, None, None,
            seq=SEQ, pool=pool,
        )
    assert len(result.records) == 4
    assert disp.n_restarts == 0
    # two workers, two segments each, with units translated host-locally
    assert sorted(tr.host_id for tr in made) == [0, 1]
    for tr in made:
        assert len(tr.runs) == 2
        assert sorted(r["units"] for r in tr.runs) == [(0,), (1,)]
    # checkpoint traffic flowed back through the message protocol
    assert sorted(pool.adapters) == [f"adapter_{i:04d}" for i in range(4)]


def test_dispatcher_refuses_a_parent_that_holds_a_chip(monkeypatch):
    """Workers are CPU subprocesses: a parent whose JAX runs on a chip would
    leave them only the CPU, so the dispatcher refuses to start."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    made = []
    with pytest.raises(RuntimeError, match="CPU subprocesses"):
        HostDispatcher([1], transport_factory=_fake_factory(made))
    assert made == []


def test_dispatch_resume_ships_state_over_the_wire():
    made = []
    cfgs = {0: _cfg()}
    segs = [
        _seg(job_id=0, run_steps=2, done=(), preempted=True, units=(0,)),
        _seg(job_id=1, start_steps=(2,), run_steps=3, units=(0,), start=1.0),
    ]
    pool = DictPool()
    with HostDispatcher([1], transport_factory=_fake_factory(made)) as disp:
        disp.run(segs, cfgs, {0: 5}, None, None, seq=SEQ, pool=pool)
    (tr,) = made
    # the preempted segment's state write landed in the central pool, and
    # the resume segment received it over the wire (FakeHostTransport
    # asserts steps_done == start_steps)
    assert tr.resumed == [(1, "0000")]
    assert pool.adapters and pool.states["0000"][1]["steps_done"] == 2


def test_killed_worker_requeues_residual_through_preempt_path():
    """Worker death mid-(resumed)-segment: the dispatcher respawns the host
    and re-dispatches the same residual — resumed from unchanged pool state,
    with nothing double-applied (writes are success-atomic)."""
    made = []
    cfgs = {0: _cfg()}
    segs = [
        _seg(job_id=0, run_steps=2, done=(), preempted=True, units=(0,)),
        _seg(job_id=1, start_steps=(2,), run_steps=3, units=(0,), start=1.0),
    ]
    pool = DictPool()
    factory = _fake_factory(made, {0: {"die_on": lambda idx, payload: idx == 1}})
    with HostDispatcher([1], transport_factory=factory) as disp:
        result = disp.run(segs, cfgs, {0: 5}, None, None, seq=SEQ, pool=pool)
    assert disp.n_restarts == 1
    assert len(made) == 2  # original + respawn
    # the respawned worker got the SAME residual segment, resumed at step 2
    retry = made[1].runs[0]
    assert retry["seg"].start_steps == (2,)
    assert retry["seg"].run_steps == 3
    assert made[1].resumed == [(0, "0000")]
    assert len(result.records) == 2
    assert sorted(pool.adapters) == ["adapter_0000"]


def test_worker_dying_forever_raises_not_hangs():
    made = []
    factory = _fake_factory(
        made, {i: {"die_on": lambda idx, payload: True} for i in range(5)}
    )
    with HostDispatcher(
        [1], transport_factory=factory, max_restarts=1
    ) as disp:
        with pytest.raises(WorkerDied, match="died 2 times"):
            disp.run(
                [_seg(units=(0,))], {0: _cfg()}, {0: 3}, None, None,
                seq=SEQ, pool=DictPool(),
            )
    assert len(made) == 2  # initial + one restart


def test_kernel_policy_ships_to_workers():
    """`impl`/`remat` ride the wire as a typed KernelPolicy with every
    segment (previously multi-host loudly rejected non-default policy)."""
    from repro.cluster.multihost import KernelPolicy

    made = []
    segs = [_seg(job_id=i, cids=(i,), units=(0,), start=float(i))
            for i in range(2)]
    with HostDispatcher([1], transport_factory=_fake_factory(made)) as disp:
        disp.run(
            segs, {i: _cfg() for i in range(2)}, {i: 3 for i in range(2)},
            None, None, seq=SEQ, pool=DictPool(),
            impl="fused_xla", remat="recompute",
        )
    (tr,) = made
    assert tr.policies == [KernelPolicy("fused_xla", "recompute")] * 2


def test_kernel_policy_defaults_to_context(monkeypatch):
    """With no explicit impl, the caller's context-local default is captured
    and shipped ("auto" normalizes to None = worker default)."""
    from repro.cluster.multihost import KernelPolicy
    from repro.kernels.ops import use_impl

    made = []
    with HostDispatcher([1], transport_factory=_fake_factory(made)) as disp:
        with use_impl("fused"):
            disp.run(
                [_seg(units=(0,))], {0: _cfg()}, {0: 3}, None, None,
                seq=SEQ, pool=DictPool(),
            )
        disp.run(
            [_seg(units=(0,))], {0: _cfg()}, {0: 3}, None, None,
            seq=SEQ, pool=DictPool(),
        )
    (tr,) = made
    assert tr.policies[0] == KernelPolicy("fused", None)
    assert tr.policies[1] == KernelPolicy(None, None)  # "auto" -> None


def test_payload_reinit_on_new_workload():
    """Regression: the init-payload memo keys on *values*, not object ids —
    a second workload with different configs re-initializes the workers,
    while a content-identical one (fresh dict objects) does not."""
    made = []
    segs = [_seg(units=(0,))]
    with HostDispatcher([1], transport_factory=_fake_factory(made)) as disp:
        disp.run(segs, {0: _cfg()}, {0: 3}, None, None, seq=SEQ,
                 pool=DictPool())
        v1 = disp._payload_version
        disp.run(segs, {0: _cfg()}, {0: 3}, None, None, seq=SEQ,
                 pool=DictPool())
        assert disp._payload_version == v1  # same values: no re-init
        disp.run(segs, {0: _cfg(rank=16, alpha=16.0)}, {0: 3}, None, None,
                 seq=SEQ, pool=DictPool())
        assert disp._payload_version == v1 + 1  # new workload: re-init


def test_host_spanning_slice_rejected():
    made = []
    with HostDispatcher([2, 2], transport_factory=_fake_factory(made)) as disp:
        with pytest.raises(RuntimeError, match="span hosts"):
            disp.run(
                [_seg(degree=2, units=(1, 2), run_steps=1)],
                {0: _cfg()}, {0: 1}, None, None, seq=SEQ, pool=DictPool(),
            )


def test_adaptive_engine_runs_over_dispatch_tier():
    """run_online_local's adaptive loop (probe -> checkpoint -> resume) runs
    unchanged over the dispatcher: probes round-trip their state through the
    message protocol and every budget lands exactly."""
    from repro.sched.cost_model import A100_40G, CostModel
    from repro.sched.engine import Arrival, ExecutionEngine
    from repro.sched.profile import ProfiledCostModel

    prior = CostModel(get_config("qwen25-7b"), A100_40G)
    prior.setup_time = 0.0
    est = ProfiledCostModel(prior, drift_threshold=0.5)
    made = []
    with HostDispatcher([1], transport_factory=_fake_factory(made)) as disp:
        eng = ExecutionEngine(est, 1, host_size=1)
        records, sched = eng.run_online_local(
            [Arrival(0.0, _cfg(), 12)],
            reduced(get_config("qwen25-7b")),
            None,
            n_steps=12,
            seq=SEQ,
            pool=DictPool(),
            runner=disp,
            probe_steps=4,
        )
    assert sched.n_probes == 1
    executed = sum(
        min(sched.total_steps[cid] - s.start_steps[i], s.run_steps)
        for s in sched.segments
        for i, cid in enumerate(s.config_ids)
    )
    assert executed == 12
    assert sorted(sched.completed) == [0]


# ---------------------------------------------------------------------------
# Heartbeats, elastic membership, graceful drain (ISSUE 10). CI's chaos smoke
# runs exactly this section: pytest -k "elastic or drain or heartbeat".
# ---------------------------------------------------------------------------


def _state_spy(disp):
    """Record every membership transition deterministically (a sampler thread
    could miss a short-lived state)."""
    seen = []
    orig = disp._set_host_state

    def spy(host, state, **why):
        seen.append((host, state, why.get("reason")))
        orig(host, state, **why)

    disp._set_host_state = spy
    return seen


def test_heartbeat_pongs_keep_host_alive():
    from repro.obs import Tracer

    made = []
    tracer = Tracer()
    with HostDispatcher(
        [1], transport_factory=_fake_factory(made), tracer=tracer,
        heartbeat_interval=0.02,
    ) as disp:
        disp.run(
            [_seg(units=(0,))], {0: _cfg()}, {0: 3}, None, None,
            seq=SEQ, pool=DictPool(),
        )
        deadline = time.perf_counter() + 2.0
        while made[0].pings < 3 and time.perf_counter() < deadline:
            time.sleep(0.01)
    assert made[0].pings >= 3
    assert disp.host_state(0) == "ALIVE"
    assert disp.hosts_alive == 1
    assert disp.n_restarts == 0
    rtt = tracer.metrics.histogram("cluster.heartbeat_rtt").summary()
    assert rtt["count"] >= 3 and rtt["max"] < 2.0


def test_heartbeat_detects_hung_worker_and_recovers():
    """A worker that wedges mid-segment (silent, but the process stays alive
    — only silence distinguishes it) must not hang run(): the watchdog walks
    it ALIVE -> SUSPECT -> DEAD, fails the in-flight segment, and the normal
    restart path re-runs it on a fresh worker."""
    made = []
    factory = _fake_factory(
        made, {0: {"hang_on": lambda idx, payload: idx == 0}}
    )
    with HostDispatcher(
        [1], transport_factory=factory,
        heartbeat_interval=0.02, heartbeat_timeout=0.04,
        heartbeat_dead_after=2,
    ) as disp:
        seen = _state_spy(disp)
        result = disp.run(
            [_seg(units=(0,))], {0: _cfg()}, {0: 3}, None, None,
            seq=SEQ, pool=DictPool(),
        )
    assert len(result.records) == 1
    assert len(made) == 2  # hung original + respawn
    assert made[0].error is None  # it wedged; it did not crash
    assert disp.n_restarts == 1  # died with a segment in flight
    # the *heartbeat* made the call (the pump alone cannot: the process
    # stayed alive until the watchdog killed it)
    reasons = {r for _, _, r in seen}
    assert {"heartbeat_timeout", "heartbeat_expired"} <= reasons
    states = [(h, s) for h, s, _ in seen]
    assert (0, "SUSPECT") in states and (0, "DEAD") in states
    assert states.index((0, "SUSPECT")) < states.index((0, "DEAD"))
    assert disp.host_state(0) == "ALIVE"  # respawn rejoined the fleet


def test_heartbeat_pong_recovers_suspect_host():
    """One late pong un-suspects a host (misses reset; no restart burned)."""
    from repro.cluster.multihost import HealthReply

    made = []
    with HostDispatcher(
        [1], transport_factory=_fake_factory(made)
    ) as disp:
        disp.run(
            [_seg(units=(0,))], {0: _cfg()}, {0: 3}, None, None,
            seq=SEQ, pool=DictPool(),
        )
        disp._set_host_state(0, "SUSPECT", reason="test")
        disp._hb_misses[0] = 2
        disp._on_pong(0, HealthReply(
            host=0, seq=7, t_send=time.perf_counter(), in_flight=0,
        ))
        assert disp.host_state(0) == "ALIVE"
        assert disp._hb_misses[0] == 0
    assert disp.n_restarts == 0


def test_heartbeat_idle_death_burns_no_restart_credit():
    """Regression (the idle-death accounting bug): a worker dying *between*
    segments — spot reclaim while idle — must not burn a ``max_restarts``
    credit; only in-flight deaths do (see
    test_killed_worker_requeues_residual_through_preempt_path, which pins
    the in-flight counterpart at n_restarts == 1)."""
    made = []
    with HostDispatcher(
        [1], transport_factory=_fake_factory(made), max_restarts=0
    ) as disp:
        disp.run(
            [_seg(units=(0,))], {0: _cfg()}, {0: 3}, None, None,
            seq=SEQ, pool=DictPool(),
        )
        disp.kill_host(0)  # idle: nothing in flight
        deadline = time.perf_counter() + 5.0
        while not disp._workers[0].dead and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert disp._workers[0].dead
        # with max_restarts=0 an (incorrectly) burned credit would raise
        # WorkerDied here instead of respawning
        result = disp.run(
            [_seg(job_id=1, units=(0,), start=1.0)], {0: _cfg()}, {0: 3},
            None, None, seq=SEQ, pool=DictPool(),
        )
    assert disp.n_restarts == 0
    assert len(made) == 2  # respawned, just not *charged*
    assert len(result.records) == 1


def _adaptive_over(disp, arrivals, *, pool=None, probe_steps=4):
    from repro.sched.cost_model import A100_40G, CostModel
    from repro.sched.engine import ExecutionEngine
    from repro.sched.profile import ProfiledCostModel

    prior = CostModel(get_config("qwen25-7b"), A100_40G)
    prior.setup_time = 0.0
    est = ProfiledCostModel(prior, drift_threshold=0.5)
    eng = ExecutionEngine(est, disp.total_units, host_size=1)
    return eng.run_online_local(
        arrivals,
        reduced(get_config("qwen25-7b")),
        None,
        n_steps=max(a.steps for a in arrivals),
        seq=SEQ,
        pool=pool if pool is not None else DictPool(),
        runner=disp,
        probe_steps=probe_steps,
    )


def _executed_steps(sched):
    return sum(
        min(sched.total_steps[cid] - s.start_steps[i], s.run_steps)
        for s in sched.segments
        for i, cid in enumerate(s.config_ids)
    )


def test_elastic_join_mid_run_engine_replans_onto_new_host():
    """add_host() mid-run: the engine learns of the join through the
    membership feed and replans onto the new host's units — both jobs
    finish their exact budgets, and the joiner really ran work."""
    from repro.sched.engine import Arrival

    made = []
    box = {}
    joined = []

    def on_run(idx, payload):
        if idx == 0 and not joined:  # first segment lands -> a host joins
            joined.append(box["disp"].add_host(1, host_class="fast"))

    def factory(host_id, n_devices):
        tr = FakeHostTransport(
            host_id, n_devices, real_time=True, iter_scale=0.02,
            on_run=on_run if host_id == 0 else None,
        )
        made.append(tr)
        return tr

    with HostDispatcher([1], transport_factory=factory) as disp:
        box["disp"] = disp
        # staggered so the jobs can't pack into one segment: the second
        # arrives while the first trains, after the join — with host 0 busy
        # the only place for it is the joiner
        arrivals = [Arrival(0.0, _cfg(), 12),
                    Arrival(0.05, _cfg(alpha=16.0), 12)]
        records, sched = _adaptive_over(disp, arrivals)
    assert joined == [1]
    assert disp.total_units == 2
    assert disp.host_classes == ("", "fast")
    assert sorted(sched.completed) == [0, 1]
    assert _executed_steps(sched) == 24
    by_host = {tr.host_id for tr in made if tr.runs}
    assert by_host == {0, 1}  # the joiner actually executed segments


def test_graceful_drain_loses_zero_steps():
    """drain_host() mid-run: in-flight work finishes (checkpoints land
    through the normal success-atomic path), the residual migrates to the
    surviving host at the exact step count, and the drained host's units
    retire from the pool — zero steps lost, zero double-run."""
    from repro.sched.engine import Arrival

    made = []
    box = {}
    threads = []

    def on_run(idx, payload):
        if idx == 0 and not threads:  # host 1's first segment is in flight
            t = threading.Thread(
                target=lambda: box["disp"].drain_host(1, timeout=30)
            )
            t.start()
            threads.append(t)

    def factory(host_id, n_devices):
        tr = FakeHostTransport(
            host_id, n_devices, real_time=True, iter_scale=0.02,
            on_run=on_run if host_id == 1 else None,
        )
        made.append(tr)
        return tr

    pool = DictPool()
    with HostDispatcher([1, 1], transport_factory=factory) as disp:
        box["disp"] = disp
        # staggered so the jobs can't pack into one segment: the second
        # lands on host 1 (host 0 is busy) and is the one drained mid-run
        arrivals = [Arrival(0.0, _cfg(), 12),
                    Arrival(0.05, _cfg(alpha=16.0), 12)]
        records, sched = _adaptive_over(disp, arrivals, pool=pool)
        for t in threads:
            t.join(timeout=30)
    assert threads and not threads[0].is_alive()  # drain completed
    assert disp.host_state(1) == "DEAD"
    assert disp.device_pool.retired == (1,)
    assert sorted(sched.completed) == [0, 1]
    assert _executed_steps(sched) == 24  # nothing lost, nothing doubled
    tr1 = next(tr for tr in made if tr.host_id == 1)
    assert len(tr1.runs) == 1  # nothing dispatched after the drain announce
    # the drained host's job resumed elsewhere from its checkpointed steps
    resumed_on_0 = [
        aid for tr in made if tr.host_id == 0 for _, aid in tr.resumed
    ]
    assert "0001" in resumed_on_0
    assert sorted(pool.adapters) == ["adapter_0000", "adapter_0001"]


def test_drain_mid_death_checkpoint_writes_atomic():
    """Satellite: a host killed *mid-drain* (segment in flight) must leave
    the pool atomic — the killed attempt's writes never half-apply, and the
    residual re-enters at the pre-drain step count (the respawned worker's
    shipped state is asserted by the fake)."""
    from repro.sched.engine import Arrival

    made = []
    box = {}

    def die1(idx, payload):
        if idx != 1:
            return False
        # the resumed continuation (start_steps=4) is in flight: start the
        # drain, let the announce land, then die silently (SIGKILL)
        t = threading.Thread(
            target=lambda: box["disp"].drain_host(0, timeout=60)
        )
        t.start()
        box["drain"] = t
        time.sleep(0.05)
        return True

    def factory(host_id, n_devices):
        tr = FakeHostTransport(host_id, n_devices, die_on=die1)
        made.append(tr)
        return tr

    pool = DictPool()
    with HostDispatcher([1], transport_factory=factory) as disp:
        box["disp"] = disp
        records, sched = _adaptive_over(
            disp, [Arrival(0.0, _cfg(), 12)], pool=pool
        )
        box["drain"].join(timeout=60)
    assert not box["drain"].is_alive()
    assert disp.host_state(0) == "DEAD"
    assert disp.n_restarts == 1  # the mid-drain kill was in flight
    assert len(made) == 2
    # atomicity: the killed attempt applied nothing — the retry resumed
    # from the probe checkpoint (steps_done == 4), not a torn write
    assert pool.states["0000"][1]["steps_done"] == 4
    retry = made[1].runs[0]
    assert retry["seg"].start_steps == (4,)
    assert made[1].resumed == [(0, "0000")]
    assert _executed_steps(sched) == 12
    assert sorted(pool.adapters) == ["adapter_0000"]


def test_elastic_pool_add_and_retire_units():
    from repro.cluster.pool import DevicePool

    p = DevicePool(devices=["d0", "d1"])
    assert p.add_devices(["d2", "d3"]) == (2, 3)
    assert p.total == 4 and p.free == 4
    s = p.acquire_units([1])
    # retire blocks until the unit is free, then removes it for good
    done = threading.Event()

    def retire():
        p.retire_units([1], timeout=5.0)
        done.set()

    t = threading.Thread(target=retire)
    t.start()
    time.sleep(0.05)
    assert not done.is_set()  # still busy -> retire waits
    p.release(s)
    t.join(timeout=5)
    assert done.is_set() and p.retired == (1,)
    with pytest.raises(RuntimeError, match="retired"):
        p.acquire_units([1])
    assert p.acquire_units([0, 2, 3]).units == (0, 2, 3)


def test_elastic_class_aware_unit_pick():
    """pick_class_units: wide jobs go to the fastest class, narrow jobs to
    the slowest (keeping fast hosts open), SUSPECT hosts are last resort."""
    from repro.cluster.pool import pick_class_units

    classes = {0: "fast", 1: "fast", 2: "slow"}
    ratios = {"fast": 1.0, "slow": 4.0}
    kw = dict(
        class_of_host=lambda h: classes[h],
        ratio_of_class=lambda c: ratios[c],
    )
    free = [0, 1, 2, 3, 4, 5]  # hosts 0..2, 2 units each
    assert pick_class_units(free, 2, 2, **kw) == (0, 1)  # wide -> fast
    assert pick_class_units(free, 1, 2, **kw) == (4,)    # narrow -> slow
    # suspect fast host: wide work flees to the healthy fast host
    assert pick_class_units(
        free, 2, 2, avoid_host=lambda h: h == 0, **kw
    ) == (2, 3)
    assert pick_class_units([0], 2, 2, **kw) is None  # nothing fits


# ---------------------------------------------------------------------------
# Real subprocesses (CPU-forced workers; CI's multihost matrix entry)
# ---------------------------------------------------------------------------


def _grid4():
    return [
        _cfg(rank=8, alpha=8.0, lr=1e-3),
        _cfg(rank=8, alpha=16.0, lr=5e-4),
        _cfg(rank=16, alpha=16.0, lr=1e-3),
        _cfg(rank=16, alpha=32.0, lr=2e-4),
    ]


def _run_schedule(disp, host_size, grid, cfg, base, n_steps=3):
    from repro.sched.cost_model import A100_40G, CostModel
    from repro.sched.engine import ExecutionEngine
    from repro.sched.planner import Schedule

    g = disp.total_units
    jobs = [ScheduledJob((i,), 1, 0.0, 1.0) for i in range(len(grid))]
    eng = ExecutionEngine(CostModel(cfg, A100_40G), g, host_size=host_size)
    records, makespan = eng.run_local(
        Schedule(jobs, 1.0, g), grid, cfg, base, n_steps=n_steps, seq=SEQ,
        runner=disp,
    )
    by_cid = {r.job.config_ids[0]: r.final_losses for r in records}
    return np.concatenate([by_cid[i] for i in range(len(grid))])


@pytest.mark.slow
def test_two_hosts_bitexact_vs_single_host_subprocess():
    """Acceptance: the 4-group schedule on 2 hosts x 4 devices produces
    per-adapter losses bit-identical to the 1-host 8-device run."""
    import jax

    from repro.core.adapter import pack_meta
    from repro.models.model import init_model

    cfg = reduced(get_config("qwen25-7b"))
    grid = _grid4()
    base, _ = init_model(jax.random.PRNGKey(0), cfg, pack_meta(grid))
    with HostDispatcher([8]) as disp1:
        ref = _run_schedule(disp1, 8, grid, cfg, base)
    with HostDispatcher([4, 4]) as disp2:
        out = _run_schedule(disp2, 4, grid, cfg, base)
    assert np.isfinite(ref).all()
    np.testing.assert_array_equal(ref, out)
    assert disp2.last_result.max_overlap() >= 2  # hosts really overlapped


@pytest.mark.slow
def test_killed_subprocess_worker_recovers_bitexact(tmp_path):
    """Acceptance: SIGKILL a real HostWorker mid-segment — the run completes
    (no hang), every adapter's exact step budget is honored, and losses are
    bit-identical to an unkilled in-process reference."""
    import jax

    from repro.cluster import ClusterRunner, DevicePool, SliceExecutor
    from repro.core.adapter import pack_meta
    from repro.models.model import init_model
    from repro.sched.cost_model import A100_40G, CostModel
    from repro.sched.engine import ExecutionEngine
    from repro.sched.planner import Schedule
    from repro.train.checkpoint import CheckpointPool

    cfg = reduced(get_config("qwen25-7b"))
    grid = [_cfg()]
    base, _ = init_model(jax.random.PRNGKey(0), cfg, pack_meta(grid))
    cm = CostModel(cfg, A100_40G)
    jobs = [ScheduledJob((0,), 1, 0.0, 1.0)]
    n_steps = 6

    # unkilled in-process reference (also proves dispatch == in-process)
    eng = ExecutionEngine(cm, 1)
    runner = ClusterRunner(
        SliceExecutor(), DevicePool(jax.devices()[:1]), concurrent=False
    )
    recs, _ = eng.run_local(
        Schedule(jobs, 1.0, 1), grid, cfg, base, n_steps=n_steps, seq=SEQ,
        runner=runner,
    )
    ref = np.concatenate([r.final_losses for r in recs])

    eng_mh = ExecutionEngine(cm, 1, host_size=1)
    # the killed segment is the first on a fresh (cold) worker, so the
    # in-flight window is many seconds wide (spawn + jax init + compile);
    # the retry loop still guards the theoretical completed-before-kill race
    for attempt in range(2):
        pool = CheckpointPool(str(tmp_path / f"pool{attempt}"))
        with HostDispatcher([1]) as disp:
            stop = threading.Event()

            def killer():
                while not stop.is_set():
                    if disp.in_flight(0) > 0:
                        time.sleep(1.5)  # land mid-compile / mid-steps
                        if disp.in_flight(0) > 0 and not stop.is_set():
                            disp.kill_host(0)
                        return
                    time.sleep(0.02)

            th = threading.Thread(target=killer)
            th.start()
            try:
                recs_mh, _ = eng_mh.run_local(
                    Schedule(jobs, 1.0, 1), grid, cfg, base, n_steps=n_steps,
                    seq=SEQ, pool=pool, runner=disp,
                )
            finally:
                stop.set()
                th.join()
        out = np.concatenate([r.final_losses for r in recs_mh])
        np.testing.assert_array_equal(ref, out)  # holds killed or not
        if disp.n_restarts >= 1:
            break  # the kill landed mid-segment and was recovered
    assert disp.n_restarts >= 1
    meta = pool.load_meta("adapter_0000")
    assert meta["total_steps"] == n_steps
    assert np.isfinite(meta["final_loss"])
