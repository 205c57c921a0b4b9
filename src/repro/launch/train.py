"""Training launcher: run a packed-LoRA fine-tuning job for a selected
architecture on this host (real execution) through the cluster subsystem —
the job trains on a :class:`~repro.cluster.DevicePool` mesh slice wide
enough for the requested mesh (the whole-host slice by default), via the
same compile-cached :class:`~repro.cluster.SliceExecutor` the concurrent
engine uses.

  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-7b \
      --reduced --steps 20 --ranks 8,16 --lrs 1e-3,5e-4 --seq 32

  # sharded on 8 forced host devices (4 data x 2 model):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch qwen25-7b --reduced \
      --mesh 4x2 --steps 10

Full (non-reduced) configs are for the dry-run (repro.launch.dryrun); this
driver trains for real, so use --reduced on CPU.
"""
import argparse

import jax
import numpy as np

from repro.cluster import DevicePool, SliceExecutor
from repro.cluster.multihost import require_cpu_parent
from repro.configs.base import LoraConfig, get_config, list_archs, reduced
from repro.core.adapter import pack_meta
from repro.core.packed_lora import extract_adapter
from repro.kernels.quant import quantize_base_params
from repro.launch.cache import enable_compile_cache
from repro.models.model import init_model
from repro.train.checkpoint import CheckpointPool


def _estimator(args, cfg):
    """Profiled estimator shared by the single- and multi-host paths:
    analytic prior for the selected hardware + (optionally pre-seeded)
    observation store."""
    from repro.sched.cost_model import (
        A10_24G, A100_40G, TPU_V5E, CostModel, tpu_prior,
    )
    from repro.sched.profile import ObservationStore, ProfiledCostModel

    if args.hw is not None:
        hw = {"a100-40g": A100_40G, "a10-24g": A10_24G,
              "tpu-v5e": TPU_V5E}[args.hw]
    elif jax.default_backend() == "tpu":
        hw = tpu_prior(jax.devices()[0].device_kind)
    else:
        hw = A100_40G
    store = (
        ObservationStore.load(args.profile_in) if args.profile_in
        else ObservationStore()
    )
    # a quantized frozen base shrinks the per-job memory floor, so the plan
    # itself gets denser (more configs co-packed per device) — the estimator
    # must price the same base bytes the kernels will actually stream
    quant = None if args.quant == "none" else args.quant
    return ProfiledCostModel(CostModel(cfg, hw, base_dtype=quant), store), store


def _make_tracer(args):
    """One Tracer for the whole launch when --trace-out/--metrics-out asked
    for it, else the shared no-op — every tier below receives this object."""
    from repro.obs import NULL_TRACER, Tracer

    if args.trace_out or args.metrics_out:
        return Tracer()
    return NULL_TRACER


def _export_obs(args, tracer):
    if args.trace_out:
        tracer.export(args.trace_out)
        print(f"saved Chrome trace to {args.trace_out} "
              f"({len(tracer.spans())} span(s)) — open in ui.perfetto.dev")
    if args.metrics_out:
        tracer.export_metrics(args.metrics_out)
        print(f"saved metrics to {args.metrics_out}")


def _drift_table(records, timings, seq):
    """Join executed records to their measured timings by (config_ids, seq).

    The two lists are usually parallel, but the runner orders timings by
    virtual start while records come back in the engine's order — a plain
    zip mispairs them whenever those differ, so key the join instead."""
    from collections import deque

    by_key = {}
    for t in timings:
        by_key.setdefault((t.config_ids, t.seq), deque()).append(t)
    for rec in records:
        key = (tuple(rec.job.config_ids), seq)
        q = by_key.get(key)
        seg_timing = q.popleft() if q else None
        per_adapter = (
            np.round(np.asarray(rec.final_losses), 3)
            if rec.final_losses is not None else None
        )
        if seg_timing is None:
            print(f"  job cids={rec.job.config_ids} deg={rec.job.degree} "
                  f"     (no timing)  losses={per_adapter}")
            continue
        drift = seg_timing.drift
        drift_s = f"{100 * drift:+.1f}%" if drift == drift else "n/a"
        print(f"  job cids={rec.job.config_ids} deg={rec.job.degree} "
              f"{1e3 * seg_timing.measured_iter:8.1f} ms/step "
              f"(plan drift {drift_s})  losses={per_adapter}")


def _run_multihost(args, cfg, configs, tracer):
    """--hosts N: plan host-aware, execute process-per-host.

    Each simulated host is a subprocess that forces its own
    ``--devices-per-host`` CPU devices, so this runs on any machine without
    touching the parent's XLA_FLAGS. The plan caps per-job parallelism at
    the host width and keeps every job's device units on one host; the
    dispatch tier then overlaps jobs across hosts for real.

    Elastic knobs: ``--host-classes`` tags each host (the adaptive engine
    then places wide jobs on fast classes and narrow ones on slow),
    ``--heartbeat`` arms the liveness watchdog, and ``--drain-after`` /
    ``--join-after`` exercise membership mid-run (drain the last host /
    admit a new one after N seconds). Drain/join need replanning, so they
    switch execution to the adaptive online path (``run_online_local``)."""
    import threading
    import time

    from repro.cluster import HostDispatcher
    from repro.sched.engine import Arrival, ExecutionEngine
    from repro.sched.planner import plan

    per = args.devices_per_host
    g = args.hosts * per
    classes = None
    if args.host_classes:
        classes = [c.strip() for c in args.host_classes.split(",")]
        if len(classes) != args.hosts:
            raise SystemExit(
                f"--host-classes names {len(classes)} classes for "
                f"{args.hosts} hosts"
            )
    est, store = _estimator(args, cfg)
    elastic = args.drain_after is not None or args.join_after is not None
    sched = plan(est, configs, g, args.seq, args.steps, max_degree=per)
    print(f"multi-host plan: {len(sched.jobs)} job(s) on {args.hosts} hosts "
          f"x {per} device(s), virtual makespan {sched.makespan:.1f}s")
    meta = pack_meta(configs)
    base, _ = init_model(jax.random.PRNGKey(0), cfg, meta)
    quant = None if args.quant == "none" else args.quant
    if quant:
        base = quantize_base_params(base, quant)
        print(f"quantized frozen base to {quant} "
              f"(projection weights -> codes+scales dicts)")
    pool = CheckpointPool(args.pool) if args.pool else None
    eng = ExecutionEngine(est, g, host_size=per, tracer=tracer)
    timers = []
    with HostDispatcher(
        args.hosts, per, tracer=tracer, host_classes=classes,
        heartbeat_interval=args.heartbeat,
    ) as disp:
        if args.drain_after is not None:
            target = len(disp.hosts) - 1
            timers.append(threading.Timer(
                args.drain_after, lambda: disp.drain_host(target)
            ))
        if args.join_after is not None:
            join_class = classes[-1] if classes else ""
            timers.append(threading.Timer(
                args.join_after,
                lambda: disp.add_host(per, host_class=join_class),
            ))
        for t in timers:
            t.daemon = True
            t.start()
        t0 = time.perf_counter()
        if elastic:
            # membership changes need replanning: run the same workload as
            # an online trace through the adaptive loop, which subscribes
            # to the dispatcher's join/drain feed
            arrivals = [Arrival(0.0, c, args.steps) for c in configs]
            records, osched = eng.run_online_local(
                arrivals, cfg, base, n_steps=args.steps, seq=args.seq,
                pool=pool, runner=disp,
                probe_steps=min(4, args.steps),
            )
            makespan = osched.makespan
        else:
            # --impl/--remat ride the wire as a KernelPolicy with every
            # segment, so each host worker runs the tier selected here
            records, makespan = eng.run_local(
                sched, configs, cfg, base, n_steps=args.steps, seq=args.seq,
                pool=pool, runner=disp, impl=args.impl, remat=args.remat,
                base_dtype=quant,
            )
        elapsed = time.perf_counter() - t0
        for t in timers:
            t.cancel()
    result = disp.last_result
    overlap = result.max_overlap() if result is not None else "n/a"
    print(f"{len(records)} job(s) in {elapsed:.1f}s wall "
          f"(makespan {makespan:.1f}s, peak overlap "
          f"{overlap}, {disp.n_restarts} worker restart(s))")
    if elastic or args.heartbeat:
        states = ", ".join(
            f"host{h}={disp.host_state(h)}"
            for h in range(len(disp.hosts))
        )
        print(f"membership: {states}")
    if result is not None:
        _drift_table(records, result.timings, args.seq)
    if args.profile_out:
        store.save(args.profile_out)
        print(f"saved profile to {args.profile_out}")
    if pool is not None:
        print(f"saved {len(pool.list())} adapters to {args.pool}")
    _export_obs(args, tracer)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen25-7b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the same family")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--ranks", default="8,16")
    ap.add_argument("--lrs", default="1e-3,5e-4")
    ap.add_argument("--alphas", default=None, help="default: 2*rank")
    ap.add_argument("--batch-sizes", default=None, help="default: 1 each")
    ap.add_argument("--mesh", default=None, help="e.g. 4x2 (data x model)")
    ap.add_argument("--impl", default=None,
                    choices=["auto", "pallas", "xla", "fused", "fused_pallas",
                             "fused_xla"],
                    help="packed-LoRA kernel backend (kernels/ops.py): "
                         "'fused' runs base+delta as one megakernel "
                         "(fused_pallas on TPU, fused_xla elsewhere); "
                         "default: context default ('auto')")
    ap.add_argument("--quant", default="none", choices=["none", "int8", "nf4"],
                    help="quantize the frozen base (kernels/quant.py): "
                         "projection weights are stored as int8 per-channel "
                         "or nf4 block-scaled codes and dequantized inside "
                         "the fused kernel's K-loop; adapters/optimizer "
                         "stay full precision, so losses match the "
                         "dequantized-base run bit-for-bit")
    ap.add_argument("--remat", default=None, choices=["recompute", "save"],
                    help="backward xA policy of the LoRA kernels (default: "
                         "measured crossover, see bench_kernels)")
    ap.add_argument("--autotune-cache", default=None,
                    help="JSON autotune cache (kernels/autotune.py): "
                         "micro-benchmark fused-kernel block sizes / rates "
                         "for this arch's projection shapes, persist them "
                         "here, and calibrate the cost-model prior with the "
                         "measured rates")
    ap.add_argument("--hosts", type=int, default=1,
                    help="run through the multi-host dispatch tier: N "
                         "simulated hosts (one subprocess each, self-forcing "
                         "--devices-per-host CPU devices via XLA_FLAGS); the "
                         "configs are planned host-aware and executed "
                         "process-per-host")
    ap.add_argument("--devices-per-host", type=int, default=1,
                    help="device units per simulated host; values > 1 route "
                         "through the dispatch tier even with --hosts 1 "
                         "(one subprocess host of that width)")
    ap.add_argument("--host-classes", default=None,
                    help="comma list tagging each host's hardware class "
                         "(e.g. 'fast,fast,slow'); the adaptive engine "
                         "learns per-class step-time ratios and places "
                         "wide jobs on fast classes, narrow jobs on slow")
    ap.add_argument("--heartbeat", type=float, default=0.0,
                    help="heartbeat interval in seconds (0 = off): the "
                         "dispatcher pings every worker, marks silent hosts "
                         "SUSPECT then DEAD, and re-runs their segments")
    ap.add_argument("--drain-after", type=float, default=None,
                    help="gracefully drain the last host N seconds into the "
                         "run (elastic demo; switches to the adaptive "
                         "online execution path)")
    ap.add_argument("--join-after", type=float, default=None,
                    help="admit one extra host N seconds into the run "
                         "(elastic demo; switches to the adaptive online "
                         "execution path)")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--pool", default=None, help="checkpoint pool dir")
    ap.add_argument("--profile-in", default=None,
                    help="load a profile (observation store JSON) from a "
                         "previous run; predictions below use it")
    ap.add_argument("--profile-out", default=None,
                    help="dump the observation store (with this run's "
                         "measured step time folded in) for reuse via "
                         "--profile-in / the adaptive engine")
    ap.add_argument("--hw", default=None,
                    choices=["a100-40g", "a10-24g", "tpu-v5e"],
                    help="hardware prior for the plan-vs-measured summary "
                         "(default: the TPU's own prior from its device "
                         "kind, a100-40g off-TPU)")
    ap.add_argument("--save-state", action="store_true",
                    help="checkpoint the full packed state (adapters + "
                         "optimizer + step counts) into --pool at the end")
    ap.add_argument("--resume-state", action="store_true",
                    help="resume a packed run saved with --save-state "
                         "(same arch/ranks) instead of initializing fresh")
    ap.add_argument("--state-id", default=None,
                    help="packed-state id in the pool (default: the arch)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON of the run "
                         "(spans from every tier, one Perfetto track per "
                         "device unit / host / serve row); load it at "
                         "ui.perfetto.dev or chrome://tracing")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics registry (counters / gauges / "
                         "histogram summaries) as JSON")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()
    if (args.save_state or args.resume_state) and not args.pool:
        ap.error("--save-state/--resume-state require --pool")
    if args.resume_state and args.mesh:
        ap.error("--resume-state is not supported together with --mesh")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    ranks = [int(r) for r in args.ranks.split(",")]
    lrs = [float(x) for x in args.lrs.split(",")]
    alphas = (
        [float(a) for a in args.alphas.split(",")]
        if args.alphas
        else [2.0 * r for r in ranks]
    )
    bss = (
        [int(b) for b in args.batch_sizes.split(",")]
        if args.batch_sizes
        else [1] * len(ranks)
    )
    assert len(lrs) == len(ranks) == len(alphas) == len(bss)
    configs = [
        LoraConfig(rank=r, alpha=a, learning_rate=lr, batch_size=b, seq_len=args.seq)
        for r, a, lr, b in zip(ranks, alphas, lrs, bss)
    ]
    meta = pack_meta(configs)
    print(f"arch={cfg.name} pack N={meta.n} r_bucket={meta.r_bucket} "
          f"steps={args.steps} seq={args.seq}")

    tracer = _make_tracer(args)
    if args.hosts > 1 or args.devices_per_host > 1:
        try:
            require_cpu_parent()
        except RuntimeError as e:
            ap.error(str(e))
        if (args.mesh or args.fsdp or args.seq_parallel or args.save_state
                or args.resume_state):
            ap.error("--hosts is incompatible with --mesh/--fsdp/"
                     "--seq-parallel/--save-state/--resume-state (per-job "
                     "parallelism comes from the planner; use "
                     "--devices-per-host for host width)")
        _run_multihost(args, cfg, configs, tracer)
        return
    if (args.host_classes or args.heartbeat
            or args.drain_after is not None or args.join_after is not None):
        ap.error("--host-classes/--heartbeat/--drain-after/--join-after "
                 "need the dispatch tier: pass --hosts N (or "
                 "--devices-per-host > 1)")

    mesh_shape = None
    width = 1
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh_shape = (d, m)
        width = d * m

    device_pool = DevicePool()
    if width > device_pool.total:
        raise SystemExit(
            f"--mesh {args.mesh} needs {width} devices but this host has "
            f"{device_pool.total}; set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={width} or request a smaller mesh"
        )
    slice_ = device_pool.acquire(width)
    print(f"device pool: {device_pool.total} device(s), job slice "
          f"units={slice_.units}")

    key = jax.random.PRNGKey(0)
    base, lora = init_model(key, cfg, meta)
    quant = None if args.quant == "none" else args.quant
    if quant:
        base = quantize_base_params(base, quant)
        print(f"quantized frozen base to {quant} "
              f"(projection weights -> codes+scales dicts)")
    opt = None

    state_id = args.state_id or cfg.name
    if args.resume_state:
        pool = CheckpointPool(args.pool)
        lora, opt, smeta = pool.load_packed_state(state_id)
        if tuple(smeta["ranks"]) != meta.ranks:
            raise SystemExit(
                f"saved state {state_id!r} has ranks {smeta['ranks']}, "
                f"requested {list(meta.ranks)}"
            )
        done = np.asarray(opt["step"]).tolist()
        print(f"resumed packed state {state_id!r} (per-adapter steps {done})")

    def log(i, m):
        if args.log_every and i % args.log_every == 0:
            per = np.asarray(m["per_adapter_loss"])
            print(f"step {i:4d}  loss={float(m['loss']):.4f}  "
                  f"per-adapter={np.round(per, 3)}")

    # profile feedback loop: prior + (optionally pre-seeded) observations
    est, store = _estimator(args, cfg)
    blocks = None
    if args.autotune_cache:
        from repro.kernels.autotune import model_shapes, tune_for_model

        # the calibration prices FUSED-kernel rates, so the run must
        # execute the fused tier — otherwise the planner would predict work
        # the kernels never do
        if args.impl in (None, "auto"):
            args.impl = "fused"
            print("autotune: --impl not set; running the fused tier the "
                  "calibration measures")
        elif args.impl in ("xla", "pallas"):
            ap.error("--autotune-cache calibrates measured FUSED rates; "
                     "combine it with --impl fused/fused_xla/fused_pallas")
        prof = tune_for_model(
            cfg, configs, seq=args.seq, cache_path=args.autotune_cache,
            fast=True, tracer=tracer,
        )
        est = type(est)(prof.calibrate(est.prior), est.store)
        # tuned Pallas tile sizes for this pack's representative projection
        # (None off-TPU: the XLA path has no block parameter)
        blocks = prof.best_blocks(*model_shapes(cfg, configs, args.seq)[0])
        print(f"autotune: {len(prof.entries)} shape bucket(s) in "
              f"{args.autotune_cache} (backend={prof.backend}); prior "
              f"calibrated with measured fused rates"
              + (f", blocks={blocks}" if blocks else ""))
    degree = max(width, 1)
    pred_prior = est.prior.iter_time(configs, degree, args.seq)
    pred_profiled = est.iter_time(configs, degree, args.seq)  # before observing

    ex = SliceExecutor(tracer=tracer)
    with tracer.watch_gc():
        res = ex.train_pack(
            cfg,
            configs,
            n_steps=args.steps,
            seq=args.seq,
            base=base,
            lora=lora,
            opt=opt,
            slice_=slice_,
            mesh_shape=mesh_shape,
            fsdp=args.fsdp,
            seq_parallel=args.seq_parallel,
            step_callback=log if args.log_every else None,
            impl=args.impl,
            remat=args.remat,
            blocks=blocks,
            base_dtype=quant,
        )
    device_pool.release(slice_)
    lora, opt = res.lora, res.opt
    print(f"{args.steps} steps in {res.wall_seconds:.1f}s "
          f"({1e3 * res.wall_seconds / max(args.steps, 1):.0f} ms/step)")

    # plan-vs-measured summary: how far the analytic prior (and, when a
    # profile was loaded, the calibrated estimator) was from reality
    if args.steps > 0:
        measured = res.wall_seconds / args.steps
        est.observe(configs, degree, args.seq, measured)

        def _row(label, pred):
            drift = measured / pred - 1.0 if pred > 0 else float("nan")
            print(f"  {label:<22} {1e3 * pred:9.2f} ms/step   "
                  f"drift {100.0 * drift:+8.1f}%")

        print(f"\nplan-vs-measured  key={est.key(configs, degree, args.seq)}")
        print(f"  {'measured':<22} {1e3 * measured:9.2f} ms/step")
        _row(f"prior ({est.hw.name})", pred_prior)
        if args.profile_in:
            _row("profiled (loaded)", pred_profiled)
        print(f"  store: {len(store)} key(s), "
              f"{store.n_observations} observation(s)")
    if args.profile_out:
        store.save(args.profile_out)
        print(f"saved profile to {args.profile_out}")

    if args.save_state:
        pool = CheckpointPool(args.pool)
        pool.save_packed_state(
            state_id, lora, opt,
            {"arch": cfg.name, "ranks": list(meta.ranks),
             "alphas": list(meta.alphas), "seq": args.seq,
             "steps_done": np.asarray(opt["step"]).tolist()},
        )
        print(f"saved packed state {state_id!r} to {args.pool}")

    if args.pool:
        pool = CheckpointPool(args.pool)
        per = res.losses if res.losses is not None else np.full(meta.n, np.nan)
        for i, c in enumerate(configs):
            pool.save_adapter(
                f"{cfg.name}_adapter_{i:03d}",
                extract_adapter(lora, i, meta.ranks),
                {"rank": c.rank, "alpha": c.alpha, "learning_rate": c.learning_rate,
                 "batch_size": c.batch_size, "final_loss": float(per[i])},
            )
        print(f"saved {len(configs)} adapters to {args.pool}")

    _export_obs(args, tracer)


if __name__ == "__main__":
    main()
