"""Run the packed-LoRA sweep on TPU at qwen25-7b's published widths.

One chip, as it runs with no arguments::

    python chip_smoke.py

plans a pack of four configs of ``default_search_space()`` (ranks 8, 16, 32
and 64; seq 1024; batch 1) and trains it for a few steps through the path a
user calls: ``plan`` -> ``ExecutionEngine.run_local`` -> ``ClusterRunner`` ->
``SliceExecutor``. It does so three times from one seed: with
``impl="xla"``, the plain reference; ``"auto"``, the two-pass Pallas
kernels; and ``"fused"``, the fused Pallas kernel. It checks that each
kernel family compiled into the step (``tpu_custom_call``) and that the
per-adapter losses of the first and of the last step agree with the
reference. Then ``ServeEngine`` answers four greedy requests with the
trained adapters, read from the checkpoint pool the sweep wrote.

Four chips::

    python chip_smoke.py --chips 4

plans the same pack on four chips, runs it through ``ClusterRunner`` on
disjoint slices, and compares its losses with the pack trained on one chip.
It also checks that each slice's arrays sit on that slice's own devices.

The model is qwen25-7b cut to 4 layers, with random bf16 weights made from
``--seed``. The last line of stdout is ``{"ok": true, "device": {...}}``.
A backend that is not a TPU, a phase that raises or a check that fails
exits non-zero without that line. JAX's persistent compilation cache is
kept where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen25-7b"
N_LAYERS = 4
SEQ = 1024
STEPS = 4
RANKS = (8, 16, 32, 64)
# Pallas kernels against the XLA reference, relative to the reference loss.
# Weights and activations are bf16, and the kernel families round at other
# points (the fused kernel adds the delta before its one bf16 rounding, the
# two-pass path rounds base and delta apart) and sum in another order. Each
# rounding is within bf16's unit roundoff, 2**-9 of the value. A per-adapter
# loss averages 1024 tokens whose rounding errors do not add up coherently,
# so one unit roundoff of the loss bounds the difference.
LOSS_RTOL = 2.0**-9


class CheckFailed(AssertionError):
    """A result of the run is not what the system should produce."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def pick_configs(seq: int = SEQ):
    """One config per rank of the paper's grid: batch 1, alpha = rank."""
    from repro.configs.base import default_search_space

    space = default_search_space(n=300, seq_len=seq)
    return [
        next(c for c in space if c.rank == r and c.batch_size == 1
             and c.alpha == r and c.learning_rate == 1e-4)
        for r in RANKS
    ]


def init_base(cfg, configs, seed: int):
    """Random bf16 base, made on the device by one jitted program."""
    import jax
    import jax.numpy as jnp

    from repro.core.adapter import pack_meta
    from repro.models.model import init_model

    meta = pack_meta(configs)
    return jax.jit(
        lambda k: init_model(k, cfg, meta, jnp.bfloat16)[0]
    )(jax.random.PRNGKey(seed))


def losses_by_config(records, n_configs: int):
    """Per-adapter losses of a run's last step, indexed by config id."""
    import numpy as np

    out = np.full((n_configs,), np.nan)
    for rec in records:
        out[list(rec.job.config_ids)] = rec.final_losses
    return out


def train(cm, cfg, configs, base, runner, *, impl, steps, seq, pool=None):
    """Plan ``configs`` on the runner's devices and run the plan through
    ``ExecutionEngine.run_local`` for one step, then afresh for ``steps``.

    Returns the plan, the losses of step 1 and of step ``steps``, the warm
    ms/step of each job and the seconds of the first run, which include
    compiling."""
    from repro.sched.engine import ExecutionEngine
    from repro.sched.planner import plan

    g = runner.device_pool.total
    sched = plan(cm, configs, g, seq, steps)
    eng = ExecutionEngine(cm, g)
    t0 = time.perf_counter()
    first, _ = eng.run_local(sched, configs, cfg, base, n_steps=1, seq=seq,
                             runner=runner, impl=impl)
    first_s = time.perf_counter() - t0
    last, makespan = eng.run_local(sched, configs, cfg, base, n_steps=steps,
                                   seq=seq, runner=runner, impl=impl,
                                   pool=pool)
    return {
        "packs": [tuple(j.config_ids) for j in sched.jobs],
        "degrees": [j.degree for j in sched.jobs],
        "first": losses_by_config(first, len(configs)),
        "last": losses_by_config(last, len(configs)),
        "ms_per_step": [1e3 * r.wall_seconds / steps for r in last],
        "first_run_s": first_s,
        "makespan_s": makespan,
    }


def step_hlo(executor, cfg, pack, base, *, impl, seq) -> str:
    """Compiled text of the executor's one-device step for ``pack``."""
    import numpy as np

    from repro.cluster.executor import NO_BUDGET
    from repro.core.adapter import pack_meta
    from repro.train.data import packed_batch_iterator

    meta = pack_meta(pack)
    # the runner hands "auto" to the executor as None (its cache key)
    step, _ = executor.step_fn(
        cfg, meta.n, impl=None if impl == "auto" else impl, ranks=meta.ranks,
    )
    lora, opt = executor.pack_template(cfg, pack)
    batch = next(packed_batch_iterator(cfg, list(pack), seq=seq))
    budgets = np.full((meta.n,), NO_BUDGET, np.int32)
    lowered = step.lower(base, lora, opt, batch, meta.scales(),
                         meta.lr_vector(), budgets)
    return lowered.compile().as_text()


def check_losses(name: str, got, ref) -> float:
    """Largest relative difference of ``got`` from ``ref``, checked."""
    import numpy as np

    check(bool(np.all(np.isfinite(got))), f"{name}: non-finite loss {got}")
    rel = float(np.max(np.abs(got - ref) / np.abs(ref)))
    check(rel <= LOSS_RTOL,
          f"{name}: losses {got} differ from the reference {ref} by "
          f"{rel:.3g} relative (tolerance {LOSS_RTOL:.3g})")
    return rel


def serve(cfg, base, pool, configs, *, new_tokens: int = 8):
    """Four greedy requests, one per trained adapter, through
    ``ServeEngine``; the adapters come from the checkpoint pool."""
    import numpy as np

    from repro.serve.engine import ServeEngine, ServeRequest

    eng = ServeEngine(cfg, base, rows=len(configs), smax=64,
                      r_bucket=max(c.rank for c in configs),
                      slot_capacity=len(configs), checkpoint_pool=pool)
    rng = np.random.RandomState(0)
    reqs = [
        ServeRequest(i, f"adapter_{i:04d}",
                     rng.randint(0, cfg.vocab_size, size=8).astype(np.int32),
                     max_new_tokens=new_tokens)
        for i in range(len(configs))
    ]
    stats = eng.serve(reqs)
    check(len(stats.results) == len(reqs),
          f"serve: {len(stats.results)} results for {len(reqs)} requests")
    for r in stats.results:
        check(r.error is None, f"serve: request {r.request_id}: {r.error}")
        check(len(r.tokens) == new_tokens,
              f"serve: request {r.request_id} gave {len(r.tokens)} tokens")
        check(bool(np.all((r.tokens >= 0) & (r.tokens < cfg.vocab_size))),
              f"serve: request {r.request_id} tokens out of vocabulary")
    check(stats.adapters_served == len(configs),
          f"serve: {stats.adapters_served} adapters served")
    return stats


def one_chip(cfg, configs, base, device, pool_dir: str) -> None:
    """The sweep on one chip in the three kernel families, then serving."""
    from repro.cluster import ClusterRunner, DevicePool, SliceExecutor
    from repro.obs import Tracer
    from repro.sched.cost_model import CostModel, tpu_prior
    from repro.train.checkpoint import CheckpointPool

    cm = CostModel(cfg, tpu_prior(device.device_kind))
    runs = {}
    for impl in ("xla", "auto", "fused"):
        tracer = Tracer()
        executor = SliceExecutor(tracer=tracer)
        runner = ClusterRunner(executor, DevicePool([device]), tracer=tracer)
        pool = CheckpointPool(pool_dir) if impl == "fused" else None
        r = train(cm, cfg, configs, base, runner, impl=impl, steps=STEPS,
                  seq=SEQ, pool=pool)
        compile_s = sum(s.end - s.start for s in tracer.spans()
                        if s.name == "executor.compile")
        log(f"train impl={impl}: packs={r['packs']} degrees={r['degrees']} "
            f"compile+warm-step={compile_s:.2f}s "
            f"first-run={r['first_run_s']:.2f}s "
            f"warm ms/step per job={[round(m, 2) for m in r['ms_per_step']]}")
        log(f"  losses step 1={r['first'].tolist()}")
        log(f"  losses step {STEPS}={r['last'].tolist()}")
        if impl != "xla":
            for pack in sorted(set(r["packs"])):
                text = step_hlo(executor, cfg, [configs[c] for c in pack],
                                base, impl=impl, seq=SEQ)
                n = text.count("tpu_custom_call")
                check(n > 0, f"impl={impl}: no Pallas kernel "
                      f"(tpu_custom_call) in the step of pack {pack}")
                log(f"  pack {pack}: {n} tpu_custom_call in the step")
            rel1 = check_losses(f"impl={impl} step 1", r["first"],
                                runs["xla"]["first"])
            relk = check_losses(f"impl={impl} step {STEPS}", r["last"],
                                runs["xla"]["last"])
            log(f"  vs xla: max rel diff step 1={rel1:.3g} "
                f"step {STEPS}={relk:.3g} (tolerance {LOSS_RTOL:.3g})")
        runs[impl] = r

    t0 = time.perf_counter()
    stats = serve(cfg, base, CheckpointPool(pool_dir), configs)
    log(f"serve: {len(stats.results)} requests, {stats.tokens_emitted} "
        f"tokens in {stats.steps} decode steps, "
        f"{time.perf_counter() - t0:.2f}s with compiling; tokens="
        f"{[r.tokens.tolist() for r in stats.results]}")


def four_chips(cfg, configs, base, devices) -> None:
    """The pack planned on four chips against the same pack on one."""
    import jax

    from repro.cluster import ClusterRunner, DevicePool, SliceExecutor
    from repro.sched.cost_model import CostModel, tpu_prior

    class PlacementCheck(SliceExecutor):
        """Records the devices that hold each segment's trained adapters."""

        def __init__(self):
            super().__init__()
            self.placed = []

        def train_pack(self, cfg, configs, **kw):
            res = super().train_pack(cfg, configs, **kw)
            held = {d for x in jax.tree.leaves(res.lora) for d in x.devices()}
            self.placed.append((kw["slice_"], held))
            return res

    cm = CostModel(cfg, tpu_prior(devices[0].device_kind))
    ref = train(cm, cfg, configs, base,
                ClusterRunner(SliceExecutor(), DevicePool(devices[:1])),
                impl="auto", steps=STEPS, seq=SEQ)
    log(f"one chip: packs={ref['packs']} makespan={ref['makespan_s']:.2f}s "
        f"warm ms/step per job={[round(m, 2) for m in ref['ms_per_step']]}")
    executor = PlacementCheck()
    runner = ClusterRunner(executor, DevicePool(devices[:4]))
    check(runner.concurrent, "four-chip runner is not concurrent")
    got = train(cm, cfg, configs, base, runner, impl="auto", steps=STEPS,
                seq=SEQ)
    log(f"four chips: packs={got['packs']} degrees={got['degrees']} "
        f"makespan={got['makespan_s']:.2f}s "
        f"warm ms/step per job={[round(m, 2) for m in got['ms_per_step']]}")
    rel1 = check_losses("four chips step 1", got["first"], ref["first"])
    relk = check_losses(f"four chips step {STEPS}", got["last"], ref["last"])
    log(f"  vs one chip: max rel diff step 1={rel1:.3g} step {STEPS}="
        f"{relk:.3g} (tolerance {LOSS_RTOL:.3g})")
    used = set()
    for slice_, held in executor.placed:
        check(held == set(slice_.devices),
              f"slice units {slice_.units}: adapters on {held}, "
              f"expected {set(slice_.devices)}")
        used |= held
    check(used == set(devices[:4]),
          f"the slices used {len(used)} of the 4 chips")
    log(f"  {len(executor.placed)} segments, each on its own slice; "
        f"chips used: {sorted(d.id for d in used)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs.base import get_config
    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache(ROOT)
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache: {cache_dir}")
    cfg = get_config(ARCH).replace(n_layers=N_LAYERS)
    configs = pick_configs()
    t0 = time.perf_counter()
    base = init_base(cfg, configs, args.seed)
    jax.block_until_ready(base)
    n_params = sum(x.size for x in jax.tree.leaves(base))
    log(f"model: {ARCH} d_model={cfg.d_model} heads={cfg.attention.n_heads}"
        f"/{cfg.attention.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} layers={cfg.n_layers}: "
        f"{n_params / 1e9:.3f}B bf16 params in "
        f"{time.perf_counter() - t0:.2f}s; configs="
        f"{[(c.rank, c.alpha, c.learning_rate, c.batch_size) for c in configs]}"
        f" seq={SEQ} steps={STEPS}")

    if args.chips == 4:
        four_chips(cfg, configs, base, devices[:4])
    else:
        pool_dir = os.path.join(ROOT, ".smoke_pool")
        shutil.rmtree(pool_dir, ignore_errors=True)
        try:
            one_chip(cfg, configs, base, dev, pool_dir)
        finally:
            shutil.rmtree(pool_dir, ignore_errors=True)
    log(f"compile cache: {cache_events['hits']} hits, "
        f"{cache_events['misses']} misses in {cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
