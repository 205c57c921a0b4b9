"""Slice executor: compile-cached packed train steps placed on mesh slices.

One :class:`SliceExecutor` owns a cache of jitted packed train steps keyed by
(model config, pack width, slice shape). The step itself
(:func:`repro.train.trainer.make_packed_step`) takes the per-adapter
hyperparameter vectors — scales, learning rates, step budgets — as *runtime
arguments*, so two packs with the same (n, r_bucket, batch, seq) shape share
one compiled executable even when their hyperparameters differ. Segment
execution (`run_segment`) is what the engine's ``_execute_segments`` used to
do inline, plus explicit placement onto the segment's :class:`MeshSlice`:

  * width-1 slice — everything ``device_put`` onto the slice's device;
  * width-g slice — params sharded per the production rules
    (``launch.sharding.param_specs``) over a ``slice_mesh`` covering exactly
    the slice's devices, batch per ``batch_specs``, vectors replicated.

Batches are pre-generated and pre-placed in bounded chunks (``PREGEN_CHUNK``)
ahead of the step stream: Python-side data synthesis holds the GIL, and
interleaving it step-by-step serializes concurrently dispatched segments
(measured: it flips a 1.7x concurrency win into a 0.8x loss on a 2-core
host); chunking keeps resident batch memory O(chunk), not O(n_steps).
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LoraConfig, ModelConfig
from repro.core.adapter import pack_meta
from repro.core.packed_lora import extract_adapter, inject_adapter
from repro.cluster.pool import MeshSlice
from repro.obs import NULL_TRACER
from repro.train.trainer import make_packed_step, step_rows

# per-adapter step cap meaning "no budget": always larger than any real
# step count, so the budget mask stays 1.0 and the update is bit-identical
# to an unbudgeted AdamW step.
NO_BUDGET = np.int32(2**31 - 1)

# batches pre-generated and pre-placed per refill (bounds resident batch
# memory for long runs while keeping GIL-bound data synthesis out of the
# concurrent step stream for a whole chunk at a time)
PREGEN_CHUNK = 256


def _slice_track(slice_: Optional[MeshSlice]) -> str:
    """Perfetto track name for a slice: one row per device unit group."""
    if slice_ is None or not slice_.units:
        return "device"
    if len(slice_.units) == 1:
        return f"unit{slice_.units[0]}"
    return f"units{min(slice_.units)}-{max(slice_.units)}"


def _accepts_start_steps(fn) -> bool:
    """Whether a custom data_iter_fn can take per-adapter stream offsets."""
    import inspect

    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    return "start_steps" in params or any(
        p.kind == inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


@dataclass
class PackResult:
    """Final state of one packed training run on a slice."""

    lora: Any
    opt: Any
    losses: Optional[np.ndarray]  # final per-adapter losses (None if 0 steps)
    wall_seconds: float  # steady-state loop time (compile excluded)
    real_start: float = 0.0  # absolute perf_counter timestamps of the
    real_end: float = 0.0  # placed+timed region (overlap accounting)


class SliceExecutor:
    """Compile-cached packed-step execution on device slices (thread-safe)."""

    def __init__(self, *, tracer=None):
        self._steps: Dict[Tuple, Callable] = {}
        self._templates: Dict[Tuple, Tuple] = {}
        self._warmed: set = set()
        self._lock = threading.Lock()
        self.n_builds = 0
        self.n_hits = 0
        self.tracer = tracer if tracer is not None else NULL_TRACER

    # ---------------- pack-state templates ----------------

    def pack_template(self, cfg: ModelConfig, configs: Sequence[LoraConfig],
                      seed: int = 0):
        """Fresh (lora, opt) state for this pack shape, from a cached
        template: adapter init depends only on (seed, model config, pack
        meta), and rebuilding it per segment dominated segment runtime.
        ``init_lora`` builds the adapters without the base model. Returned
        trees share leaves with the cache — callers get fresh containers,
        and placement copies the leaves before anything donates them."""
        meta = pack_meta(configs)
        # adapter init depends only on the rank tuple (shapes + rank mask),
        # not on alphas / learning rates / batch sizes
        key = (cfg, meta.ranks, seed)
        with self._lock:
            hit = self._templates.get(key)
        if hit is None:
            from repro.models.model import init_lora
            from repro.train.optimizer import init_opt_state

            lora = init_lora(jax.random.PRNGKey(seed), cfg, meta)
            opt = init_opt_state(lora, n_pack=meta.n)
            hit = (lora, opt)
            with self._lock:
                self._templates.setdefault(key, hit)
        lora, opt = hit
        return (
            jax.tree.map(lambda x: x, lora),
            jax.tree.map(lambda x: x, opt),
        )

    # ---------------- compile cache ----------------

    def step_fn(
        self,
        cfg: ModelConfig,
        n_pack: int,
        slice_: Optional[MeshSlice] = None,
        *,
        nb: int = 0,
        mesh_shape: Optional[Tuple[int, int]] = None,
        fsdp: bool = False,
        seq_parallel: bool = False,
        impl: Optional[str] = None,
        remat: Optional[str] = None,
        ranks: Optional[Tuple[int, ...]] = None,
        blocks: Optional[Tuple[int, int, int]] = None,
        base_dtype: Optional[str] = None,
        batch_sizes: Optional[Tuple[int, ...]] = None,
    ) -> Tuple[Callable, Optional[Any]]:
        """Jitted packed step for this (config, pack width, slice shape).

        Returns ``(step, dist)``; ``dist`` is None for width-1 slices. The
        Python-level cache is the subsystem's compile cache: same-shape packs
        hit the same jitted callable (and, through jax's executable cache,
        the same XLA compilation when placed identically). The kernel policy
        (``impl``/``remat``/the pack's static ``ranks`` tuple, which drives
        ragged same-rank segmentation) and a mixed ``batch_sizes`` tuple
        (which picks the rows the step computes) are part of the trace, so
        they are part of the key."""
        width = 1 if slice_ is None else slice_.width
        # homogeneous rank and batch tuples normalize to None (trace-
        # identical: ragged segmentation and row slots only engage on mixed
        # tuples) so same-width packs keep sharing one compiled step
        ranks = tuple(ranks) if ranks and len(set(ranks)) > 1 else None
        batch_sizes = (tuple(batch_sizes)
                       if batch_sizes and len(set(batch_sizes)) > 1 else None)
        kkey = (impl, remat, ranks, blocks, base_dtype, batch_sizes)
        if width == 1:
            key: Tuple = (cfg, n_pack, 1, kkey)
        else:
            key = (
                cfg, n_pack, width, slice_.devices, nb,
                mesh_shape, fsdp, seq_parallel, kkey,
            )
        with self._lock:
            hit = self._steps.get(key)
            if hit is not None:
                self.n_hits += 1
                self.tracer.metrics.counter("executor.compile_cache_hits").inc()
                return hit
            dist = None
            if width > 1:
                from repro.launch.sharding import make_dist

                data, model = mesh_shape or (1, width)
                mesh = slice_.mesh(data=data, model=model)
                dist = make_dist(
                    mesh, nb or None, fsdp=fsdp,
                    seq_sharded_residuals=seq_parallel,
                )
            step = make_packed_step(
                cfg, n_pack, dist=dist, impl=impl, remat=remat, ranks=ranks,
                blocks=blocks, base_dtype=base_dtype, batch_sizes=batch_sizes,
            )
            self._steps[key] = (step, dist)
            self.n_builds += 1
            self.tracer.metrics.counter("executor.compile_cache_builds").inc()
            return step, dist

    # ---------------- placement ----------------

    @staticmethod
    def _place(slice_: Optional[MeshSlice], cfg, dist, base, lora, opt, vecs):
        """Commit all step inputs to the slice's devices.

        ``lora``/``opt`` leaves may alias a cached pack template, and the
        train step *donates* them — so they are deep-copied on-device
        (``x + 0`` stays on the target placement) while ``base`` (never
        donated, shared by every concurrent segment) is placed as-is."""
        from jax.sharding import NamedSharding, PartitionSpec

        copy = lambda t: jax.tree.map(lambda x: x + 0, t)  # noqa: E731
        if slice_ is None or slice_.width == 1:
            dev = None if slice_ is None else slice_.lead
            put = (lambda t: t) if dev is None else (
                lambda t: jax.device_put(t, dev)
            )
            return (
                put(base), copy(put(lora)), copy(put(opt)),
                tuple(put(v) for v in vecs), put,
            )
        from repro.launch.sharding import param_specs, to_named

        mesh = dist.mesh
        repl = NamedSharding(mesh, PartitionSpec())
        bspec = to_named(param_specs(jax.eval_shape(lambda: base), cfg, mesh), mesh)
        lspec = to_named(param_specs(jax.eval_shape(lambda: lora), cfg, mesh), mesh)
        base_d = jax.device_put(base, bspec)
        lora_d = copy(jax.device_put(lora, lspec))
        opt_d = copy({
            "m": jax.device_put(opt["m"], lspec),
            "v": jax.device_put(opt["v"], lspec),
            "step": jax.device_put(opt["step"], repl),
        })
        vecs_d = tuple(jax.device_put(v, repl) for v in vecs)

        def put_batch(b):
            from repro.launch.sharding import batch_specs

            spec = to_named(batch_specs(jax.eval_shape(lambda: b), mesh), mesh)
            return jax.device_put(b, spec)

        return base_d, lora_d, opt_d, vecs_d, put_batch

    # ---------------- packed training on one slice ----------------

    def train_pack(
        self,
        cfg: ModelConfig,
        configs: Sequence[LoraConfig],
        *,
        n_steps: int,
        seq: int,
        base,
        lora=None,
        opt=None,
        slice_: Optional[MeshSlice] = None,
        seed: int = 0,
        budgets: Optional[np.ndarray] = None,
        data_iter_fn: Optional[Callable] = None,
        data_start_steps: Optional[Sequence[int]] = None,
        mesh_shape: Optional[Tuple[int, int]] = None,
        fsdp: bool = False,
        seq_parallel: bool = False,
        step_callback: Optional[Callable] = None,
        impl: Optional[str] = None,
        remat: Optional[str] = None,
        blocks: Optional[Tuple[int, int, int]] = None,
        base_dtype: Optional[str] = None,
    ) -> PackResult:
        """Train one pack for ``n_steps`` on ``slice_`` (default device when
        None). ``lora``/``opt`` may carry resumed state; ``budgets`` is the
        per-adapter step-cap vector (None = uncapped); ``data_start_steps``
        fast-forwards each adapter's data stream past batches consumed in
        earlier segments (resumed packs see the same samples they would have
        seen uninterrupted). ``step_callback(i, metrics)`` is invoked after
        every step (it synchronizes — use for logging, not benchmarking).
        Compilation happens on throwaway copies outside the timed region, so
        ``wall_seconds`` is steady-state."""
        from repro.train.data import packed_batch_iterator
        from repro.train.optimizer import init_opt_state

        meta = pack_meta(configs)
        track = _slice_track(slice_)
        if lora is None:
            lora, tmpl_opt = self.pack_template(cfg, configs, seed)
            if opt is None:
                opt = tmpl_opt
        if opt is None:
            opt = init_opt_state(lora, n_pack=meta.n)
        if budgets is None:
            budgets = np.full((meta.n,), NO_BUDGET, np.int32)
        nb = meta.n * meta.max_batch
        step, dist = self.step_fn(
            cfg, meta.n, slice_, nb=nb, mesh_shape=mesh_shape,
            fsdp=fsdp, seq_parallel=seq_parallel,
            impl=impl, remat=remat, ranks=meta.ranks, blocks=blocks,
            base_dtype=base_dtype, batch_sizes=meta.batch_sizes,
        )
        rows = step_rows(meta.batch_sizes, dist)
        with self.tracer.span("executor.place", cat="executor", track=track):
            vecs = (
                meta.scales(),
                meta.lr_vector(),
                jnp.asarray(budgets, jnp.int32),
            )
            real_start = time.perf_counter()
            base_d, lora_d, opt_d, (scales, lr_vec, budg), put_batch = (
                self._place(slice_, cfg, dist, base, lora, opt, vecs)
            )
        wall = 0.0
        losses = None
        m = None
        if n_steps > 0:
            skip = (
                tuple(int(s) for s in data_start_steps)
                if data_start_steps is not None and any(data_start_steps)
                else None
            )
            n_first = min(n_steps, PREGEN_CHUNK)
            with self.tracer.span("executor.batches", cat="executor",
                                  track=track, n_steps=n_first):
                if data_iter_fn:
                    # custom iterators own their stream; the offsets are
                    # passed through only when a resumed segment actually
                    # needs them AND the callable opts in by accepting
                    # ``start_steps`` — legacy 3-arg iterators keep their
                    # pre-offset behavior (resumed adapters replay the
                    # stream) instead of crashing
                    if skip and _accepts_start_steps(data_iter_fn):
                        it = data_iter_fn(
                            cfg, list(configs), seq, start_steps=skip
                        )
                    else:
                        it = data_iter_fn(cfg, list(configs), seq)
                else:
                    it = packed_batch_iterator(
                        cfg, list(configs), seq=seq, start_steps=skip
                    )
                # Pre-generate + pre-place batches in bounded chunks: the
                # GIL-bound data synthesis stays out of the (possibly
                # concurrent) step stream for a whole chunk at a time,
                # while resident batch memory stays O(PREGEN_CHUNK)
                # instead of O(n_steps) for long launcher runs.
                first = [put_batch(next(it)) for _ in range(n_first)]
            # compile outside the timed region on throwaway copies (the
            # paper times steady state); `x + 0` keeps each copy on the
            # slice's own devices, so donation cannot invalidate the
            # originals. Skipped when this exact executable (step key +
            # batch shapes + placement) was already warmed — segmented runs
            # (probe / preempt / resume) would otherwise pay one throwaway
            # iteration per segment for a compile that is already cached.
            wkey = (
                cfg, meta.n, meta.r_bucket, meta.ranks, impl, remat, blocks,
                base_dtype,
                None if slice_ is None else slice_.devices,
                nb, meta.batch_sizes, mesh_shape, fsdp, seq_parallel,
                tuple(sorted(
                    (k, tuple(v.shape), str(v.dtype))
                    for k, v in first[0].items()
                )),
            )
            with self._lock:
                need_warm = wkey not in self._warmed
            if need_warm:
                with self.tracer.span(
                    "executor.compile", cat="executor", track=track,
                    n_pack=meta.n, width=1 if slice_ is None else slice_.width,
                ):
                    lora_w = jax.tree.map(lambda x: x + 0, lora_d)
                    opt_w = jax.tree.map(lambda x: x + 0, opt_d)
                    _, _, warm = step(
                        base_d, lora_w, opt_w, first[0], scales, lr_vec, budg
                    )
                    jax.block_until_ready(warm["loss"])
                with self._lock:
                    self._warmed.add(wkey)
            with self.tracer.span(
                "executor.train", cat="executor", track=track,
                n_pack=meta.n, n_steps=n_steps,
                rows=rows, real_rows=sum(meta.batch_sizes),
            ) as train_span:
                t0 = time.perf_counter()
                i = 0
                batches = first
                while batches:
                    with self.tracer.span(
                        "executor.dispatch", cat="executor", track=track,
                        first_step=i, n_steps=len(batches),
                    ):
                        for batch in batches:
                            lora_d, opt_d, m = step(
                                base_d, lora_d, opt_d, batch, scales, lr_vec,
                                budg,
                            )
                            if step_callback is not None:
                                step_callback(i, m)
                            i += 1
                    n_next = min(n_steps - i, PREGEN_CHUNK)
                    batches = []
                    if n_next:
                        with self.tracer.span(
                            "executor.batches", cat="executor", track=track,
                            n_steps=n_next,
                        ):
                            batches = [
                                put_batch(next(it)) for _ in range(n_next)
                            ]
                with self.tracer.span("executor.wait", cat="executor",
                                      track=track):
                    jax.block_until_ready(m["loss"])
                wall = time.perf_counter() - t0
            with self.tracer.span("executor.losses", cat="executor",
                                  track=track):
                losses, expert_rows, computed = jax.device_get(
                    (m["per_adapter_loss"], m.get("expert_rows"),
                     m.get("expert_rows_computed")))
                losses = np.asarray(losses)
            if expert_rows is not None and expert_rows.size:
                # the last step's routed rows, and the rows the grouped
                # matmuls computed for them
                train_span.args["routed_rows"] = int(expert_rows.sum())
                train_span.args["routed_rows_computed"] = int(computed)
        return PackResult(
            lora=lora_d,
            opt=opt_d,
            losses=losses,
            wall_seconds=wall,
            real_start=real_start,
            real_end=time.perf_counter(),
        )

    # ---------------- one planned segment (engine integration) ----------------

    def run_segment(
        self,
        seg,  # JobSegment
        configs_by_cid: Dict[int, LoraConfig],
        total_steps: Dict[int, int],
        cfg: ModelConfig,
        base_params,
        *,
        seq: int,
        pool,  # Optional[CheckpointPool]
        data_iter_fn: Optional[Callable] = None,
        seed: int = 0,
        slice_: Optional[MeshSlice] = None,
        impl: Optional[str] = None,
        remat: Optional[str] = None,
        base_dtype: Optional[str] = None,
    ):
        """Execute one planned segment on ``slice_``: resume preempted
        adapters from the checkpoint pool, train ``seg.run_steps`` packed
        iterations, then save finished adapters / re-checkpoint the
        still-unfinished ones. Returns a ``JobRecord``."""
        from repro.sched.engine import JobRecord
        from repro.sched.planner import ScheduledJob

        track = _slice_track(slice_)
        with self.tracer.span(
            "executor.segment", cat="executor", track=track,
            job_id=seg.job_id, cids=list(seg.config_ids),
            degree=seg.degree, units=list(seg.units),
        ):
            return self._run_segment_inner(
                seg, configs_by_cid, total_steps, cfg, base_params,
                seq=seq, pool=pool, data_iter_fn=data_iter_fn, seed=seed,
                slice_=slice_, impl=impl, remat=remat,
                base_dtype=base_dtype, track=track,
                JobRecord=JobRecord, ScheduledJob=ScheduledJob,
            )

    def _run_segment_inner(
        self, seg, configs_by_cid, total_steps, cfg, base_params, *,
        seq, pool, data_iter_fn, seed, slice_, impl, remat, base_dtype,
        track, JobRecord, ScheduledJob,
    ):
        job_cfgs = [configs_by_cid[cid] for cid in seg.config_ids]
        meta = pack_meta(job_cfgs)
        with self.tracer.span("executor.template", cat="executor",
                              track=track):
            lora, opt = self.pack_template(cfg, job_cfgs, seed)
        resumed_ids = [
            cid for cid, st0 in zip(seg.config_ids, seg.start_steps) if st0
        ]
        resume_cm = (
            self.tracer.span(
                "executor.resume_load", cat="executor", track=track,
                cids=resumed_ids,
            )
            if resumed_ids
            else contextlib.nullcontext()
        )
        with resume_cm:
            for slot, (cid, st0) in enumerate(
                zip(seg.config_ids, seg.start_steps)
            ):
                if st0 == 0:
                    continue
                if pool is None or not pool.has_adapter_state(f"{cid:04d}"):
                    raise RuntimeError(
                        f"segment resumes config {cid} at step {st0} but the "
                        "pool holds no checkpointed state for it"
                    )
                state, smeta = pool.load_adapter_state(f"{cid:04d}")
                assert int(smeta["steps_done"]) == st0, (cid, smeta, st0)
                lora = inject_adapter(lora, state["w"], slot)
                opt["m"] = inject_adapter(opt["m"], state["m"], slot)
                opt["v"] = inject_adapter(opt["v"], state["v"], slot)
                opt["step"] = opt["step"].at[slot].set(st0)
        budgets = np.asarray(
            [total_steps[cid] for cid in seg.config_ids], np.int32
        )
        res = self.train_pack(
            cfg,
            job_cfgs,
            n_steps=seg.run_steps,
            seq=seq,
            base=base_params,
            lora=lora,
            opt=opt,
            slice_=slice_,
            seed=seed,
            budgets=budgets,
            data_iter_fn=data_iter_fn,
            data_start_steps=seg.start_steps,
            impl=impl,
            remat=remat,
            base_dtype=base_dtype,
        )
        lora, opt, losses = res.lora, res.opt, res.losses
        done = set(seg.done_ids)
        with self.tracer.span("executor.save", cat="executor", track=track):
            save_cm = (
                self.tracer.span(
                    "executor.checkpoint_save", cat="executor", track=track,
                    cids=list(seg.config_ids),
                )
                if pool is not None
                else contextlib.nullcontext()
            )
            with save_cm:
                self._save_segment_state(
                    seg, configs_by_cid, total_steps, meta, pool,
                    lora, opt, losses, done,
                )
            return JobRecord(
                ScheduledJob(seg.config_ids, seg.degree, seg.start, seg.end),
                res.wall_seconds,
                losses,
                real_start=res.real_start,
                real_end=res.real_end,
            )

    def _save_segment_state(self, seg, configs_by_cid, total_steps, meta,
                            pool, lora, opt, losses, done):
        for slot, cid in enumerate(seg.config_ids):
            c = configs_by_cid[cid]
            if cid in done:
                if pool is None:
                    continue
                adapter = extract_adapter(lora, slot, meta.ranks)
                pool.save_adapter(
                    f"adapter_{cid:04d}",
                    adapter,
                    {
                        "rank": c.rank,
                        "alpha": c.alpha,
                        "learning_rate": c.learning_rate,
                        "batch_size": c.batch_size,
                        "final_loss": (
                            float(losses[slot]) if losses is not None
                            else float("nan")
                        ),
                        "total_steps": int(total_steps[cid]),
                    },
                )
            else:  # preempted mid-training: checkpoint resumable state
                assert pool is not None
                state = {
                    "w": extract_adapter(lora, slot, meta.ranks),
                    "m": extract_adapter(opt["m"], slot, meta.ranks),
                    "v": extract_adapter(opt["v"], slot, meta.ranks),
                }
                pool.save_adapter_state(
                    f"{cid:04d}",
                    state,
                    {
                        "steps_done": int(seg.start_steps[slot] + seg.run_steps),
                        "rank": c.rank,
                        "total_steps": int(total_steps[cid]),
                    },
                )
